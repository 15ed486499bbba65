"""chip_smoke.py, as far as a machine without a chip can show.

The slow test is the dry run its docstring names: every step at a tiny
size on the CPU, ending non-zero because the server is not on a TPU.
The fast ones pin the launcher's own pieces: the numpy reference agrees
with the engine on every query shape, the launcher refuses to spawn once
a JAX backend exists in its process, and alone in a directory it fails
without printing a result.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def test_reference_agrees_with_the_engine(tmp_path):
    """Every bulk query, answered by TempoDB.search over the blocks the
    smoke wrote, equals the smoke's plain numpy scan — substring values,
    inclusive bounds, dictionary pruning, exhaustive flag."""
    from tempo_tpu.api.params import parse_search_request
    from tempo_tpu.backend.local import LocalBackend
    from tempo_tpu.db import TempoDB, TempoDBConfig

    queries = chip_smoke.bulk_queries(time_base=1_600_000_000)
    # bounds loose enough that a 12K-entry corpus has matches to compare
    for q in queries:
        if q.min_ms:
            q.min_ms = 55_000
        if q.start:
            q.start, q.end = q.start - 1800, q.end + 1800
    corpus = chip_smoke.write_bulk_corpus(str(tmp_path), 3, 4096, 11,
                                          queries)
    assert corpus["entries"] == 3 * 4096
    db = TempoDB(LocalBackend(str(tmp_path / "blocks")),
                 str(tmp_path / "wal"), TempoDBConfig(host_state_dir=""))
    db.poll()
    compared = 0
    for q in queries:
        resp = db.search(chip_smoke.BULK_TENANT,
                         parse_search_request(q.params())).response()
        assert q.deterministic or len(resp.traces) == q.limit, q.name
        got = sorted(chip_smoke.key_of_trace_id(t.trace_id)
                     for t in resp.traces)
        if not q.deterministic:
            continue
        assert resp.metrics.inspected_traces == q.inspected, q.name
        if q.matches <= q.limit:
            assert got == q.keys.tolist(), q.name
        else:
            starts = sorted((t.start_time_unix_nano // 10**9
                             for t in resp.traces), reverse=True)
            want = sorted(q.starts.tolist(), reverse=True)[:q.limit]
            assert starts == want, q.name
        compared += q.matches
    assert compared > 0  # the corpus was big enough to mean something
    absent = next(q for q in queries if q.name == "absent-value")
    assert absent.inspected == 0 and absent.skipped_blocks == 3


def test_launcher_refuses_to_spawn_after_touching_jax(tmp_path):
    import jax

    jax.devices()  # the test process holds a backend; a launcher must not
    with pytest.raises(chip_smoke.Fatal, match="initialised a JAX backend"):
        chip_smoke.assert_jax_untouched()
    srv = chip_smoke.Server("s", str(tmp_path), "unused.yaml", 1)
    with pytest.raises(chip_smoke.Fatal):
        srv.start()
    assert srv.proc is None


def test_metrics_parsing():
    text = (
        "# HELP x y\n"
        'tempo_search_scan_dispatches_total{mode="batched"} 7\n'
        'tempo_search_scan_dispatches_total{mode="host_fallback"} 2\n'
        "tempo_search_h2d_bytes_total 1.5e+09\n")
    m = chip_smoke.parse_metrics(text)
    name = "tempo_search_scan_dispatches_total"
    assert chip_smoke.metric_sum(m, name) == 9
    assert chip_smoke.metric_sum(m, name, mode="host_fallback") == 2
    assert chip_smoke.metric_sum(m, name, mode="coalesced") == 0
    assert m["tempo_search_h2d_bytes_total"][""] == 1.5e9


def test_alone_in_a_directory_it_fails_without_a_result(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert '"ok"' not in p.stdout


@pytest.mark.slow
def test_dry_run_on_cpu_runs_every_step_and_exits_nonzero(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax-cache"))
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"),
         "--blocks", "4", "--entries-per-block", "4096",
         "--push-traces", "200", "--workdir", str(tmp_path / "run")],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    out = p.stdout
    assert p.returncode == 1, out[-3000:] + p.stderr[-2000:]
    for step in ("== build", "== corpus", "== server 1", "== queries",
                 "== write path", "== device", "== restart"):
        assert step in out, step
    assert "platform=cpu" in out
    assert "the server is not on a TPU" in out
    # the platform is the ONLY thing wrong: every answer matched, every
    # trace came back, both servers exited 0, the cache was hit
    last = out.strip().splitlines()[-1]
    assert last == ("FAILED: device.platform-is-tpu, "
                    "device.platform-is-tpu"), last
    assert "pushed=200 acked=200" in out
    assert "read_back_by_id=200 found_by_search=200" in out
    assert f"compile_cache={tmp_path / 'jax-cache'}" in out
    with pytest.raises(ValueError):
        json.loads(last)
