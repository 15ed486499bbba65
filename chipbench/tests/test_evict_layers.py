"""The readers of the cell whose tenant is larger than its HBM budget,
each on a hand-made run with the value worked by hand and on a program
that lacks what it reads (the reader returns None and the metric is
left out of the line); the window draw of op `search_aged`; and the
rehearsal of `share16.evict`, which has to evict."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from chipbench.tests.test_mesh_layers import reduced
from chipbench.tests.test_span_layers import EMPTY, Spans, reader
from chipbench.tests.test_traffic import _manifest, _requests

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CACHE = "tempo_search_batch_cache_events_total"
MEMO = "tempo_search_prepare_memo_total"
PEAK = "tempo_search_hbm_cache_peak_bytes"
GIB = 1 << 30


DISPATCHES = "tempo_search_scan_dispatches_total"
STAGE = "tempo_search_dispatch_stage_seconds"
LAUNCHES = (
    {DISPATCHES: {'{mode="batched"}': 5.0, '{mode="coalesced"}': 1.0},
     "tempo_search_coalesced_queries_total": {"": 2.0},
     STAGE + "_sum": {'{stage="d2h"}': 1.0},
     STAGE + "_count": {'{stage="d2h"}': 6.0}},
    {DISPATCHES: {'{mode="batched"}': 13.0, '{mode="coalesced"}': 5.0},
     "tempo_search_coalesced_queries_total": {"": 12.0},
     STAGE + "_sum": {'{stage="d2h"}': 1.06},
     STAGE + "_count": {'{stage="d2h"}': 18.0}})


@pytest.fixture
def run():
    """Ten searches completed and one failed. In the window 60 group
    visits, 15 of them misses; 100 memo lookups, 20 misses; the cache's
    high water 5 GiB against the shipped 4 GiB. Three `batcher.stage`
    spans found their group in the host tier (40, 80 and 120 ms), one
    was a hit; two puts of 256 MB took 30 and 34 ms. Twelve launches,
    four of them fused and serving ten queries; their `d2h` stage 60 ms
    together; two `coalescer.wait` of 3 and 5 ms; launch 7 enqueued at
    611 ms and seen on the device at 613. The trace is `test_xplane.py`'s:
    one device, its scan programs."""
    s = Spans()
    s.add("batcher.Search", 0, 10, groups=2)
    s.add("batcher.stage", 0, 40, cache="hbm_miss_host_hit")
    s.add("batcher.stage", 50, 130, cache="hbm_miss_host_hit")
    s.add("batcher.stage", 200, 320, cache="hbm_miss_host_hit")
    s.add("batcher.stage", 400, 401, cache="hbm_hit")
    s.add("batcher.stage", 500, 2500, cache="hbm_miss_cold")
    s.add("batcher.place", 5, 35, bytes=256_000_000, blocks=64)
    s.add("batcher.place", 60, 94, bytes=256_000_000, blocks=64)
    s.add("coalescer.wait", 600, 603)
    s.add("coalescer.wait", 605, 610)
    s.add("coalescer.launch", 610, 611, launch=7)
    s.add("device.scan", 613, 615, launch=7)
    return {
        "trace": reduced("trace_fixture.textproto"), "spans": s.out,
        "device_kind": "TPU v5 lite", "config": {"yaml": {}},
        "manifest": {"pages": 8, "kv_per_entry": 16,
                     "key_names": tuple(f"k{i}" for i in range(16)),
                     "present": np.ones((3, 200), dtype=bool)},
        "requests": [{"op": "search_aged"}],
        "records": [{"i": 0, "status": 200}] * 10 + [{"i": 0, "status": 500}],
        "counters": {
            "before": {CACHE: {'{result="hit"}': 100.0,
                               '{result="miss"}': 30.0,
                               '{result="evict"}': 6.0},
                       MEMO: {'{result="hit"}': 10.0,
                              '{result="miss"}': 10.0},
                       PEAK: {"": 4.5 * GIB}, **LAUNCHES[0]},
            "after": {CACHE: {'{result="hit"}': 145.0,
                              '{result="miss"}': 45.0,
                              '{result="evict"}': 21.0},
                      MEMO: {'{result="hit"}': 90.0,
                             '{result="miss"}': 30.0},
                      PEAK: {"": 5.0 * GIB}, **LAUNCHES[1]}},
    }


WANT = {
    "restage_share.evict": 25.0,
    "restage_ms.evict": 80.0,
    "h2d_gbytes_per_s.evict": 8.0,
    "groups_per_search.evict": 6.0,
    "hbm_peak_over_budget.evict": 1.25,
    "memo_miss_share.evict": 20.0,
    "searches_per_dispatch.evict": 1.5,
    "sync_ms.evict": 5.0,
    "coalesce_wait_ms.evict": 4.0,
    "launch_queue_ms.evict": 2.0,
}
FROM_THE_TRACE = ("kernel_ms.evict", "evict_kernel_roofline")
# a reader of another cell under this cell's name
TWINS = dict(zip(FROM_THE_TRACE, ("kernel_ms.scan", "scan_kernel_roofline")),
             **{n + ".evict": n + ".scan" for n in (
                 "searches_per_dispatch", "sync_ms", "coalesce_wait_ms",
                 "launch_queue_ms")})


@pytest.mark.parametrize("name", sorted(WANT))
def test_evict_reader_on_a_run_that_exercises_it(run, name):
    assert reader(name)(run) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(TWINS))
def test_the_shared_readers_are_the_scan_cells(run, name):
    got = reader(name)(run)
    assert got is not None and got > 0
    assert got == reader(TWINS[name])(run)


@pytest.mark.parametrize("name", sorted(WANT) + list(FROM_THE_TRACE))
def test_evict_reader_finds_nothing_and_says_so(run, name):
    """No spans, counters or trace at all; then the parent's program:
    it has the cache and memo counters and the `cache` attribute, but
    no `batcher.place` span and no high-water gauge."""
    assert reader(name)(dict(EMPTY, config={}, trace=None, records=[],
                             requests=[])) is None
    parent = dict(run, spans=[s for s in run["spans"]
                              if s["name"] != "batcher.place"])
    parent["counters"] = {
        side: {k: v for k, v in c.items() if k != PEAK}
        for side, c in run["counters"].items()}
    got = reader(name)(parent)
    if name in ("h2d_gbytes_per_s.evict", "hbm_peak_over_budget.evict"):
        assert got is None
    else:
        assert got is not None


def test_the_budget_is_the_rehearsals_override_or_the_shipped_default(run):
    over = dict(run, config={"yaml": {"storage": {
        "search_batch_cache_bytes": 2 * GIB}}})
    assert reader("hbm_peak_over_budget.evict")(over) == pytest.approx(2.5)


def test_every_evict_metric_is_registered_for_the_evict_cell_alone():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in list(WANT) + list(FROM_THE_TRACE):
        assert by_name[name]["workloads"] == ["share16.evict"]
        assert by_name[name]["moves"] == "scan_rate"
    for name, m in by_name.items():
        if name not in WANT and name not in FROM_THE_TRACE:
            assert "share16.evict" not in m["workloads"], name
    (cell,) = [w for w in bench["workloads"] if w["name"] == "share16.evict"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "tempo-search-share4", "evict", 1)
    (rate,) = [m for m in bench["end_to_end"] if m["name"] == "scan_rate"]
    assert rate["workloads"][-1] == "share16.evict"


def test_the_configuration_names_no_cache_size():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "tempo-search-share4.json")) as f:
        conf = json.load(f)
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "tempo-search-share16.json")) as f:
        base = json.load(f)
    assert conf["yaml"] == base["yaml"]
    assert "cache_bytes" not in json.dumps(conf["yaml"])
    widths = {k: v for k, v in conf["corpus"].items()
              if k not in ("tenant", "blocks")}
    assert widths == {k: v for k, v in base["corpus"].items()
                      if k not in ("tenant", "blocks")}
    assert conf["corpus"]["blocks"] == 1536 and len(conf["guarantees"]) == 3


# ---- op `search_aged`


def test_a_window_lies_inside_the_corpus_and_ends_on_a_whole_hour():
    man = _manifest()
    newest = man["time_base"] + man["time_span_s"]
    for seed in (3, 2**31 + 77):
        reqs, ops = _requests("evict", seed)
        assert [len(o["pool"]) for o in ops] == [12, 8, 6, 2]
        assert len({r["path"] for r in reqs}) == 28
        for r in reqs:
            q = r["ref"]
            assert r["op"] == "search_aged" and not q.get("exhaustive")
            assert q["end"] - q["start"] in (3600, 21600, 86400)
            assert man["time_base"] <= q["start"] < q["end"] <= newest
            assert (newest - q["end"]) % 3600 == 0
            assert f"start={q['start']}&end={q['end']}" in r["path"]
        assert all(reqs[i]["ref"]["end"] == newest for i in ops[3]["pool"])
    a, _ = _requests("evict", 2**31 + 77)
    assert [r["path"] for r in a] == [r["path"] for r in reqs]


def test_ages_follow_the_law_by_strata():
    """In one seed the newest hour is there as often as Zipf(1.1) over
    the window's positions has it (to within one request); over many
    seeds every age has its share; and which variant looks how far back
    is shuffled, so the most popular service is not always the one with
    the newest window."""
    from chipbench.ops import search_aged

    man = _manifest()
    newest = man["time_base"] + man["time_span_s"]
    assert [search_aged.age_positions(86400, w)
            for w in (3600, 21600, 86400)] == [24, 19, 1]
    w = 1.0 / np.arange(1, 25) ** 1.1
    law = w / w.sum()
    counts, n, first_is_newest = np.zeros(24), 0, 0
    for seed in range(300):
        reqs, ops = _requests("evict", seed)
        ages = [(newest - reqs[i]["ref"]["end"]) // 3600
                for i in ops[0]["pool"]]
        assert abs(ages.count(0) - law[0] * len(ages)) <= 1
        first_is_newest += ages[0] == 0
        for a in ages:
            counts[a] += 1
            n += 1
    assert np.abs(counts / n - law).max() < 0.01
    assert 0.15 < first_is_newest / 300 < 0.5
    # the eight coldest hours: a tenth of the one-hour windows
    assert counts[16:].sum() / n == pytest.approx(law[16:].sum(), abs=0.01)
    assert 0.07 < law[16:].sum() < 0.11


def test_the_op_refuses_a_program_whose_metrics_lack_the_guarantees_number(
        monkeypatch):
    """A program whose `/metrics` has no high-water gauge (the parent's)
    is not run under this traffic: the op exits before the first
    request."""
    from chipbench.ops import search_aged
    from tempo_tpu.observability.metrics import REGISTRY

    assert search_aged.publishes(search_aged.PEAK)
    assert not search_aged.publishes(search_aged.PEAK[:-6])
    text = REGISTRY.expose()
    monkeypatch.setattr(
        REGISTRY, "expose", lambda: "\n".join(
            line for line in text.splitlines() if PEAK not in line))
    with pytest.raises(SystemExit, match=PEAK):
        _requests("evict", 3)


# ---- the rehearsal


def test_rehearsal_of_the_evict_cell():
    """Every step of `share16.evict` at the tiny size: 12 blocks of 512
    pages in groups of at most 4,096 pages, against a budget of 300 MiB
    that cannot hold two of them, so the rehearsal evicts and stages
    again, in set-up and in the window. The CPU's profile has no device
    plane: the two `device_trace` readers find nothing here."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload",
         "share16.evict", "--seed", str(2**31 + 3000), "--seconds", "3",
         "--trace", "1", "--scale", "tiny"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=1500)
    lines = p.stdout.strip().splitlines()
    assert p.returncode == 3, p.stdout[-3000:] + p.stderr[-3000:]
    assert "REHEARSAL on cpu" in lines[-1]
    for name in WANT:
        assert name in lines[-1], lines[-1]
    assert "mismatches=0 (limit 0)" in p.stdout
    assert "jit misses inside the window=0" in p.stdout
    staged = int(p.stdout.split("groups staged=")[1].split()[0])
    assert staged > 12, "no group was staged twice: nothing was evicted"
