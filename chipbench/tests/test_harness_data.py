"""The harness is driven by data: a new configuration, traffic mix and
per-layer metric are new files and appended entries, nothing edited.
And the rest of a run, driven on the CPU past the look for a chip: a
sound run is correct, a run whose timed path is broken underneath is
not."""

import argparse
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench.tests.breakages import BREAKAGES  # noqa: E402


def test_new_cell_is_only_new_files(tmp_path):
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", ".cache"))
    for name in ("tempo_tpu", "native", "operations"):
        os.symlink(os.path.join(ROOT, name), tmp_path / name)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    first = bench["configs"][0]
    with open(os.path.join(ROOT, first["file"])) as f:
        config = json.load(f)
    config["name"] = "throwaway"
    config["corpus"]["tenant"] = "throwaway"
    (tmp_path / "chipbench/configs/throwaway.json").write_text(
        json.dumps(config))
    (tmp_path / "chipbench/traffic/throwaway-mix.json").write_text(json.dumps({
        "loop": "open", "rate": 15.0,
        "warm": {"seconds": 1, "bursts": [2], "burst_repeats": 1},
        "ops": [{"op": "search", "name": "only", "share": 1.0, "variants": 3,
                 "tags": {"cloud.region": {"draw": "strata"}},
                 "min_duration_quantile": "0.999"}]}))
    (tmp_path / "chipbench/layers/throwaway_count.py").write_text(
        "def compute(run):\n    return float(len(run['records']))\n")
    bench["configs"].append({
        "name": "throwaway", "source": "none", "reduced": [], "why": "test",
        "file": "chipbench/configs/throwaway.json"})
    bench["workloads"].append({
        "name": "throwaway.cell", "config": "throwaway",
        "traffic": "throwaway-mix", "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "throwaway_count", "unit": "1", "better": "higher",
        "source": "program_counter", "layer": "test",
        "moves": "search_p50_ms", "workloads": ["throwaway.cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jaxcache"))
    p = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload",
         "throwaway.cell", "--seed", str(2**31 + 7), "--seconds", "2",
         "--trace", "1", "--scale", "tiny"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    lines = p.stdout.strip().splitlines()
    assert p.returncode == 3, p.stdout[-3000:] + p.stderr[-3000:]
    assert "REHEARSAL on cpu" in lines[-1]
    assert "throwaway_count" in lines[-1]
    assert "mismatches=0 (limit 0)" in p.stdout
    assert all(ln.startswith("[platform=cpu") for ln in lines
               if not ln.startswith("ts="))
    for name in ("run.py", "client.py", "server.py", "xplane.py"):
        with open(os.path.join(ROOT, "chipbench", name)) as f:
            src = f.read()
        for w in bench["workloads"] + bench["configs"] + bench[
                "end_to_end"] + bench["per_layer"]:
            assert f'"{w["name"]}"' not in src, (name, w["name"])


def _ns(workload, seed):
    return argparse.Namespace(workload=workload, seed=seed, seconds=3.0,
                              trace=0, scale="tiny")


CASES = [("share16.triage", None), ("share16.triage", "answer-altered"),
         ("share16.triage", "block-skipped"), ("share16.scan", None),
         ("share16.scan", "block-skipped"), ("share16.scan", "answer-altered")]


@pytest.mark.parametrize("cell,breakage", CASES,
                         ids=[f"{c}-{b or 'sound'}" for c, b in CASES])
def test_correct_follows_the_timed_path(cell, breakage):
    from chipbench import run as harness

    seen = {}

    def hook(stage, state):
        seen.setdefault("state", state)
        if breakage is not None:
            BREAKAGES[breakage](stage, seen["state"])

    try:
        result, code = harness.run(_ns(cell, 2**31 + 99), hook=hook,
                                   require_tpu=False)
    finally:
        undo = seen.get("state", {}).get("undo")
        if undo:
            undo()
    assert code == 0 and result is not None
    assert result["correct"] is (breakage is None)
    assert result["attempted"] > 0
    if breakage is None:
        assert result["failed"] == 0
