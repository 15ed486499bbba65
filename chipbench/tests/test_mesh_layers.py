"""The readers of what a mesh adds, each on a hand-made run (two device
planes with known collective ops, known counters) with the value worked
by hand, and on a program or a trace that lacks what it reads: the
reader returns None and the metric is left out of the line."""

import os

import numpy as np
import pytest

from chipbench import xplane
from chipbench.tests.test_span_layers import EMPTY, Spans, reader

HERE = os.path.dirname(os.path.abspath(__file__))
STAGE = "tempo_search_dispatch_stage_seconds"
LAUNCHES = "tempo_search_scan_dispatches_total"


def reduced(name):
    from jax.profiler import ProfileData

    with open(os.path.join(HERE, name)) as f:
        return xplane.reduce(ProfileData.from_text_proto(f.read()))


@pytest.fixture
def run():
    """Window 1,000-21,000 ns on two devices. Each runs one solo and
    one fused mesh launch (a third lies outside the window):
      device 0: programs 4,000 + 6,000 ns, busy 10,000, of which
                collectives 500 + 100 + 400 + 1,000 = 2,000
      device 1: programs 5,000 + 7,000 ns, busy 12,000, of which
                collectives 100 + 100 + 300 + 500 = 1,000
    `while.3` holds `fusion.7` nested: busy counts the union.
    Counters: 300 mesh launches (240 solo, 60 fused) and 2 one-device
    ones, 10 searches completed; lock_wait 0.6 s, d2h 3 s over 300."""
    s = Spans()
    s.add("batcher.Search", 0, 10, groups=2)
    s.add("batcher.Search", 0, 10, groups=1)
    return {
        "trace": reduced("mesh_trace_fixture.textproto"), "spans": s.out,
        "device_kind": "TPU v5 lite",
        "config": {"chips": {"count": 4}},
        "manifest": {"pages": 8, "kv_per_entry": 16,
                     "key_names": tuple(f"k{i}" for i in range(16)),
                     "present": np.ones((3, 200), dtype=bool)},
        "requests": [{"op": "search"}],
        "records": [{"i": 0, "status": 200}] * 10 + [{"i": 0, "status": 500}],
        "counters": {
            "before": {
                LAUNCHES: {'{mode="batched",shards="4"}': 10.0,
                           '{mode="coalesced",shards="4"}': 5.0,
                           '{mode="batched",shards="1"}': 7.0},
                STAGE + "_sum": {'{mode="mesh",stage="lock_wait"}': 1.0,
                                 '{mode="mesh",stage="d2h"}': 2.0,
                                 '{mode="mesh",stage="execute"}': 9.0},
                STAGE + "_count": {'{mode="mesh",stage="lock_wait"}': 100.0,
                                   '{mode="mesh",stage="d2h"}': 50.0,
                                   '{mode="mesh",stage="execute"}': 100.0}},
            "after": {
                LAUNCHES: {'{mode="batched",shards="4"}': 250.0,
                           '{mode="coalesced",shards="4"}': 65.0,
                           '{mode="batched",shards="1"}': 9.0},
                STAGE + "_sum": {'{mode="mesh",stage="lock_wait"}': 1.6,
                                 '{mode="mesh",stage="d2h"}': 5.0,
                                 '{mode="mesh",stage="execute"}': 19.0},
                STAGE + "_count": {'{mode="mesh",stage="lock_wait"}': 400.0,
                                   '{mode="mesh",stage="d2h"}': 350.0,
                                   '{mode="mesh",stage="execute"}': 400.0}}},
    }


# a group is 8 pages / 2 groups = 4 pages, a shard's part of it 2 pages
# of 1,024 entries of 16 x (1 + 2) + 13 = 61 B: 124,928 B a launch and
# device; four program calls at 819 GB/s over 22,000 ns of device time
ROOFLINE = 100.0 * (4 * 2 * 1024 * 61 / 819e9) / 22_000e-9

WANT = {
    "kernel_ms.mesh": 22_000 / 4 / 1e6,
    "mesh_kernel_roofline": ROOFLINE,
    "collective_share.mesh": 100.0 * 3_000 / 22_000,
    "device_skew.mesh": 1.2,
    "lock_wait_ms.mesh": 2.0,
    "launches_per_search.mesh": 30.0,
    "sync_ms.mesh": 10.0,
}


def test_the_fixture_reduces_as_worked_by_hand(run):
    t = run["trace"]
    assert [d["busy_ns"] for d in t["devices"]] == [10_000, 12_000]
    assert t["program_calls"] == {"jit_dist_multi_scan_kernel": 2,
                                  "jit_dist_coalesced_scan_kernel": 2}
    ops = dict(t["ops_ns"])
    assert ops["all-reduce.1"] == 600 and ops["all-gather-done.2"] == 700
    assert ROOFLINE == pytest.approx(2.7734, abs=1e-4)


@pytest.mark.parametrize("name", sorted(WANT))
def test_mesh_reader_on_a_run_that_exercises_it(run, name):
    assert reader(name)(run) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_mesh_reader_finds_nothing_and_says_so(run, name):
    """No trace and no counters at all; then a one-device run of a
    program from before the `shards` label: only the kernel's time per
    launch and the sync, which exist off a mesh too, have a reading."""
    assert reader(name)(dict(EMPTY, config={}, trace=None, records=[],
                             requests=[])) is None
    old = dict(run, trace=reduced("trace_fixture.textproto"), counters={
        side: {LAUNCHES: {'{mode="batched"}': 5.0 * i},
               STAGE + "_sum": {'{mode="batched",stage="d2h"}': 1.0 * i},
               STAGE + "_count": {'{mode="batched",stage="d2h"}': 10.0 * i}}
        for i, side in enumerate(("before", "after"), 1)})
    got = reader(name)(old)
    if name in ("kernel_ms.mesh", "sync_ms.mesh", "mesh_kernel_roofline"):
        assert got is not None and got > 0
    else:
        assert got is None


def test_the_collective_rule():
    is_collective = reader("collective_share.mesh").__globals__[
        "is_collective"]
    for op in ("all-reduce.3", "all-reduce-start.1", "all-reduce-done.1",
               "all-gather.2", "all-gather-start", "collective-permute.9",
               "all-to-all.1", "reduce-scatter.4"):
        assert is_collective(op), op
    for op in ("fusion.18", "while.3", "reduce.7", "and_reduce_fusion.2",
               "sort.28", "gather.3", "copy.2"):
        assert not is_collective(op), op


def test_every_mesh_metric_is_registered_for_the_mesh_cell_alone():
    import json

    with open(os.path.join(os.path.dirname(os.path.dirname(HERE)),
                           "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    four = [w["name"] for w in bench["workloads"] if w["chips"] == 4]
    assert four == ["share16x4.scan"]
    for name in WANT:
        assert by_name[name]["workloads"] == four
        assert by_name[name]["moves"] == "scan_rate"
    # the one-chip readers keep their cells: none was given the mesh cell
    for name, m in by_name.items():
        if name not in WANT:
            assert "share16x4.scan" not in m["workloads"], name


def _four_cpu_devices(argv, timeout=900):
    """A child on four virtual CPU devices: `run.py` refuses a cell
    whose `chips` JAX does not see, and this process has made its
    backend already."""
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(HERE))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    return subprocess.run([sys.executable] + argv, cwd=root, env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_rehearsal_of_the_mesh_cell():
    """Every step of `share16x4.scan` at the tiny size (two groups, both
    ragged on four shards), every launch a mesh launch. The CPU's
    profile has no device plane, so the four `device_trace` readers find
    nothing here (the hand-made trace above is their test); the three
    that read the program's counters are named."""
    p = _four_cpu_devices(
        ["-m", "chipbench.run", "--workload", "share16x4.scan", "--seed",
         str(2**31 + 2600), "--seconds", "3", "--trace", "1", "--scale",
         "tiny"])
    lines = p.stdout.strip().splitlines()
    assert p.returncode == 3, p.stdout[-3000:] + p.stderr[-3000:]
    assert "REHEARSAL on cpu" in lines[-1]
    for name in ("lock_wait_ms.mesh", "launches_per_search.mesh",
                 "sync_ms.mesh"):
        assert name in lines[-1], lines[-1]
    assert "[platform=cpu kind=cpu n=4]" in lines[0]
    assert "groups staged=2 " in p.stdout
    assert "mismatches=0 (limit 0)" in p.stdout
    assert "jit misses inside the window=0" in p.stdout


def test_control_on_the_mesh_cell():
    """The mesh cell with a block left out underneath: `correct` false."""
    p = _four_cpu_devices(["-c", (
        "import argparse, json\n"
        "from chipbench import run as harness\n"
        "from chipbench.tests.breakages import BREAKAGES\n"
        "seen = {}\n"
        "def hook(stage, state):\n"
        "    seen.setdefault('state', state)\n"
        "    BREAKAGES['block-skipped'](stage, seen['state'])\n"
        "ns = argparse.Namespace(workload='share16x4.scan', seed=2**31 + 2601,"
        " seconds=3.0, trace=0, scale='tiny')\n"
        "result, code = harness.run(ns, hook=hook, require_tpu=False)\n"
        "print('CONTROL', code, json.dumps(result))\n")])
    last = [ln for ln in p.stdout.splitlines() if ln.startswith("CONTROL")]
    assert last, p.stdout[-3000:] + p.stderr[-3000:]
    _, code, result = last[0].split(" ", 2)
    import json

    result = json.loads(result)
    assert code == "0" and result["correct"] is False
    assert result["device"]["count"] == 4 and result["attempted"] > 0
    assert "scan_rate" in result["metrics"]
