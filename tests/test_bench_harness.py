"""Hang-proofing of the bench harness, and no CPU stand-in.

The harness runs each phase in its own subprocess with its own deadline
and checkpoints results as they land; these tests prove a hung phase
loses only itself, that a device which does not answer the preflight
probe ends the run non-zero with nothing measured, and that no run
without an accelerator exits 0 or fills the headline.

All children run with JAX_PLATFORMS=cpu and tiny corpora so the suite
stays fast; that makes every run here a harness dry run (exit code 4,
headline zeroed), which is exactly the contract under test. The hang is
simulated with the documented BENCH_TEST_HANG_PHASE hook (a hang is a
hang — the orchestrator cannot tell a sleeping child from one stuck in
a device op).
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")

TINY = {
    "JAX_PLATFORMS": "cpu",
    "BENCH_ENTRIES": "8192",
    "BENCH_ITERS": "2",
    "BENCH_BLOCKS": "2",
    "BENCH_CARDINALITY_FULL": "0",
    "BENCH_SCALE_BLOCKS": "0",
    "BENCH_LARGE_BLOCKS": "0",
}


def run_bench(tmp_path, extra_env, timeout=240):
    env = dict(os.environ)
    env.update(TINY)
    env["BENCH_CKPT_DIR"] = str(tmp_path / "ckpt")
    env.update(extra_env)
    p = subprocess.run(
        [sys.executable, BENCH], env=env, cwd=REPO, timeout=timeout,
        capture_output=True, text=True)
    lines = [ln for ln in p.stdout.strip().splitlines() if ln.startswith("{")]
    assert lines, f"no JSON line emitted\nstderr: {p.stderr[-2000:]}"
    return p.returncode, json.loads(lines[-1])


def _is_cpu_dry_run(doc):
    """No accelerator: the headline is the device's and stays empty."""
    return (doc["detail"]["platform"] == "cpu" and doc["value"] == 0
            and doc["vs_baseline"] == 0
            and "no accelerator" in doc["error"])


@pytest.mark.slow
def test_hung_phase_loses_only_itself(tmp_path):
    rc, doc = run_bench(tmp_path, {
        "BENCH_PHASES": "single,multiblock,serving",
        "BENCH_TEST_HANG_PHASE": "multiblock",
        "BENCH_TIMEOUT_MULTIBLOCK": "4",
    })
    cfg = doc["detail"]["configs"]
    # the phases before and after the hang kept their numbers
    assert cfg["duration_only_traces_per_sec"] > 0
    assert cfg["serving_path"]["p50_ms"] > 0
    # the hung phase is an explicit error, not silence — and a caught
    # phase failure never becomes exit 0
    assert "timed out" in cfg["multiblock"]["error"]
    assert rc == 3


@pytest.mark.slow
def test_hung_headline_still_reports_other_phases(tmp_path):
    rc, doc = run_bench(tmp_path, {
        "BENCH_PHASES": "single,multiblock",
        "BENCH_TEST_HANG_PHASE": "single",
        "BENCH_TIMEOUT_SINGLE": "4",
    })
    assert doc["value"] == 0
    assert "timed out" in doc["error"]
    assert doc["detail"]["configs"]["multiblock"]["traces_per_sec"] > 0
    assert rc == 3  # headline lost → failure exit, but numbers present


@pytest.mark.slow
def test_preflight_probe_failure_is_explicit(tmp_path):
    # hang the probe itself: the emitted line must say the device never
    # answered, within the probe deadline, and nothing is measured
    rc, doc = run_bench(tmp_path, {
        "BENCH_TEST_HANG_PHASE": "probe",
        "BENCH_TIMEOUT_PROBE": "4",
        "BENCH_WATCHDOG_S": "30",
    })
    assert rc == 3
    assert doc["value"] == 0
    assert "preflight" in doc["error"] or "probe" in doc["error"]
    assert doc["detail"]["platform"] == "unknown"


@pytest.mark.slow
def test_probe_failure_has_no_cpu_stand_in(tmp_path):
    # ONE hung probe (counted hang hook) and the run is over: no second
    # attempt on another platform, no phase runs, no number appears
    # under any metric name. BENCH_CPU_FALLBACK, were anyone to still
    # set it, changes nothing.
    rc, doc = run_bench(tmp_path, {
        "BENCH_PHASES": "single",
        "BENCH_TEST_HANG_PHASE": "probe",
        "BENCH_TEST_HANG_TIMES": "1",
        "BENCH_TIMEOUT_PROBE": "4",
        "BENCH_CPU_FALLBACK": "1",
    }, timeout=300)
    assert rc == 3
    assert doc["value"] == 0 and doc["vs_baseline"] == 0
    assert "degraded" not in doc
    assert "1x" in doc["error"]
    cfg = doc["detail"]["configs"]
    assert cfg["duration_only_traces_per_sec"] is None
    assert all(v is None for v in cfg.values())


@pytest.mark.slow
def test_preflight_attempts_env_configurable(tmp_path):
    # BENCH_PREFLIGHT_ATTEMPTS=3: probes 1-2 hang, the third answers and
    # the run goes on — on the platform the probe names, here cpu, so it
    # is a dry run and still not exit 0
    rc, doc = run_bench(tmp_path, {
        "BENCH_PHASES": "single",
        "BENCH_PREFLIGHT_ATTEMPTS": "3",
        "BENCH_TEST_HANG_PHASE": "probe",
        "BENCH_TEST_HANG_TIMES": "2",
        "BENCH_TIMEOUT_PROBE": "4",
    }, timeout=300)
    assert rc == 4
    assert _is_cpu_dry_run(doc)
    assert doc["detail"]["configs"]["duration_only_traces_per_sec"] > 0


@pytest.mark.slow
def test_run_without_accelerator_never_exits_zero(tmp_path):
    # every phase succeeds, and the run is still not a measurement: the
    # doc names platform cpu, the headline is empty, the exit code is 4
    rc, doc = run_bench(tmp_path, {"BENCH_PHASES": "single,scale_10k",
                                   "BENCH_SCALE_BLOCKS": "4",
                                   "BENCH_SCALE_ENTRIES": "128"},
                        timeout=420)
    assert rc == 4
    assert _is_cpu_dry_run(doc)
    scale = doc["detail"]["configs"]["scale_10k"]
    assert "error" not in scale, scale
    assert scale["blocks"] == 4
    # the restart child (a second process needing the chip its parent
    # holds) is gone, and so are the degraded-mode markers
    assert not any(k.startswith("restart_") for k in scale)
    assert "degraded_reduced_size" not in scale


@pytest.mark.slow
def test_unselected_headline_is_not_a_failure(tmp_path):
    rc, doc = run_bench(tmp_path, {"BENCH_PHASES": "multiblock"})
    assert rc == 4  # all selected phases ok; still no accelerator
    assert doc["detail"]["configs"]["multiblock"]["traces_per_sec"] > 0
    assert "partial" in doc


def test_assemble_keeps_the_headline_for_the_device():
    """The final doc names the device the probe found, and only a run
    on an accelerator fills the headline: on platform cpu the same
    phase results assemble to value 0 and an explicit error."""
    sys.path.insert(0, REPO)
    import bench

    single = {"n_entries": 8192, "tpu_traces_per_sec": 1000,
              "cpu_traces_per_sec": 100, "matches": 3,
              "duration_only_traces_per_sec": 900}
    serving = {"traces_per_sec": 10, "p50_ms": 1.0, "p95_ms": 2.0}
    tpu = {"ok": True, "platform": "tpu", "device_kind": "TPU v5 lite",
           "device_count": 1, "device": "TPU_0", "sync_ms": 0.05}
    doc = bench._assemble({"probe": tpu, "single": single,
                           "serving": serving})
    assert doc["value"] == 1000 and doc["vs_baseline"] == 10.0
    assert doc["detail"]["device_kind"] == "TPU v5 lite"
    assert doc["detail"]["device_count"] == 1
    assert doc["detail"]["configs"]["serving_path"]["sync_floor_ms"] == 0.05
    assert "error" not in doc

    cpu = dict(tpu, platform="cpu", device_kind="cpu")
    doc = bench._assemble({"probe": cpu, "single": single})
    assert _is_cpu_dry_run(doc)
    assert "degraded" not in doc and "device_wedged" not in doc


def test_assemble_surfaces_dict_probe_trajectory():
    """The host-prefilter vs device-probe timings of BOTH high-
    cardinality phases must land at detail.dict_probe in the final doc
    (the round-over-round trajectory for the PR4 optimization) — and a
    failed phase must drop out instead of contributing nulls."""
    sys.path.insert(0, REPO)
    import bench

    hc = {"distinct_values": 1_000_000, "traces_per_sec": 100,
          "dict_prefilter_ms": 38.0, "matches": 5,
          "device_probe_ms": 2.5, "device_probe_stage_ms": 40.0,
          "device_probe_rate": 120}
    full = dict(hc, distinct_values=10_000_000, dict_prefilter_ms=312.0)
    doc = bench._assemble({"high_cardinality": hc,
                           "high_cardinality_full": full})
    traj = doc["detail"]["dict_probe"]
    assert traj["high_cardinality"]["dict_prefilter_ms"] == 38.0
    assert traj["high_cardinality"]["device_probe_ms"] == 2.5
    assert traj["high_cardinality_full"]["distinct_values"] == 10_000_000
    assert traj["high_cardinality_full"]["device_probe_stage_ms"] == 40.0

    doc = bench._assemble({"high_cardinality": hc,
                           "high_cardinality_full": {"error": "hung"}})
    assert list(doc["detail"]["dict_probe"]) == ["high_cardinality"]
    assert bench._assemble({}).get("detail", {}).get("dict_probe") is None


@pytest.mark.slow
def test_checkpoints_land_per_phase(tmp_path):
    rc, doc = run_bench(tmp_path, {"BENCH_PHASES": "single"})
    assert rc == 4  # a dry run: platform cpu
    ckpt = tmp_path / "ckpt"
    single = json.loads((ckpt / "single.json").read_text())
    assert single["data"]["tpu_traces_per_sec"] > 0
    assert single["_fp"]["jax_platforms"] == "cpu"  # resume fingerprint
    assert json.loads((ckpt / "final.json").read_text())["value"] == 0
