"""Deterministic tier-1 chaos suite (ISSUE 9): the fault-injection
harness drives every faultpoint through the REAL serving path and
asserts the robustness layer's contracts —

  - breaker lifecycle: trip at threshold, open routes host with ZERO
    device attempts, half-open probe recovers;
  - hang → watchdog timeout → host fallback, byte-identical vs an
    uninjected run, within the request deadline;
  - coalesced in-flight futures resubmit member queries on host;
  - deadline propagation through a sharded frontend query (partial
    answer, never a hang);
  - disarmed-noop byte identity (breaker off + faults disarmed runs the
    historical inline path);
  - docs drift: every faultpoint and every robustness knob documented.

Byte-identity canon: `device_seconds` is measured wall time and
`inspected_bytes_device` moves to the host side under fallback BY
DESIGN (the placement split must tell the truth), so identity is
asserted on the canonical response — traces + the deterministic
metrics — exactly the determinism stance the frontend takes by zeroing
device_seconds on external responses.
"""

from __future__ import annotations

import json
import os
import time

import pytest

from tempo_tpu import robustness, tempopb
from tempo_tpu.backend.local import LocalBackend
from tempo_tpu.backend.types import (
    BlockMeta,
    NAME_SEARCH,
    NAME_SEARCH_HEADER,
)
from tempo_tpu.db import TempoDB, TempoDBConfig
from tempo_tpu.encoding.v2.compression import compress
from tempo_tpu.observability import metrics as obs
from tempo_tpu.robustness.breaker import CLOSED, HALF_OPEN, OPEN
from tempo_tpu.robustness.faults import CATALOG
from tempo_tpu.search.columnar import ColumnarPages, PageGeometry

import numpy as np

from conftest import check_budget, drop_hbm

_ROOT = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture(autouse=True)
def _clean_robustness():
    """Every test starts closed/disarmed and leaves no armed faultpoint
    or tripped breaker behind for the rest of the suite."""
    robustness.FAULTS.disarm_all()
    robustness.BREAKER.reset()
    robustness.BREAKER.enabled = True
    robustness.BREAKER.threshold = 3
    robustness.BREAKER.window_s = 30.0
    robustness.BREAKER.cooldown_s = 5.0
    robustness.GUARD.timeout_s = 30.0
    robustness.GUARD.lock_timeout_s = 60.0
    yield
    robustness.FAULTS.disarm_all()
    robustness.BREAKER.reset()
    robustness.BREAKER.enabled = True
    robustness.GUARD.timeout_s = 30.0
    robustness.GUARD.lock_timeout_s = 60.0


def _corpus(n_entries: int, seed: int,
            extra_vals: tuple = ()) -> ColumnarPages:
    """Small corpus with unique start seconds: the identity assertions
    here are about the control plane, not tie arbitration (equal start
    seconds resolve to the lowest flat index on every path:
    tests/test_topk.py)."""
    rng = np.random.default_rng(seed)
    E, C = 256, 4
    P = -(-n_entries // E)
    key_dict = sorted(["service.name", "http.status_code"])
    services = [f"svc-{i:02d}" for i in range(8)]
    statuses = ["200", "500"]
    val_dict = sorted(set(services + statuses + list(extra_vals)))
    vidx = {v: i for i, v in enumerate(val_dict)}
    kv_key = np.full((P, E, C), -1, dtype=np.int32)
    kv_val = np.full((P, E, C), -1, dtype=np.int32)
    svc = rng.integers(0, len(services), size=(P, E))
    st = rng.integers(0, len(statuses), size=(P, E))
    kv_key[:, :, 0] = key_dict.index("service.name")
    kv_val[:, :, 0] = np.array(
        [vidx[s] for s in services], dtype=np.int32)[svc]
    kv_key[:, :, 1] = key_dict.index("http.status_code")
    kv_val[:, :, 1] = np.array(
        [vidx[s] for s in statuses], dtype=np.int32)[st]
    # unique, shuffled start seconds
    starts = rng.permutation(P * E).astype(np.uint32).reshape(P, E) + 1000
    durs = rng.integers(1, 5000, size=(P, E)).astype(np.uint32)
    valid = np.zeros((P, E), dtype=bool)
    flat = np.arange(P * E).reshape(P, E)
    valid[flat < n_entries] = True
    trace_ids = rng.integers(0, 255, size=(P, E, 16), dtype=np.uint8)
    return ColumnarPages(
        geometry=PageGeometry(entries_per_page=E, kv_per_entry=C),
        key_dict=key_dict, val_dict=val_dict,
        kv_key=kv_key, kv_val=kv_val,
        entry_start=starts, entry_end=starts + durs // 1000 + 1,
        entry_dur=durs, entry_valid=valid, trace_ids=trace_ids,
        entry_root_svc=np.full((P, E), -1, dtype=np.int32),
        entry_root_name=np.full((P, E), -1, dtype=np.int32),
        n_entries=n_entries,
        header={"n_entries": n_entries, "n_pages": P,
                "entries_per_page": E, "kv_per_entry": C},
    )


def _mkdb(tmp_path, n_blocks: int = 4, n_entries: int = 4096,
          **cfg_kw) -> TempoDB:
    cfg_kw.setdefault("auto_mesh", False)
    be = LocalBackend(str(tmp_path / "blocks"))
    db = TempoDB(be, str(tmp_path / "wal"), TempoDBConfig(**cfg_kw))
    metas = []
    for s in range(n_blocks):
        pages = _corpus(n_entries, seed=100 + s)
        m = BlockMeta(tenant_id="t", encoding="none")
        blob = compress(pages.to_bytes(), "none")
        hdr = dict(pages.header)
        hdr["encoding"] = "none"
        hdr["compressed_size"] = len(blob)
        be.write("t", m.block_id, NAME_SEARCH, blob)
        be.write("t", m.block_id, NAME_SEARCH_HEADER,
                 json.dumps(hdr).encode())
        metas.append(m)
    db.blocklist.update("t", add=metas)
    return db


def _req(limit: int = 50) -> tempopb.SearchRequest:
    req = tempopb.SearchRequest()
    req.tags["service.name"] = "svc-03"
    req.tags["http.status_code"] = "500"
    req.limit = limit
    return req


def _canon(resp: tempopb.SearchResponse) -> bytes:
    r = tempopb.SearchResponse()
    r.CopyFrom(resp)
    r.metrics.device_seconds = 0.0       # measured wall time
    r.metrics.inspected_bytes_device = 0  # placement moves under fallback
    return r.SerializeToString()


# ---------------------------------------------------------------- registry


def test_registry_arm_disarm_active_flag():
    F = robustness.FAULTS
    assert not F.active
    F.arm("poll_error", count=2)
    assert F.active
    F.disarm("poll_error")
    assert not F.active
    with pytest.raises(ValueError):
        F.arm("no_such_faultpoint")


def test_count_auto_disarm_and_fired_accounting():
    F = robustness.FAULTS
    F.arm("poll_error", count=2)
    for _ in range(2):
        with pytest.raises(robustness.InjectedFault):
            F.hit("poll_error")
    assert not F.active  # count exhausted -> auto-disarm
    F.hit("poll_error")  # disarmed: no-op
    assert F.snapshot()["fired_total"]["poll_error"] == 2


def test_spec_parsing_and_context_manager():
    F = robustness.FAULTS
    F.arm_spec("poll_error:count=1,p=1; flush_error:delay=0.01,raise=0")
    snap = F.snapshot()["armed"]
    assert snap["poll_error"]["count"] == 1
    assert snap["flush_error"]["delay_s"] == 0.01
    assert snap["flush_error"]["raises"] is False
    F.disarm_all()
    with F.armed("backend_read_error"):
        assert F.active
    assert not F.active


def test_probability_zero_never_fires():
    with robustness.FAULTS.armed("poll_error", probability=0.0):
        robustness.FAULTS.hit("poll_error")  # must not raise


# ------------------------------------------------------------ breaker unit


def test_breaker_lifecycle_trip_halfopen_recover():
    b = robustness.CircuitBreaker(threshold=2, window_s=10.0,
                                  cooldown_s=0.05, enabled=True)
    assert b.allow_device() and b.state == CLOSED
    b.record_fault("error", mode="batched")
    assert b.state == CLOSED
    b.record_fault("timeout", mode="batched")
    assert b.state == OPEN and b.blocking()
    assert not b.allow_device()          # open, cooldown not elapsed
    time.sleep(0.06)
    assert b.allow_device()              # half-open probe token granted
    assert b.state == HALF_OPEN
    assert not b.allow_device()          # probe tokens spent
    b.record_success()
    assert b.state == CLOSED and not b.blocking()
    assert b.snapshot()["transitions"]["half_open->closed"] == 1


def test_breaker_halfopen_fault_reopens():
    b = robustness.CircuitBreaker(threshold=1, cooldown_s=0.05,
                                  enabled=True)
    b.record_fault("timeout")
    time.sleep(0.06)
    assert b.allow_device()              # the recovery probe
    b.record_fault("timeout")            # ...fails
    assert b.state == OPEN
    assert not b.allow_device()          # cooldown restarted


def test_breaker_disabled_is_passthrough():
    b = robustness.CircuitBreaker(threshold=1, enabled=False)
    b.record_fault("error")
    assert b.allow_device() and not b.blocking() and b.state == CLOSED


def test_breaker_halfopen_token_regrant_after_silent_probe():
    """A granted probe token whose consumer never dispatches (its group
    pruned away, its request early-quit/deadlined) must not wedge the
    breaker in half-open forever: after another cooldown a new probe is
    granted."""
    b = robustness.CircuitBreaker(threshold=1, cooldown_s=0.05,
                                  enabled=True)
    b.record_fault("timeout")
    time.sleep(0.06)
    assert b.allow_device()       # probe token granted... and goes silent
    assert not b.allow_device()   # tokens spent, cooldown not elapsed
    time.sleep(0.06)
    assert b.allow_device()       # re-granted — recovery still possible
    b.record_success()
    assert b.state == CLOSED


# --------------------------------------------------- serving-path fallback


def test_dispatch_raise_falls_back_byte_identical(tmp_path):
    db = _mkdb(tmp_path)
    req = _req()
    base = _canon(db.search("t", req).response())
    robustness.BREAKER.reset()
    with robustness.FAULTS.armed("device_dispatch_raise", count=100):
        got = _canon(db.search("t", req).response())
    assert got == base
    assert obs.scan_dispatches.value(mode="host_fallback") >= 1
    assert obs.device_faults.value(kind="error", mode="batched") >= 1


def test_dispatch_hang_times_out_within_deadline(tmp_path):
    """The acceptance scenario: device_dispatch_hang mid-query → search
    returns byte-identical results via host fallback, bounded by the
    watchdog (no hung thread), breaker books the fault."""
    db = _mkdb(tmp_path)
    req = _req()
    base = _canon(db.search("t", req).response())
    robustness.BREAKER.reset()
    robustness.GUARD.timeout_s = 0.3
    faults0 = obs.device_faults.value(kind="timeout", mode="batched")
    with robustness.FAULTS.armed("device_dispatch_hang", delay_s=5.0,
                                 count=1):
        t0 = time.perf_counter()
        got = _canon(db.search("t", req).response())
        wall = time.perf_counter() - t0
    assert got == base
    assert wall < 3.0, f"hang leaked into the caller ({wall:.2f}s)"
    assert obs.device_faults.value(kind="timeout", mode="batched") \
        == faults0 + 1


def test_breaker_trips_and_open_routes_host_with_zero_dispatches(tmp_path):
    db = _mkdb(tmp_path)
    req = _req()
    base = _canon(db.search("t", req).response())
    robustness.BREAKER.reset()
    robustness.BREAKER.threshold = 3
    with robustness.FAULTS.armed("device_dispatch_raise", count=1000):
        for _ in range(3):
            assert _canon(db.search("t", req).response()) == base
        assert robustness.BREAKER.state == OPEN
        # while open, nothing reaches the (armed!) dispatch site
        fired0 = robustness.FAULTS.snapshot()["fired_total"][
            "device_dispatch_raise"]
        assert _canon(db.search("t", req).response()) == base
        assert robustness.FAULTS.snapshot()["fired_total"][
            "device_dispatch_raise"] == fired0
    assert robustness.BREAKER.state == OPEN


def test_breaker_recovers_through_half_open(tmp_path):
    db = _mkdb(tmp_path)
    req = _req()
    base = _canon(db.search("t", req).response())
    robustness.BREAKER.reset()
    robustness.BREAKER.threshold = 1
    robustness.BREAKER.cooldown_s = 0.05
    with robustness.FAULTS.armed("device_dispatch_raise", count=1):
        assert _canon(db.search("t", req).response()) == base
    assert robustness.BREAKER.state == OPEN
    time.sleep(0.06)  # cooldown elapses; fault is cleared (count=1)
    assert _canon(db.search("t", req).response()) == base
    snap = robustness.BREAKER.snapshot()
    assert snap["state"] == CLOSED
    assert snap["transitions"]["open->half_open"] == 1
    assert snap["transitions"]["half_open->closed"] == 1


def test_h2d_hang_host_routes_group(tmp_path):
    db = _mkdb(tmp_path)
    req = _req()
    base = _canon(db.search("t", req).response())
    drop_hbm(db.batcher)               # force a re-stage
    robustness.BREAKER.reset()
    robustness.GUARD.timeout_s = 0.3
    with robustness.FAULTS.armed("h2d_delay", delay_s=5.0, count=1):
        t0 = time.perf_counter()
        got = _canon(db.search("t", req).response())
        wall = time.perf_counter() - t0
    assert got == base
    assert wall < 3.0
    assert obs.device_faults.value(kind="timeout", mode="h2d") >= 1


def test_fallback_with_coalescer_disabled(tmp_path):
    """coalesce_max_queries <= 1 takes the DIRECT dispatch path — a
    DeviceFault there must host-fallback too, not fail the query."""
    db = _mkdb(tmp_path, search_coalesce_max_queries=1)
    req = _req()
    base = _canon(db.search("t", req).response())
    robustness.BREAKER.reset()
    with robustness.FAULTS.armed("device_dispatch_raise", count=100):
        got = _canon(db.search("t", req).response())
    assert got == base


def test_drain_resubmit_no_double_skip_count(tmp_path):
    """A dict-pruned block's skip is booked once by the main loop; the
    drain-time host resubmit must not book it again (skipped_blocks
    would inflate and break wedged-vs-healthy identity)."""
    be = LocalBackend(str(tmp_path / "blocks"))
    db = TempoDB(be, str(tmp_path / "wal"), TempoDBConfig(auto_mesh=False))
    for seed, extra in ((1, ("special-xyz",)), (2, ())):
        pages = _corpus(2048, seed=seed, extra_vals=extra)
        m = BlockMeta(tenant_id="t", encoding="none")
        blob = compress(pages.to_bytes(), "none")
        hdr = dict(pages.header)
        hdr["encoding"] = "none"
        hdr["compressed_size"] = len(blob)
        be.write("t", m.block_id, NAME_SEARCH, blob)
        be.write("t", m.block_id, NAME_SEARCH_HEADER,
                 json.dumps(hdr).encode())
        db.blocklist.update("t", add=[m])
    req = tempopb.SearchRequest()
    req.tags["service.name"] = "special-xyz"
    req.limit = 10
    healthy = db.search("t", req).response()
    # block 2's dictionary lacks the value: exactly one dict-prune
    assert healthy.metrics.skipped_blocks == 1
    robustness.BREAKER.reset()
    with robustness.FAULTS.armed("device_dispatch_raise", count=1):
        wedged = db.search("t", req).response()
    assert wedged.metrics.skipped_blocks == 1
    assert _canon(wedged) == _canon(healthy)


def test_one_block_search_host_fallback(tmp_path):
    """One block searched by its meta (TempoDB.search_meta, the
    ingester's recently-completed leg) is a one-block batch through the
    batcher: it honors the breaker and falls back byte-identically on
    DeviceFault."""
    from tempo_tpu.search import SearchResults

    db = _mkdb(tmp_path, n_blocks=1)
    m = db.blocklist.metas("t")[0]
    req = _req()

    def search():
        results = SearchResults.for_request(req)
        db.search_meta(m, req, results)
        return results.response().SerializeToString()

    base = search()
    robustness.BREAKER.reset()
    with robustness.FAULTS.armed("device_dispatch_raise", count=100):
        got = search()
    assert got == base
    # breaker forced open: host route, zero dispatch attempts
    for _ in range(3):
        robustness.BREAKER.record_fault("timeout")
    assert robustness.BREAKER.state == OPEN
    before = obs.scan_dispatches.value(mode="host_fallback")
    assert search() == base
    assert obs.scan_dispatches.value(mode="host_fallback") > before


def test_coalesced_inflight_resubmits_members_on_host(tmp_path):
    """A fused multi-query dispatch that faults delivers DeviceFault to
    every member future; each member's drain resubmits ITS query on the
    host path — all answers stay byte-identical to serial."""
    import threading

    db = _mkdb(tmp_path, n_blocks=4,
               search_coalesce_window_s=0.05, search_coalesce_max_queries=4)
    reqs = []
    for i in range(4):
        r = tempopb.SearchRequest()
        r.tags["service.name"] = f"svc-{i:02d}"
        r.limit = 30
        reqs.append(r)
    serial = [_canon(db.search("t", r).response()) for r in reqs]
    robustness.BREAKER.reset()
    robustness.BREAKER.threshold = 100   # keep it closed: test the drain
    got = [None] * 4
    with robustness.FAULTS.armed("device_dispatch_raise", count=2):
        barrier = threading.Barrier(4)

        def worker(i):
            barrier.wait()
            got[i] = _canon(db.search("t", reqs[i]).response())

        ts = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
    assert got == serial


def test_deadline_propagates_through_sharded_frontend(tmp_path):
    """An expired request deadline makes a sharded frontend query come
    back PARTIAL (marked, counted) — fast — instead of stacking
    sub-queries behind a wedged device."""
    from tempo_tpu.modules.app import App, AppConfig
    from tempo_tpu.modules.frontend import FrontendConfig

    app = App(AppConfig(
        wal_dir=str(tmp_path / "wal"),
        db=TempoDBConfig(auto_mesh=False),
        frontend=FrontendConfig(query_shards=4)))
    tr = _trace_batches()
    app.push("t", tr)
    app.flush_tick(force=True)
    app.poll_tick()
    req = tempopb.SearchRequest()
    req.limit = 10
    # healthy: a generous deadline changes nothing
    with robustness.deadline.start(30.0):
        ok = app.search("t", req)
    assert not ok.metrics.partial
    # an already-expired deadline: partial, immediate
    before = obs.partial_results.value(reason="deadline")
    with robustness.deadline.start(1e-9):
        time.sleep(0.01)
        t0 = time.perf_counter()
        resp = app.search("t", req)
        wall = time.perf_counter() - t0
    assert resp.metrics.partial
    # never-started batches count FAILED: the client can see how much
    # of the corpus went unsearched, not just that "something" did
    assert resp.metrics.failed_blocks >= 1
    assert wall < 5.0
    assert obs.partial_results.value(reason="deadline") > before
    # trace-by-id honors the deadline too: returns fast with the
    # unsearched shards counted failed instead of hanging
    with robustness.deadline.start(1e-9):
        time.sleep(0.01)
        t0 = time.perf_counter()
        tr_resp = app.find_trace("t", b"\x01" * 16)
        wall = time.perf_counter() - t0
    assert wall < 5.0
    assert tr_resp.metrics.failed_blocks >= 1


def _trace_batches():
    from tempo_tpu.utils.test_data import make_trace

    return list(make_trace(trace_id=b"\x01" * 16).batches)


def test_batcher_deadline_stops_queueing(tmp_path):
    db = _mkdb(tmp_path, n_blocks=4)
    req = _req()
    db.search("t", req)  # warm
    with robustness.deadline.start(1e-9):
        time.sleep(0.01)
        resp = db.search("t", req).response()
    assert resp.metrics.partial
    assert resp.metrics.inspected_blocks == 0  # nothing dispatched


def test_replica_error_partial_results(tmp_path):
    from tempo_tpu.modules.app import App, AppConfig

    app = App(AppConfig(wal_dir=str(tmp_path / "wal"),
                        db=TempoDBConfig(auto_mesh=False)))
    app.push("t", _trace_batches())
    q = app.queriers[0]
    req = tempopb.SearchRequest()
    req.limit = 10
    before = obs.partial_results.value(reason="replica")
    with robustness.FAULTS.armed("replica_error", count=10):
        resp = q.search_recent("t", req)
    assert resp.metrics.partial
    assert resp.metrics.failed_blocks >= 1
    assert obs.partial_results.value(reason="replica") > before
    # partial-ness survives the frontend merge
    from tempo_tpu.search import SearchResults

    merged = SearchResults(limit=10)
    merged.merge_response(resp)
    assert merged.metrics.partial


def test_flush_error_books_retry_not_loss(tmp_path):
    from tempo_tpu.modules.app import App, AppConfig

    app = App(AppConfig(wal_dir=str(tmp_path / "wal"),
                        db=TempoDBConfig(auto_mesh=False)))
    app.push("t", _trace_batches())
    with robustness.FAULTS.armed("flush_error", count=1):
        completed = app.flush_tick(force=True)
    assert completed == []  # first attempt injected away
    ing = next(iter(app.ingesters.values()))
    meta = ing.instance("t").complete_one(ignore_backoff=True)
    assert meta is not None  # retry lands; nothing lost


def test_poll_error_and_backend_read_error_surface(tmp_path):
    db = _mkdb(tmp_path)
    with robustness.FAULTS.armed("poll_error", count=1), \
            pytest.raises(robustness.InjectedFault):
        db.poll()
    (tmp_path / "b2").mkdir()
    db2 = _mkdb(tmp_path / "b2", n_blocks=2)
    db2.search("t", _req())  # warm headers
    # cold headers + injected read error: the DIRECT path surfaces the
    # flake loudly (the partial-swallow lives at the querier/frontend
    # layer, where it books tempo_search_partial_results_total)
    db2._headers.clear()
    db2._search_blocks.clear()
    db2._jobs_cache.clear()
    db2.batcher.cache.invalidate(set())
    with robustness.FAULTS.armed("backend_read_error", count=1), \
            pytest.raises(robustness.InjectedFault):
        db2.search("t", _req())
    # next query (fault exhausted) is healthy again
    assert db2.search("t", _req()).response().metrics.inspected_blocks == 2


def test_dispatch_lock_timeout_books_breaker_fault():
    from tempo_tpu.parallel import mesh

    robustness.BREAKER.reset()
    robustness.GUARD.lock_timeout_s = 0.1
    before = obs.dispatch_lock_timeouts.value()
    acquired = mesh.dispatch_lock.acquire()
    try:
        with pytest.raises(robustness.DispatchLockTimeout):
            with mesh.locked_collective():
                pass
    finally:
        if acquired:
            mesh.dispatch_lock.release()
    assert obs.dispatch_lock_timeouts.value() == before + 1
    assert robustness.BREAKER.snapshot()["faults_in_window"] >= 1


def test_disarmed_noop_byte_identity(tmp_path):
    """The noop contract: breaker off + faults disarmed answers
    byte-identically to breaker on (healthy device) — the guard's
    worker hop changes nothing but placement of the wait."""
    db = _mkdb(tmp_path)
    req = _req()
    robustness.BREAKER.enabled = True
    on = _canon(db.search("t", req).response())
    robustness.BREAKER.enabled = False
    assert not robustness.GUARD.active
    off = _canon(db.search("t", req).response())
    assert on == off


def test_status_device_block_reads_breaker(tmp_path):
    from tempo_tpu.observability.profile import device_status

    robustness.BREAKER.reset()
    robustness.BREAKER.enabled = True
    d = device_status()
    assert d["breaker"]["state"] == CLOSED
    assert d["wedged"] is False
    robustness.BREAKER.record_fault("timeout")
    robustness.BREAKER.record_fault("timeout")
    robustness.BREAKER.record_fault("timeout")
    d = device_status()
    assert d["breaker"]["state"] == OPEN
    assert d["wedged"] is True


def test_status_device_block_names_the_device():
    """/status names what the process runs on: backend, device kind and
    count, and per-device memory as the runtime reports it. On the CPU
    backend the allocator keeps no stats, so the memory keys are absent
    (not zero); a booked fault's text is readable in last_fault."""
    import jax

    from tempo_tpu.observability.profile import device_status

    devs = jax.devices()  # the test harness's backend is initialized
    d = device_status()
    assert d["backend"] == "cpu"
    assert d["device_kind"] == devs[0].device_kind
    assert d["device_count"] == len(devs)
    assert [x["id"] for x in d["devices"]] == [x.id for x in devs]
    for x in d["devices"]:
        assert "bytes_in_use" not in x and "peak_bytes_in_use" not in x
    robustness.BREAKER.reset()
    robustness.BREAKER.enabled = True
    try:
        robustness.BREAKER.record_fault(
            "error", mode="batched",
            detail="XlaRuntimeError: RESOURCE_EXHAUSTED: out of HBM")
        last = device_status()["breaker"]["last_fault"]
        assert "RESOURCE_EXHAUSTED" in last["detail"]
    finally:
        robustness.BREAKER.reset()
        robustness.BREAKER.enabled = False


def test_absorbed_device_error_is_logged_with_its_text(caplog):
    """The host route answers byte-identically, so the error-level log
    line is where a compiler refusal can be read."""
    import logging

    robustness.BREAKER.reset()
    robustness.BREAKER.enabled = True
    try:
        with caplog.at_level(logging.ERROR, logger="tempo_tpu.breaker"):
            with pytest.raises(robustness.DeviceDispatchError):
                robustness.GUARD.run("batched", _raise_xla_like)
        text = "\n".join(r.getMessage() for r in caplog.records)
        assert "kind=error mode=batched" in text
        assert "Mosaic failed to compile" in text
    finally:
        robustness.BREAKER.reset()
        robustness.BREAKER.enabled = False


def _raise_xla_like():
    raise RuntimeError("INTERNAL: Mosaic failed to compile TPU kernel")


def test_debug_faults_route_json(tmp_path):
    """/debug/faults is covered by test_debug_routes' generic contract;
    here: the payload carries catalog + armed + breaker and is
    json-serializable with a faultpoint armed."""
    from tempo_tpu.api.http import HTTPApi

    class _App:
        pass

    api = HTTPApi(_App(), debug_endpoints=True)
    with robustness.FAULTS.armed("h2d_delay", delay_s=0.5):
        code, body = api._debug_faults_route({})
    assert code == 200
    doc = json.loads(json.dumps(body))
    assert "h2d_delay" in doc["faults"]["armed"]
    assert set(doc["faults"]["catalog"]) == set(CATALOG)
    assert doc["breaker"]["state"] in (CLOSED, OPEN, HALF_OPEN)


# ------------------------------------------------- owner-routed HBM chaos


@pytest.fixture()
def _clean_ownership():
    from tempo_tpu.search.ownership import OWNERSHIP

    OWNERSHIP.reset()
    yield OWNERSHIP
    OWNERSHIP.reset()


def test_chaos_owner_death_mid_query(tmp_path, _clean_ownership):
    """Owner death mid-query: the owner's querier dies between batches
    of one request (replica_error armed on the recent leg too); retries
    land on the surviving non-owner, which answers through the host
    route — byte-identical to the ownership-disabled path, PARTIAL only
    for the injected replica legs, never a hang."""
    from tempo_tpu.modules.frontend import FrontendConfig, QueryFrontend
    from tempo_tpu.modules.querier import Querier
    from tempo_tpu.modules.ring import Ring
    from tempo_tpu.search import ownership

    db = _mkdb(tmp_path, n_blocks=6, search_max_batch_pages=8)
    q = Querier(db, Ring(), {})

    class _Dying:
        def __init__(self, inner, die=False):
            self.inner = inner
            self.db = inner.db
            self.die = die
            self.calls = 0

        def search_recent(self, tenant, req):
            return self.inner.search_recent(tenant, req)

        def search_blocks(self, breq):
            self.calls += 1
            if self.die:
                raise RuntimeError("owner died mid-query")
            return self.inner.search_blocks(breq)

    owner = _Dying(q)
    peer = _Dying(q)
    fe = QueryFrontend([owner, peer], FrontendConfig(retries=3))
    req = _req(limit=10_000)
    # baseline: ownership disabled, everyone healthy, replica fault
    # armed identically (count high enough to cover both runs' legs)
    with robustness.FAULTS.armed("replica_error", count=1000):
        base = _canon(fe.search("t", req))
        ownership.configure(enabled=True, members="m0,m1", self_id="m0",
                            groups=32)
        owner.die = True  # member 0's process is gone
        t0 = time.perf_counter()
        got = _canon(fe.search("t", req))
        wall = time.perf_counter() - t0
    assert got == base
    assert owner.calls >= 1  # the owner WAS tried first
    assert wall < 30.0


def test_chaos_wedged_owner_breaker_to_host_route(tmp_path,
                                                  _clean_ownership):
    """A wedged owner: its device dispatches hang, the watchdog faults
    them, the breaker opens, and every owned group degrades to the host
    route — byte-identical to the ownership-disabled uninjected run and
    bounded by the watchdog, with device_dispatch_hang armed."""
    from tempo_tpu.search import ownership

    db = _mkdb(tmp_path, n_blocks=6, search_max_batch_pages=8)
    req = _req(limit=10_000)
    base = _canon(db.search("t", req).response())
    ownership.configure(enabled=True, members="m0,m1", self_id="m0",
                        groups=32)
    # block ids are uuid4: be the member that owns a staged group's
    # anchor, or the wedge has no owner to hit (a run in some dozens)
    anchor = str(next(iter(db.batcher.cache.snapshot()["entries"]))[0][0])
    ownership.configure(self_id=ownership.OWNERSHIP.owner_of(anchor))
    robustness.BREAKER.reset()
    robustness.GUARD.timeout_s = 0.3
    with robustness.FAULTS.armed("device_dispatch_hang", delay_s=5.0,
                                 count=1000):
        t0 = time.perf_counter()
        got = _canon(db.search("t", req).response())
        wall = time.perf_counter() - t0
    assert got == base
    assert wall < 10.0  # watchdog-bounded, never the 5s hang per group
    # the wedge tripped the breaker; the non-owner share host-routed
    assert robustness.BREAKER.snapshot()["faults_in_window"] >= 1
    # and with the breaker now open: still byte-identical, zero device
    for _ in range(3):
        robustness.BREAKER.record_fault("timeout")
    assert robustness.BREAKER.state == OPEN
    assert _canon(db.search("t", req).response()) == base


def test_chaos_rebalance_under_load_4way(tmp_path, _clean_ownership):
    """Rebalance under load: 4 concurrent searchers while membership
    flips repeatedly — every answer byte-identical to the
    ownership-disabled path, deferred evictions keep the HBM accounting
    non-negative, and nothing hangs."""
    import threading

    from tempo_tpu.search import ownership

    db = _mkdb(tmp_path, n_blocks=6, search_max_batch_pages=8)
    reqs = []
    for i in range(4):
        r = tempopb.SearchRequest()
        r.tags["service.name"] = f"svc-{i:02d}"
        r.limit = 10_000
        reqs.append(r)
    serial = [_canon(db.search("t", r).response()) for r in reqs]
    ownership.configure(enabled=True, members="m0,m1", self_id="m0",
                        groups=32)
    stop = threading.Event()
    errors: list = []

    def searcher(i):
        while not stop.is_set():
            try:
                got = _canon(db.search("t", reqs[i]).response())
            except Exception as e:  # noqa: BLE001 — surfaced below
                errors.append(e)
                return
            if got != serial[i]:
                errors.append(AssertionError(
                    f"query {i} diverged mid-rebalance"))
                return

    ts = [threading.Thread(target=searcher, args=(i,)) for i in range(4)]
    for t in ts:
        t.start()
    memberships = (["m0"], ["m0", "m1"], ["m0", "m1", "m2"],
                   ["m1", "m0"], ["m0", "m1"])
    for round_ in range(3):
        for ms in memberships:
            db.rebalance_ownership(list(ms), self_id="m0",
                                   prestage=False)
            time.sleep(0.02)
    stop.set()
    for t in ts:
        t.join(timeout=60)
        assert not t.is_alive(), "searcher hung across rebalances"
    assert not errors, errors[:1]
    # accounting survived the churn: totals never went negative and a
    # final unpinned sweep leaves a consistent cache
    cache = db.batcher.cache
    with cache.group_lock:
        cache._run_deferred_evictions_locked()
    check_budget(cache)


# --------------------------------------- replicated ownership + hedging


def test_chaos_primary_death_mid_hedge(tmp_path, _clean_ownership):
    """Primary death MID-HEDGE: the promoted group's primary wedges
    past the hedge delay and then dies; the hedge already fired at the
    replica, the replica's answer wins, and the response stays
    byte-identical — the primary's late failure is swallowed by the
    race, never surfaced."""
    from tempo_tpu.modules.frontend import FrontendConfig, QueryFrontend
    from tempo_tpu.modules.querier import Querier
    from tempo_tpu.modules.ring import Ring
    from tempo_tpu.search import ownership
    from tempo_tpu.search.ownership import OWNERSHIP

    db = _mkdb(tmp_path, n_blocks=6, search_max_batch_pages=8)
    q = Querier(db, Ring(), {})

    class _DyingSlow:
        def __init__(self, inner):
            self.inner = inner
            self.db = inner.db
            self.wedged = False

        def search_recent(self, tenant, req):
            return self.inner.search_recent(tenant, req)

        def search_blocks(self, breq):
            if self.wedged:
                time.sleep(0.2)  # past the 20 ms hedge delay...
                raise RuntimeError("primary died mid-hedge")
            return self.inner.search_blocks(breq)

    primary, replica = _DyingSlow(q), _DyingSlow(q)
    fe = QueryFrontend([primary, replica], FrontendConfig(retries=3))
    req = _req(limit=10_000)
    base = _canon(fe.search("t", req))
    ownership.configure(enabled=True, members="m0,m1", self_id="m0",
                        groups=32, rf=2, hot_rate=0.01,
                        hedge_delay_ms=20)
    # one access per block promotes every group past the tiny threshold
    for m in db.blocklist.metas("t"):
        OWNERSHIP.record_access(m.block_id)
    won0 = obs.hedged_dispatches.value(result="hedge_won")
    primary.wedged = True  # member 0's process wedges, then dies
    t0 = time.perf_counter()
    got = _canon(fe.search("t", req))
    wall = time.perf_counter() - t0
    assert got == base
    assert wall < 30.0
    batches = fe._search_batches("t")
    if any(b[2] == 0 for b in batches):  # some group owned by m0
        assert obs.hedged_dispatches.value(result="hedge_won") > won0


def test_chaos_both_replicas_wedged_breaker_host_route(
        tmp_path, _clean_ownership):
    """Both replicas of every promoted group wedge at the device (the
    shared device dispatch hangs): the watchdog faults the dispatches,
    the breaker opens, every group — replicated or not — degrades to
    the host route, byte-identical and bounded by the watchdog."""
    from tempo_tpu.modules.frontend import FrontendConfig, QueryFrontend
    from tempo_tpu.modules.querier import Querier
    from tempo_tpu.modules.ring import Ring
    from tempo_tpu.search import ownership
    from tempo_tpu.search.ownership import OWNERSHIP

    db = _mkdb(tmp_path, n_blocks=6, search_max_batch_pages=8)
    q = Querier(db, Ring(), {})
    fe = QueryFrontend([q, q], FrontendConfig(retries=3))
    req = _req(limit=10_000)
    base = _canon(fe.search("t", req))
    ownership.configure(enabled=True, members="m0,m1", self_id="m0",
                        groups=32, rf=2, hot_rate=0.01,
                        hedge_delay_ms=10)
    for m in db.blocklist.metas("t"):
        OWNERSHIP.record_access(m.block_id)
    robustness.BREAKER.reset()
    robustness.GUARD.timeout_s = 0.3
    with robustness.FAULTS.armed("device_dispatch_hang", delay_s=5.0,
                                 count=1000):
        t0 = time.perf_counter()
        got = _canon(fe.search("t", req))
        wall = time.perf_counter() - t0
    assert got == base
    assert wall < 30.0  # watchdog-bounded, never a hang per attempt
    assert robustness.BREAKER.snapshot()["faults_in_window"] >= 1
    # breaker now forced open: still byte-identical, zero device
    for _ in range(3):
        robustness.BREAKER.record_fault("timeout")
    assert robustness.BREAKER.state == OPEN
    assert _canon(fe.search("t", req)) == base


def test_chaos_promotion_flapping_residency_conserved(
        tmp_path, _clean_ownership):
    """Promotion/demotion flapping under concurrent searchers: a
    background thread force-demotes every promoted group (far-future
    sweep) while the serving loop's heat feed re-promotes on each scan
    — every answer stays byte-identical and the HBM accounting never
    goes negative (cache total == sum of entries)."""
    import threading

    from tempo_tpu.search import ownership
    from tempo_tpu.search.ownership import OWNERSHIP

    db = _mkdb(tmp_path, n_blocks=4, search_max_batch_pages=8)
    req = _req(limit=10_000)
    base = _canon(db.search("t", req).response())
    ownership.configure(enabled=True, members="m0,m1", self_id="m0",
                        groups=32, rf=2, hot_rate=0.02)
    stop = threading.Event()
    errors: list = []

    def searcher():
        while not stop.is_set():
            try:
                got = _canon(db.search("t", req).response())
            except Exception as e:  # noqa: BLE001 — surfaced below
                errors.append(e)
                return
            if got != base:
                errors.append(AssertionError("diverged mid-flap"))
                return

    def flapper():
        while not stop.is_set():
            # far-future decay: every promoted group demotes, firing
            # the TempoDB hook's residency rebalance in background;
            # the next scan's record_access promotes again
            OWNERSHIP.sweep(now=time.monotonic() + 600.0)
            time.sleep(0.005)

    ts = [threading.Thread(target=searcher) for _ in range(3)]
    ts.append(threading.Thread(target=flapper))
    for t in ts:
        t.start()
    time.sleep(1.5)
    stop.set()
    for t in ts:
        t.join(timeout=60)
        assert not t.is_alive(), "hung under promotion flapping"
    assert not errors, errors[:1]
    up = obs.hbm_replica_promotions.value(dir="up")
    down = obs.hbm_replica_promotions.value(dir="down")
    assert up >= 1 and down >= 1  # it really flapped
    cache = db.batcher.cache
    with cache.group_lock:
        cache._run_deferred_evictions_locked()
    check_budget(cache)
    assert _canon(db.search("t", req).response()) == base


# ----------------------------------------------------------- docs drift


def test_faultpoint_catalog_documented():
    """Every registered faultpoint must appear in docs/robustness.md —
    the faultpoint twin of test_config_docs.py. Thin wrapper over the
    analysis drift engine's "faultpoints" catalog (same invariant the
    hand-rolled pre-PR-10 version enforced)."""
    from tempo_tpu.analysis.drift import catalog_findings

    findings = catalog_findings("faultpoints")
    assert not findings, (
        "faultpoints missing from docs/robustness.md catalog:\n"
        + "\n".join(f"{f.path}:{f.line}: {f.message}" for f in findings))


def test_robustness_knobs_documented():
    """Every robustness TempoDBConfig knob (search_breaker_*,
    search_*_timeout_s, robustness_*) must appear in both
    docs/robustness.md and docs/configuration.md — drift-engine
    catalog "robustness-knobs"."""
    from tempo_tpu.analysis.drift import catalog_findings

    findings = catalog_findings("robustness-knobs")
    assert not findings, (
        "robustness knobs missing from docs/robustness.md or "
        "docs/configuration.md:\n"
        + "\n".join(f"{f.path}:{f.line}: {f.message}" for f in findings))
