"""Adaptive host/device offload planner (ISSUE 6 tentpole).

The contracts pinned here, from the acceptance criteria:

  - ``search_device_probe_min_vals <= 0`` forces host-only probing even
    with the planner enabled (the static threshold stays the floor);
  - planner-on vs planner-off results are byte-identical across the
    one-block, multi-block, coalesced, and mesh dispatch paths,
    whichever side the cost model picks (both placements are exact);
  - a cold process (empty profiler aggregates) makes a sane seeded
    decision instead of crashing or staging hundreds of MB blindly;
  - a fused/coalesced group plans once — repeated queries over a staged
    batch don't burn a decision per member;
  - decisions and predicted-vs-actual error surface at /debug/planner,
    and the offline replay tool rebuilds the model from a profiler dump.
"""

from __future__ import annotations

import json
import random
import threading

import numpy as np
import pytest

from tempo_tpu import tempopb
from tempo_tpu.search import dict_probe, pipeline, planner
from tempo_tpu.search.columnar import ColumnarPages, PageGeometry
from tempo_tpu.search.data import SearchData
from tempo_tpu.search.engine import query_device_params
from tempo_tpu.search.multiblock import (
    MultiBlockEngine,
    compile_multi,
    stack_blocks,
    stack_queries,
)
from tempo_tpu.search.pipeline import compile_query

from conftest import scan_batch, staged_dict


@pytest.fixture(autouse=True)
def _fresh_state(monkeypatch):
    """Cold planner + compile cache per test; planner disabled on exit
    (it is process-wide, like the profiler). These tests tell a device
    verdict from a host one by the product's hit mask, so every device
    product is held to the mask form (R_MAX 0): with the shipped rule
    their one-run needles would leave the probe as the host's ranges."""
    monkeypatch.setattr(dict_probe, "R_MAX", 0)
    pipeline._COMPILE_CACHE.clear()
    planner.configure(enabled=False, seed=False, reset=True)
    yield
    planner.configure(enabled=False, seed=True, reset=True)
    pipeline._COMPILE_CACHE.clear()


def _force(target: str) -> planner.OffloadPlanner:
    """Enable the planner with injected observations that make `target`
    win every probe decision — deterministic tests, no microbenchmark."""
    p = planner.configure(enabled=True, seed=False, reset=True)
    p.seed_on_first_use = False
    slow, fast = 10.0, 1e-7
    if target == "device":
        p.observe("host_probe", slow, nbytes=1024)
        p.observe("device_probe", fast, nbytes=1024)
    else:
        p.observe("host_probe", fast, nbytes=1024)
        p.observe("device_probe", slow, nbytes=1024)
    p.observe("h2d", fast, nbytes=1024)
    p.observe("pack", fast, nbytes=1024)
    for k in ("dispatch", "compile", "collective"):
        p._update(k, fast, 0)
    return p


def _mk_req(tags=None, **kw):
    req = tempopb.SearchRequest()
    for k, v in (tags or {}).items():
        req.tags[k] = v
    for k, v in kw.items():
        setattr(req, k, v)
    return req


def _corpus(n, seed, card=300):
    rng = random.Random(seed)
    out = []
    for i in range(n):
        tid = (seed.to_bytes(2, "big") + i.to_bytes(4, "big")).rjust(16, b"\x00")
        sd = SearchData(trace_id=tid)
        sd.start_s = 1_600_000_000 + seed * 1_000_000 + i
        sd.end_s = sd.start_s + 5
        sd.dur_ms = rng.randint(1, 30_000)
        sd.kvs = {"session.id": {f"session-{rng.randint(0, card - 1):04d}"},
                  "svc": {rng.choice(["frontend", "cart"])}}
        out.append(sd)
    return out


def _blocks(n=3, entries=150, small_tail=True):
    blocks = [ColumnarPages.build(_corpus(entries, seed=s),
                                  PageGeometry(32, 8)) for s in range(n)]
    if small_tail:
        blocks.append(ColumnarPages.build(_corpus(80, seed=9, card=3),
                                          PageGeometry(32, 8)))
    return blocks


# ---------------------------------------------------------------------------
# floor / override semantics


def test_threshold_off_forces_host_even_with_planner_enabled():
    """`search_device_probe_min_vals <= 0` is host-only, planner or not:
    the call sites never reach the planner below the floor."""
    _force("device")  # planner would demand device everywhere
    pages = ColumnarPages.build(_corpus(200, seed=1), PageGeometry(32, 8))

    assert staged_dict(pages, probe_min_vals=0) is None
    assert staged_dict(pages, probe_min_vals=-1) is None
    # no decision was ever burned: the floor short-circuits the planner
    snap = planner.PLANNER.snapshot()
    assert snap["decisions"] == {"host": 0, "device": 0}

    # ... and the batcher end to end: results identical to planner-off
    from tempo_tpu.search.batcher import BlockBatcher, ScanJob

    def jobs():
        return [ScanJob(key=("b0", 0, pages.n_pages),
                        pages_fn=lambda: pages, header=dict(pages.header),
                        n_pages=pages.n_pages, n_entries=pages.n_entries,
                        geometry=(pages.header["entries_per_page"],
                                  pages.header["kv_per_entry"]))]
    req = _mk_req({"session.id": "session-00"}, limit=500)
    r_on = BlockBatcher(coalesce_max_queries=1, device_probe_min_vals=0) \
        .search(jobs(), req).response().SerializeToString()
    planner.configure(enabled=False)
    pipeline._COMPILE_CACHE.clear()
    r_off = BlockBatcher(coalesce_max_queries=1, device_probe_min_vals=0) \
        .search(jobs(), req).response().SerializeToString()
    assert r_on == r_off


def test_planner_disabled_is_static_path():
    """Disabled planner == today's behavior: above the threshold the
    dictionary stages and the probe runs on device, no decisions."""
    planner.configure(enabled=False)
    pages = ColumnarPages.build(_corpus(200, seed=2), PageGeometry(32, 8))
    sd = staged_dict(pages)
    assert sd is not None
    cq = compile_query(pages.key_dict, pages.val_dict, _mk_req(
        {"session.id": "session-00"}, limit=100), staged_dict=sd)
    assert cq.val_hits is not None
    assert planner.PLANNER.snapshot()["decisions"] == {"host": 0,
                                                       "device": 0}


# ---------------------------------------------------------------------------
# byte-identity across dispatch paths, both verdicts


def _one_block_result(probe_min_vals):
    pages = ColumnarPages.build(_corpus(300, seed=3), PageGeometry(64, 8))
    req = _mk_req({"session.id": "session-00"}, limit=1000)
    got = scan_batch([pages], req, top_k=1024,
                     probe_min_vals=probe_min_vals)
    res = [(m.trace_id, m.start_time_unix_nano) for m in got.metas]
    return int(got.count), int(got.inspected), res, got


def test_one_block_batch_byte_identical_both_verdicts():
    planner.configure(enabled=False)
    base = _one_block_result(0)[:3]

    for verdict in ("device", "host"):
        _force(verdict)
        pipeline._COMPILE_CACHE.clear()
        count, inspected, res, got = _one_block_result(1)
        if verdict == "device":
            assert got.batch.staged_dicts
            assert got.mq.val_hits is not None
        else:
            # stage-time veto: the planner kept the dictionary on host
            assert not got.batch.staged_dicts
            assert got.mq.val_hits is None
        assert (count, inspected, res) == base, verdict


def test_compile_time_veto_over_staged_dict():
    """A dictionary already resident in HBM can still be HOST-probed
    when the model says the kernel loses (the CPU 10M case): the staged
    bytes stay, only the placement changes — results identical."""
    planner.configure(enabled=False)
    pages = ColumnarPages.build(_corpus(250, seed=4), PageGeometry(32, 8))
    req = _mk_req({"session.id": "session-01"}, limit=500)
    # staged while planner off
    dev = scan_batch([pages], req, top_k=1024, probe_min_vals=1)
    assert dev.batch.staged_dicts
    assert dev.mq.val_hits is not None
    out_dev = dev.out

    _force("host")
    pipeline._COMPILE_CACHE.clear()
    host = scan_batch([pages], req, engine=dev.engine, batch=dev.batch)
    assert host.batch.staged_dicts   # the staged bytes stay
    assert host.mq.val_hits is None  # vetoed at compile time
    out_host = host.out
    assert out_dev[0] == out_host[0] and out_dev[1] == out_host[1]
    assert np.array_equal(out_dev[2], out_host[2])
    # the compile-site decision landed in the ring with its inputs
    snap = planner.PLANNER.snapshot()
    assert snap["decisions"]["host"] >= 1
    assert any(d["site"] == "compile" and d["target"] == "host"
               for d in snap["recent"])


def test_multiblock_and_coalesced_byte_identical_both_verdicts():
    blocks = _blocks()
    reqs = [_mk_req({"session.id": v}, limit=1000)
            for v in ("session-001", "session-01")]
    planner.configure(enabled=False)
    eng = MultiBlockEngine(top_k=1024)
    batch_off = stack_blocks(blocks, pad_to=32, probe_min_vals=50)
    base = []
    for req in reqs:
        mq = compile_multi(blocks, req, cache_on=batch_off)
        out = eng.scan(batch_off, mq)
        base.append((out[0], out[1],
                     [(m.trace_id, m.start_time_unix_nano)
                      for m in eng.results(batch_off, mq, out[2], out[3])]))

    for verdict in ("device", "host"):
        _force(verdict)
        pipeline._COMPILE_CACHE.clear()
        batch = stack_blocks(blocks, pad_to=32, probe_min_vals=50)
        if verdict == "host":
            assert not batch.staged_dicts  # stage-time veto
        else:
            assert len(batch.staged_dicts) == 3
        mqs = []
        for i, req in enumerate(reqs):
            mq = compile_multi(blocks, req, cache_on=batch)
            out = eng.scan(batch, mq)
            got = (out[0], out[1],
                   [(m.trace_id, m.start_time_unix_nano)
                    for m in eng.results(batch, mq, out[2], out[3])])
            assert got == base[i], (verdict, i)
            mqs.append(mq)
        # coalesced fused dispatch over the same batch, same verdicts
        cq = stack_queries(mqs)
        counts = eng.fetch(eng.coalesced_scan_async(batch, cq, 1024), cq)[0]
        for qi in range(len(mqs)):
            assert counts[qi] == base[qi][0], (verdict, qi)


def test_mesh_byte_identical_both_verdicts():
    from tempo_tpu.parallel.mesh import make_mesh

    mesh = make_mesh()
    blocks = _blocks(n=2, entries=256, small_tail=False)
    req = _mk_req({"session.id": "session-00"}, limit=1000)

    planner.configure(enabled=False)
    eng_off = MultiBlockEngine(top_k=1024)
    batch_off = eng_off.stage(blocks)
    mq_off = compile_multi(blocks, req, cache_on=batch_off)
    out_base = eng_off.scan(batch_off, mq_off)
    ids_base = {m.trace_id for m in eng_off.results(
        batch_off, mq_off, out_base[2], out_base[3])}

    for verdict in ("device", "host"):
        _force(verdict)
        pipeline._COMPILE_CACHE.clear()
        eng = MultiBlockEngine(top_k=1024, mesh=mesh,
                               device_probe_min_vals=50)
        batch = eng.stage(blocks)
        assert bool(batch.staged_dicts) == (verdict == "device")
        mq = compile_multi(blocks, req, cache_on=batch)
        assert (mq.val_hits is not None) == (verdict == "device")
        out = eng.scan(batch, mq)
        assert out[0] == out_base[0] and out[1] == out_base[1]
        ids = {m.trace_id
               for m in eng.results(batch, mq, out[2], out[3])}
        assert ids == ids_base, verdict


def test_one_block_batch_mesh_staged_dict_and_identity():
    """One block as a one-block batch over the mesh stages the
    dictionary value-axis-sharded and yields host-identical results;
    a threshold of 0 keeps the probe on the host."""
    from tempo_tpu.parallel.mesh import make_mesh

    mesh = make_mesh()
    pages = ColumnarPages.build(_corpus(256, seed=5), PageGeometry(32, 8))
    req = _mk_req({"session.id": "session-00"}, limit=1000)

    assert not MultiBlockEngine(
        mesh=mesh, device_probe_min_vals=0).stage([pages]).staged_dicts

    planner.configure(enabled=False)
    dist = scan_batch([pages], req, top_k=1024, mesh=mesh,
                      probe_min_vals=1)
    (sd,) = dist.batch.staged_dicts.values()
    assert sd.mesh is mesh
    assert dist.mq.val_hits is not None
    out = dist.out

    pipeline._COMPILE_CACHE.clear()
    host = scan_batch([pages], req, top_k=1024, probe_min_vals=0)
    assert host.mq.val_hits is None
    out_h = host.out
    assert out[0] == out_h[0] and out[1] == out_h[1]
    assert np.array_equal(np.sort(out[2]), np.sort(out_h[2]))


def test_batcher_concurrent_planner_on_identical():
    """Concurrent coalesced searches with the planner choosing device
    serialize to the same bytes as solo planner-off runs."""
    from tempo_tpu.search.batcher import BlockBatcher, ScanJob

    blocks = _blocks(n=2, small_tail=False)

    def jobs():
        out = []
        for i, p in enumerate(blocks):
            out.append(ScanJob(
                key=(f"blk-{i:03d}", 0, p.n_pages), pages_fn=(lambda p=p: p),
                header=dict(p.header), n_pages=p.n_pages,
                n_entries=p.n_entries,
                geometry=(p.header["entries_per_page"],
                          p.header["kv_per_entry"])))
        return out

    reqs = [_mk_req({"session.id": f"session-0{i:02d}"[:11]}, limit=200)
            for i in range(4)]
    planner.configure(enabled=False)
    serial_b = BlockBatcher(coalesce_max_queries=1, device_probe_min_vals=10)
    serial = [serial_b.search(jobs(), r).response().SerializeToString()
              for r in reqs]

    _force("device")
    pipeline._COMPILE_CACHE.clear()
    co_b = BlockBatcher(coalesce_window_s=0.05, coalesce_max_queries=4,
                        device_probe_min_vals=10)
    co_b.search(jobs(), reqs[0])  # warm staging + compile
    barrier = threading.Barrier(len(reqs))
    got = [None] * len(reqs)

    def worker(i):
        barrier.wait()
        got[i] = co_b.search(jobs(), reqs[i]).response().SerializeToString()

    ts = [threading.Thread(target=worker, args=(i,))
          for i in range(len(reqs))]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert got == serial


# ---------------------------------------------------------------------------
# planning cost: once per group, not per member/query


def test_plans_once_per_group_and_memoizes_repeats():
    from tempo_tpu.search.batcher import BlockBatcher, ScanJob

    blocks = _blocks(n=2, small_tail=False)
    jobs = [ScanJob(key=(f"blk-{i:03d}", 0, p.n_pages),
                    pages_fn=(lambda p=p: p), header=dict(p.header),
                    n_pages=p.n_pages, n_entries=p.n_entries,
                    geometry=(p.header["entries_per_page"],
                              p.header["kv_per_entry"]))
            for i, p in enumerate(blocks)]
    _force("device")
    b = BlockBatcher(coalesce_max_queries=1, device_probe_min_vals=10)
    req = _mk_req({"session.id": "session-01"}, limit=100)
    b.search(jobs, req)
    first = planner.PLANNER.snapshot()["decisions"]
    # 2 distinct dictionaries: one stage + one compile decision each
    assert first["device"] + first["host"] == 4
    b.search(jobs, req)  # repeat: staged batch + compile cache hit
    again = planner.PLANNER.snapshot()["decisions"]
    assert again == first, "a repeated query over a staged group re-planned"


def test_host_veto_memoized_per_dictionary():
    """Blocks sharing one dictionary get ONE stage-site decision even
    when the verdict is host (a veto produces no staged entry to dedup
    on — the vetoed-fingerprint memo must dedup instead, or a 64-block
    batch books 64 duplicate decisions into the ring and metrics)."""
    from tempo_tpu.search.multiblock import _pack_batch_dicts

    p = _force("host")
    base = _corpus(60, seed=3)
    shared = [ColumnarPages.build(base, PageGeometry(32, 8))
              for _ in range(4)]  # same entries -> same dictionary
    out = _pack_batch_dicts(shared, probe_min_vals=5)
    assert out == {}  # host verdict: nothing staged
    dec = p.snapshot()["decisions"]
    assert dec["host"] == 1, dec  # one shared dict -> one decision


# ---------------------------------------------------------------------------
# cold start / seeding


def test_cold_process_decides_without_crashing():
    """Empty aggregates + seeding enabled: the first decision runs the
    microbenchmark and returns finite costs (no guessing, no crash)."""
    p = planner.configure(enabled=True, seed=True, reset=True)
    d = p.decide_probe(n_vals=10_000_000, dict_bytes=160 << 20,
                       resident=False, site="stage")
    assert d.target in ("host", "device")
    assert 0 < d.predicted_host_s < float("inf")
    assert 0 < d.predicted_device_s < float("inf")
    snap = p.snapshot()
    assert snap["seeded"] is True
    assert snap["seed_ms"] is not None
    # the seed populated every decision-consumed per-byte rate ("scan"
    # is observational — it fills from the first live scan dispatches)
    for kind in planner.SEEDED_KINDS:
        assert snap["cost_model"]["rates"][kind]["observations"] > 0


def test_seed_does_not_double_feed_and_cold_stage_predicts_compile():
    """The seed microbenchmark's own probe dispatch emits a dict_probe
    record + h2d staging observation through the profiler; the listener
    gate must keep those from landing ON TOP of the seed's direct
    updates (contradictory EWMA samples). And a seeded-but-otherwise
    cold process must still predict the first-shape XLA compile for
    stage-site decisions — the first real dictionary WILL pay it."""
    from tempo_tpu.observability import profile

    profile.configure(enabled=True)
    p = planner.configure(enabled=True, seed=True, reset=True)
    d = p.decide_probe(n_vals=10_000_000, dict_bytes=160 << 20,
                       resident=False, site="stage")
    snap = p.snapshot()
    assert snap["seeded"] is True
    # exactly the seed's one direct update per rate — the seed dispatch's
    # profiler record did not double-feed device_probe or h2d
    assert snap["cost_model"]["rates"]["device_probe"]["observations"] == 1
    assert snap["cost_model"]["rates"]["h2d"]["observations"] == 1
    # no real probe has run yet: the stage-site prediction charges the
    # compile cost (the seed's rates deliberately don't clear this)
    assert d.inputs["jit_miss"] is True
    p.observe("device_probe", 0.01, nbytes=800 << 20)  # a real probe
    d2 = p.decide_probe(n_vals=10_000_000, dict_bytes=160 << 20,
                        resident=False, site="stage")
    assert d2.inputs["jit_miss"] is False


def test_cold_process_does_not_stage_huge_dict_blindly():
    """With a slow observed H2D, a non-resident 720 MB dictionary
    must NOT be staged: the staging bytes dominate any probe win."""
    p = planner.configure(enabled=True, seed=False, reset=True)
    p.seed_on_first_use = False
    p.observe("h2d", 1.0, nbytes=50 << 20)       # a slow ~50 MB/s H2D
    p.observe("host_probe", 0.35, nbytes=160 << 20)  # PR4's measured 312ms/10M
    p.observe("device_probe", 0.01, nbytes=800 << 20)  # chip-fast probe
    d = p.decide_probe(n_vals=10_000_000, dict_bytes=160 << 20,
                       resident=False, site="stage")
    assert d.target == "host"
    # once resident, the same dictionary flips to the fast device probe
    d2 = p.decide_probe(n_vals=10_000_000, dict_bytes=160 << 20,
                        resident=True, staged_bytes=800 << 20,
                        site="compile")
    assert d2.target == "device"


# ---------------------------------------------------------------------------
# calibration: predicted vs actual, metrics, /debug/planner, offline replay


def test_predicted_vs_actual_resolution():
    p = _force("device")
    fp = b"\xaa" * 32
    d = p.decide_probe(n_vals=1000, dict_bytes=10_000, resident=True,
                       staged_bytes=50_000, fp=fp, site="compile")
    assert d.target == "device" and d.actual_s is None
    p.observe("device_probe", d.predicted_probe_s * 2, nbytes=50_000, fp=fp)
    snap = p.snapshot()
    rec = next(r for r in snap["recent"] if r.get("fp") == fp.hex()[:16])
    assert rec["actual_probe_ms"] > 0
    assert abs(rec["abs_rel_error"] - 0.5) < 0.01  # pred = actual/2
    assert snap["mispredict"]["observations"] == 1


def test_compile_record_resolves_compile_inclusive():
    """A compile-stage dispatch record measures trace+compile+run in one
    wall time; resolving it against the probe-only prediction would book
    ~100% error on every correctly predicted cold-shape compile. The
    resolution must include the decision's predicted compile cost."""
    p = _force("device")
    for _ in range(100):  # converge the compile EWMA to ~0.5s
        p._update("compile", 0.5, 0)
    fp = b"\xbb" * 32
    d = p.decide_probe(n_vals=1000, dict_bytes=10_000, resident=True,
                       staged_bytes=50_000, fp=fp, site="compile",
                       shape_key=("never-seen-shape", 0))
    assert d.target == "device" and d.inputs["jit_miss"]
    assert d.predicted_compile_s > 0.1  # the compile term was charged
    actual_s = d.predicted_probe_s + d.predicted_compile_s  # spot-on
    n = p.ingest_record({
        "mode": "dict_probe",
        "stages_ms": {"compile": actual_s * 1e3},
        "attrs": {"probe_bytes": 50_000, "fp": fp.hex()[:16]},
    })
    assert n >= 1
    rec = next(r for r in p.snapshot()["recent"]
               if r.get("fp") == fp.hex()[:16])
    assert rec["abs_rel_error"] < 0.01  # NOT ~1.0


def test_profiler_listener_feeds_device_rate():
    """A finished dict_probe dispatch record (the profiler's listener
    path) updates the device rate and resolves the pending decision."""
    from tempo_tpu.observability import profile

    p = _force("device")
    profile.configure(enabled=True)
    pages = ColumnarPages.build(_corpus(150, seed=6), PageGeometry(32, 8))
    staged = staged_dict(pages)
    # the probe's program compiled where this process had not yet: a
    # jit miss books `compile`, and only an `execute` feeds the rate
    compile_query(pages.key_dict, pages.val_dict,
                  _mk_req({"session.id": "session-02"}, limit=20),
                  cache_on=pages, staged_dict=staged)
    before = p.snapshot()["cost_model"]["rates"]["device_probe"][
        "observations"]
    cq = compile_query(pages.key_dict, pages.val_dict,
                       _mk_req({"session.id": "session-01"}, limit=20),
                       cache_on=pages, staged_dict=staged)
    assert cq is not None and cq.val_hits is not None
    after = p.snapshot()["cost_model"]["rates"]["device_probe"][
        "observations"]
    assert after > before


def test_debug_planner_endpoint():
    from tempo_tpu.api.http import HTTPApi

    _force("host")
    planner.PLANNER.decide_probe(n_vals=100, dict_bytes=1000,
                                 site="compile")
    api = HTTPApi(app=None)
    code, body = api.handle("GET", "/debug/planner", {}, {})
    assert code == 200
    assert body["enabled"] is True
    assert body["decisions"]["host"] >= 1
    assert body["recent"], "decision ring empty"
    code, body = api.handle("GET", "/debug/planner", {"recent": "0"}, {})
    assert code == 200 and body["recent"] == []
    # gated off with the other /debug routes
    api_off = HTTPApi(app=None, debug_endpoints=False)
    code, body = api_off.handle("GET", "/debug/planner", {}, {})
    assert code == 404


def test_offline_replay_from_profile_snapshot(tmp_path, capsys):
    """scripts/calibrate_offload.py rebuilds the cost model from a
    /debug/profile dump and prints the decision table."""
    snap = {
        "dispatches": 3,
        "aggregates": {
            "host_probe": {"build": {"count": 4, "total_ms": 1200.0,
                                     "mean_ms": 300.0,
                                     "bytes": 4 * (160 << 20)}},
            "dict_probe": {"h2d": {"count": 2, "total_ms": 30000.0,
                                   "mean_ms": 15000.0,
                                   "bytes": 2 * (800 << 20)}},
        },
        "recent": [{
            "mode": "dict_probe",
            "stages_ms": {"build": 0.2, "execute": 12.0},
            "attrs": {"probe_bytes": 800 << 20, "fp": "ab" * 8},
        }],
    }
    p = planner.OffloadPlanner(enabled=True, seed=False)
    n = p.ingest_profile_snapshot(snap)
    assert n >= 3
    # chip-fast probe + slow H2D: big non-resident dict stays host,
    # resident flips device
    d_cold = p.decide_probe(n_vals=10_000_000, dict_bytes=160 << 20,
                            resident=False, site="offline")
    d_warm = p.decide_probe(n_vals=10_000_000, dict_bytes=160 << 20,
                            resident=True, staged_bytes=800 << 20,
                            site="offline")
    assert d_cold.target == "host" and d_warm.target == "device"

    import importlib.util
    import os

    path = os.path.join(os.path.dirname(__file__), "..", "scripts",
                        "calibrate_offload.py")
    spec = importlib.util.spec_from_file_location("calibrate_offload", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    dump = tmp_path / "profile.json"
    dump.write_text(json.dumps(snap))
    assert mod.main([str(dump), "--recent", "2"]) == 0
    out = capsys.readouterr().out
    assert "decision table" in out and "10000000" in out
    assert "host" in out and "device" in out


def test_planner_metrics_documented_and_incremented():
    from tempo_tpu.observability import metrics as obs

    p = _force("host")
    before = obs.offload_decisions.value(target="host", site="compile")
    p.decide_probe(n_vals=100, dict_bytes=1000, site="compile")
    assert obs.offload_decisions.value(target="host",
                                       site="compile") == before + 1


# ---------------------------------------------------------------------------
# satellite: value-memoized device scalars


def test_device_scalar_params_shared_across_queries():
    """Two distinct compiled queries with the same (default) bounds must
    reuse the SAME device scalar arrays — each scalar put is its own
    host→device transfer (engine.py docstring)."""
    from tempo_tpu.search.engine import device_scalar

    pages = ColumnarPages.build(_corpus(50, seed=7), PageGeometry(32, 8))
    mq1 = compile_multi([pages],
                        _mk_req({"session.id": "session-00"}, limit=20))
    mq2 = compile_multi([pages], _mk_req({"svc": "frontend"}, limit=20))
    p1 = query_device_params(mq1)
    p2 = query_device_params(mq2)
    for i in (2, 3, 4, 5):  # dur_lo, dur_hi, win_start, win_end
        assert p1[i] is p2[i]
    assert device_scalar(12345) is device_scalar(12345)
    # cached params still yield correct scans
    eng = MultiBlockEngine(top_k=64)
    batch = eng.stage([pages])
    c1 = eng.scan(batch, mq1)[0]
    c2 = eng.scan(batch, mq2)[0]
    assert query_device_params(mq1) is p1
    assert c1 >= 0 and c2 >= 0
