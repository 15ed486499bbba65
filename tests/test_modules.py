import os
import time

import pytest

from tempo_tpu import tempopb
from tempo_tpu.modules import App, AppConfig, Overrides, Limits, Ring
from tempo_tpu.modules.distributor import RateLimited, IngestError
from tempo_tpu.modules.frontend import create_block_boundaries
from tempo_tpu.modules.ingester import LimitError
from tempo_tpu.db import TempoDBConfig
from tempo_tpu.utils.ids import random_trace_id
from tempo_tpu.utils.test_data import make_trace

from tests.test_search import _mk_req


def _app(tmp_path, **kw):
    cfg = AppConfig(wal_dir=str(tmp_path / "wal"), **kw)
    return App(cfg)


def _push_traces(app, tenant, n, seed_base=0):
    traces = {}
    for i in range(n):
        tid = random_trace_id()
        tr = make_trace(tid, seed=seed_base + i)
        app.push(tenant, list(tr.batches))
        traces[tid] = tr
    return traces


# ---- ring ----

def test_ring_replication_and_health():
    ring = Ring(replication_factor=2)
    for i in range(3):
        ring.register(f"i{i}")
    got = ring.get(12345)
    assert len(got) == 2 and len(set(got)) == 2
    # same token → same placement
    assert ring.get(12345) == got
    # leaving shifts placement to the remaining healthy instances
    ring.leave(got[0])
    got2 = ring.get(12345)
    assert got[0] not in got2 and len(got2) == 2


def test_ring_owns_exactly_one():
    ring = Ring()
    for i in range(4):
        ring.register(f"i{i}")
    for token in (0, 123, 2**31, 2**32 - 1):
        owners = [i for i in ring.instance_ids() if ring.owns(i, token)]
        assert len(owners) == 1


# ---- overrides ----

def test_overrides_limits_and_reload():
    ov = Overrides(Limits(max_live_traces=5), {"vip": {"max_live_traces": 100}})
    assert ov.limits("any").max_live_traces == 5
    assert ov.limits("vip").max_live_traces == 100
    ov.reload({"any": {"max_live_traces": 7}})
    assert ov.limits("any").max_live_traces == 7
    assert ov.limits("vip").max_live_traces == 5


def test_overrides_rate_limit():
    ov = Overrides(Limits(ingestion_rate_bytes=100, ingestion_burst_bytes=100))
    assert ov.allow_ingestion("t", 80)
    assert not ov.allow_ingestion("t", 80)  # burst exhausted


# ---- write path e2e ----

def test_push_cut_complete_find(tmp_path):
    app = _app(tmp_path)
    traces = _push_traces(app, "t1", 20)

    # live lookup via frontend (ingester leg)
    tid, tr = next(iter(traces.items()))
    resp = app.find_trace(tid=tid, tenant="t1") if False else app.find_trace("t1", tid)
    assert len(resp.trace.batches) == len(tr.batches)

    # flush everything to the backend, then read the block leg
    completed = app.flush_tick(force=True)
    assert len(completed) == 1
    app.poll_tick()
    resp = app.find_trace("t1", tid)
    assert len(resp.trace.batches) == len(tr.batches)


def test_search_live_and_backend(tmp_path):
    app = _app(tmp_path)
    _push_traces(app, "t1", 30)

    req = _mk_req({})
    req.limit = 100
    # live (ingester) search before any flush
    resp = app.search("t1", req)
    assert len(resp.traces) == 30

    app.flush_tick(force=True)
    app.poll_tick()
    resp = app.search("t1", req)
    assert len(resp.traces) == 30

    # tag search against specific content
    req2 = _mk_req({"component": "db"})
    req2.limit = 100
    resp2 = app.search("t1", req2)
    assert 0 < len(resp2.traces) <= 30


def test_replication_factor_2_survives_one_down(tmp_path):
    app = _app(tmp_path, n_ingesters=3, replication_factor=2)
    traces = _push_traces(app, "t1", 10)

    # kill one ingester entirely: reads still find every trace
    dead = next(iter(app.ingesters))
    app.queriers[0].ingesters = dict(app.ingesters)
    del app.queriers[0].ingesters[dead]
    for tid in traces:
        resp = app.queriers[0].find_trace_by_id("t1", tid)
        assert len(resp.trace.batches) > 0, "trace lost with one replica down"


def test_ingester_replay_after_crash(tmp_path):
    app = _app(tmp_path)
    traces = _push_traces(app, "t1", 15)
    # cut live traces into the WAL head block but do NOT complete
    for ing in app.ingesters.values():
        ing.instance("t1").cut_complete_traces(force=True)

    # "crash": rebuild the app over the same wal dir + backend
    from tempo_tpu.modules.ingester import Ingester

    ing2 = Ingester(app.ingesters["ingester-0"].db, app.overrides,
                    instance_id="ingester-0")
    assert ing2.replayed_blocks >= 1
    completed = ing2.sweep(force=True)
    assert completed and completed[0].total_objects == 15

    app.poll_tick()
    tid = next(iter(traces))
    obj, _ = app.reader_db.find_trace_by_id("t1", tid)
    assert obj is not None

    # search WAL replayed too: search the completed block
    req = _mk_req({})
    req.limit = 100
    res = app.reader_db.search("t1", req)
    assert len(res.response().traces) == 15


def test_limits_enforced(tmp_path):
    app = _app(tmp_path)
    app.overrides.reload({"t1": {"max_live_traces": 3}})
    # the replica's LimitError surfaces through the distributor's quorum
    # check as an IngestError (the client-facing failure)
    with pytest.raises((LimitError, IngestError)):
        _push_traces(app, "t1", 10)

    app2 = _app(tmp_path / "b")
    app2.overrides.reload({"t1": {"ingestion_rate_bytes": 10,
                                  "ingestion_burst_bytes": 10}})
    with pytest.raises(RateLimited):
        _push_traces(app2, "t1", 5)


def test_multitenancy_isolated(tmp_path):
    app = _app(tmp_path)
    t1 = _push_traces(app, "t1", 5)
    t2 = _push_traces(app, "t2", 5)
    app.flush_tick(force=True)
    app.poll_tick()
    # t1 ids are not visible under t2
    tid = next(iter(t1))
    assert len(app.find_trace("t2", tid).trace.batches) == 0
    assert len(app.find_trace("t1", tid).trace.batches) > 0
    req = _mk_req({})
    req.limit = 100
    assert len(app.search("t2", req).traces) == 5


def test_block_boundaries_cover_space():
    bounds = create_block_boundaries(4)
    assert len(bounds) == 5
    assert bounds[0] == "00000000-0000-0000-0000-000000000000"
    assert bounds[-1] == "ffffffff-ffff-ffff-ffff-ffffffffffff"
    assert bounds == sorted(bounds)


def test_full_lifecycle_with_compaction(tmp_path):
    """ingest → flush → poll → compact → search + find still correct."""
    # fabricated traces sit at a 2020 epoch — disable retention so the
    # compacted output isn't immediately aged out
    app = _app(tmp_path, db=TempoDBConfig(compaction_window_s=10**10,
                                          retention_s=10**10))
    all_traces = {}
    for round_ in range(3):
        all_traces.update(_push_traces(app, "t1", 10, seed_base=round_ * 100))
        app.flush_tick(force=True)
    app.poll_tick()
    assert len(app.reader_db.blocklist.metas("t1")) == 3

    app.compaction_tick()
    live = app.reader_db.blocklist.metas("t1")
    assert len(live) == 1 and live[0].compaction_level == 1

    req = _mk_req({})
    req.limit = 100
    assert len(app.search("t1", req).traces) == 30
    tid = next(iter(all_traces))
    assert len(app.find_trace("t1", tid).trace.batches) > 0

    # shutdown flushes cleanly
    app.shutdown()


def test_ready_and_shutdown(tmp_path):
    app = _app(tmp_path)
    assert app.ready()
    _push_traces(app, "t1", 3)
    app.shutdown()
    app.poll_tick()
    req = _mk_req({})
    req.limit = 10
    res = app.reader_db.search("t1", req)
    assert len(res.response().traces) == 3


def test_startup_poll_failure_is_logged_not_swallowed(tmp_path, caplog):
    """A backend broken at boot must not be silent in single-binary
    mode: the immediate startup poll's exception is logged (as
    microservices.py logs it) and the loops stay alive."""
    import logging

    app = _app(tmp_path)

    def broken():
        raise RuntimeError("backend down at boot")

    app.poll_tick = broken
    try:
        with caplog.at_level(logging.ERROR, logger="tempo_tpu.app"):
            app.run_maintenance()
            deadline = time.time() + 5
            while time.time() < deadline and not any(
                    "startup maintenance tick" in r.getMessage()
                    for r in caplog.records):
                time.sleep(0.01)
        hit = [r for r in caplog.records
               if "startup maintenance tick" in r.getMessage()]
        assert hit and hit[0].exc_info \
            and "backend down at boot" in str(hit[0].exc_info[1])
    finally:
        del app.poll_tick
        app.shutdown()


def test_find_during_blocklist_poll_gap(tmp_path):
    """After a block completes but BEFORE the reader polls, traces must
    stay queryable via the ingester's recently-completed window
    (regression: complete_one dropped visibility until the next poll)."""
    app = _app(tmp_path)
    traces = _push_traces(app, "t1", 8)
    completed = app.flush_tick(force=True)
    assert completed
    # NOTE: no app.poll_tick() — reader blocklist is empty
    assert app.reader_db.blocklist.metas("t1") == []
    tid = next(iter(traces))
    resp = app.find_trace("t1", tid)
    assert len(resp.trace.batches) > 0
    req = _mk_req({})
    req.limit = 20
    assert len(app.search("t1", req).traces) == 8


def test_complete_one_restores_on_failure(tmp_path):
    """A failed backend write must not lose the completing block."""
    app = _app(tmp_path)
    _push_traces(app, "t1", 5)
    ing = app.ingesters["ingester-0"]
    inst = ing.instance("t1")
    inst.cut_complete_traces(force=True)
    inst.cut_block_if_ready(force=True)
    assert len(inst.completing) == 1

    real_write = app.backend.write
    app.backend.write = lambda *a, **k: (_ for _ in ()).throw(OSError("flake"))
    with pytest.raises(OSError):
        inst.complete_one()
    assert len(inst.completing) == 1  # restored, not lost
    app.backend.write = real_write
    inst.completing[0].retry_at = 0.0  # elapse the flush backoff window
    assert inst.complete_one() is not None  # retried successfully


# ---- round 2: page-range job sharding + batched dispatch + early quit ----

def _frontend_db(tmp_path, n_blocks=3, per_block=200, **db_kw):
    from tempo_tpu.backend.local import LocalBackend
    from tempo_tpu.db import TempoDB, TempoDBConfig
    from tempo_tpu.model.codec import codec_for
    from tempo_tpu.search.columnar import PageGeometry
    from tempo_tpu.search.data import extract_search_data

    db = TempoDB(LocalBackend(str(tmp_path / "blocks")), str(tmp_path / "w"),
                 TempoDBConfig(search_geometry=PageGeometry(32, 16), **db_kw))
    codec = codec_for("v2")
    all_sds = []
    for b in range(n_blocks):
        objs, sds = [], []
        for i in range(per_block):
            tid = random_trace_id()
            tr = make_trace(tid, seed=b * 1000 + i)
            sd = extract_search_data(tid, tr)
            objs.append((tid, codec.marshal(tr, sd.start_s, sd.end_s),
                         sd.start_s, sd.end_s))
            sds.append(sd)
        db.write_block_direct("t1", sorted(objs), search_entries=sds)
        all_sds.extend(sds)
    return db, all_sds


def test_frontend_page_range_jobs_merge_to_whole(tmp_path):
    """A large block splits into N page-range jobs whose merged result
    equals the single-job result (reference searchsharding.go:323-367),
    and the job encoding comes from the block meta, not a constant."""
    from tempo_tpu.modules.frontend import FrontendConfig, QueryFrontend
    from tempo_tpu.modules.querier import Querier
    from tempo_tpu.search.data import search_data_matches

    db, all_sds = _frontend_db(tmp_path)
    metas = db.blocklist.metas("t1")
    assert all(m.search_pages > 1 for m in metas)  # multi-page containers

    q = Querier(db, Ring(), {})
    # tiny job target -> one page per job
    fe_split = QueryFrontend([q], FrontendConfig(target_bytes_per_job=1,
                                                 batch_jobs_per_request=4))
    jobs = fe_split._block_jobs(metas)
    assert len(jobs) == sum(m.search_pages for m in metas)
    assert {j[0].encoding for j in jobs} == {m.encoding for m in metas}

    # huge target -> one job per block
    fe_whole = QueryFrontend([q], FrontendConfig())
    assert len(fe_whole._block_jobs(metas)) == len(metas)

    req = _mk_req({"component": "grpc"})
    req.limit = 10_000
    r_split = fe_split.search("t1", req)
    r_whole = fe_whole.search("t1", req)
    expected = {sd.trace_id.hex() for sd in all_sds
                if search_data_matches(sd, req)}
    assert {t.trace_id for t in r_split.traces} == expected
    assert {t.trace_id for t in r_whole.traces} == expected
    assert r_split.metrics.inspected_traces == r_whole.metrics.inspected_traces


def test_frontend_mixed_encoding_blocks(tmp_path):
    """Blocks written with different codecs search correctly through the
    page-range path (round-1 hardcoded 'zstd' would corrupt this)."""
    from tempo_tpu.encoding.v2.compression import encoding_usable
    from tempo_tpu.modules.frontend import FrontendConfig, QueryFrontend
    from tempo_tpu.modules.querier import Querier
    from tempo_tpu.search.data import search_data_matches

    if not (encoding_usable("lz4") and encoding_usable("snappy")):
        pytest.skip("mixed-codec test needs the native lib")

    db, sds1 = _frontend_db(tmp_path, n_blocks=1)
    db.cfg.block_encoding = "lz4"
    db.cfg.search_encoding = "snappy"
    from tempo_tpu.model.codec import codec_for
    from tempo_tpu.search.data import extract_search_data
    codec = codec_for("v2")
    objs, sds2 = [], []
    for i in range(150):
        tid = random_trace_id()
        tr = make_trace(tid, seed=5000 + i)
        sd = extract_search_data(tid, tr)
        objs.append((tid, codec.marshal(tr, sd.start_s, sd.end_s),
                     sd.start_s, sd.end_s))
        sds2.append(sd)
    db.write_block_direct("t1", sorted(objs), search_entries=sds2)

    metas = db.blocklist.metas("t1")
    assert {m.encoding for m in metas} == {"zstd", "lz4"}

    q = Querier(db, Ring(), {})
    fe = QueryFrontend([q], FrontendConfig(target_bytes_per_job=1))
    req = _mk_req({"component": "grpc"})
    req.limit = 10_000
    r = fe.search("t1", req)
    expected = {sd.trace_id.hex() for sd in sds1 + sds2
                if search_data_matches(sd, req)}
    assert {t.trace_id for t in r.traces} == expected


def test_frontend_early_quit_stops_dispatch(tmp_path):
    """A limit-hit query over many batches cancels the remaining jobs:
    inspected_blocks << total (reference results.go:38-78 quit +
    searchsharding.go stop-dispatch)."""
    from tempo_tpu.modules.frontend import FrontendConfig, QueryFrontend
    from tempo_tpu.modules.querier import Querier

    db, all_sds = _frontend_db(tmp_path, n_blocks=8, per_block=64)
    q = Querier(db, Ring(), {})
    fe = QueryFrontend([q], FrontendConfig(batch_jobs_per_request=1,
                                           max_concurrent_jobs=1))
    req = _mk_req({})
    req.limit = 5
    r = fe.search("t1", req)
    assert len(r.traces) == 5
    assert r.metrics.inspected_blocks < 8, r.metrics


def test_frontend_tolerance_counts_blocks_not_batches(tmp_path):
    """One failed SearchBlocksRequest covers all its blocks: tolerance
    compares BLOCK counts (reference tolerate_failed_blocks semantics),
    not batch counts."""
    from tempo_tpu.modules.frontend import FrontendConfig, QueryFrontend
    from tempo_tpu.modules.querier import Querier

    db, _ = _frontend_db(tmp_path, n_blocks=4, per_block=40)
    q = Querier(db, Ring(), {})

    class FailingBatches:
        """Querier facade that fails every batched block request."""
        def search_recent(self, tenant, req):
            return q.search_recent(tenant, req)

        def search_blocks(self, breq):
            raise RuntimeError("querier down")

    req = _mk_req({})
    req.limit = 10_000

    # tolerance 3 < 4 failed blocks (one batch of 4) -> error surfaces
    fe = QueryFrontend([FailingBatches()], FrontendConfig(
        batch_jobs_per_request=4, retries=0, tolerate_failed_blocks=3), db=db)
    with pytest.raises(RuntimeError):
        fe.search("t1", req)

    # tolerance 4 covers it -> partial (ingester-only) result, FAILED=4
    # (failed stays failed — pruning skips, breakage fails)
    fe2 = QueryFrontend([FailingBatches()], FrontendConfig(
        batch_jobs_per_request=4, retries=0, tolerate_failed_blocks=4), db=db)
    r = fe2.search("t1", req)
    assert r.metrics.failed_blocks == 4
    assert r.metrics.skipped_blocks == 0


def test_frontend_failed_block_spanning_batches_counts_once(tmp_path):
    """A block whose page-range jobs land in SEVERAL failed batches is one
    failed block, not one per batch (ADVICE r2 item 2: shared id set)."""
    from tempo_tpu.modules.frontend import FrontendConfig, QueryFrontend
    from tempo_tpu.modules.querier import Querier

    db, _ = _frontend_db(tmp_path, n_blocks=1, per_block=120)
    (meta,) = db.blocklist.metas("t1")
    assert meta.search_pages >= 3  # jobs will span >1 batch
    q = Querier(db, Ring(), {})

    class FailingBatches:
        def search_recent(self, tenant, req):
            return q.search_recent(tenant, req)

        def search_blocks(self, breq):
            raise RuntimeError("querier down")

    req = _mk_req({})
    req.limit = 10_000
    # one page per job, one job per batch -> the single block spans
    # search_pages failed batches; tolerance 1 must still cover it
    fe = QueryFrontend([FailingBatches()], FrontendConfig(
        target_bytes_per_job=1, batch_jobs_per_request=1, retries=0,
        tolerate_failed_blocks=1), db=db)
    r = fe.search("t1", req)
    assert r.metrics.failed_blocks == 1


def test_frontend_batches_are_geometry_pure(tmp_path):
    """Blocks with different page geometries must not share a
    SearchBlocksRequest: the querier's batcher can only stack same-(E,C)
    pages into one kernel, so a mixed batch fragments into extra
    dispatches. The meta now carries the geometry for exactly this."""
    from tempo_tpu.modules.frontend import FrontendConfig, QueryFrontend
    from tempo_tpu.modules.querier import Querier
    from tempo_tpu.search.columnar import PageGeometry

    db, sds_a = _frontend_db(tmp_path, n_blocks=3)
    # second geometry, same tenant/db: write blocks with (16, 8) pages
    from tempo_tpu.model import codec_for
    from tempo_tpu.search import extract_search_data
    from tempo_tpu.utils.ids import random_trace_id
    from tempo_tpu.utils.test_data import make_trace

    codec = codec_for("v2")
    db.cfg.search_geometry = PageGeometry(16, 8)
    for b in range(3):
        objs, sds = [], []
        for i in range(20):
            tid = random_trace_id()
            tr = make_trace(tid, seed=9000 + b * 100 + i)
            sd = extract_search_data(tid, tr)
            objs.append((tid, codec.marshal(tr, sd.start_s, sd.end_s),
                         sd.start_s, sd.end_s))
            sds.append(sd)
        db.write_block_direct("t1", sorted(objs), search_entries=sds)
    db.poll()
    metas = db.blocklist.metas("t1")
    geos = {(m.search_entries_per_page, m.search_kv_per_entry) for m in metas}
    assert len(geos) == 2

    q = Querier(db, Ring(), {})
    seen_batches = []
    orig = Querier.search_blocks

    def spy(self, breq):
        by_block = {m.block_id: m for m in metas}
        seen_batches.append([by_block[j.block_id] for j in breq.jobs])
        return orig(self, breq)

    Querier.search_blocks = spy
    try:
        fe = QueryFrontend([q], FrontendConfig(batch_jobs_per_request=4))
        req = _mk_req({})
        req.limit = 10_000
        fe.search("t1", req)
    finally:
        Querier.search_blocks = orig
    assert seen_batches
    for batch in seen_batches:
        batch_geos = {(m.search_entries_per_page, m.search_kv_per_entry)
                      for m in batch}
        assert len(batch_geos) == 1, "mixed-geometry batch"


def test_flush_backoff_and_sibling_isolation(tmp_path):
    """A failing completion backs off exponentially (30s→120s envelope,
    reference flush.go:359-389) and must not stop the same tenant's other
    ready completions in that sweep (VERDICT r2 #7)."""
    app = _app(tmp_path)
    ing = app.ingesters["ingester-0"]
    inst = ing.instance("t1")
    inst.FLUSH_BACKOFF_S = 0.05
    inst.FLUSH_BACKOFF_MAX_S = 0.2

    # two completing blocks for one tenant
    _push_traces(app, "t1", 5)
    inst.cut_complete_traces(force=True)
    inst.cut_block_if_ready(force=True)
    _push_traces(app, "t1", 5, seed_base=100)
    inst.cut_complete_traces(force=True)
    inst.cut_block_if_ready(force=True)
    assert len(inst.completing) == 2
    poisoned = inst.completing[0].blk.meta.block_id

    real_write = app.backend.write
    def flaky(tenant, block_id, name, data):
        if block_id == poisoned:
            raise OSError("flake")
        return real_write(tenant, block_id, name, data)
    app.backend.write = flaky

    # one sweep: the poisoned block fails + backs off, the sibling lands
    completed = ing.sweep(force=False, max_idle_s=0)
    assert len(completed) == 1 and completed[0].block_id != poisoned
    assert len(inst.completing) == 1
    c = inst.completing[0]
    assert c.backoff_s == inst.FLUSH_BACKOFF_S and c.retry_at > 0

    # within the backoff window the block is skipped, not hot-looped.
    # Pin the window open first: the real 0.05s window can elapse
    # between the sweep above and this call on a loaded host, making
    # complete_one RETRY (and raise) instead of skip — observed flaky
    # under the full suite.
    import time as _time

    c.retry_at = _time.monotonic() + 60.0
    assert inst.complete_one() is None

    # repeated failures double the backoff up to the cap
    import pytest as _pytest
    for expect in (0.1, 0.2, 0.2):
        c.retry_at = 0.0  # simulate the window elapsing
        with _pytest.raises(OSError):
            inst.complete_one()
        assert inst.completing[0].backoff_s == expect

    # backend heals → the block completes on the next eligible sweep
    app.backend.write = real_write
    inst.completing[0].retry_at = 0.0
    assert inst.complete_one() is not None
    assert not inst.completing


# ---- round 3: serving through the per-tenant fairness queue ----

def test_queue_pool_fair_interleaving():
    """With one worker and two tenants' jobs queued, execution alternates
    tenants (round-robin) instead of draining the first tenant's backlog
    first (reference v1/frontend.go per-tenant fair queue)."""
    import threading
    from tempo_tpu.modules.queue import QueueWorkerPool

    pool = QueueWorkerPool(workers=1)
    order = []
    gate = threading.Event()

    blocker = pool.submit("warm", gate.wait)  # hold the single worker
    futs = []
    for i in range(6):
        futs.append(pool.submit("loud", lambda: order.append("loud")))
    for i in range(3):
        futs.append(pool.submit("quiet", lambda: order.append("quiet")))
    gate.set()
    for f in futs:
        f.result(timeout=10)
    blocker.result(timeout=10)
    # quiet's 3 jobs are served round-robin against loud's 6: the first
    # six slots alternate, they never all queue behind loud's backlog
    assert order[:6] == ["loud", "quiet"] * 3, order
    assert order[6:] == ["loud"] * 3, order
    pool.stop()


def test_frontend_queue_429_and_http_mapping(tmp_path):
    """A tenant at max outstanding REQUESTS gets TooManyRequests,
    surfaced as HTTP 429 (reference frontend v1 max-outstanding counts
    requests, not sub-requests — a single large fan-out must not 429
    itself on an idle system)."""
    import threading
    from tempo_tpu.api.http import HTTPApi
    from tempo_tpu.modules.frontend import QueryFrontend, FrontendConfig
    from tempo_tpu.modules.queue import TooManyRequests

    app = _app(tmp_path)
    fe = QueryFrontend(app.queriers, FrontendConfig(
        query_shards=8, max_concurrent_jobs=1,
        max_outstanding_per_tenant=1))
    gate = threading.Event()
    blocker = fe.pool.submit("warm", gate.wait)  # saturate the one worker

    # first request occupies t1's single outstanding slot (jobs queued
    # behind the blocker)
    t = threading.Thread(target=lambda: fe.find_trace_by_id(
        "t1", random_trace_id()))
    t.start()
    while fe.pool.queue.outstanding("t1") < 1:
        time.sleep(0.001)

    with pytest.raises(TooManyRequests):
        fe.find_trace_by_id("t1", random_trace_id())

    # same condition through the HTTP layer -> 429, not 500
    app.frontend = fe
    api = HTTPApi(app)
    code, body = api.handle(
        "GET", "/api/traces/" + random_trace_id().hex(), {},
        {"X-Scope-OrgID": "t1"})
    assert code == 429, (code, body)

    gate.set()
    blocker.result(timeout=10)
    t.join(timeout=10)
    # slot released: the same tenant serves again (8 sub-requests fit in
    # ONE outstanding request even though the cap is 1)
    code, body = api.handle(
        "GET", "/api/traces/" + random_trace_id().hex(), {},
        {"X-Scope-OrgID": "t1"})
    assert code == 404, (code, body)  # served (unknown id), NOT 429
    fe.pool.stop()


def test_two_tenant_saturation_fairness(tmp_path):
    """Two-tenant saturation through the real frontend: a noisy tenant
    with a large backlog does not starve a quiet tenant's search — the
    quiet tenant's sub-requests interleave and finish while the noisy
    backlog is still draining (VERDICT r2 #4)."""
    import threading
    from tempo_tpu.modules.frontend import QueryFrontend, FrontendConfig

    events = []

    class SlowQuerier:
        def search_recent(self, tenant, req):
            events.append(tenant)
            time.sleep(0.005)
            return tempopb.SearchResponse()

        def search_blocks(self, breq):
            events.append(breq.tenant_id)
            time.sleep(0.005)
            return tempopb.SearchResponse()

    app = _app(tmp_path)
    # give the loud tenant a real backlog of block jobs (several blocks,
    # one page-range job each)
    for r in range(6):
        _push_traces(app, "loud", 5, seed_base=10 * r)
        app.flush_tick(force=True)
    app.poll_tick()
    db = app.reader_db
    fe = QueryFrontend([SlowQuerier()], FrontendConfig(
        max_concurrent_jobs=1, batch_jobs_per_request=1,
        target_bytes_per_job=1), db=db)

    req = _mk_req({})
    req.limit = 10**6  # no early quit: drain every job
    t_loud = threading.Thread(target=lambda: fe.search("loud", req))
    t_loud.start()
    while events.count("loud") < 2:  # loud's backlog is in the queue
        time.sleep(0.001)
    fe.search("quiet", req)  # returns while loud still has queued jobs
    quiet_done_at = len(events)
    t_loud.join()
    assert events.count("quiet") >= 1
    # quiet finished before the full loud backlog drained
    assert quiet_done_at < len(events), events
    fe.pool.stop()


def test_exclusive_flush_queue_dedupes_concurrent_sweeps(tmp_path):
    """Racing sweeps (periodic tick vs /flush vs shutdown) must not
    double-complete a block: the keyed-exclusive op queue refuses the
    duplicate enqueue while the op is queued or in flight."""
    import threading
    app = _app(tmp_path)
    ing = app.ingesters["ingester-0"]
    inst = ing.instance("t1")
    _push_traces(app, "t1", 10)
    inst.cut_complete_traces(force=True)
    inst.cut_block_if_ready(force=True)

    db = app.ingesters["ingester-0"].db
    real_complete = db.complete_block
    started = threading.Event()
    release = threading.Event()

    def slow_complete(blk, entries):
        started.set()
        release.wait(5)
        return real_complete(blk, entries)

    db.complete_block = slow_complete
    t1 = threading.Thread(target=lambda: ing.sweep(force=False, max_idle_s=0))
    t1.start()
    started.wait(5)
    # racing sweep while the op is in flight: enqueue refused, nothing to drain
    done2 = ing.sweep(force=False, max_idle_s=0)
    assert done2 == []
    release.set()
    t1.join()
    db.complete_block = real_complete
    from tempo_tpu.observability.metrics import blocks_completed
    assert len(inst.completing) == 0
    assert inst.recent and len(inst.recent) == 1  # completed exactly once


def test_force_flush_bypasses_backoff(tmp_path):
    """flush_all / shutdown must attempt backed-off blocks too — a
    scale-down must not strand a block in the local WAL because its
    retry window hadn't elapsed (code-review r3 finding)."""
    app = _app(tmp_path)
    ing = app.ingesters["ingester-0"]
    inst = ing.instance("t1")
    _push_traces(app, "t1", 5)
    inst.cut_complete_traces(force=True)
    inst.cut_block_if_ready(force=True)

    real_write = app.backend.write
    app.backend.write = lambda *a, **k: (_ for _ in ()).throw(OSError("flake"))
    assert ing.sweep(force=False, max_idle_s=0) == []
    assert inst.completing[0].retry_at > time.monotonic()  # backed off

    app.backend.write = real_write
    # NO retry_at reset: force alone must complete it
    done = ing.flush_all()
    assert len(done) == 1 and not inst.completing


def test_completing_block_stays_queryable_during_completion(tmp_path):
    """While a (long, streaming) completion is in flight the block's
    traces must stay visible to find/search — the block leaves
    `completing` only once the backend write succeeds (code-review r3
    finding; reference swaps the block out after CompleteBlock returns)."""
    import threading
    app = _app(tmp_path)
    ing = app.ingesters["ingester-0"]
    inst = ing.instance("t1")
    traces = _push_traces(app, "t1", 5)
    inst.cut_complete_traces(force=True)
    inst.cut_block_if_ready(force=True)
    tid = next(iter(traces))

    db = ing.db
    real_complete = db.complete_block
    started, release = threading.Event(), threading.Event()

    def slow_complete(blk, entries):
        started.set()
        assert release.wait(5)
        return real_complete(blk, entries)

    db.complete_block = slow_complete
    t = threading.Thread(target=lambda: ing.sweep(force=False, max_idle_s=0))
    t.start()
    try:
        assert started.wait(5)
        # completion in flight: the trace must still be findable
        partials = inst.find(tid)
        assert partials, "trace invisible while its block completes"
        req = _mk_req({})
        req.limit = 100
        from tempo_tpu.search import SearchResults
        res = SearchResults.for_request(req)
        inst.search(req, res)
        assert len(res.response().traces) == 5
    finally:
        release.set()
        t.join()
        db.complete_block = real_complete
    # and after completion it is still findable (via recent/backend)
    assert inst.find(tid)


def test_force_op_survives_nonforce_drain(tmp_path):
    """A force-enqueued flush op keeps its force semantics no matter which
    sweep drains it: the shared op queue carries the flag per op, so a
    racing periodic (non-force) drain still bypasses the block's backoff
    (code-review r3 finding)."""
    app = _app(tmp_path)
    ing = app.ingesters["ingester-0"]
    inst = ing.instance("t1")
    _push_traces(app, "t1", 5)
    inst.cut_complete_traces(force=True)
    inst.cut_block_if_ready(force=True)

    real_write = app.backend.write
    app.backend.write = lambda *a, **k: (_ for _ in ()).throw(OSError("flake"))
    assert ing.sweep(force=False, max_idle_s=0) == []
    assert inst.completing[0].retry_at > time.monotonic()
    app.backend.write = real_write

    # simulate the shutdown race: flush_all enqueued the op with force,
    # but the PERIODIC sweep's drain gets to it first
    bid = inst.completing[0].blk.meta.block_id
    ing.flush_ops.enqueue(("t1", bid), 0.0, ("t1", bid, True))
    done = ing.sweep(force=False, max_idle_s=0)
    assert len(done) == 1 and not inst.completing


def test_wal_find_tolerates_concurrent_clear(tmp_path):
    """blk.find() on a cleared WAL block returns None instead of crashing
    — readers legitimately hold refs to completing blocks while the
    successful hand-off clears them."""
    app = _app(tmp_path)
    inst = app.ingesters["ingester-0"].instance("t1")
    traces = _push_traces(app, "t1", 3)
    inst.cut_complete_traces(force=True)
    tid = next(iter(traces))
    from tempo_tpu.utils.ids import pad_trace_id
    assert inst.head.find(pad_trace_id(tid)) is not None
    blk = inst.head
    blk.clear()
    assert blk.find(pad_trace_id(tid)) is None  # no AttributeError


def test_flush_all_raises_when_backend_down(tmp_path):
    """A shutdown caller must be able to distinguish 'all flushed' from
    'gave up': when the backend stays down, flush_all raises
    FlushIncompleteError (with the successfully-flushed list attached)
    instead of returning as if the WAL were safe to delete (advisor r3)."""
    from tempo_tpu.modules.ingester import FlushIncompleteError

    app = _app(tmp_path)
    ing = app.ingesters["ingester-0"]
    inst = ing.instance("t1")
    _push_traces(app, "t1", 3)
    inst.cut_complete_traces(force=True)
    inst.cut_block_if_ready(force=True)

    app.backend.write = lambda *a, **k: (_ for _ in ()).throw(OSError("down"))
    with pytest.raises(FlushIncompleteError) as ei:
        ing.flush_all(settle_timeout_s=2.0)
    assert ei.value.left_behind == 1
    assert ei.value.completed == []
    assert len(inst.completing) == 1  # block still in the local WAL


def test_flush_all_waits_for_inflight_completion(tmp_path):
    """flush_all must not conclude 'stalled' while a racing periodic
    sweep's drain thread holds the completion op — a streaming completion
    can take a long time, during which flush_all's own passes are no-ops
    by ExclusiveQueue dedupe (advisor r3 medium)."""
    import threading

    app = _app(tmp_path)
    ing = app.ingesters["ingester-0"]
    inst = ing.instance("t1")
    _push_traces(app, "t1", 3)
    inst.cut_complete_traces(force=True)
    inst.cut_block_if_ready(force=True)

    db = ing.db
    real_complete = db.complete_block
    started, release = threading.Event(), threading.Event()

    def slow_complete(blk, entries):
        started.set()
        assert release.wait(10)
        return real_complete(blk, entries)

    db.complete_block = slow_complete
    racer = threading.Thread(
        target=lambda: ing.sweep(force=False, max_idle_s=0))
    racer.start()
    assert started.wait(5)
    # release the slow completion shortly after flush_all starts waiting
    threading.Timer(0.3, release.set).start()
    done = ing.flush_all(settle_timeout_s=30.0)
    racer.join()
    db.complete_block = real_complete
    # the racer's completion counts as flushed state: nothing left behind
    assert not inst.completing
    assert inst.recent  # completed exactly once, queryable via recent


def test_frontend_batch_cache_sees_new_blocks(tmp_path):
    """The frontend's memoized job sharding must not serve a stale plan
    after the blocklist changes: a block added (and polled) after the
    first query must be searched by the next one (r4: _search_batches is
    cached per blocklist epoch)."""
    from tempo_tpu.model.codec import codec_for
    from tempo_tpu.modules.frontend import FrontendConfig, QueryFrontend
    from tempo_tpu.modules.querier import Querier
    from tempo_tpu.search.data import extract_search_data

    db, all_sds = _frontend_db(tmp_path, n_blocks=2, per_block=50)
    q = Querier(db, Ring(), {})
    fe = QueryFrontend([q], FrontendConfig())
    req = _mk_req({})
    req.limit = 10_000
    r1 = fe.search("t1", req)
    assert r1.metrics.inspected_traces == 100

    codec = codec_for("v2")
    objs, sds = [], []
    for i in range(30):
        tid = random_trace_id()
        tr = make_trace(tid, seed=9000 + i)
        sd = extract_search_data(tid, tr)
        objs.append((tid, codec.marshal(tr, sd.start_s, sd.end_s),
                     sd.start_s, sd.end_s))
        sds.append(sd)
    db.write_block_direct("t1", sorted(objs), search_entries=sds)

    r2 = fe.search("t1", req)
    assert r2.metrics.inspected_traces == 130  # new block included
    new_ids = {sd.trace_id.hex() for sd in sds}
    assert new_ids <= {t.trace_id for t in r2.traces}


def test_frontend_auto_batch_one_request_per_querier(tmp_path):
    """Default (auto) batch sizing spreads the job list over the querier
    pool — with one querier a whole-tenant search is ONE batched
    SearchBlocksRequest, not a fixed-size fan-out (r4: one request ~ one
    device sync on TPU)."""
    from tempo_tpu.modules.frontend import FrontendConfig, QueryFrontend
    from tempo_tpu.modules.querier import Querier

    db, _ = _frontend_db(tmp_path, n_blocks=4, per_block=40)
    q = Querier(db, Ring(), {})
    calls = []
    real = q.search_blocks
    q.search_blocks = lambda breq: (calls.append(len(breq.jobs)),
                                    real(breq))[1]
    fe = QueryFrontend([q], FrontendConfig())
    req = _mk_req({})
    req.limit = 10_000
    fe.search("t1", req)
    assert len(calls) == 1  # one request carried every job
    assert calls[0] == len(fe._block_jobs(db.blocklist.metas("t1")))


def test_search_blocks_jobs_cache_consistent(tmp_path):
    """Repeated identical SearchBlocksRequests hit the memoized job list
    and return identical results; a blocklist epoch bump invalidates the
    memo (r4: search_blocks O(blocks) host work must not repeat per
    query)."""
    from tempo_tpu import tempopb
    from tempo_tpu.modules.frontend import FrontendConfig, QueryFrontend
    from tempo_tpu.modules.querier import Querier

    db, all_sds = _frontend_db(tmp_path, n_blocks=3, per_block=40)
    metas = db.blocklist.metas("t1")
    breq = tempopb.SearchBlocksRequest()
    breq.tenant_id = "t1"
    req = _mk_req({})
    req.limit = 10_000
    breq.search_req.CopyFrom(req)
    for m in metas:
        j = breq.jobs.add()
        j.block_id = m.block_id
        j.encoding = m.encoding
        j.version = m.version
        j.data_encoding = m.data_encoding
    r1 = db.search_blocks(breq).response()
    r2 = db.search_blocks(breq).response()
    assert ({t.trace_id for t in r1.traces}
            == {t.trace_id for t in r2.traces})
    assert r1.metrics.inspected_traces == r2.metrics.inspected_traces == 120
    assert len(db._breq_jobs_cache) == 1
    epoch0, jobs0 = db._breq_jobs_cache.values()[0][:2]
    assert len(jobs0) == 3
    # epoch bump -> rebuild on next request
    db.blocklist.update("t1", add=[])
    db.search_blocks(breq)
    epoch1 = db._breq_jobs_cache.values()[0][0]
    assert epoch1 > epoch0


def test_search_blocks_cache_promotes_late_container(tmp_path):
    """A transient DoesNotExist (read-after-write lag: meta visible
    before the search container) must not pin a block to the slow proto
    fallback for the whole epoch — the cached entry re-probes and
    promotes on the next request (code-review r4)."""
    from tempo_tpu import tempopb
    from tempo_tpu.backend.raw import DoesNotExist
    from tempo_tpu.backend.types import NAME_SEARCH

    db, all_sds = _frontend_db(tmp_path, n_blocks=1, per_block=40)
    m = db.blocklist.metas("t1")[0]

    # hide the container: first request classifies the block as fallback
    real_read = db.backend.read
    def read_no_container(tenant, bid, name, **kw):
        if name == NAME_SEARCH:
            raise DoesNotExist(f"{bid}/{name}")
        return real_read(tenant, bid, name, **kw)
    # the header read decides _scan_job; hide it too
    from tempo_tpu.backend.types import NAME_SEARCH_HEADER
    def read_hidden(tenant, bid, name, **kw):
        if name in (NAME_SEARCH, NAME_SEARCH_HEADER):
            raise DoesNotExist(f"{bid}/{name}")
        return real_read(tenant, bid, name, **kw)
    db.backend.read = read_hidden

    breq = tempopb.SearchBlocksRequest()
    breq.tenant_id = "t1"
    req = _mk_req({})
    req.limit = 10_000
    breq.search_req.CopyFrom(req)
    j = breq.jobs.add()
    j.block_id = m.block_id
    j.encoding = m.encoding
    j.version = m.version
    j.data_encoding = m.data_encoding

    r1 = db.search_blocks(breq)
    assert db._breq_jobs_cache.values()[0][2]  # cached as fallback
    # container appears; the SAME cached request must promote it
    db.backend.read = real_read
    r2 = db.search_blocks(breq)
    entry = db._breq_jobs_cache.values()[0]
    assert not entry[2] and len(entry[1]) == 1  # promoted to a ScanJob
    assert r2.metrics.inspected_traces == 40


def test_search_blocks_cache_keyed_by_encoding(tmp_path):
    """Requests differing only in job encoding/version must not alias to
    one cached job list (code-review r4: the key carries every field
    that shapes the ScanJob)."""
    from tempo_tpu import tempopb

    db, _ = _frontend_db(tmp_path, n_blocks=1, per_block=20)
    m = db.blocklist.metas("t1")[0]

    def mk(encoding):
        breq = tempopb.SearchBlocksRequest()
        breq.tenant_id = "t1"
        req = _mk_req({})
        req.limit = 100
        breq.search_req.CopyFrom(req)
        j = breq.jobs.add()
        j.block_id = m.block_id
        j.encoding = encoding
        j.version = m.version
        j.data_encoding = m.data_encoding
        return breq

    db.search_blocks(mk(m.encoding))
    db.search_blocks(mk("gzip"))
    assert len(db._breq_jobs_cache) == 2  # distinct cache entries


def test_shutdown_surfaces_incomplete_flush(tmp_path):
    """App.shutdown must not return success while WAL data remains: the
    FlushIncompleteError re-raises AFTER the full drain so an
    orchestrator cannot tear down the WAL volume on a clean-looking
    return (code-review r4)."""
    from tempo_tpu.modules.ingester import FlushIncompleteError

    app = _app(tmp_path)
    inst = app.ingesters["ingester-0"].instance("t1")
    _push_traces(app, "t1", 3)
    inst.cut_complete_traces(force=True)
    inst.cut_block_if_ready(force=True)
    app.backend.write = lambda *a, **k: (_ for _ in ()).throw(OSError("down"))
    for ing in app.ingesters.values():
        ing.flush_all = lambda _f=ing.flush_all: _f(settle_timeout_s=1.0)
    with pytest.raises(FlushIncompleteError):
        app.shutdown()
    assert len(inst.completing) == 1


def test_windowed_search_skips_containerless_block(tmp_path):
    """A container-less block entirely outside the request window must be
    window-pruned via the meta times carried in the job — not fully
    proto-scanned — now that the frontend ships all blocks and defers
    window pruning to the executor (code-review r4)."""
    from tempo_tpu.model.codec import codec_for
    from tempo_tpu.modules.frontend import FrontendConfig, QueryFrontend
    from tempo_tpu.modules.querier import Querier
    from tempo_tpu.observability import metrics as obs

    db, all_sds = _frontend_db(tmp_path, n_blocks=1, per_block=20)
    in_window = db.blocklist.metas("t1")[0]

    # a second block WITHOUT search entries (no container -> proto
    # fallback path), far outside the window
    codec = codec_for("v2")
    objs = []
    for i in range(10):
        tid = random_trace_id()
        tr = make_trace(tid, seed=7000 + i)
        objs.append((tid, codec.marshal(tr, 100, 200), 100, 200))
    db.write_block_direct("t1", sorted(objs), search_entries=None)

    q = Querier(db, Ring(), {})
    fe = QueryFrontend([q], FrontendConfig())
    req = _mk_req({})
    req.limit = 10_000
    req.start = in_window.start_time
    req.end = in_window.end_time
    f0 = obs.fallback_scans.value(tenant="t1")
    r = fe.search("t1", req)
    assert obs.fallback_scans.value(tenant="t1") == f0  # no proto scan
    assert r.metrics.inspected_traces == 20  # container block only
    assert r.metrics.skipped_blocks >= 1  # the out-of-window block


# ---------------------------------------------------------------------------
# concurrent replica fan-out (reference querier.go:252-276)


class _FanoutIngester:
    """Duck-typed ingester replica with injectable delay/failure."""

    def __init__(self, name, n_traces=0, delay_s=0.0, fail=False):
        self.name = name
        self.n_traces = n_traces
        self.delay_s = delay_s
        self.fail = fail

    def search(self, tenant, req, results):
        if self.delay_s:
            time.sleep(self.delay_s)
        if self.fail:
            raise RuntimeError(f"{self.name} down")
        for i in range(self.n_traces):
            m = tempopb.TraceSearchMetadata(
                trace_id=f"{self.name}-{i}", root_service_name=self.name,
                start_time_unix_nano=1, duration_ms=1)
            results.add(m)
        results.metrics.inspected_traces += self.n_traces

    def find_trace_by_id(self, tenant, tid):
        if self.delay_s:
            time.sleep(self.delay_s)
        if self.fail:
            raise RuntimeError(f"{self.name} down")
        return []


def test_search_recent_fanout_is_concurrent_not_additive():
    """Three replicas × 0.4s each must cost ~0.4s, not ~1.2s."""
    from tempo_tpu.modules.querier import Querier

    ings = {f"i{k}": _FanoutIngester(f"i{k}", n_traces=1, delay_s=0.4)
            for k in range(3)}
    q = Querier(None, Ring(), ings)
    req = tempopb.SearchRequest()
    req.limit = 100
    t0 = time.monotonic()
    resp = q.search_recent("t1", req)
    elapsed = time.monotonic() - t0
    assert len(resp.traces) == 3
    assert elapsed < 0.9, f"fan-out took {elapsed:.2f}s — additive, not concurrent"


def test_search_recent_early_quit_skips_slow_straggler():
    """Limit satisfied by fast replicas: don't wait for the slow one."""
    from tempo_tpu.modules.querier import Querier

    ings = {"fast1": _FanoutIngester("fast1", n_traces=2),
            "fast2": _FanoutIngester("fast2", n_traces=2),
            "slow": _FanoutIngester("slow", n_traces=1, delay_s=2.0)}
    q = Querier(None, Ring(), ings)
    req = tempopb.SearchRequest()
    req.limit = 2
    t0 = time.monotonic()
    resp = q.search_recent("t1", req)
    elapsed = time.monotonic() - t0
    assert len(resp.traces) == 2
    assert elapsed < 1.0, f"early quit waited on the straggler ({elapsed:.2f}s)"


def test_search_recent_failed_replica_counts_failed_not_skipped():
    from tempo_tpu.modules.querier import Querier

    ings = {"ok": _FanoutIngester("ok", n_traces=2),
            "dead": _FanoutIngester("dead", fail=True)}
    q = Querier(None, Ring(), ings)
    req = tempopb.SearchRequest()
    req.limit = 100
    resp = q.search_recent("t1", req)
    assert len(resp.traces) == 2
    assert resp.metrics.failed_blocks == 1
    assert resp.metrics.skipped_blocks == 0


def test_trace_by_id_ingester_leg_concurrent():
    """The replica leg of trace-by-id fans out concurrently too."""
    from tempo_tpu.modules.querier import Querier

    ring = Ring(replication_factor=3)
    ings = {}
    for k in range(3):
        ring.register(f"i{k}")
        ings[f"i{k}"] = _FanoutIngester(f"i{k}", delay_s=0.4)

    class _NoBlocks:
        def find_trace_by_id(self, tenant, tid, bs, be):
            return None, 0

    q = Querier(_NoBlocks(), ring, ings)
    t0 = time.monotonic()
    resp = q.find_trace_by_id("t1", b"\x01" * 16, mode="ingesters")
    elapsed = time.monotonic() - t0
    assert resp.metrics.failed_blocks == 0
    assert elapsed < 0.9, f"replica leg additive ({elapsed:.2f}s)"


def test_corrupt_search_fragment_does_not_wedge_sweep(tmp_path):
    """A corrupt search_data blob is dropped at fold time; the trace
    still cuts, flushes, and reads — sweep never wedges (code-review r4:
    the lazy decode must not move a push-time reject into an infinite
    completion retry)."""
    app = _app(tmp_path)
    ing = app.ingesters["ingester-0"]
    tid = random_trace_id()
    tr = make_trace(tid, seed=1)
    app.push("t1", list(tr.batches))
    # inject a corrupt fragment alongside the good one
    from tempo_tpu.model.codec import segment_codec_for
    codec = segment_codec_for("v2")
    seg = codec.prepare_for_write(make_trace(tid, seed=2), 100, 200)
    ing.instance("t1").push(tid, seg, search_data=b"\x01\x02garbage")

    completed = app.flush_tick(force=True)
    assert completed and completed[0].total_objects >= 1
    app.poll_tick()
    assert len(app.find_trace("t1", tid).trace.batches) > 0


def test_tag_endpoints_cap_block_sweep(tmp_path):
    """Tag queries consult the newest TAG_BLOCKS_LIMIT blocks, not the
    whole corpus — a 10K-block tenant must not stage every container
    through the 64-entry LRU per tags call."""
    from tempo_tpu.modules.querier import Querier

    db, _ = _frontend_db(tmp_path, n_blocks=6, per_block=10)
    q = Querier(db, Ring(), {})
    q.TAG_BLOCKS_LIMIT = 3
    staged = []
    orig = db._search_block_for

    def counting(m):
        staged.append(m.block_id)
        return orig(m)

    db._search_block_for = counting
    resp = q.search_tags("t1")
    assert resp.tag_names  # still answers
    assert len(set(staged)) <= 3, staged
    # the consulted blocks are the NEWEST by end_time
    metas = sorted(db.blocklist.metas("t1"),
                   key=lambda m: m.end_time or 0, reverse=True)
    assert set(staged) <= {m.block_id for m in metas[:3]}


def test_tag_endpoints_cover_blocklist_poll_gap(tmp_path):
    """find() and search() already swept recently-completed blocks; the
    tag endpoints did not — so a service's tags vanished from UI
    dropdowns for a full poll interval right after flush (observed via
    the jaeger bridge in r5). Flush WITHOUT polling the reader: tag
    names and values must still be visible through the querier."""
    app = App(AppConfig(
        backend={"backend": "local", "local": {"path": str(tmp_path / "b")}},
        wal_dir=str(tmp_path / "w")))
    from tempo_tpu.utils.ids import random_trace_id
    from tempo_tpu.utils.test_data import make_trace

    for i in range(5):
        app.push("t1", list(make_trace(random_trace_id(), seed=i).batches))
    completed = app.flush_tick(force=True)
    assert completed  # blocks left the ingester...
    # ...and the reader has NOT polled: the gap under test
    assert not app.reader_db.blocklist.metas("t1")

    tags = app.queriers[0].search_tags("t1")
    assert "service.name" in tags.tag_names
    vals = app.queriers[0].search_tag_values("t1", "service.name")
    assert vals.tag_values, "tag values invisible during the poll gap"
