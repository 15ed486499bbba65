"""Per-query execution inspector (search/query_stats.py).

Contract under test:

  - SearchMetrics population on EVERY scan path: one-block batch, batched
    multi-block, coalesced (8-way concurrency), mesh-sharded — all
    report non-zero inspected counts; skipped_blocks carries time-range
    / duration / dictionary prunes with per-reason stats
  - the conservation invariant: a fused Q-way dispatch apportions its
    stage seconds (and h2d bytes) across member queries so the shares
    sum EXACTLY to the dispatch totals
  - explain opt-in: ?explain=1 / SearchRequest.explain returns the full
    breakdown on the response, populated end-to-end (frontend merge
    included)
  - search_query_stats_enabled: false is a true noop — byte-identical
    results, no record created
  - slow-query log (one rate-limited JSON line), /debug/querystats,
    per-tenant counters
"""

import json
import logging
import threading
import time

import pytest

from tempo_tpu import tempopb
from tempo_tpu.observability import metrics as obs
from tempo_tpu.observability.profile import PROFILER
from tempo_tpu.search import SearchResults
from tempo_tpu.search import query_stats
from tempo_tpu.search.batcher import BlockBatcher
from tempo_tpu.search.coalescer import QueryCoalescer
from tempo_tpu.search.multiblock import MultiBlockEngine, compile_multi
from tempo_tpu.search.engine import resolve_top_k

from tests.test_coalesce import _blocks, _jobs, _mk_req


@pytest.fixture(autouse=True)
def _fresh_registry():
    query_stats.configure(enabled=True, slow_s=10.0)
    query_stats.REGISTRY.reset()
    yield
    query_stats.configure(enabled=True, slow_s=10.0)
    query_stats.REGISTRY.reset()


def _search_with_stats(batcher, jobs, req, tenant="t1"):
    qs = query_stats.begin(tenant, req)
    with query_stats.activate(qs):
        results = batcher.search(jobs, req)
    d = qs.finish()
    return results, qs, d


# ---------------------------------------------------------------------------
# apportioning / conservation primitives


def test_apportion_conserves_totals_exactly():
    totals = {"execute": 0.123456789, "compile": 3.14159, "h2d": 1e-9}
    for weights in ([1, 1, 1, 1], [5, 1, 3], [7], [1000, 1, 1, 1, 1, 1]):
        shares = query_stats.apportion(totals, weights)
        assert len(shares) == len(weights)
        for stage, total in totals.items():
            # folded in member order with `+`, as add_device_stages does
            # (builtin sum() is compensated from Python 3.12 on)
            acc = 0.0
            for s in shares:
                acc += s[stage]
            assert acc == total  # EXACT


def test_apportion_weights_proportional():
    shares = query_stats.apportion({"execute": 1.0}, [3, 1])
    assert abs(shares[0]["execute"] - 0.75) < 1e-12
    assert abs(shares[1]["execute"] - 0.25) < 1e-12


# ---------------------------------------------------------------------------
# metrics population per path


def test_batched_path_populates_metrics_and_stats():
    blocks = _blocks(3, entries=128)
    batcher = BlockBatcher()
    req = _mk_req({"service.name": "svc-1"}, limit=500)
    results, qs, d = _search_with_stats(batcher, _jobs(blocks), req)
    m = results.metrics
    assert m.inspected_blocks > 0
    assert m.inspected_traces > 0
    assert m.inspected_bytes >= 0  # synthetic headers carry no size
    assert d["blocks_inspected"] == m.inspected_blocks
    assert d["device_seconds"] > 0
    assert d["dispatches"] >= 1
    assert d["stages_ms"]  # host stages recorded
    assert "hbm_miss_cold" in d["cache"] or "hbm_hit" in d["cache"]


def test_one_block_search_populates_metrics():
    from tempo_tpu.backend import MockBackend
    from tempo_tpu.backend.types import BlockMeta
    from tempo_tpu.search.backend_search_block import (
        BackendSearchBlock, write_search_block)
    from tests.test_coalesce import _corpus

    be = MockBackend()
    meta = BlockMeta(tenant_id="t1")
    write_search_block(be, meta, _corpus(64, seed=1), encoding="zlib")
    batcher = BlockBatcher()
    jobs = [BackendSearchBlock(be, meta).scan_job()]
    req = _mk_req({"service.name": "svc-1"}, limit=100)
    qs = query_stats.begin("t1", req)
    with query_stats.activate(qs):
        results = batcher.search(jobs, req)
    d = qs.finish()
    m = results.metrics
    assert m.inspected_blocks == 1 and m.inspected_traces > 0
    assert m.inspected_bytes > 0
    assert d["bytes_inspected"]["device"] == m.inspected_bytes
    assert d["device_seconds"] > 0

    # dictionary prune: a tag value no dictionary contains
    qs2 = query_stats.begin("t1", req)
    with query_stats.activate(qs2):
        r2 = batcher.search(
            jobs, _mk_req({"service.name": "nope-xyz"}, limit=10))
    d2 = qs2.finish()
    assert r2.metrics.skipped_blocks == 1
    assert d2["skipped_blocks"] == {"dict": 1}


def test_skip_reasons_time_range_duration_and_dict():
    blocks = _blocks(3, entries=64)
    batcher = BlockBatcher()
    jobs = _jobs(blocks)

    # time window far in the future → header prune, reason time_range
    req = _mk_req({}, limit=10, start=2_000_000_000, end=2_000_000_100)
    results, _qs, d = _search_with_stats(batcher, jobs, req)
    assert results.metrics.skipped_blocks == len(jobs)
    assert d["skipped_blocks"] == {"time_range": len(jobs)}

    # duration beyond every entry → header prune, reason duration
    req = _mk_req({}, limit=10, min_duration_ms=10_000_000)
    results, _qs, d = _search_with_stats(batcher, jobs, req)
    assert results.metrics.skipped_blocks == len(jobs)
    assert d["skipped_blocks"] == {"duration": len(jobs)}

    # unsatisfiable tag → dictionary prune
    req = _mk_req({"service.name": "no-such-service"}, limit=10)
    results, _qs, d = _search_with_stats(batcher, jobs, req)
    assert results.metrics.skipped_blocks == len(jobs)
    assert d["skipped_blocks"] == {"dict": len(jobs)}


def test_mesh_path_populates_metrics():
    from tempo_tpu.parallel.mesh import make_mesh

    blocks = _blocks(2, entries=128)
    batcher = BlockBatcher(mesh=make_mesh())
    req = _mk_req({"service.name": "svc-2"}, limit=500)
    results, _qs, d = _search_with_stats(batcher, _jobs(blocks), req)
    assert results.metrics.inspected_blocks > 0
    assert results.metrics.inspected_traces > 0
    assert d["device_seconds"] > 0
    # mesh dispatches serialize on the collective lock → the stage
    # breakdown must carry the mesh record's stages
    assert d["device_stages_ms"]


def test_mesh_launch_attributes_to_active_stats():
    """A mesh launch straight on the engine, inside an attributing
    body (as the batcher's dispatch sites are), bills the active
    stats."""
    from tempo_tpu.parallel import make_mesh
    from tempo_tpu.search.multiblock import MultiBlockEngine, compile_multi
    from tests.test_coalesce import _corpus
    from tempo_tpu.search import ColumnarPages, PageGeometry

    pages = ColumnarPages.build(_corpus(128, seed=3), PageGeometry(32, 8))
    eng = MultiBlockEngine(top_k=64, mesh=make_mesh())
    batch = eng.stage([pages])
    mq = compile_multi([pages],
                       _mk_req({"service.name": "svc-1"}, limit=20))
    qs = query_stats.begin("t1", None)
    with query_stats.activate(qs), query_stats.attributed_dispatch(qs):
        count, inspected, _s, _i = eng.scan(batch, mq)
    assert inspected > 0
    assert qs.device_seconds > 0
    assert qs.dispatches >= 1


# ---------------------------------------------------------------------------
# conservation under fused dispatch


def test_conservation_8way_stacked_structural():
    """A fused plan-shape-STACKED structural dispatch (ISSUE 15)
    apportions its stage seconds and h2d bytes across the member
    queries through the same conservation invariant as the legacy
    coalescer — structural table sizes join the weights — and each
    member's ?explain structural tree carries per-node device-seconds
    that conserve to that member's own execute share."""
    import random

    from tempo_tpu.search import ir, structural as structural_mod
    from tempo_tpu.search.columnar import ColumnarPages, PageGeometry
    from tempo_tpu.search.data import SearchData, SpanData
    from tempo_tpu.search.structural import (STRUCTURAL,
                                             compile_structural)

    rng = random.Random(7)
    entries = []
    for i in range(128):
        sd = SearchData(trace_id=i.to_bytes(16, "big"), start_s=1,
                        end_s=5, dur_ms=rng.randint(1, 2000),
                        kvs={"service.name": {f"svc-{i % 6}"}})
        for j in range(rng.randint(1, 6)):
            sd.spans.append(SpanData(
                parent=(-1 if j == 0 else rng.randrange(j)),
                dur_ms=rng.randint(1, 900), kind=rng.randint(0, 5),
                kvs={"service.name": {f"svc-{rng.randint(0, 5)}"}}))
        entries.append(sd)
    prev = STRUCTURAL.enabled
    prev_stack = STRUCTURAL.stack_enabled
    STRUCTURAL.enabled = True
    STRUCTURAL.stack_enabled = True
    try:
        blocks = [ColumnarPages.build(entries, PageGeometry(64, 8))]
        eng = MultiBlockEngine(top_k=64)
        batch = eng.stage(blocks)
        co = QueryCoalescer(eng, window_s=60.0, max_queries=8,
                            active_fn=lambda: 8)
        caught: list[dict] = []
        listener = caught.append
        PROFILER.add_listener(listener)
        try:
            mqs, stats, futs = [], [], []
            for i in range(8):
                expr = ir.parse(
                    '{"child": {"parent": {"tag": {"k": "service.name",'
                    ' "v": "svc-%d"}}, "child": {"dur": {"min_ms": %d}}}}'
                    % (i % 6, 50 * (i + 1)))
                req = tempopb.SearchRequest()
                req.limit = 64
                structural_mod.attach_query(req, expr)
                mq = compile_multi(blocks, req, cache_on=batch)
                mq.structural = compile_structural(expr, blocks,
                                                   cache_on=batch)
                mqs.append(mq)
                stats.append(query_stats.QueryStats(f"t{i % 3}"))

            def submit(i):
                with query_stats.activate(stats[i]):
                    # the serving path registers the compiled plan at
                    # prepare time; mirror it for the explain tree
                    stats[i].add_structural(mqs[i].structural)
                    futs.append(co.submit(
                        batch, mqs[i],
                        resolve_top_k(eng.top_k, mqs[i].limit),
                        peers=8))

            threads = [threading.Thread(target=submit, args=(i,))
                       for i in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            for f in futs:
                f.result(timeout=60)
        finally:
            PROFILER._listeners.remove(listener)

        # ONE fused launch served all 8 structural members
        assert co.fused == 1 and co.queries == 8
        assert co.structural_stacked == 8
        fused = [rd for rd in caught if rd.get("mode") == "coalesced"]
        assert len(fused) == 1
        totals = {k: v / 1e3 for k, v in fused[0]["stages_ms"].items()}
        for stage, total in totals.items():
            attributed = sum(qs.device_stages.get(stage, 0.0)
                             for qs in stats)
            assert attributed == pytest.approx(total, rel=1e-9), stage
        assert sum(qs.h2d_bytes for qs in stats) == pytest.approx(
            fused[0].get("h2d_bytes", 0), rel=1e-9)
        # per-member explain: each member's plan tree apportions its
        # OWN execute share over its node weights, conserved
        for qs in stats:
            d = qs.to_dict()
            nodes = d["structural"]["nodes"]
            assert nodes and {n["op"] for n in nodes} >= {"child"}
            exec_s = (qs.device_stages.get("execute")
                      or sum(qs.device_stages.values()))
            assert sum(n["device_ms"] for n in nodes) == pytest.approx(
                exec_s * 1e3, abs=1e-3)
    finally:
        STRUCTURAL.enabled = prev
        STRUCTURAL.stack_enabled = prev_stack


def test_conservation_8way_coalesced():
    """8 concurrent queries fuse into ONE dispatch (max_queries=8, size
    flush); the per-query attributed stage seconds and h2d bytes must
    sum to the fused dispatch record's totals within float tolerance."""
    blocks = _blocks(2, entries=128)
    eng = MultiBlockEngine(top_k=64)
    batch = eng.stage(blocks)
    co = QueryCoalescer(eng, window_s=60.0, max_queries=8,
                        active_fn=lambda: 8)

    caught: list[dict] = []
    listener = caught.append
    PROFILER.add_listener(listener)
    try:
        reqs = [_mk_req({"service.name": f"svc-{i % 6}"},
                        limit=10 + i) for i in range(8)]
        mqs = [compile_multi(blocks, r) for r in reqs]
        stats = [query_stats.QueryStats("t%d" % (i % 3)) for i in range(8)]
        futs = []

        def submit(i):
            with query_stats.activate(stats[i]):
                futs.append(co.submit(
                    batch, mqs[i],
                    resolve_top_k(eng.top_k, mqs[i].limit), peers=8))

        threads = [threading.Thread(target=submit, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for f in futs:
            f.result(timeout=60)
    finally:
        PROFILER._listeners.remove(listener)

    assert co.fused == 1 and co.queries == 8
    fused = [rd for rd in caught if rd.get("mode") == "coalesced"]
    assert len(fused) == 1
    rec = fused[0]
    totals = {k: v / 1e3 for k, v in rec["stages_ms"].items()}

    for qs in stats:
        assert qs.fused_dispatches == 1
        assert qs.coalesced_with == 7
        assert qs.device_seconds > 0

    for stage, total in totals.items():
        attributed = sum(qs.device_stages.get(stage, 0.0) for qs in stats)
        assert attributed == pytest.approx(total, rel=1e-9), stage
    total_h2d = rec.get("h2d_bytes", 0)
    assert sum(qs.h2d_bytes for qs in stats) == pytest.approx(
        total_h2d, rel=1e-9)
    # and the whole bill conserves: sum of device_seconds == sum stages
    assert sum(qs.device_seconds for qs in stats) == pytest.approx(
        sum(totals.values()), rel=1e-9)


def test_concurrent_batcher_searches_all_report_stats():
    """Through the real batcher under 8-way concurrency: every query's
    results carry non-zero inspected counts and its own stats record
    (fused or not)."""
    blocks = _blocks(2, entries=128)
    batcher = BlockBatcher(coalesce_window_s=0.05, coalesce_max_queries=8)
    jobs = _jobs(blocks)
    barrier = threading.Barrier(8)
    out: list = [None] * 8

    def run(i):
        req = _mk_req({"service.name": f"svc-{i % 6}"}, limit=20)
        qs = query_stats.begin(f"tenant-{i % 2}", req)
        barrier.wait()
        with query_stats.activate(qs):
            res = batcher.search(jobs, req)
        out[i] = (res, qs.finish())

    threads = [threading.Thread(target=run, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    for res, d in out:
        assert res.metrics.inspected_blocks > 0
        assert res.metrics.inspected_traces > 0
        assert d["device_seconds"] > 0
        assert d["dispatches"] >= 1
    snap = query_stats.REGISTRY.snapshot()
    assert snap["tenants"]["tenant-0"]["queries"] == 4
    assert snap["tenants"]["tenant-1"]["queries"] == 4
    assert snap["tenants"]["tenant-0"]["device_seconds"] > 0


# ---------------------------------------------------------------------------
# noop contract


def test_disabled_is_true_noop_and_byte_identical():
    blocks = _blocks(2, entries=128)
    batcher = BlockBatcher()
    jobs = _jobs(blocks)
    req = _mk_req({"service.name": "svc-1"}, limit=50)

    query_stats.configure(enabled=False)
    assert query_stats.begin("t1", req) is None
    r_off = batcher.search(jobs, req).response()
    published_off = query_stats.REGISTRY._published

    query_stats.configure(enabled=True)
    qs = query_stats.begin("t1", req)
    with query_stats.activate(qs):
        r_on = batcher.search(jobs, req).response()
    qs.finish()

    t_off = b"".join(t.SerializeToString() for t in r_off.traces)
    t_on = b"".join(t.SerializeToString() for t in r_on.traces)
    assert t_off == t_on
    # legacy metrics identical; only the stats layer differs
    assert r_off.metrics.inspected_traces == r_on.metrics.inspected_traces
    assert r_off.metrics.device_seconds == 0.0
    assert not r_off.metrics.query_stats_json
    assert query_stats.REGISTRY._published == published_off + 1


# ---------------------------------------------------------------------------
# explain end-to-end (TempoDB + frontend merge + HTTP)


def _seeded_db(tmp_path, n_blocks=2, **cfg):
    from tempo_tpu.backend import LocalBackend
    from tempo_tpu.db import TempoDB, TempoDBConfig
    from tempo_tpu.model import segment_codec_for
    from tempo_tpu.search import extract_search_data
    from tempo_tpu.utils.ids import random_trace_id
    from tempo_tpu.utils.test_data import make_trace

    db = TempoDB(LocalBackend(str(tmp_path / "blocks")),
                 str(tmp_path / "wal"), TempoDBConfig(**cfg))
    sc = segment_codec_for("v2")
    for b in range(n_blocks):
        blk = db.wal.new_block("acme")
        entries = {}
        for i in range(30):
            tid = random_trace_id()
            tr = make_trace(tid, seed=b * 100 + i)
            sd = extract_search_data(tid, tr)
            blk.append(tid, sc.prepare_for_write(tr, sd.start_s, sd.end_s),
                       sd.start_s, sd.end_s)
            entries[tid] = sd
        db.complete_block(blk, [entries[t] for t in sorted(entries)])
        blk.clear()
    return db


def test_explain_rides_search_response(tmp_path):
    db = _seeded_db(tmp_path)
    req = tempopb.SearchRequest()
    req.limit = 100
    req.explain = True
    resp = db.search("acme", req).response()
    assert resp.metrics.device_seconds > 0
    assert resp.metrics.inspected_bytes_device > 0
    d = json.loads(resp.metrics.query_stats_json)
    assert d["tenant"] == "acme"
    assert d["device_seconds"] > 0
    assert d["blocks_inspected"] == resp.metrics.inspected_blocks
    # without explain the heavy JSON stays off the wire but the
    # accounting fields still ride
    req2 = tempopb.SearchRequest()
    req2.limit = 100
    resp2 = db.search("acme", req2).response()
    assert resp2.metrics.device_seconds > 0
    assert not resp2.metrics.query_stats_json


def test_search_blocks_protocol_carries_stats(tmp_path):
    db = _seeded_db(tmp_path)
    meta = db.blocklist.metas("acme")[0]
    breq = tempopb.SearchBlocksRequest()
    breq.tenant_id = "acme"
    breq.search_req.limit = 50
    breq.search_req.explain = True
    j = breq.jobs.add()
    j.block_id = meta.block_id
    j.encoding = db.cfg.search_encoding
    j.version = meta.version
    j.data_encoding = meta.data_encoding
    resp = db.search_blocks(breq).response()
    assert resp.metrics.device_seconds > 0
    d = json.loads(resp.metrics.query_stats_json)
    assert d["scope"] == "exec" and d["tenant"] == "acme"


def test_frontend_merges_subquery_stats(tmp_path):
    """The frontend's request-scope record merges sub-responses'
    breakdowns; explain returns ONE merged breakdown."""
    from tempo_tpu.modules.app import App, AppConfig

    app = App(AppConfig(wal_dir=str(tmp_path / "wal")))
    tid_seed = 0
    from tempo_tpu.utils.ids import random_trace_id
    from tempo_tpu.utils.test_data import make_trace

    for i in range(10):
        app.push("t1", list(make_trace(random_trace_id(),
                                       seed=tid_seed + i).batches))
    app.flush_tick(force=True)
    app.poll_tick()
    req = tempopb.SearchRequest()
    req.limit = 50
    req.explain = True
    resp = app.search("t1", req)
    assert resp.metrics.inspected_traces > 0
    d = json.loads(resp.metrics.query_stats_json)
    assert d["scope"] == "request"
    assert d.get("subqueries", 0) >= 1
    assert d["device_seconds"] >= 0
    # the merged breakdown never contradicts the metrics beside it:
    # sub-responses WITHOUT a breakdown (the live ingester leg) are
    # absorbed as a remainder
    assert d["blocks_inspected"] == resp.metrics.inspected_blocks
    assert (d["bytes_inspected"]["host"] + d["bytes_inspected"]["device"]
            ) == resp.metrics.inspected_bytes
    # ring saw both scopes (request + exec) in-process
    scopes = {e["scope"] for e in query_stats.REGISTRY.snapshot()["recent"]}
    assert {"exec", "request"} <= scopes


def test_http_explain_param_and_debug_endpoint(tmp_path):
    from tempo_tpu.api.http import HTTPApi
    from tempo_tpu.modules.app import App, AppConfig
    from tempo_tpu.utils.ids import random_trace_id
    from tempo_tpu.utils.test_data import make_trace

    app = App(AppConfig(wal_dir=str(tmp_path / "wal")))
    for i in range(5):
        app.push("t1", list(make_trace(random_trace_id(), seed=i).batches))
    app.flush_tick(force=True)
    app.poll_tick()
    api = HTTPApi(app)
    hdr = {"X-Scope-OrgID": "t1"}
    code, body = api.handle("GET", "/api/search",
                            {"limit": "10", "explain": "1"}, hdr)
    assert code == 200
    assert "queryStats" in body, body
    assert body["queryStats"]["scope"] == "request"
    assert "queryStatsJson" not in body.get("metrics", {})

    # header opt-in too
    code, body = api.handle(
        "GET", "/api/search", {"limit": "10"},
        {"X-Scope-OrgID": "t1", "X-Tempo-Explain": "1"})
    assert code == 200 and "queryStats" in body

    # "X-Tempo-Explain: 0" is an explicit NO, not a truthy string
    code, body = api.handle(
        "GET", "/api/search", {"limit": "10"},
        {"X-Scope-OrgID": "t1", "X-Tempo-Explain": "0"})
    assert code == 200 and "queryStats" not in body

    # without the opt-in: no breakdown
    code, body = api.handle("GET", "/api/search", {"limit": "10"}, hdr)
    assert code == 200 and "queryStats" not in body

    code, body = api.handle("GET", "/debug/querystats", {}, hdr)
    assert code == 200
    assert body["enabled"] is True
    assert body["recent"], "ring must carry the queries above"
    assert body["tenants"]
    assert "top_by_device_seconds" in body

    # /status gained the device block
    code, body = api.handle("GET", "/status", {}, hdr)
    assert code == 200
    assert "device" in body
    assert "backend" in body["device"]
    assert "last_dispatch_age_s" in body["device"]


def test_debug_querystats_respects_debug_gate(tmp_path):
    from tempo_tpu.api.http import HTTPApi
    from tempo_tpu.modules.app import App, AppConfig

    app = App(AppConfig(wal_dir=str(tmp_path / "wal")))
    api = HTTPApi(app, debug_endpoints=False)
    code, _ = api.handle("GET", "/debug/querystats", {}, {})
    assert code == 404


# ---------------------------------------------------------------------------
# slow-query log + counters


def test_slow_query_log_emits_one_json_line(caplog):
    query_stats.configure(slow_s=0.0001)
    qs = query_stats.QueryStats("noisy-tenant")
    qs.add_device_stages({"execute": 0.5})
    time.sleep(0.002)
    with caplog.at_level(logging.WARNING, logger="tempo_tpu.slowquery"):
        qs.finish()
    lines = [r.getMessage() for r in caplog.records
             if r.name == "tempo_tpu.slowquery"]
    assert len(lines) == 1
    doc = json.loads(lines[0])
    assert doc["msg"] == "slow query"
    assert doc["tenant"] == "noisy-tenant"
    assert doc["device_seconds"] == 0.5
    assert obs.slow_queries.value(tenant="noisy-tenant") >= 1


def test_slow_query_log_rate_limited(caplog):
    query_stats.configure(slow_s=0.0001)
    with caplog.at_level(logging.WARNING, logger="tempo_tpu.slowquery"):
        for _ in range(50):
            qs = query_stats.QueryStats("flood")
            time.sleep(0.0002)
            qs.finish()
    lines = [r for r in caplog.records if r.name == "tempo_tpu.slowquery"]
    assert len(lines) <= 6  # burst 5 + at most one refill
    # every slow query still COUNTS even when its line was dropped
    assert obs.slow_queries.value(tenant="flood") >= 50


def test_per_tenant_counters_accumulate():
    before_dev = obs.query_device_seconds.value(tenant="bill-me")
    before_b = obs.query_bytes_inspected.value(tenant="bill-me",
                                               placement="device")
    qs = query_stats.QueryStats("bill-me")
    qs.add_device_stages({"execute": 0.25, "h2d": 0.05})
    qs.add_inspected(blocks=2, nbytes=1 << 20, placement="device")
    qs.add_inspected(nbytes=1 << 10, placement="host")
    qs.finish()
    assert obs.query_device_seconds.value(tenant="bill-me") \
        == pytest.approx(before_dev + 0.30)
    assert obs.query_bytes_inspected.value(
        tenant="bill-me", placement="device") == before_b + (1 << 20)
    assert obs.query_bytes_inspected.value(
        tenant="bill-me", placement="host") >= 1 << 10


def test_request_scope_does_not_book_tenant_counters():
    before = obs.query_device_seconds.value(tenant="front-only")
    qs = query_stats.QueryStats("front-only", scope="request")
    qs.add_device_stages({"execute": 1.0})
    qs.finish()
    assert obs.query_device_seconds.value(tenant="front-only") == before
    # but it IS in the ring
    assert any(e["tenant"] == "front-only"
               for e in query_stats.REGISTRY.snapshot()["recent"])


def test_nested_attribution_bills_once():
    """A body that itself runs an attributing engine must not be
    double-billed: the inner context attributes, the outer skips its
    wall fallback."""
    qs = query_stats.QueryStats("t1")
    with query_stats.attributed_dispatch(qs):
        with query_stats.attributed_dispatch(qs):
            time.sleep(0.005)
    assert qs.dispatches == 1
    # sequential sibling contexts still each bill
    with query_stats.attributed_dispatch(qs):
        time.sleep(0.001)
    assert qs.dispatches == 2


def test_slow_counter_books_once_per_query_per_process(caplog):
    """Counter and log share one dedupe rule: fronted exec records
    (in-process sub-requests of a request-scope record) book nothing —
    a 4-shard slow query must count 1, not 4 (its fan-out factor)."""
    query_stats.configure(slow_s=0.0001)
    before = obs.slow_queries.value(tenant="scoped")
    with caplog.at_level(logging.WARNING, logger="tempo_tpu.slowquery"):
        with query_stats.fronted():
            for _ in range(4):  # the request's shard fan-out
                qs = query_stats.QueryStats("scoped")
                time.sleep(0.001)
                qs.finish()
        qreq = query_stats.QueryStats("scoped", scope="request")
        time.sleep(0.001)
        qreq.finish()
    assert obs.slow_queries.value(tenant="scoped") == before + 1
    lines = [r for r in caplog.records if r.name == "tempo_tpu.slowquery"]
    assert len(lines) == 1
    # a standalone querier (exec, unfronted) books its own view
    qs2 = query_stats.QueryStats("scoped")
    time.sleep(0.001)
    qs2.finish()
    assert obs.slow_queries.value(tenant="scoped") == before + 2


def test_slow_log_limiter_is_per_tenant():
    """Tenant A's flood must not starve tenant B's line — B's slow
    query is exactly the diagnostic the log exists for."""
    query_stats.configure(slow_s=0.0001)
    lim = query_stats.REGISTRY._limiter
    for _ in range(50):
        assert lim.allow("flood-a") or True  # drain A's bucket
    assert not lim.allow("flood-a")
    assert lim.allow("quiet-b"), "B starved by A's flood"


def test_fronted_exec_suppresses_slow_log_line(caplog):
    """In-process frontend sub-requests (the fronted() mark) must not
    emit their own slow-log line — the request-scope line covers the
    query; ONE line per slow query per process. Counters still book."""
    query_stats.configure(slow_s=0.0001)
    before = obs.slow_queries.value(tenant="one-line")
    with caplog.at_level(logging.WARNING, logger="tempo_tpu.slowquery"):
        with query_stats.fronted():
            qs = query_stats.QueryStats("one-line")  # exec, fronted
            time.sleep(0.001)
            qs.finish()
        qs2 = query_stats.QueryStats("one-line", scope="request")
        time.sleep(0.001)
        qs2.finish()
    lines = [r for r in caplog.records if r.name == "tempo_tpu.slowquery"]
    assert len(lines) == 1
    assert json.loads(lines[0].getMessage())["scope"] == "request"
    # the counter still booked the (fronted) exec record
    assert obs.slow_queries.value(tenant="one-line") == before + 1


def test_explain_param_roundtrip():
    from tempo_tpu.api.params import build_search_request, \
        parse_search_request

    req = _mk_req({"a": "b"}, limit=5)
    req.explain = True
    qs = build_search_request(req)
    import urllib.parse

    parsed = parse_search_request(
        {k: v[0] for k, v in urllib.parse.parse_qs(qs).items()})
    assert parsed.explain is True
