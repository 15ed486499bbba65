"""Dogfood trace pipeline (`selftrace_ingest_enabled`): self-traces are
ingested into the reserved ``_selftrace`` tenant and searchable end to
end, dispatch profiler records lower into per-stage child spans,
request-scope QueryStats attach as ``query.*`` span attributes, and the
anomaly flight recorder snapshots bounded diagnostic bundles whose
trace ids resolve via ordinary trace-by-ID.

The acceptance centerpiece: ONE external search request, with the gate
on, yields a ``_selftrace`` trace that is (a) retrievable by
trace-by-ID and (b) matched by a structural ``?q=`` over span.stage —
within one flush+poll cycle. Plus: gate off is byte-identical noop, and
an injected breaker trip produces a flight-recorder bundle whose trace
id resolves.
"""

import json
import os
import time

import pytest

from tempo_tpu import robustness, tempopb
from tempo_tpu.api.http import HTTPApi
from tempo_tpu.db.tempodb import TempoDBConfig
from tempo_tpu.modules import App, AppConfig
from tempo_tpu.observability import profile, selftrace, tracing
from tempo_tpu.observability.flightrecorder import (RECORDER,
                                                    TRIGGER_BREAKER,
                                                    TRIGGER_SLOW_QUERY,
                                                    TRIGGER_WATCHDOG,
                                                    FlightRecorder)
from tempo_tpu.observability.selftrace import SELFTRACE
from tempo_tpu.observability.tracing import (SELFTRACE_TENANT,
                                             CollectExporter,
                                             InProcessExporter,
                                             SyncProcessor, Tracer)
from tempo_tpu.utils.ids import random_trace_id
from tempo_tpu.utils.test_data import make_trace


@pytest.fixture(autouse=True)
def _clean_selftrace():
    yield
    tracing.set_tracer(None)
    selftrace.configure(ingest_enabled=False, flight_recorder_max=32)
    RECORDER.reset()
    robustness.FAULTS.disarm_all()
    robustness.BREAKER.reset()
    robustness.BREAKER.enabled = True
    robustness.BREAKER.threshold = 3


def _dogfood_app(tmp_path, **db_kw):
    db_kw.setdefault("search_structural_enabled", True)
    db_kw.setdefault("auto_mesh", False)
    return App(AppConfig(
        wal_dir=str(tmp_path / "wal"),
        db=TempoDBConfig(**db_kw),
        self_tracing={"enabled": True, "exporter": "self",
                      "selftrace_ingest_enabled": True,
                      "sample_ratio": 1.0,
                      "flush_interval_s": 0.05},
    ))


def _seed_corpus(app, tenant="t1", n=3):
    for seed in range(1, n + 1):
        app.push(tenant, list(make_trace(random_trace_id(),
                                         seed=seed).batches))
    app.flush_tick(force=True)
    app.poll_tick()


# ------------------------------------------------ the dogfood loop


def test_dogfood_round_trip_one_request_one_cycle(tmp_path):
    """One external search → a `_selftrace` trace retrievable by
    trace-by-ID AND matched by a structural query on span.stage, within
    one flush+poll cycle."""
    app = _dogfood_app(tmp_path)
    try:
        assert SELFTRACE.ingest_enabled
        assert RECORDER.enabled
        assert isinstance(app.tracer.processor.exporter, InProcessExporter)
        api = HTTPApi(app)
        _seed_corpus(app)

        # warm the jit cache: the profiler books a cache-miss dispatch
        # under "compile"; the SECOND (hit) request records "execute"
        for _ in range(2):
            code, body = api.handle(
                "GET", "/api/search",
                {"tags": "service.name=frontend", "limit": "10"},
                {"X-Scope-OrgID": "t1"})
            assert code == 200

        # one flush+poll cycle makes the self-spans block-searchable
        app.tracer.processor.force_flush()
        app.flush_tick(force=True)
        app.poll_tick()

        hdr = {"X-Scope-OrgID": SELFTRACE_TENANT}

        # structural query over dispatch stage spans — "execute" is
        # recorded for every device dispatch
        q = json.dumps({"exists": {"tag": {"k": "stage", "v": "execute"}}})
        code, sbody = api.handle("GET", "/api/search",
                                 {"q": q, "limit": "20"}, hdr)
        assert code == 200
        hits = sbody.get("traces") or []
        assert hits, "structural span.stage query found no self-traces"

        # among the structural hits, the external request's own trace
        # must resolve by trace-by-ID and carry the dispatch children
        request_trace = None
        for hit in hits:
            code, trace = api.handle(
                "GET", f"/api/traces/{hit['traceId']}", {}, hdr)
            assert code == 200, f"trace-by-ID failed for {hit['traceId']}"
            flat = json.dumps(trace)
            if "/api/search" in flat:
                request_trace = flat
                break
        assert request_trace is not None, \
            "no structural hit resolved to the external search request"
        assert "dispatch.execute" in request_trace
        # QueryStats breakdown rode along as query.* span attributes
        assert "query.wall_ms" in request_trace
    finally:
        app.shutdown()


def test_gate_off_is_inert_and_reserved_tenant_untouched(tmp_path):
    """Default (gate off): plain SelfExporter, dead singletons, and no
    `_selftrace` tenant materializes anywhere in the pipeline."""
    app = App(AppConfig(
        wal_dir=str(tmp_path / "wal"),
        self_tracing={"enabled": True, "exporter": "self",
                      "flush_interval_s": 0.05},
    ))
    try:
        assert not SELFTRACE.ingest_enabled
        assert not RECORDER.enabled
        assert RECORDER.record(TRIGGER_BREAKER) is None
        assert not isinstance(app.tracer.processor.exporter,
                              InProcessExporter)

        _seed_corpus(app)
        api = HTTPApi(app)
        code, _ = api.handle("GET", "/api/search",
                             {"tags": "service.name=frontend"},
                             {"X-Scope-OrgID": "t1"})
        assert code == 200
        app.tracer.processor.force_flush()
        app.flush_tick(force=True)
        app.poll_tick()

        # self-spans went to the CONFIGURED tenant (legacy behavior),
        # never the reserved one
        req = tempopb.SearchRequest()
        req.tags["service.name"] = "tempo-tpu"
        assert len(app.frontend.search(SELFTRACE_TENANT, req).traces) == 0
        wal = tmp_path / "wal"
        if wal.exists():
            assert SELFTRACE_TENANT not in os.listdir(wal)
    finally:
        app.shutdown()


def test_gate_on_vs_off_external_responses_identical(tmp_path):
    """Contract check: the gate must not leak into user-visible
    responses — same corpus, same query, byte-identical /api/search
    answers with the gate on and off."""
    def run(enabled, where):
        cfg = {"enabled": True, "exporter": "self",
               "flush_interval_s": 0.05}
        if enabled:
            cfg["selftrace_ingest_enabled"] = True
        app = App(AppConfig(
            wal_dir=str(where / "wal"),
            db=TempoDBConfig(auto_mesh=False),
            self_tracing=cfg))
        try:
            for seed in (1, 2):
                app.push("t1", list(make_trace(
                    bytes([seed]) * 16, seed=seed).batches))
            app.flush_tick(force=True)
            app.poll_tick()
            api = HTTPApi(app)
            code, body = api.handle(
                "GET", "/api/search",
                {"tags": "service.name=frontend", "limit": "10"},
                {"X-Scope-OrgID": "t1"})
            assert code == 200
            return json.dumps(body, sort_keys=True)
        finally:
            app.shutdown()

    on = run(True, tmp_path / "on")
    off = run(False, tmp_path / "off")
    assert on == off


def test_sse_stream_metrics_and_self_trace(tmp_path):
    """Satellite: the SSE surfaces are instrumented — active-stream
    gauge balances to zero, per-tenant event counters tick, and the
    streaming leg leaves its own span in `_selftrace`."""
    from tempo_tpu.observability import metrics as obs

    app = _dogfood_app(tmp_path)
    try:
        api = HTTPApi(app)
        _seed_corpus(app)
        hdr = {"X-Scope-OrgID": "t1"}
        g0 = obs.sse_active_streams.value(endpoint="search_stream",
                                          tenant="t1")
        done0 = obs.sse_events_streamed.value(
            endpoint="search_stream", tenant="t1", event="done")
        code, body = api.handle("GET", "/api/search/stream",
                                {"limit": "10"}, hdr)
        assert code == 200
        frames = list(body.events)
        assert frames and frames[-1].startswith("event: done")
        assert obs.sse_active_streams.value(
            endpoint="search_stream", tenant="t1") == g0
        assert obs.sse_events_streamed.value(
            endpoint="search_stream", tenant="t1", event="done") \
            == done0 + 1

        app.tracer.processor.force_flush()
        app.flush_tick(force=True)
        app.poll_tick()
        shdr = {"X-Scope-OrgID": SELFTRACE_TENANT}
        code, sbody = api.handle("GET", "/api/search",
                                 {"tags": "service.name=tempo-tpu",
                                  "limit": "20"}, shdr)
        assert code == 200
        seen = []
        for hit in sbody.get("traces") or []:
            code, trace = api.handle(
                "GET", f"/api/traces/{hit['traceId']}", {}, shdr)
            assert code == 200
            seen.append(json.dumps(trace))
        assert any("sse.search_stream" in t for t in seen), \
            "streaming leg span missing from _selftrace"
    finally:
        app.shutdown()


# --------------------------------------------------- stage child spans


def _sync_tracer():
    exp = CollectExporter()
    tracer = Tracer(SyncProcessor(exp))
    tracing.set_tracer(tracer)
    return exp, tracer


def _timed_dispatch(stages=("build", "h2d", "compile", "d2h")):
    """One profiler record whose stages are timed as the dispatch sites
    time them (`rec.stage(...)`), with transfer bytes and a jit miss."""
    with profile.dispatch("batched") as rec:
        rec.compile_check(("test_selftrace", time.perf_counter_ns()))
        rec.add_bytes(h2d=4096, d2h=128)
        for stage in stages:
            with rec.stage(stage):
                time.sleep(0.0005)
    return rec


def test_dispatch_stage_children_carry_observed_intervals():
    """The dogfood tenant's `dispatch.<stage>` spans come from the
    profiler's stage timers: whether or not the ingest gate is on, each
    child's start and end are the two clock reads of its timer."""
    exp, tracer = _sync_tracer()
    selftrace.configure(ingest_enabled=False)
    with tracer.start_span("req") as parent:
        rec = _timed_dispatch()
    children = [s for s in exp.spans if s.name.startswith("dispatch.")]
    assert [s.name for s in children] == [
        "dispatch.build", "dispatch.h2d", "dispatch.compile",
        "dispatch.d2h"]
    for s in children:
        assert s.parent_span_id == parent.context.span_id
        assert s.context.trace_id == parent.context.trace_id
        assert s.attributes["mode"] == "batched"
        assert s.attributes["stage"] == s.name.split(".")[1]
        assert parent.start_ns <= s.start_ns < s.end_ns <= parent.end_ns
        # the span and the stage's seconds are the same two stamps
        assert (s.end_ns - s.start_ns) / 1e9 == rec.stages[
            s.attributes["stage"]]
    # in the order they ran, none starting before the one before ended
    # and none laid end to end with it
    for a, b in zip(children, children[1:]):
        assert a.end_ns < b.start_ns
    by_name = {s.name: s for s in children}
    # transfer bytes + jit verdict ride along
    assert by_name["dispatch.h2d"].attributes["bytes"] == 4096
    assert by_name["dispatch.d2h"].attributes["bytes"] == 128
    assert by_name["dispatch.compile"].attributes["jit_cache"] == "miss"
    assert "jit_cache" not in by_name["dispatch.h2d"].attributes


def test_dispatch_stage_children_noop_paths():
    exp, tracer = _sync_tracer()
    # no recording parent (no span open) → no children
    _timed_dispatch()
    assert exp.spans == []
    # no timed stage → nothing
    with tracer.start_span("req"):
        _timed_dispatch(stages=())
    assert [s.name for s in exp.spans] == ["req"]
    # no tracer installed → the record keeps no intervals at all
    tracing.set_tracer(None)
    rec = _timed_dispatch()
    assert rec.intervals is None and set(rec.stages) == {
        "build", "h2d", "compile", "d2h"}
    assert [s.name for s in exp.spans] == ["req"]


def test_annotate_query_attaches_headline_costs():
    exp, tracer = _sync_tracer()
    selftrace.configure(ingest_enabled=True)
    d = {"wall_ms": 12.5, "device_seconds": 0.003,
         "blocks_inspected": 7,
         "bytes_inspected": {"host": 1000, "device": 2000},
         "dispatches": 4, "fused_dispatches": 2}
    with tracer.start_span("request") as span:
        SELFTRACE.annotate_query(d)
    attrs = exp.spans[0].attributes
    assert attrs["query.wall_ms"] == 12.5
    assert attrs["query.device_seconds"] == 0.003
    assert attrs["query.blocks_inspected"] == 7
    assert attrs["query.bytes_host"] == 1000
    assert attrs["query.bytes_device"] == 2000
    assert attrs["query.dispatches"] == 4
    assert attrs["query.fused_dispatches"] == 2
    assert "query.subqueries" not in attrs
    # gate off → span untouched
    selftrace.configure(ingest_enabled=False)
    with tracer.start_span("request2"):
        SELFTRACE.annotate_query(d)
    assert "query.wall_ms" not in exp.spans[1].attributes
    assert span is not None


# ------------------------------------------------ flight recorder


def test_flight_recorder_ring_and_snapshot():
    rec = FlightRecorder(max_bundles=2)
    assert rec.record(TRIGGER_SLOW_QUERY) is None  # disabled
    rec.enabled = True
    b1 = rec.record(TRIGGER_SLOW_QUERY, trace_id="aa" * 16,
                    detail={"wall_ms": 900})
    assert b1["seq"] == 1 and b1["trigger"] == TRIGGER_SLOW_QUERY
    assert b1["trace_id"] == "aa" * 16
    assert b1["detail"] == {"wall_ms": 900}
    # every subsystem key present (value may be None outside an App)
    for key in ("profile", "breaker", "planner", "ownership"):
        assert key in b1
    rec.record(TRIGGER_BREAKER)
    rec.record(TRIGGER_BREAKER)
    snap = rec.snapshot()
    assert snap["recorded"] == 3
    assert snap["by_trigger"] == {TRIGGER_SLOW_QUERY: 1, TRIGGER_BREAKER: 2}
    assert len(snap["bundles"]) == 2  # ring bound: oldest evicted
    assert [b["seq"] for b in snap["bundles"]] == [2, 3]
    json.loads(json.dumps(snap, default=str))  # /debug-renderable
    rec.resize(1)
    assert len(rec.snapshot()["bundles"]) == 1
    rec.reset()
    assert rec.snapshot()["recorded"] == 0


def test_flight_recorder_captures_current_trace_id():
    _, tracer = _sync_tracer()
    rec = FlightRecorder()
    rec.enabled = True
    with tracer.start_span("victim") as span:
        bundle = rec.record(TRIGGER_WATCHDOG)
    assert bundle["trace_id"] == span.context.trace_id.hex()


def test_breaker_trip_produces_resolvable_bundle(tmp_path):
    """An injected dispatch fault trips the breaker; the flight
    recorder snapshots a bundle whose trace id resolves in
    `_selftrace`; /debug/flightrecorder renders it."""
    app = _dogfood_app(tmp_path)
    try:
        api = HTTPApi(app)
        _seed_corpus(app)
        RECORDER.reset()
        robustness.BREAKER.reset()
        robustness.BREAKER.enabled = True
        robustness.BREAKER.threshold = 1
        with robustness.FAULTS.armed("device_dispatch_raise", count=1):
            code, _ = api.handle(
                "GET", "/api/search",
                {"tags": "service.name=frontend", "limit": "10"},
                {"X-Scope-OrgID": "t1"})
            assert code == 200  # host fallback keeps the answer intact

        snap = RECORDER.snapshot()
        trips = [b for b in snap["bundles"]
                 if b["trigger"] == TRIGGER_BREAKER]
        assert trips, f"no breaker_trip bundle recorded: {snap}"
        bundle = trips[-1]
        assert bundle["trace_id"], "bundle did not capture a trace id"
        assert bundle["breaker"] is not None
        assert bundle["profile"] is not None

        # the offending request's own self-trace resolves by ID
        app.tracer.processor.force_flush()
        app.flush_tick(force=True)
        app.poll_tick()
        code, trace = api.handle(
            "GET", f"/api/traces/{bundle['trace_id']}", {},
            {"X-Scope-OrgID": SELFTRACE_TENANT})
        assert code == 200, \
            f"flight-recorder trace id did not resolve: {bundle['trace_id']}"
        assert "/api/search" in json.dumps(trace)

        dbg = HTTPApi(app, debug_endpoints=True)
        code, page = dbg.handle("GET", "/debug/flightrecorder", {}, {})
        assert code == 200
        assert page["by_trigger"].get(TRIGGER_BREAKER, 0) >= 1
        json.loads(json.dumps(page, default=str))
    finally:
        app.shutdown()
