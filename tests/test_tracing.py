"""Self-tracing subsystem (observability/tracing).

Mirrors the reference's tracer-init + spanlogger role (cmd/tempo/main.go
installOpenTelemetryTracer, pkg/util/spanlogger): span lifecycle and
parenting, sampling, W3C propagation, batch export, and the
"tempo traces tempo" self-ingest loop end-to-end through a real App.
"""

import logging
import threading
import time

import pytest

from tempo_tpu import tempopb
from tempo_tpu.modules import App, AppConfig
from tempo_tpu.observability import tracing
from tempo_tpu.observability.tracing import (
    BatchProcessor, CollectExporter, SelfExporter, Span, SpanLogger,
    SyncProcessor, Tracer, extract_traceparent, inject_traceparent,
    spans_to_resource_spans,
)
from tests.test_coalesce import _blocks, _jobs, _mk_req


@pytest.fixture(autouse=True)
def _clean_tracer():
    yield
    tracing.set_tracer(None)


def _tracer(ratio=1.0):
    exp = CollectExporter()
    return Tracer(SyncProcessor(exp), sample_ratio=ratio), exp


def test_span_lifecycle_and_attributes():
    tr, exp = _tracer()
    with tr.start_span("op", tenant="t1") as span:
        span.set_attribute("k", 42)
        span.add_event("milestone", n=1)
    (s,) = exp.spans
    assert s.name == "op"
    assert s.attributes == {"tenant": "t1", "k": 42}
    assert s.end_ns >= s.start_ns
    assert s.events[0][1] == "milestone"
    assert len(s.context.trace_id) == 16 and len(s.context.span_id) == 8


def test_span_parenting_nested():
    tr, exp = _tracer()
    with tr.start_span("parent") as p:
        with tr.start_span("child") as c:
            assert c.context.trace_id == p.context.trace_id
            assert c.parent_span_id == p.context.span_id
    # both exported, same trace
    assert {s.name for s in exp.spans} == {"parent", "child"}


def test_parenting_across_threads():
    """contextvars copy into threads started with a copied context."""
    import contextvars

    tr, exp = _tracer()
    child_ids = []
    with tr.start_span("parent") as p:
        ctx = contextvars.copy_context()

        def work():
            with tr.start_span("worker") as w:
                child_ids.append((w.context.trace_id, w.parent_span_id))

        t = threading.Thread(target=ctx.run, args=(work,))
        t.start()
        t.join()
    assert child_ids == [(p.context.trace_id, p.context.span_id)]


def test_sampling_zero_ratio_is_noop():
    tr, exp = _tracer(ratio=0.0)
    with tr.start_span("never") as s:
        assert not s.recording
        # all mutators are free no-ops
        s.set_attribute("a", 1).add_event("e").set_status(2)
    assert exp.spans == []


def test_child_inherits_sampling_decision():
    tr, exp = _tracer(ratio=0.0)
    with tr.start_span("root") as r:
        with tr.start_span("child") as c:
            assert not c.recording
            # same trace: the negative decision propagated, the child did
            # not re-roll into a fresh root trace
            assert c.context.trace_id == r.context.trace_id
    assert exp.spans == []


def test_remote_unsampled_parent_suppresses_whole_stack():
    """traceparent flags 00 → no span anywhere below, and outgoing
    injection forwards the negative decision."""
    tr, exp = _tracer(ratio=1.0)
    ctx = extract_traceparent(
        {"traceparent": "00-" + "ab" * 16 + "-" + "cd" * 8 + "-00"})
    assert ctx is not None and not ctx.sampled
    with tr.start_span("server", parent=ctx) as s:
        assert not s.recording
        with tr.start_span("inner") as i:
            assert not i.recording
            hdrs = inject_traceparent({})
    assert exp.spans == []
    assert hdrs["traceparent"].startswith("00-" + "ab" * 16)
    assert hdrs["traceparent"].endswith("-00")


def test_grpc_client_metadata_carries_traceparent():
    from tempo_tpu.api.grpc_service import _Base

    tr, _ = _tracer()
    tracing.set_tracer(tr)
    client = _Base.__new__(_Base)
    client.tenant = None
    with tr.start_span("client-call") as s:
        md = dict(client._md("t1"))
    assert md["x-scope-orgid"] == "t1"
    assert md["traceparent"].split("-")[1] == s.context.trace_id.hex()


def test_exception_recorded_and_status_error():
    tr, exp = _tracer()
    with pytest.raises(ValueError):
        with tr.start_span("boom"):
            raise ValueError("bad")
    (s,) = exp.spans
    assert s.status_code == tracing.STATUS_ERROR
    assert s.events[0][1] == "exception"
    assert s.events[0][2]["exception.type"] == "ValueError"


def test_module_level_noop_without_tracer():
    tracing.set_tracer(None)
    with tracing.start_span("free") as s:
        assert s is tracing.NOOP_SPAN


def test_traceparent_roundtrip():
    tr, _ = _tracer()
    hdrs = {}
    with tr.start_span("client"):
        inject_traceparent(hdrs)
    ctx = extract_traceparent(hdrs)
    assert ctx is not None and ctx.sampled
    # remote parent continues the trace
    with tr.start_span("server", parent=ctx) as s:
        assert s.context.trace_id == ctx.trace_id
        assert s.parent_span_id == ctx.span_id


@pytest.mark.parametrize("header", [
    "", "garbage", "00-short-aaaa-01",
    "00-" + "0" * 32 + "-" + "1" * 16 + "-01",  # all-zero trace id
    "00-" + "1" * 32 + "-" + "0" * 16 + "-01",  # all-zero parent id
    "00-" + "1" * 32 + "-" + "1" * 16 + "-1",   # short flags
    "00-zz" + "0" * 30 + "-" + "1" * 16 + "-01",  # non-hex
])
def test_traceparent_rejects_malformed(header):
    assert extract_traceparent({"traceparent": header} if header else {}) is None


def test_batch_processor_flushes_and_bounds():
    exp = CollectExporter()
    proc = BatchProcessor(exp, max_batch=4, max_queue=8, interval_s=0.05)
    tr = Tracer(proc)
    for i in range(6):
        tr.start_span(f"s{i}").end()
    deadline = time.monotonic() + 5
    while len(exp.spans) < 6 and time.monotonic() < deadline:
        time.sleep(0.02)
    assert len(exp.spans) == 6
    proc.shutdown()


def test_spans_to_resource_spans_wire():
    tr, exp = _tracer()
    with tr.start_span("a", tenant="x") as s:
        s.add_event("ev", detail="d")
    rs = spans_to_resource_spans(exp.spans, "svc", "inst-1")
    res_attrs = {kv.key: kv.value.string_value
                 for kv in rs.resource.attributes}
    assert res_attrs["service.name"] == "svc"
    (span,) = rs.scope_spans[0].spans
    assert span.name == "a"
    assert span.end_time_unix_nano >= span.start_time_unix_nano
    attrs = {kv.key: kv.value.string_value for kv in span.attributes}
    assert attrs["tenant"] == "x"
    assert span.events[0].name == "ev"
    # the batch is a valid tempopb.Trace member (self-ingest wire format)
    t = tempopb.Trace()
    t.batches.append(rs)
    assert tempopb.Trace.FromString(t.SerializeToString())


def test_self_export_suppression_no_recursion():
    """Exporting spans through a push path that itself creates spans must
    not recurse: the exporter thread is suppressed."""
    depth = []

    class TracingPush:
        def __call__(self, tenant, batches):
            with tracing.start_span("push-internal") as s:
                depth.append(s.recording)

    exp = SelfExporter(TracingPush())
    tr = Tracer(SyncProcessor(exp))
    tracing.set_tracer(tr)
    tr.start_span("outer").end()
    assert depth == [False]  # inner span was noop — no recursion


def test_spanlogger_couples_logs_to_span(caplog):
    tr, exp = _tracer()
    tracing.set_tracer(tr)
    with caplog.at_level(logging.INFO, logger="tempo_tpu"):
        with SpanLogger("frontend.Search", tenant="t1") as sl:
            sl.log("inspected", level=logging.INFO, blocks=3)
    (s,) = exp.spans
    assert s.attributes["tenant"] == "t1"
    assert s.events[0][1] == "inspected"
    assert s.events[0][2] == {"blocks": 3}
    assert any("inspected" in r.message for r in caplog.records)


def test_app_self_tracing_end_to_end(tmp_path):
    """Query spans land back in the framework and are searchable — the
    reference's "tempo traces tempo" deployment, in-process."""
    app = App(AppConfig(
        wal_dir=str(tmp_path / "wal"),
        self_tracing={"enabled": True, "exporter": "self", "tenant": "self",
                      "flush_interval_s": 0.05},
    ))
    try:
        assert app.tracer is not None
        # generate traced work: a search against an empty store
        req = tempopb.SearchRequest()
        req.tags["service.name"] = "nope"
        app.search("t1", req)
        app.tracer.processor.force_flush()

        # exported spans entered the distributor as tenant "self" and are
        # queryable through the normal read path (live-trace search)
        sreq = tempopb.SearchRequest()
        sreq.tags["service.name"] = "tempo-tpu"
        deadline = time.monotonic() + 5
        resp = None
        while time.monotonic() < deadline:
            resp = app.frontend.search("self", sreq)
            if len(resp.traces):
                break
            time.sleep(0.05)
        assert resp is not None and len(resp.traces) >= 1
    finally:
        app.shutdown()


def test_frontend_and_tempodb_spans_emitted(tmp_path):
    """The instrumented layers emit the reference's span names."""
    exp = CollectExporter()
    tracing.set_tracer(Tracer(SyncProcessor(exp)))
    app = App(AppConfig(wal_dir=str(tmp_path / "wal")))
    from tempo_tpu.utils.ids import random_trace_id
    from tempo_tpu.utils.test_data import make_trace

    tid = random_trace_id()
    app.push("t1", list(make_trace(tid, seed=1).batches))
    app.flush_tick(force=True)
    app.poll_tick()
    app.frontend.find_trace_by_id("t1", tid)
    req = tempopb.SearchRequest()
    req.tags["service.name"] = "svc"
    app.frontend.search("t1", req)
    names = {s.name for s in exp.spans}
    assert "frontend.TraceByID" in names
    assert "frontend.Search" in names
    assert "tempodb.Find" in names
    assert "ingester.CompleteBlock" in names
    # frontend span parents the tempodb span (same trace)
    by_name = {}
    for s in exp.spans:
        by_name.setdefault(s.name, s)
    assert (by_name["tempodb.Find"].context.trace_id
            == by_name["frontend.TraceByID"].context.trace_id)


# ------------------------------------------- stamped spans, the span clock


def test_span_clock_is_monotonic_and_on_the_wall_clock():
    a = tracing.now_ns()
    b = tracing.now_ns()
    assert b >= a
    # one anchor taken at import: it tracks the wall clock to well
    # within what an external trace needs to be laid beside it
    assert abs(time.time_ns() - b) < 5e9


@pytest.mark.parametrize("shift_ns,follows", [(10**9, True), (10**5, False)])
def test_installing_a_tracer_takes_the_clocks_anchor_anew(
        monkeypatch, shift_ns, follows):
    """The span clock follows the wall clock's corrections only at an
    anchor: one is taken when a tracer is installed, if the two have
    come a millisecond apart; below that the clock stays continuous."""
    off = time.time_ns() - time.perf_counter_ns() + shift_ns
    monkeypatch.setattr(tracing, "_CLOCK_OFFSET_NS", off)
    tracing.set_tracer(None)             # taking the tracer away: no anchor
    assert tracing._CLOCK_OFFSET_NS == off
    tracing.set_tracer(_tracer()[0])
    assert (tracing._CLOCK_OFFSET_NS != off) == follows
    assert abs(time.time_ns() - tracing.now_ns()) < (
        5e7 if follows else shift_ns + 5e7)


@pytest.mark.parametrize("give_start,give_end", [
    (True, True), (True, False), (False, True), (False, False)])
def test_span_takes_stamps_from_outside(give_start, give_end):
    """`start_ns=` / `end(end_ns=)`: a stamp the caller took at the edge
    is the span's edge; an edge not given is read from the span clock."""
    tracer, exp = _tracer()
    t0 = tracing.now_ns()
    time.sleep(0.002)
    t1 = tracing.now_ns()
    time.sleep(0.002)
    before = tracing.now_ns()
    span = tracer.start_span("wait", start_ns=t0 if give_start else None,
                             depth=3)
    span.end(t1 if give_end and give_start else None)
    after = tracing.now_ns()
    (got,) = exp.spans
    assert got.attributes == {"depth": 3}
    if give_start:
        assert got.start_ns == t0
    else:
        assert before <= got.start_ns <= after
    if give_end and give_start:
        assert got.end_ns == t1
    else:
        assert got.start_ns <= got.end_ns and before <= got.end_ns <= after


@pytest.mark.parametrize("via", ["record_span", "start_span"])
def test_wait_that_crossed_threads_is_written_after_the_fact(via):
    """Stamp + captured context where the wait began (thread A), the
    span written where it ended (thread B, which has no span open):
    same trace, child of the captured span, the two stamps as edges."""
    tracer, exp = _tracer()
    tracing.set_tracer(tracer)
    handoff = {}

    def consumer():
        assert not tracing.current_span().recording
        end = tracing.now_ns()
        if via == "record_span":
            tracing.record_span("queue.wait", handoff["t0"], end,
                                parent=handoff["ctx"], depth=1)
        else:
            tracing.start_span("queue.wait", parent=handoff["ctx"],
                               start_ns=handoff["t0"], depth=1).end(end)
        handoff["t1"] = end

    with tracer.start_span("submitter") as parent:
        handoff["t0"] = tracing.now_ns()
        handoff["ctx"] = tracing.current_span().context
        th = threading.Thread(target=consumer)
        th.start()
        th.join(timeout=10)
        assert not th.is_alive()
    wait = next(s for s in exp.spans if s.name == "queue.wait")
    assert wait.context.trace_id == parent.context.trace_id
    assert wait.parent_span_id == parent.context.span_id
    assert (wait.start_ns, wait.end_ns) == (handoff["t0"], handoff["t1"])
    assert parent.start_ns <= wait.start_ns <= wait.end_ns <= parent.end_ns


# ------------------------------------------------ the served search's trace

# every span of docs/observability.md "The spans of a served search"
# that a plain search over flushed blocks writes; `batcher.host_fallback`
# is written only when the host route runs (its own case below) and
# `dispatch.lock_wait` only on a mesh
SERVED_SPANS = {
    "http.request", "HTTP GET /api/search", "http.reply",
    "frontend.Search", "frontend.queue_wait", "querier.SearchBlocks",
    "batcher.Search", "batcher.header_prune", "batcher.stage",
    "batcher.prepare", "batcher.dispatch", "batcher.drain", "batcher.sync",
    "coalescer.wait", "coalescer.launch", "device.scan",
    "dispatch.build", "dispatch.execute",
}


@pytest.fixture
def served(tmp_path):
    """An App with flushed, polled blocks behind `serve_http`; yields a
    `get(path, **headers) -> bytes` that crosses loopback HTTP."""
    import urllib.request

    from tempo_tpu.api import HTTPApi, serve_http
    from tempo_tpu.db.tempodb import TempoDBConfig
    from tempo_tpu.utils.test_data import make_trace

    app = App(AppConfig(wal_dir=str(tmp_path / "wal"),
                        db=TempoDBConfig(auto_mesh=False)))
    for b in range(3):
        for i in range(4):
            tid = bytes([b + 1, i + 1]) * 8
            app.push("t1", list(make_trace(tid, seed=b * 10 + i).batches))
        app.flush_tick(force=True)
    app.poll_tick()
    srv = serve_http(HTTPApi(app), host="127.0.0.1", port=0)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    base = f"http://127.0.0.1:{srv.server_address[1]}"

    def get(path, **headers):
        req = urllib.request.Request(
            base + path, headers={"X-Scope-OrgID": "t1", **headers})
        with urllib.request.urlopen(req, timeout=60) as r:
            return r.read()

    try:
        yield get
    finally:
        srv.shutdown()
        srv.server_close()
        app.shutdown()


def _wait_for(cond, timeout=10.0):
    deadline = time.monotonic() + timeout
    while not cond() and time.monotonic() < deadline:
        time.sleep(0.01)
    return cond()


@pytest.mark.parametrize("route", ["device", "host_fallback"])
def test_served_search_is_one_trace_from_accept_to_reply(served, route):
    from tempo_tpu import robustness

    path = "/api/search?tags=service.name%3Dfront&limit=5"
    served(path)  # compile outside the traced request
    exp = CollectExporter()
    tracing.set_tracer(Tracer(SyncProcessor(exp)))
    want = set(SERVED_SPANS)
    try:
        if route == "host_fallback":
            # an open breaker answers every group on the host route
            robustness.BREAKER.enabled = True
            robustness.BREAKER.threshold = 1
            robustness.BREAKER.record_fault("error", mode="batched")
            want = {"http.request", "HTTP GET /api/search", "http.reply",
                    "frontend.Search", "frontend.queue_wait",
                    "batcher.Search", "batcher.host_fallback"}
        # a predicate no earlier request sent: prune and prepare memos
        # miss; the caller's traceparent goes to the root alone
        served("/api/search?tags=service.name%3Dcart&limit=7",
               traceparent=f"00-{'ab' * 16}-{'cd' * 8}-01")
        assert _wait_for(lambda: want <= {s.name for s in exp.spans})
    finally:
        robustness.BREAKER.reset()
        robustness.BREAKER.threshold = 3
        tracing.set_tracer(None)
    spans = list(exp.spans)
    roots = [s for s in spans if s.name == "http.request"]
    assert len(roots) == 1
    root = roots[0]
    assert root.parent_span_id == bytes.fromhex("cd" * 8)
    assert {s.context.trace_id for s in spans} == {bytes.fromhex("ab" * 16)}
    assert root.attributes["http.status_code"] == 200
    assert root.attributes["bytes"] > 0
    assert 0 <= root.attributes["accept_wait_ms"] < 10_000
    by_id = {s.context.span_id: s for s in spans}
    for s in spans:
        if s is root:
            continue
        parent = by_id[s.parent_span_id]
        assert parent.start_ns <= s.start_ns <= s.end_ns, (s.name,
                                                           parent.name)
        # the launch is asynchronous: the device's span is the one that
        # may end after the span that launched it
        if s.name != "device.scan":
            assert s.end_ns <= parent.end_ns, (s.name, parent.name)
    parents: dict = {}   # span name -> the names of its parents
    for s in spans:
        if s is not root:
            parents.setdefault(s.name, set()).add(
                by_id[s.parent_span_id].name)
    assert parents["HTTP GET /api/search"] == {"http.request"}
    assert parents["http.reply"] == {"http.request"}
    assert parents["frontend.queue_wait"] == {"frontend.Search"}
    if route == "device":
        assert parents["batcher.sync"] == {"batcher.drain"}
        assert parents["coalescer.wait"] == {"batcher.Search"}
        assert parents["device.scan"] == {"coalescer.launch"}
        # the live head block's one-block scans (mode=single) time their
        # stages under the frontend; a batched launch's under its launch
        assert {by_id[s.parent_span_id].name for s in spans
                if s.name == "dispatch.execute"
                and s.attributes["mode"] == "batched"} == {
                    "coalescer.launch"}
        for name in ("batcher.stage", "batcher.prepare", "batcher.dispatch",
                     "batcher.drain", "batcher.header_prune"):
            assert parents[name] == {"batcher.Search"}
    else:
        assert parents["batcher.host_fallback"] == {"batcher.Search"}


def test_fused_launch_is_joined_from_every_members_wait():
    """A fused launch runs on one thread: its `coalescer.launch` and
    `device.scan` are in ONE member's trace, and every member's
    `coalescer.wait` carries that launch's id."""
    from tempo_tpu.search.batcher import BlockBatcher

    jobs = _jobs(_blocks(2, entries=64))
    reqs = [_mk_req({"service.name": f"svc-{i}"}) for i in range(4)]
    b = BlockBatcher(coalesce_window_s=0.2, coalesce_max_queries=len(reqs))
    b.search(list(jobs), reqs[0])  # stage + compile
    exp = CollectExporter()
    tracer = Tracer(SyncProcessor(exp))
    tracing.set_tracer(tracer)
    barrier = threading.Barrier(len(reqs))

    def one(req):
        with tracer.start_span("member"):
            barrier.wait(timeout=30)
            b.search(list(jobs), req)

    threads = [threading.Thread(target=one, args=(r,)) for r in reqs]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
        assert not t.is_alive()
    assert _wait_for(lambda: sum(s.name == "device.scan"
                                 for s in exp.spans)
                     == sum(s.name == "coalescer.launch"
                            for s in exp.spans))
    launches = {s.attributes["launch"]: s for s in exp.spans
                if s.name == "coalescer.launch"}
    waits = [s for s in exp.spans if s.name == "coalescer.wait"]
    scans = {s.attributes["launch"]: s for s in exp.spans
             if s.name == "device.scan"}
    assert len(waits) == len(reqs) and set(scans) == set(launches)
    fused = [lid for lid, s in launches.items() if s.attributes["queries"] > 1]
    assert fused, "no fusion happened"
    for w in waits:
        launch = launches[w.attributes["launch"]]
        assert w.attributes["queries"] == launch.attributes["queries"]
        assert w.attributes["mode"] == (
            "coalesced" if launch.attributes["queries"] > 1 else "batched")
        # submit -> this launch enqueued
        assert w.start_ns <= launch.start_ns and w.end_ns == launch.end_ns
    for lid in fused:
        members = [w for w in waits if w.attributes["launch"] == lid]
        assert len(members) == launches[lid].attributes["queries"]
        assert len({w.context.trace_id for w in members}) == len(members)
        assert launches[lid].context.trace_id in {
            w.context.trace_id for w in members}
        assert scans[lid].attributes["kernel"] == "coalesced"
    # the device's timeline: launch order, no two spans overlapping
    ordered = [scans[k] for k in sorted(scans)]
    for a, nxt in zip(ordered, ordered[1:]):
        assert a.end_ns <= nxt.start_ns


def test_prepare_memo_counter_one_miss_then_hits():
    from tempo_tpu.observability import metrics as obs
    from tempo_tpu.search.batcher import BlockBatcher

    jobs = _jobs(_blocks(2, entries=64))
    b = BlockBatcher(coalesce_max_queries=1)
    req = _mk_req({"service.name": "svc-3"})
    hit0 = obs.prepare_memo.value(result="hit")
    miss0 = obs.prepare_memo.value(result="miss")
    groups = len(b.plan(list(jobs)))
    for _ in range(3):
        b.search(list(jobs), req)
    assert obs.prepare_memo.value(result="miss") - miss0 == groups
    assert obs.prepare_memo.value(result="hit") - hit0 == 2 * groups
    b.search(list(jobs), _mk_req({"service.name": "svc-4"}))
    assert obs.prepare_memo.value(result="miss") - miss0 == 2 * groups


def test_no_tracer_new_sites_are_noop_and_answers_identical(served):
    """With no tracer installed: the new sites hand back the shared
    noop span, stamp no address, keep no stage intervals and start no
    watcher thread; the traced answer is byte-identical to the plain
    one."""
    from tempo_tpu.observability import metrics as obs
    from tempo_tpu.observability import profile

    path = "/api/search?tags=service.name%3Dfront&limit=5"
    assert tracing.get_tracer() is None
    gone = 3 * profile.DeviceTimeline.IDLE_EXIT_S + 5
    # an earlier test's watcher leaves once it finds no tracer
    assert _wait_for(lambda: not profile.DEVICE_TIMELINE.running(), gone)
    q0 = sum(obs.frontend_queue_duration._counts.get((), [0]))
    plain = served(path)
    assert tracing.start_span("http.request",
                              start_ns=1) is tracing.NOOP_SPAN
    tracing.record_span("coalescer.wait", 1, 2)  # no tracer: nothing
    with profile.dispatch("batched") as rec:
        with rec.stage("build"):
            pass
    assert rec.intervals is None
    assert not profile.DEVICE_TIMELINE.running()
    assert "device-timeline" not in {t.name for t in threading.enumerate()}
    # the histogram is always on: one sample per sub-request
    assert sum(obs.frontend_queue_duration._counts.get((), [0])) > q0
    exp = CollectExporter()
    tracing.set_tracer(Tracer(SyncProcessor(exp)))
    try:
        traced = served(path)
        assert _wait_for(lambda: any(s.name == "device.scan"
                                     for s in exp.spans))
    finally:
        tracing.set_tracer(None)
    assert traced == plain
    # the watcher thread goes once no tracer is installed
    assert _wait_for(lambda: not profile.DEVICE_TIMELINE.running(), gone)


class _RaisesOnce(CollectExporter):
    def __init__(self):
        super().__init__()
        self.raised = 0

    def export(self, spans):
        if not self.raised:
            self.raised += 1
            raise OSError("collector gone")
        super().export(spans)


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
@pytest.mark.parametrize("fault", ["export_raises", "thread_dies"])
def test_device_timeline_outlives_a_fault(monkeypatch, fault):
    """An exporter that raises under the watcher loses that launch's
    span and is counted; whatever does end the thread, the next launch
    starts another. Either way later launches are on the timeline."""
    from tempo_tpu.observability import metrics as obs
    from tempo_tpu.observability import profile

    exp = _RaisesOnce() if fault == "export_raises" else CollectExporter()
    tracing.set_tracer(Tracer(SyncProcessor(exp)))
    if fault == "thread_dies":
        real, died = profile.fence_arrays, []

        def fence(out):
            if not died:
                died.append(threading.current_thread())
                raise SystemExit   # ends the thread
            real(out)

        monkeypatch.setattr(profile, "fence_arrays", fence)
    tl = profile.DeviceTimeline()
    d0 = obs.device_timeline_dropped.value()
    first = tl.watch((), None, 1, 4, "multi")
    if fault == "export_raises":
        assert _wait_for(lambda: exp.raised == 1)
        assert _wait_for(
            lambda: obs.device_timeline_dropped.value() == d0 + 1)
        assert tl.running()
    else:
        assert _wait_for(lambda: died and not died[0].is_alive())
        assert not tl.running()
    second = tl.watch((), None, 2, 4, "coalesced")
    assert _wait_for(lambda: len(exp.spans) == 1)
    (s,) = exp.spans
    assert s.name == "device.scan" and second == first + 1
    assert s.attributes["launch"] == second
    assert s.attributes["kernel"] == "coalesced"
    tracing.set_tracer(None)
    assert _wait_for(lambda: not tl.running(), 3 * tl.IDLE_EXIT_S + 5)


def test_device_timeline_queue_is_bounded(monkeypatch):
    """A launch that never completes holds the watcher; the launches
    behind it are queued up to a bound (each pins its outputs), then
    left off the timeline and counted."""
    from tempo_tpu.observability import metrics as obs
    from tempo_tpu.observability import profile

    exp = CollectExporter()
    tracing.set_tracer(Tracer(SyncProcessor(exp)))
    entered, release = threading.Event(), threading.Event()

    def fence(out):
        entered.set()
        release.wait(10)

    monkeypatch.setattr(profile, "fence_arrays", fence)
    tl = profile.DeviceTimeline()
    tl.MAX_QUEUED = 2
    d0 = obs.device_timeline_dropped.value()
    ids = [tl.watch((), None, 1, 4, "multi")]
    assert entered.wait(5)               # the watcher holds launch 1
    ids += [tl.watch((), None, 1, 4, "multi") for _ in range(3)]
    assert ids == list(range(ids[0], ids[0] + 4))   # ids stay dense
    assert obs.device_timeline_dropped.value() == d0 + 1
    release.set()
    assert _wait_for(lambda: len(exp.spans) == 3)
    assert [s.attributes["launch"] for s in exp.spans] == ids[:3]
    # in launch order, each starting no earlier than the one before ended
    assert all(a.end_ns <= b.start_ns
               for a, b in zip(exp.spans, exp.spans[1:]))
    tracing.set_tracer(None)
    assert _wait_for(lambda: not tl.running(), 3 * tl.IDLE_EXIT_S + 5)
