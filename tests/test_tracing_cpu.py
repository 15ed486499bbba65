"""CPU time on the span clock: `thread.id` and `thread.cpu_ns` on the
spans whose two edges one thread stamped, and the process's own CPU
counter. Every CPU stamp goes through `tracing.cpu_ns`, which these
tests replace with a scripted clock: none sleeps or spins to make CPU
time."""

import itertools
import threading
import urllib.request

import pytest

from tempo_tpu.modules import App, AppConfig
from tempo_tpu.observability import tracing
from tempo_tpu.observability.tracing import (
    NOOP_SPAN, CollectExporter, NonRecordingSpan, SpanContext,
    SyncProcessor, Tracer,
)
from tests.test_tracing import _wait_for

CPU, TID = "thread.cpu_ns", "thread.id"


@pytest.fixture(autouse=True)
def _clean_tracer():
    yield
    tracing.set_tracer(None)


@pytest.fixture
def collected():
    exp = CollectExporter()
    tracing.set_tracer(Tracer(SyncProcessor(exp)))
    return exp


@pytest.fixture
def clock(monkeypatch):
    """`tracing.cpu_ns` scripted: every read is 1,000 ns after the last,
    whichever thread reads, so the stamps of one thread nest as its
    spans do. Returns the list of reads."""
    reads: list = []
    ticks = itertools.count(1)

    def cpu_ns():
        reads.append(next(ticks) * 1000)
        return reads[-1]

    monkeypatch.setattr(tracing, "cpu_ns", cpu_ns)
    return reads


def test_live_span_carries_its_thread_and_the_scripted_difference(
        collected, clock):
    with tracing.start_span("outer", tenant="t1"):
        with tracing.start_span("inner"):
            pass
    inner, outer = collected.spans
    # outer 1000 .. 4000, inner 2000 .. 3000
    assert (inner.attributes[CPU], outer.attributes[CPU]) == (1000, 3000)
    assert inner.attributes[TID] == outer.attributes[TID] \
        == threading.get_ident()
    assert outer.attributes["tenant"] == "t1"
    assert len(clock) == 4


def test_span_ended_on_another_thread_carries_neither(collected, clock):
    span = tracing.start_span("crosses")
    th = threading.Thread(target=span.end)
    th.start()
    th.join(timeout=10)
    assert not th.is_alive()
    (got,) = collected.spans
    assert got.end_ns and CPU not in got.attributes \
        and TID not in got.attributes
    # the other thread's clock is not even read
    assert len(clock) == 1


@pytest.mark.parametrize("give_start,give_end", [
    (True, True), (True, False), (False, True), (False, False)])
def test_after_the_fact_span_carries_cpu_only_with_both_stamps(
        collected, clock, give_start, give_end):
    t0 = tracing.now_ns()
    tracing.record_span("stage", t0, t0 + 5_000,
                        cpu_start_ns=700 if give_start else None,
                        cpu_end_ns=1900 if give_end else None, group=0)
    (got,) = collected.spans
    if give_start and give_end:
        assert got.attributes[CPU] == 1200
        assert got.attributes[TID] == threading.get_ident()
    else:
        assert CPU not in got.attributes and TID not in got.attributes
    assert got.attributes["group"] == 0
    assert clock == []      # the call site's stamps, never the span's own


def test_a_stamp_of_zero_is_a_stamp(collected, clock):
    """Where the kernel accounts CPU by the tick a young thread's clock
    reads 0 (the v5e hosts): that is a stamp, not the lack of one."""
    t0 = tracing.now_ns()
    tracing.record_span("young", t0, t0 + 1, cpu_start_ns=0, cpu_end_ns=0)
    assert collected.spans[0].attributes[CPU] == 0
    assert collected.spans[0].attributes[TID] == threading.get_ident()


def test_start_stamp_handed_over_and_the_end_read_by_the_span(
        collected, clock):
    """`start_span(start_ns=, cpu_start_ns=)` then a plain `end()`: the
    span reads the end stamps itself, on the thread that ends it."""
    span = tracing.start_span("drain", start_ns=tracing.now_ns(),
                              cpu_start_ns=400)
    span.end()
    assert collected.spans[0].attributes[CPU] == 1000 - 400


def test_tracer_start_span_takes_no_cpu_stamp_of_its_own(collected, clock):
    """The stamp is the site API's (`tracing.start_span`): a `Tracer`
    driven directly is handed its stamps or has none."""
    tracing.get_tracer().start_span("plain").end()
    assert clock == [] and CPU not in collected.spans[0].attributes


@pytest.mark.parametrize("span", [
    NOOP_SPAN, NonRecordingSpan(SpanContext(b"\1" * 16, b"\2" * 8, False))],
    ids=["noop", "non_recording"])
def test_spans_that_record_nothing_take_the_new_keywords(span, clock):
    assert span.end(end_ns=5, cpu_end_ns=7) is None
    assert tracing.start_span("no tracer", start_ns=1,
                              cpu_start_ns=2) is NOOP_SPAN
    tracing.record_span("no tracer", 1, 2, cpu_start_ns=3, cpu_end_ns=4)
    assert clock == []


def test_keys_sort_after_service_name():
    """PERF.md section 7 h11: an entry keeps its first 64 (key, value)
    pairs in key order and `service.name` is the last a served search's
    self-trace keeps; a key on every span has to sort after it."""
    assert "service.name" < TID and "service.name" < CPU


# ------------------------------------------------ the served search's trace


@pytest.fixture
def served_app(tmp_path):
    """`tests/test_tracing.py served`, with the App beside the getter."""
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
        yield get, app
    finally:
        srv.shutdown()
        srv.server_close()
        app.shutdown()


class _NotDoneYet:
    """A coalescer future as a drain finds it when another thread has
    the flush: not done, and `result()` waits for it."""

    def __init__(self, fut):
        self._fut = fut

    def done(self):
        return False

    def result(self, timeout=None):
        return self._fut.result(timeout)

    @property
    def launch(self):
        return self._fut.launch


STAMPED = {"HTTP GET /api/search", "frontend.Search", "querier.SearchBlocks",
           "batcher.Search", "batcher.header_prune", "batcher.stage",
           "batcher.prepare", "batcher.dispatch", "batcher.drain",
           "batcher.sync", "coalescer.launch", "dispatch.build",
           "dispatch.execute"}
WAITS = {"http.request", "frontend.queue_wait", "coalescer.wait",
         "device.scan"}


@pytest.mark.parametrize("flush", ["inline", "another_thread"])
def test_served_search_says_where_its_threads_were_on_a_core(
        served_app, clock, monkeypatch, flush):
    from tempo_tpu.search.coalescer import QueryCoalescer

    get, _app = served_app
    get("/api/search?tags=service.name%3Dfront&limit=5")  # compile
    if flush == "another_thread":
        submit = QueryCoalescer.submit
        monkeypatch.setattr(
            QueryCoalescer, "submit",
            lambda self, *a, **kw: _NotDoneYet(submit(self, *a, **kw)))
    exp = CollectExporter()
    tracing.set_tracer(Tracer(SyncProcessor(exp)))
    try:
        get("/api/search?tags=service.name%3Dcart&limit=7")
        assert _wait_for(lambda: (STAMPED | WAITS)
                         <= {s.name for s in exp.spans})
    finally:
        tracing.set_tracer(None)
    spans = list(exp.spans)
    for s in spans:
        if s.name in STAMPED | {"batcher.await_launch", "batcher.place"}:
            assert s.attributes[CPU] >= 0 and TID in s.attributes, s.name
        if s.name in WAITS:
            assert CPU not in s.attributes and TID not in s.attributes, \
                s.name
    by_id = {s.context.span_id: s for s in spans}
    awaits = [s for s in spans if s.name == "batcher.await_launch"]
    drains = [s for s in spans if s.name == "batcher.drain"]
    if flush == "inline":
        # the submitter flushed its own launch: nothing to sleep on
        assert not awaits
    else:
        assert len(awaits) == len(drains) > 0
        launches = {s.attributes["launch"] for s in spans
                    if s.name == "coalescer.launch"}
        for w in awaits:
            assert by_id[w.parent_span_id].name == "batcher.drain"
            assert w.attributes["launch"] in launches
    # a parent and its child on one thread: the child's interval and
    # its CPU lie inside the parent's
    nested = 0
    for s in spans:
        p = by_id.get(s.parent_span_id)
        if p is None or CPU not in s.attributes \
                or p.attributes.get(TID) != s.attributes[TID]:
            continue
        nested += 1
        assert p.start_ns <= s.start_ns <= s.end_ns <= p.end_ns, s.name
        assert s.attributes[CPU] <= p.attributes[CPU], (s.name, p.name)
    assert nested >= 6


def test_no_tracer_no_site_reads_the_cpu_clock(served_app, monkeypatch):
    """With no tracer installed a served search, a flush and a poll
    never read the thread clock, and the answer is the traced one byte
    for byte."""
    from tempo_tpu.utils.test_data import make_trace

    get, app = served_app
    path = "/api/search?tags=service.name%3Dfront&limit=5"
    exp = CollectExporter()
    tracing.set_tracer(Tracer(SyncProcessor(exp)))
    try:
        traced = get(path)
    finally:
        tracing.set_tracer(None)
    assert any(CPU in s.attributes for s in exp.spans)

    def boom():
        raise AssertionError("cpu_ns read with no tracer installed")

    monkeypatch.setattr(tracing, "cpu_ns", boom)
    assert tracing.get_tracer() is None
    assert get(path) == traced
    # a predicate no memo knows, a push, a flush and a poll
    get("/api/search?tags=service.name%3Dcheckout&limit=3")
    app.push("t1", list(make_trace(b"\x09\x09" * 8, seed=99).batches))
    app.flush_tick(force=True)
    app.poll_tick()
    assert get(path)


def test_cpu_clock_reads_are_guarded_calls(tmp_path):
    from tempo_tpu.analysis.contracts import (GUARDED_CALLS,
                                              NoopContractChecker)
    from tempo_tpu.analysis.core import Package

    assert ("cpu_ns", "self_tracing") in {
        (m, g.knob) for g in GUARDED_CALLS for m in g.methods}
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "sites.py").write_text(
        "from tempo_tpu.observability import tracing\n\n\n"
        "def bare(span):\n"
        "    return tracing.cpu_ns()\n\n\n"
        "def wrong_branch(span):\n"
        "    return 0 if span.recording else tracing.cpu_ns()\n\n\n"
        "def stamped(span, rec):\n"
        "    c0 = tracing.cpu_ns() if span.recording else 0\n"
        "    c1 = tracing.cpu_ns() if rec.intervals is not None else 0\n"
        "    span.end(5, tracing.cpu_ns() if span.recording else 0)\n"
        "    return c0, c1\n")
    found = NoopContractChecker(gated=()).check(
        Package.load(str(pkg), rel_base=str(tmp_path)))
    assert {f.key for f in found} == {
        "unguarded:bare:tracing.cpu_ns",
        "unguarded:wrong_branch:tracing.cpu_ns"}


# ------------------------------------------------ the process's CPU


def test_process_cpu_counter_on_metrics_is_monotonic(served_app):
    from tempo_tpu.observability import metrics as obs

    get, _app = served_app

    def scrape():
        lines = get("/metrics").decode().splitlines()
        out = {}
        for name in ("process_cpu_seconds_total",
                     "process_cpu_user_seconds_total",
                     "process_cpu_system_seconds_total"):
            assert f"# TYPE {name} counter" in lines
            (row,) = [ln for ln in lines if ln.startswith(name + " ")]
            out[name] = float(row.split()[1])
        return out

    first = scrape()
    get("/api/search?tags=service.name%3Dfront&limit=5")  # work between
    second = scrape()
    assert all(second[k] >= first[k] for k in first)
    assert first["process_cpu_seconds_total"] > 0
    assert obs.process_cpu_seconds.value() >= \
        second["process_cpu_seconds_total"]
