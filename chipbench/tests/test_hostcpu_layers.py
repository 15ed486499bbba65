"""The readers of the spans' CPU stamps and of the process's CPU counter
(`chipbench/layers/hostcpu.py`), driven with hand-built spans: same-
thread children that nest by time under another parent id, a launch
handed to a watchdog thread, a fused launch on a pool thread in one
member's trace, waits that carry no CPU, a span whose CPU passes its
wall; and once with a program that stamps nothing (every reader
`None`)."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench.layers import hostcpu  # noqa: E402
from chipbench.tests.test_span_layers import Spans, reader  # noqa: E402

SCAN_CELLS = ["share16.scan", "share16x4.scan", "share16.evict",
              "highcard.substring"]


class Stamped(Spans):
    def on(self, thread, cpu_ms, name, start, end, trace="a", parent=None,
           **attrs):
        """A span whose two edges thread `thread` stamped."""
        attrs.update({"thread.id": thread,
                      "thread.cpu_ns": int(cpu_ms * 1_000_000)})
        return self.add(name, start, end, trace, parent, **attrs)


def _spans():
    """Search a on thread 1 (the dispatch watchdog runs its launch and
    its put on thread 9), search b on thread 2; the window's pool
    (thread 7) flushes the launch that fuses both, which hangs in a's
    trace; a metrics scrape on thread 3."""
    s = Stamped()
    ra = s.add("http.request", 0, 100, "a", accept_wait_ms=1.0)
    ha = s.on(1, 30, "HTTP GET /api/search", 1, 99, "a", ra)
    s.add("frontend.queue_wait", 2, 4, "a", ha)
    sa = s.on(1, 28, "batcher.Search", 5, 95, "a", ha)
    s.on(1, 1, "batcher.stage", 5, 15, "a", sa, group=0)
    s.on(9, 6, "batcher.place", 6, 14, "a", sa, bytes=1 << 28)
    s.on(1, 8, "batcher.prepare", 15, 25, "a", sa, group=0)
    s.on(1, 5, "batcher.dispatch", 25, 40, "a", sa, group=0)
    # inline: inside `batcher.dispatch` by time, its sibling by parent
    l1 = s.on(1, 3, "coalescer.launch", 26, 39, "a", sa, launch=1)
    s.on(9, 4, "dispatch.build", 27, 32, "a", l1)
    s.on(9, 2, "dispatch.execute", 32, 38, "a", l1)
    s.add("coalescer.wait", 25, 39, "a", sa, launch=1)
    s.add("device.scan", 39, 45, "a", l1, launch=1)
    da = s.on(1, 6, "batcher.drain", 40, 90, "a", sa, group=0)
    s.on(1, 0.5, "batcher.await_launch", 41, 60, "a", da, launch=2)
    s.on(1, 0.5, "batcher.sync", 60, 85, "a", da)
    # the fused launch, flushed by the pool, in a's trace; its stages
    # on its own thread
    l2 = s.on(7, 5, "coalescer.launch", 40, 50, "a", sa, launch=2)
    s.on(7, 3, "dispatch.execute", 42, 48, "a", l2)

    rb = s.add("http.request", 9, 81, "b", accept_wait_ms=1.0)
    hb = s.on(2, 10, "HTTP GET /api/search", 10, 80, "b", rb)
    sb = s.on(2, 9, "batcher.Search", 12, 78, "b", hb)
    s.on(2, 2, "batcher.dispatch", 12, 20, "b", sb, group=0)
    s.add("coalescer.wait", 13, 50, "b", sb, launch=2)
    db = s.on(2, 3, "batcher.drain", 20, 78, "b", sb, group=0)
    s.on(2, 0.2, "batcher.await_launch", 21, 50, "b", db, launch=2)
    s.on(2, 0.3, "batcher.sync", 50, 70, "b", db)

    s.on(3, 1.5, "HTTP GET /metrics", 0, 2, "c")
    return s.out


def _run(spans, cpu_before=100.0, cpu_after=100.08):
    counters = {"before": {}, "after": {}}
    if cpu_before is not None:
        counters["before"][hostcpu.PROCESS_CPU] = {"": cpu_before}
        counters["after"][hostcpu.PROCESS_CPU] = {"": cpu_after}
    return {"spans": spans, "counters": counters, "window_wall_s": 0.1}


def by_name(spans, name):
    """(wall_self, cpu_self, handed off) in ms of the spans of that
    name, in the order they were added."""
    return [(w / 1e6, c / 1e6, a / 1e6)
            for s, w, c, a in hostcpu.self_times(spans) if s["name"] == name]


def test_same_thread_children_nest_by_time_not_by_parent_id():
    spans = _spans()
    # `coalescer.launch` hangs under `batcher.Search` and lies inside
    # `batcher.dispatch`: it comes off the dispatch, once
    (search_a, search_b) = by_name(spans, "batcher.Search")
    assert search_a == pytest.approx((5.0, 8.0, 0.0))
    assert search_b == pytest.approx((0.0, 4.0, 0.0))
    dispatch_a, dispatch_b = by_name(spans, "batcher.dispatch")
    assert dispatch_a == pytest.approx((2.0, 2.0, 0.0))
    assert dispatch_b == pytest.approx((8.0, 2.0, 0.0))
    assert by_name(spans, "batcher.drain") == [
        pytest.approx((6.0, 5.0, 0.0)), pytest.approx((9.0, 2.5, 0.0))]


def test_a_child_on_another_thread_is_subtracted_from_nobody():
    spans = _spans()
    # the put ran on the watchdog's thread: the stage on thread 1
    # keeps its whole interval, and the put its own CPU
    assert by_name(spans, "batcher.stage") == [pytest.approx((10.0, 1.0, 0.0))]
    assert by_name(spans, "batcher.place") == [pytest.approx((8.0, 6.0, 0.0))]
    # the pool's fused launch: its stage is on its thread, inside it
    inline, fused = by_name(spans, "coalescer.launch")
    assert fused == pytest.approx((4.0, 2.0, 0.0))
    # the inline launch slept while thread 9 built and executed: the
    # 11 ms the two stages cover are not its own off-core time
    assert inline == pytest.approx((13.0, 3.0, 11.0))


def test_waits_without_cpu_enter_no_sum_and_off_core_is_never_negative():
    spans = _spans()
    rows = hostcpu.self_times(spans)
    assert not {s["name"] for s, *_ in rows} & {
        "http.request", "frontend.queue_wait", "coalescer.wait",
        "device.scan"}
    assert all(w >= 0 and c >= 0 and a >= 0 for _s, w, c, a in rows)
    # the two `batcher.Search`: 5 ms of their own wall, 12 of their own
    # CPU (a tick lands where it lands): off-core 0, not -7
    assert hostcpu.off_core(
        r for r in rows if r[0]["name"] == "batcher.Search") == 0


def test_off_core_is_taken_of_the_sum_as_a_tick_clock_needs():
    """CPU accounted by the tick: of a hundred 1 ms spans that were on
    a core all along, ten are charged 10 ms and ninety nothing. Span by
    span that reads 90 ms off-core; summed, none."""
    s = Stamped()
    for i in range(100):
        s.on(1, 10 if i % 10 == 0 else 0, "dispatch.build", i, i + 1)
    rows = hostcpu.self_times(s.out)
    assert sum(c for _s, _w, c, _a in rows) == 100 * 1_000_000
    assert hostcpu.off_core(rows) == 0


WANT = {
    # 0.08 s of CPU in a window of 0.1 s
    "host_cores_busy": 0.8,
    "host_cores_busy.triage": 0.8,
    # a: 47 ms of self CPU with the fused launch it carries, b: 10
    "search_cpu_ms": 28.5,
    "search_cpu_ms.triage": 28.5,
    # the inline launch 3 + its stages on the watchdog's thread 4 + 2,
    # the pool's 5 with its stage inside
    "launch_cpu_ms": 7.0,
    # prepare 2, build 1, execute 4 + 3, the pool's launch 2, drains
    # 1 + 6.5, b's dispatch 6, less the 3 + 4 + 1 ms by which the two
    # searches' and the inline launch's own CPU pass their own wall,
    # over 90 + 66 ms of `batcher.Search`
    "unnamed_offcore_share": 100.0 * 17.5 / 156.0,
    # the scrape's 1.5 ms too: 58.5 of 80 ms
    "spanned_cpu_share": 100.0 * 58.5 / 80.0,
}


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_on_spans_that_exercise_it(name):
    assert reader(name)(_run(_spans())) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_finds_nothing_and_says_so(name):
    """The parent of this PR: the same spans without the two
    attributes, a `/metrics` without the counter."""
    bare = [dict(s, attributes={k: v for k, v in s["attributes"].items()
                                if not k.startswith("thread.")})
            for s in _spans()]
    assert reader(name)(_run(bare, cpu_before=None)) is None
    assert reader(name)(_run([], cpu_before=None)) is None


def test_the_counter_alone_reads_in_an_untraced_run():
    run = _run([])
    assert reader("host_cores_busy")(run) == pytest.approx(0.8)
    assert reader("spanned_cpu_share")(run) is None
    assert reader("search_cpu_ms")(run) is None


def test_spans_explain_no_more_cpu_than_the_process_burned():
    run = _run(_spans())
    assert sum(c for _s, _w, c, _a in hostcpu.self_times(run["spans"])) \
        <= hostcpu.process_cpu_s(run) * 1e9


def test_every_appended_metric_has_its_reader_and_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by = {m["name"]: m for m in bench["per_layer"]}
    for name in WANT:
        m = by[name]
        triage = name.endswith(".triage")
        assert m["workloads"] == (["share16.triage"] if triage
                                  else SCAN_CELLS)
        assert m["moves"] == ("search_p50_ms" if triage else "scan_rate")
        assert m["layer"] in ("Host process", "Batcher + coalescer")
    # appended: nothing that was there moved
    assert [m["name"] for m in bench["per_layer"]][-len(WANT):] == [
        "host_cores_busy", "host_cores_busy.triage", "search_cpu_ms",
        "search_cpu_ms.triage", "launch_cpu_ms", "unnamed_offcore_share",
        "spanned_cpu_share"]
