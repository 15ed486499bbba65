"""The readers of the served search's spans, each driven once with
spans that exercise it and once with none (a program that lacks the
spans: the reader returns None and the metric is left out)."""

import importlib.util
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

MS = 1_000_000
T0 = 1_790_000_000 * 1_000_000_000   # stamps are unix nanoseconds


def reader(name):
    path = os.path.join(ROOT, "chipbench", "layers", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.compute


class Spans:
    """A hand-built trace set: times in ms from T0."""

    def __init__(self):
        self.out, self.n = [], 0

    def add(self, name, start, end, trace="a", parent=None, **attrs):
        self.n += 1
        sid = f"{self.n:016x}"
        self.out.append({
            "name": name, "start_ns": T0 + int(start * MS),
            "end_ns": T0 + int(end * MS), "span_id": sid,
            "trace_id": trace * 32, "parent_id": parent,
            "attributes": attrs})
        return sid

    def search(self, trace, start, end, accept_wait=0.5):
        root = self.add("http.request", start, end, trace,
                        accept_wait_ms=accept_wait)
        return self.add("HTTP GET /api/search", start + accept_wait,
                        end - 0.2, trace, root)


@pytest.fixture
def run():
    """Two searches open at once (a: 0-40, b: 10-30), a gap with no
    request (40-60), a third search (c: 60-100); four launches, two of
    whose device spans touch; one fused launch joined on its id; a
    metrics scrape that is no search."""
    s = Spans()
    a = s.search("a", 0, 40, accept_wait=0.5)
    b = s.search("b", 10, 30, accept_wait=2.0)
    c = s.search("c", 60, 100, accept_wait=0.25)
    s.add("http.request", 45, 46, "d", accept_wait_ms=30.0)   # /metrics
    s.add("frontend.queue_wait", 1, 2, "a", a)
    s.add("frontend.queue_wait", 1, 7, "a", a)
    s.add("frontend.queue_wait", 11, 12, "b", b)
    ba = s.add("batcher.Search", 8, 38, "a", a)
    bb = s.add("batcher.Search", 13, 29, "b", b)
    bc = s.add("batcher.Search", 62, 98, "c", c)
    s.add("batcher.prepare", 8, 14, "a", ba, group=0, blocks=4, terms=2)
    s.add("batcher.prepare", 64, 68, "c", bc, group=0, blocks=4, terms=1)
    # launch 1: fused, a's and b's waits join on it; it sat 1 ms queued
    l1 = s.add("coalescer.launch", 15, 16, "a", ba, launch=1, queries=2,
               blocks=4, kernel="coalesced", jit_cache="hit")
    s.add("coalescer.wait", 14, 16, "a", ba, launch=1, queries=2,
          mode="coalesced")
    s.add("coalescer.wait", 13, 16, "b", bb, launch=1, queries=2,
          mode="coalesced")
    s.add("device.scan", 17, 21, "a", l1, launch=1, queries=2, blocks=4,
          kernel="coalesced")
    # launch 2 queues behind launch 1: enqueued at 18, starts at 21
    l2 = s.add("coalescer.launch", 17, 18, "a", ba, launch=2, queries=1,
               blocks=4, kernel="multi", jit_cache="hit")
    s.add("coalescer.wait", 17, 18, "a", ba, launch=2, queries=1,
          mode="batched")
    s.add("device.scan", 21, 27, "a", l2, launch=2, queries=1, blocks=4,
          kernel="multi")
    # launches 3 and 4 of search c find the device free
    for lid, at in ((3, 70), (4, 80)):
        lc = s.add("coalescer.launch", at, at + 1, "c", bc, launch=lid,
                   queries=1, blocks=4, kernel="multi", jit_cache="hit")
        s.add("coalescer.wait", at - 1, at + 1, "c", bc, launch=lid,
              queries=1, mode="batched")
        s.add("device.scan", at + 1, at + 3, "c", lc, launch=lid,
              queries=1, blocks=4, kernel="multi")
    return {"spans": s.out, "counters": {
        "before": {"tempo_search_prepare_memo_total": {
            '{result="hit"}': 10.0, '{result="miss"}': 2.0}},
        "after": {"tempo_search_prepare_memo_total": {
            '{result="hit"}': 25.0, '{result="miss"}': 7.0}}}}


EMPTY = {"spans": [], "counters": {"before": {}, "after": {}}}


def _old_spans():
    """A program from before these spans: the layer spans only."""
    s = Spans()
    h = s.add("HTTP GET /api/search", 0, 10)
    s.add("frontend.Search", 1, 9, parent=h)
    s.add("batcher.Search", 2, 8, parent=h)
    return {"spans": s.out, "counters": {"before": {}, "after": {}}}


WANT = {
    # searches only: the scrape's 30 ms accept wait and 1 ms span are out
    "server_p95_ms": 40.0,
    "accept_wait_p95_ms": 2.0,
    # per search the longest wait: a 6, b 1 (c has none)
    "frontend_queue_wait_p95_ms": 6.0,
    "batcher_p95_ms": 36.0,
    "prepare_miss_ms": 5.0,
    "memo_miss_share": 25.0,
    # waits of 2, 3, 1, 2, 2 ms
    "coalesce_wait_ms": 2.0,
    "coalesce_wait_ms.scan": 2.0,
    # 1, 3, 0, 0 ms between the enqueue and the device's start
    "launch_queue_ms.scan": 0.5,
}


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_on_spans_that_exercise_it(run, name):
    assert reader(name)(run) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_finds_nothing_and_says_so(name):
    assert reader(name)(EMPTY) is None
    # `batcher.Search` is older than this file: its p95 reads there too
    old = reader(name)(_old_spans())
    assert old == 6.0 if name == "batcher_p95_ms" else old is None


def test_every_appended_metric_has_its_reader_and_cells():
    import json

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    # no device or kernel metric reads the host-observed `device.scan`
    assert not {"idle_empty_share", "idle_prepare_share",
                "idle_other_share", "device_ms_per_launch.scan"} & set(by_name)
    for name in WANT:
        m = by_name[name]
        assert m["layer"] not in ("Device", "Kernel")
        assert m["workloads"] == [
            "share16.scan" if name.endswith(".scan") else "share16.triage"]
        assert m["moves"] == ("scan_rate" if name.endswith(".scan")
                              else "search_p50_ms")
