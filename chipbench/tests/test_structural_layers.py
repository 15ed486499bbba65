"""The cell of structural searches: its readers, each on a hand-made run
with the value worked by hand and on a program that lacks what it reads;
the bytes a structural launch must move; the plain reference on a tree
small enough to check by eye; and that `BENCHMARK.json` names the cell,
its configuration and its metrics."""

import json
import os

import numpy as np
import pytest

from chipbench import costs_structural, reference_structural as rs
from chipbench.tests.test_span_layers import EMPTY, Spans, reader

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
ROWS = "tempo_search_structural_span_rows_total"
SPAN_BYTES = "tempo_search_structural_span_bytes"
CACHE = "tempo_search_hbm_cache_bytes"
STAGE = "tempo_search_dispatch_stage_seconds"
DISPATCHES = "tempo_search_scan_dispatches_total"
KEYS = "tempo_search_scan_jit_keys"
CELL = "calltree16.structural"
METRICS = ("kernel_ms.structural", "structural_kernel_roofline",
           "span_pad_share.structural",
           "span_hbm_share.structural", "plan_compile_ms.structural",
           "launches_per_search.structural", "jit_keys.structural",
           "search_p50_ms.structural",
           "search_cpu_ms.structural", "launch_cpu_ms.structural",
           "host_cores_busy.structural")
CHILD = {"exists": {"child": {
    "parent": {"tag": {"k": "service.name", "v": "a"}},
    "child": {"tag": {"k": "service.name", "v": "b"}}}}}
KINDS = {"exists": {"kind": "client"}}


@pytest.fixture
def run():
    """One group of 1,000 live span rows in 1,024 and 100 entries; four
    searches completed in the window on four launches (two of `CHILD`,
    two of `KINDS`), answered 100, 200, 300 and 400 ms after they were
    due; the traced seconds saw 4 scan programs take 2 ms; two searches
    compiled their plan (3 and 5 ms); 5 jit keys; each search's thread
    burned 2 ms in its `batcher.Search`, 1 of them in its launch; the
    process 0.5 s of CPU in a window of 2 s."""
    s = Spans()
    for i in range(4):
        t = "abcd"[i]
        s.add("HTTP GET /api/search", i * 10, i * 10 + 9, trace=t)
        b = s.add("batcher.Search", i * 10, i * 10 + 9, trace=t, groups=1,
                  **{"thread.id": i, "thread.cpu_ns": 2_000_000})
        s.add("coalescer.launch", i * 10 + 1, i * 10 + 3, trace=t, parent=b,
              **{"thread.id": i, "thread.cpu_ns": 1_000_000})
    s.add("structural.compile", 1, 4, nodes=4, terms=2)
    s.add("structural.compile", 11, 16, nodes=2, terms=0)
    present = np.zeros((2, 13_000), dtype=bool)
    present[:, :9_000] = True
    one = '{mode="%s",shards="1"}'
    requests = [{"op": "search_structural", "ref": {"q": CHILD}},
                {"op": "search_structural", "ref": {"q": KINDS}}]
    return {
        "trace": {"window_ns": 2e9,
                  "programs_ns": {"jit_batch_scan_kernel": 2e6},
                  "program_calls": {"jit_batch_scan_kernel": 4}},
        "window_wall_s": 2.0,
        "spans": s.out, "device_kind": "TPU v5 lite",
        "config": {"chips": {"count": 1}},
        "manifest": {"spans": 1000, "entries": 100, "span_slots": 4,
                     "kv_per_entry": 16, "present": present,
                     "key_names": tuple(f"k{i}" for i in range(16))},
        "requests": requests,
        "records": [{"i": i % 2, "status": 200, "due": float(i),
                     "done": i + 0.1 * (i + 1)} for i in range(4)],
        "counters": {
            "before": {
                ROWS: {'{kind="live"}': 1000.0, '{kind="pad"}': 24.0},
                "process_cpu_seconds_total": {"": 10.0},
                DISPATCHES: {one % "batched": 40.0},
                STAGE + "_sum": {'{stage="d2h"}': 1.0},
                STAGE + "_count": {'{stage="d2h"}': 40.0}},
            "after": {
                ROWS: {'{kind="live"}': 1000.0, '{kind="pad"}': 24.0},
                SPAN_BYTES: {"": 50_176.0}, CACHE: {"": 200_704.0},
                KEYS: {"": 5.0},
                "process_cpu_seconds_total": {"": 10.5},
                DISPATCHES: {one % "batched": 44.0},
                STAGE + "_sum": {'{stage="d2h"}': 1.08},
                STAGE + "_count": {'{stage="d2h"}': 44.0}}},
    }


def test_readers_on_a_hand_made_run(run):
    got = {m: reader(m)(run) for m in METRICS}
    assert got["kernel_ms.structural"] == pytest.approx(0.5)
    out = 4 * (2 + 2 * costs_structural.TOP_K)
    child = 1000 * (4 + 32 + 4 + 4) + 100 * (13 + 8) + out
    kinds = 1000 * (4 + 1) + 100 * (13 + 8) + out
    assert got["structural_kernel_roofline"] == pytest.approx(
        100 * (4 * (child + kinds) / 2 / 819e9) / 2e-3)
    assert got["span_pad_share.structural"] == pytest.approx(100 * 24 / 1024)
    assert got["span_hbm_share.structural"] == pytest.approx(25.0)
    assert got["plan_compile_ms.structural"] == pytest.approx(2.0)
    assert got["launches_per_search.structural"] == pytest.approx(1.0)
    assert got["jit_keys.structural"] == 5.0
    # nearest rank: the 2nd of four
    assert got["search_p50_ms.structural"] == pytest.approx(200.0)
    assert got["search_cpu_ms.structural"] == pytest.approx(2.0)
    assert got["launch_cpu_ms.structural"] == pytest.approx(1.0)
    assert got["host_cores_busy.structural"] == pytest.approx(0.25)


def test_readers_on_a_program_without_the_spans_and_counters(run):
    """The parent of PR 44, and an untraced run: nothing to read, and no
    reader raises."""
    bare = dict(run, spans=[s for s in run["spans"]
                            if s["name"] == "batcher.Search"])
    for name in (ROWS, SPAN_BYTES):
        for side in ("before", "after"):
            bare["counters"][side].pop(name, None)
    for side in ("before", "after"):
        bare["counters"][side].pop("process_cpu_seconds_total")
    for s in bare["spans"]:
        s["attributes"] = {"groups": 1}
    for m in ("span_pad_share.structural", "span_hbm_share.structural",
              "plan_compile_ms.structural", "search_cpu_ms.structural",
              "launch_cpu_ms.structural", "host_cores_busy.structural"):
        assert reader(m)(bare) is None, m
    # the trace and the launch counters it did have
    assert reader("structural_kernel_roofline")(bare) is not None
    untraced = dict(EMPTY, trace=None, requests=[], records=[],
                    config={"chips": {"count": 1}})
    for m in METRICS:
        assert reader(m)(untraced) is None, m


def test_a_share_of_the_roofline_cannot_count_pad_rows_or_trips():
    kw = dict(spans=1000, entries=100, span_slots=4, kv_slots=16, n_keys=16,
              n_vals=9000)
    desc = {"exists": {"desc": {"anc": CHILD["exists"]["child"]["parent"],
                                "span": CHILD["exists"]["child"]["child"]}}}
    # the join by ancestor must move what the join by parent must: the
    # parent column once, however many trips an implementation makes
    assert costs_structural.search_bytes(desc, **kw) \
        == costs_structural.search_bytes(CHILD, **kw)
    assert costs_structural.reads(desc) == {"span.tag", "relation",
                                            "aggregate"}


def test_the_reference_on_a_tree_small_enough_to_check_by_eye():
    """Two traces. The first: a(0) -> b(1) -> c(2), a(0) -> b(3); the
    second: b(0) -> a(1). Durations 100, 50, 10, 40 | 30, 20."""
    table = ["a", "b", "c"]
    corpus = {"table": table, "key_names": ("service.name",),
              "span_key_names": ("service.name",)}
    block = {"vals": np.array([[0, 1]]), "dur": np.array([100, 30]),
             "span_count": np.array([4, 2]),
             "span_parent": np.array([-1, 0, 1, 0, -1, 4]),
             "span_dur": np.array([100, 50, 10, 40, 30, 20]),
             "span_kind": np.array([2, 3, 3, 3, 2, 3]),
             "span_vals": np.array([[0], [1], [2], [1], [1], [0]])}

    def tag(v):
        return {"tag": {"k": "service.name", "v": v}}

    def ask(q):
        return rs.evaluate(q, corpus, block).tolist()

    assert ask({"child": {"parent": tag("a"), "child": tag("b")}}) \
        == [True, False]
    assert ask({"child": {"parent": tag("b"), "child": tag("a")}}) \
        == [False, True]
    assert ask({"desc": {"anc": tag("a"), "span": tag("c")}}) \
        == [True, False]
    assert ask({"child": {"parent": tag("a"), "child": tag("c")}}) \
        == [False, False]
    assert ask({"count": {"of": tag("b"), "op": ">", "n": 1}}) \
        == [True, False]
    # nearest rank: of (40, 50) the ceil(0.5 x 2) = 1st is 40; of (30,)
    # the 1st is 30
    half = {"of": tag("b"), "q": "0.5", "op": ">="}
    assert ask({"quantile": dict(half, ms=40)}) == [True, False]
    assert ask({"quantile": dict(half, ms=41)}) == [False, False]
    assert ask({"quantile": dict(half, q="0.51", ms=50)}) == [True, False]
    assert ask({"quantile": {"of": tag("c"), "q": "0.9", "ms": 0}}) \
        == [True, False]          # no matched span, no match
    assert ask({"and": [{"exists": {"kind": "client"}},
                        {"not": tag("a")}]}) == [False, True]


def test_benchmark_json_names_the_cell_and_its_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    cell = next(w for w in b["workloads"] if w["name"] == CELL)
    assert cell == dict(cell, config="tempo-search-calltree16",
                        traffic="structural", chips=1)
    scan_rate = next(m for m in b["end_to_end"] if m["name"] == "scan_rate")
    assert CELL in scan_rate["workloads"]
    mine = {m["name"]: m for m in b["per_layer"]
            if CELL in m.get("workloads", ())}
    assert sorted(mine) == sorted(METRICS)
    for m in mine.values():
        assert m["moves"] == "scan_rate" and m["workloads"] == [CELL]
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "tempo-search-calltree16.json")) as f:
        conf = json.load(f)
    assert conf["yaml"]["storage"] == {"backend": "local",
                                       "search_structural_enabled": True}
    assert conf["whole_share"]["spans"] == 62_500_000
    assert conf["whole_share"]["blocks"] == 87
