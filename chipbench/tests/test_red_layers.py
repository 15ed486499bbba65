"""The cell of RED dashboards: its readers, each on a hand-made run with
the value worked by hand and on a program that lacks what it reads; the
bytes a reducing launch must move; the plain reference on a corpus small
enough to check by eye; that `BENCHMARK.json` names the cell, its
configuration and its metrics; and the tiny rehearsal."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from chipbench import costs, costs_red, reference_red
from chipbench.tests.test_span_layers import EMPTY, Spans, reader

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CACHE = "tempo_search_hbm_cache_bytes"
KEYS = "tempo_search_agg_staged_bytes"
STAGE = "tempo_search_dispatch_stage_seconds"
DISPATCHES = "tempo_search_scan_dispatches_total"
FUSED = "tempo_search_coalesced_queries_total"
CELL = "red16.dashboard"
DEVICE = ("kernel_ms.red", "red_kernel_roofline", "sort_share.red")
METRICS = DEVICE + (
    "agg_hbm_share.red", "launches_per_search.red",
    "searches_per_dispatch.red", "decode_ms.red", "sync_ms.red",
    "search_p50_ms.red", "search_cpu_ms.red", "host_cores_busy.red")


@pytest.fixture
def run():
    """A tenant of 8 pages in two groups; four searches completed in the
    window, answered 100, 200, 300 and 400 ms after they were due, on 4
    launches (2 solo, 2 fused of 3 members each: 8 group scans); the
    traced seconds saw 4 scan programs take 8 ms of a busy 10 ms, 6 ms
    of it in two sort ops; a search decoded twice (1 ms each) and merged
    once (2 ms); each search's thread burned 2 ms in its
    `batcher.Search`; the process 0.5 s of CPU in a window of 2 s; the
    key columns hold 32,768 of the cache's 524,288 bytes."""
    s = Spans()
    for i in range(4):
        t = "abcd"[i]
        s.add("HTTP GET /api/search", i * 10, i * 10 + 9, trace=t)
        b = s.add("batcher.Search", i * 10, i * 10 + 9, trace=t, groups=2,
                  **{"thread.id": i, "thread.cpu_ns": 2_000_000})
        s.add("analytics.decode", i * 10 + 1, i * 10 + 2, trace=t, parent=b)
        s.add("analytics.decode", i * 10 + 3, i * 10 + 4, trace=t, parent=b)
        s.add("results.merge_agg", i * 10 + 5, i * 10 + 7, trace=t)
    present = np.zeros((2, 13_000), dtype=bool)
    present[:, :9_000] = True
    one = '{mode="%s",shards="1"}'
    return {
        "trace": {"window_ns": 2e9, "busy_ns": 10e6,
                  "devices": [{"busy_ns": 10e6}],
                  "programs_ns": {"jit_batch_scan_kernel": 8e6},
                  "program_calls": {"jit_batch_scan_kernel": 4},
                  "ops_ns": [("sort.3", 4e6), ("fusion.1", 3e6),
                             ("sort.12", 2e6), ("resort", 1e6)]},
        "window_wall_s": 2.0,
        "spans": s.out, "device_kind": "TPU v5 lite",
        "config": {"chips": {"count": 1}},
        "manifest": {"pages": 8, "kv_per_entry": 16, "present": present,
                     "key_names": tuple(f"k{i}" for i in range(17)),
                     "vocab": {"services": [f"s{i}" for i in range(200)]}},
        "requests": [{"op": "search_red"}],
        "records": [{"i": 0, "status": 200, "due": float(i),
                     "done": i + 0.1 * (i + 1)} for i in range(4)],
        "counters": {
            "before": {
                "process_cpu_seconds_total": {"": 10.0},
                DISPATCHES: {one % "batched": 40.0, one % "coalesced": 5.0},
                FUSED: {"": 20.0},
                STAGE + "_sum": {'{stage="d2h"}': 1.0},
                STAGE + "_count": {'{stage="d2h"}': 40.0}},
            "after": {
                KEYS: {"": 32_768.0}, CACHE: {"": 524_288.0},
                "process_cpu_seconds_total": {"": 10.5},
                DISPATCHES: {one % "batched": 42.0, one % "coalesced": 7.0},
                FUSED: {"": 26.0},
                STAGE + "_sum": {'{stage="d2h"}': 1.08},
                STAGE + "_count": {'{stage="d2h"}': 44.0}}},
    }


def test_readers_on_a_hand_made_run(run):
    got = {m: reader(m)(run) for m in METRICS}
    assert got["kernel_ms.red"] == pytest.approx(2.0)
    # a launch reads a group of 4 pages: the scan's 16 x (1 + 2) + 13 B
    # an entry and 4 B of key, and writes K + 2 + 2k words for each of
    # its (2 + 6) / 4 = 2 members; K = 256 x 15 x 2
    per_launch = 4 * 1024 * (16 * 3 + 13 + 4) + 2 * 4 * (7680 + 2 + 256)
    assert got["red_kernel_roofline"] == pytest.approx(
        100 * (4 * per_launch / 819e9) / 8e-3)
    assert got["sort_share.red"] == pytest.approx(60.0)   # `resort` is none
    assert got["agg_hbm_share.red"] == pytest.approx(6.25)
    assert got["launches_per_search.red"] == pytest.approx(1.0)
    assert got["searches_per_dispatch.red"] == pytest.approx(2.0)
    assert got["decode_ms.red"] == pytest.approx(4.0)
    assert got["sync_ms.red"] == pytest.approx(20.0)
    # nearest rank: the 2nd of four
    assert got["search_p50_ms.red"] == pytest.approx(200.0)
    assert got["search_cpu_ms.red"] == pytest.approx(2.0)
    assert got["host_cores_busy.red"] == pytest.approx(0.25)


def test_readers_on_a_program_without_the_spans_and_counters(run):
    """The parent of PR 48 (no gauge of the key columns, no decode or
    merge span), and an untraced run: nothing to read, and no reader
    raises."""
    bare = dict(run, spans=[s for s in run["spans"]
                            if s["name"] in ("batcher.Search",
                                             "HTTP GET /api/search")])
    bare["counters"]["after"].pop(KEYS)
    assert reader("agg_hbm_share.red")(bare) is None
    assert reader("decode_ms.red")(bare) is None
    # the trace and the launch counters it did have
    for m in DEVICE + ("launches_per_search.red",
                       "searches_per_dispatch.red", "sync_ms.red"):
        assert reader(m)(bare) is not None, m
    untraced = dict(EMPTY, trace=None, requests=[], records=[],
                    config={"chips": {"count": 1}})
    for m in METRICS:
        assert reader(m)(untraced) is None, m


def test_a_share_of_the_roofline_counts_the_work_not_the_way():
    """What a launch must move: the scan's columns and the key column
    once however many members, the counts once a member; nothing for a
    sort's passes, so no implementation can read past 100 % by doing
    less of them."""
    kw = dict(pages=4096, kv_slots=16, n_keys=17, n_vals=9000, services=200)
    one = costs_red.launch_bytes(members=1, **kw)
    eight = costs_red.launch_bytes(members=8, **kw)
    scan = costs.scan_bytes(4096, 16, 17, 9000)
    assert one == scan + 4096 * 1024 * 4 + 4 * (7680 + 2 + 256)
    assert eight - one == 7 * 4 * (7680 + 2 + 256)
    assert costs_red.key_space(200) == 7680    # 201 slots pad to 256
    assert costs_red.key_space(255) == 7680
    assert costs_red.key_space(256) == 15360


def test_the_reference_on_a_corpus_small_enough_to_check_by_eye():
    """Two blocks of three entries. Services a, b; durations in ms on
    both sides of an edge; errors on one entry a block. A predicate that
    skips the second block by its rollup counts nothing of it."""
    table = ["a", "b", "prod", "true"]
    corpus = {
        "table": table, "key_names": ("env", "error", "service.name"),
        # [B, K, N]: env, error, service.name
        "vals": np.array([[[2, 2, -1], [-1, 3, -1], [0, 0, 1]],
                          [[-1, -1, -1], [3, -1, -1], [1, 1, 0]]],
                         dtype=np.int16),
        "dur": np.array([[2, 3, 16385], [1, 16384, 40]], dtype=np.uint32),
        "start": np.array([[10, 20, 30], [40, 50, 60]], dtype=np.uint32),
        "end": np.array([[11, 21, 47], [41, 67, 61]], dtype=np.uint32),
    }
    corpus["present"] = np.array([[1, 1, 1, 1], [1, 1, 0, 1]], dtype=bool)
    corpus["key_present"] = np.array([[1, 1, 1], [0, 1, 1]], dtype=bool)
    corpus["error"] = corpus["vals"][:, 1, :] >= 0
    corpus["root_service"] = corpus["vals"][:, 2, :]

    def hist(*bins):
        h = [0] * 15
        for b in bins:
            h[b] += 1
        return h

    everything = reference_red.answer({"tags": {}, "limit": 20}, corpus)
    assert everything["aggregates"]["buckets_ms"] == list(
        reference_red.EDGES_MS)
    assert everything["inspected"] == 6 and everything["deterministic"]
    assert everything["aggregates"]["series"] == {
        # a: 2 ms (bin 0: <= 2), 3 ms (bin 1: <= 4, the error), 40 ms
        "a": {"calls": 3, "errors": 1, "hist": hist(0, 1, 5)},
        # b: 16,385 ms (+Inf), 1 ms (bin 0, the error), 16,384 (bin 13)
        "b": {"calls": 3, "errors": 1, "hist": hist(14, 0, 13)}}
    prod = reference_red.answer({"tags": {"env": "prod"}, "limit": 20},
                                corpus)
    assert prod["inspected"] == 3 and prod["skipped_blocks"] == 1
    assert prod["aggregates"]["series"] == {
        "a": {"calls": 2, "errors": 1, "hist": hist(0, 1)}}
    slow = reference_red.answer({"tags": {}, "min_ms": 16384, "limit": 1},
                                corpus)
    assert slow["matches"] == 2 and slow["top_starts"] == [50]
    assert sorted(slow["aggregates"]["series"]) == ["b"]
    assert reference_red.answer({"tags": {"env": "nope"}}, corpus)[
        "aggregates"]["series"] == {}


def test_the_edges_are_upstreams_fourteen_in_integer_ms():
    # prometheus.ExponentialBuckets(0.002, 2, 14), seconds -> ms
    assert list(reference_red.EDGES_MS) == [2 * 2 ** i for i in range(14)]
    assert reference_red.BINS == 15


def test_benchmark_json_names_the_cell_and_its_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    cell = next(w for w in b["workloads"] if w["name"] == CELL)
    assert cell == dict(cell, config="tempo-search-red16",
                        traffic="dashboard", chips=1)
    assert len(cell["why"]) <= 200
    assert len(b["workloads"]) == 8
    assert sum(w["chips"] == 4 for w in b["workloads"]) == 1
    scan_rate = next(m for m in b["end_to_end"] if m["name"] == "scan_rate")
    assert scan_rate["workloads"][-1] == CELL
    mine = {m["name"]: m for m in b["per_layer"]
            if CELL in m.get("workloads", ())}
    assert sorted(mine) == sorted(METRICS)
    for m in mine.values():
        assert m["moves"] == "scan_rate" and m["workloads"] == [CELL]
    entry = next(c for c in b["configs"] if c["name"] == "tempo-search-red16")
    assert len(entry["source"]) <= 200
    with open(os.path.join(ROOT, entry["file"])) as f:
        conf = json.load(f)
    assert conf["yaml"]["storage"] == {"backend": "local",
                                       "search_analytics_enabled": True}
    assert sorted(conf["reduced"]) == sorted(entry["reduced"])
    assert conf["corpus"]["blocks"] >= 64
    assert {"error_pair", "error_slot"} <= set(conf["assumed"])
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "tempo-search-share16.json")) as f:
        share16 = json.load(f)
    for k, v in share16["corpus"].items():
        if k not in ("generator", "tenant", "blocks"):
            assert conf["corpus"][k] == v, k
    assert conf["guarantees"][:2] == share16["guarantees"]
    with open(os.path.join(ROOT, "chipbench", "traffic",
                           "dashboard.json")) as f:
        mix = json.load(f)
    assert mix["loop"] == "closed" and mix["clients"] == 16
    assert sum(op["variants"] for op in mix["ops"]) == 30
    assert sum(op["share"] for op in mix["ops"]) == pytest.approx(1.0)
    assert {op["op"] for op in mix["ops"]} == {"search_red"}


# ---- the rehearsal


def test_rehearsal_of_the_dashboard_cell():
    """Every step of `red16.dashboard` at the tiny size: 12 blocks in
    one group, every answer of set-up, warm and window held to the
    reference. The CPU's profile has no device plane: the three
    `device_trace` readers find nothing here."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)   # tests/conftest.py's eight virtual devices
    p = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", CELL,
         "--seed", str(2**31 + 4800), "--seconds", "3", "--trace", "1",
         "--scale", "tiny"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=1500)
    lines = p.stdout.strip().splitlines()
    assert p.returncode == 3, p.stdout[-3000:] + p.stderr[-3000:]
    assert "REHEARSAL on cpu" in lines[-1]
    for name in METRICS:
        if name not in DEVICE:
            assert name in lines[-1], lines[-1]
    assert "mismatches=0 (limit 0)" in p.stdout
    assert "generator=otel_red" in p.stdout
    assert "jit misses inside the window=0" in p.stdout
