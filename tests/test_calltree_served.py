"""Traces with their span trees, asked by structure, through the served
path: a small `otel_calltree` corpus (the generator of the cell
`calltree16.structural`), one App with `search_structural_enabled`,
every template of `chipbench/traffic/structural.json` asked through the
HTTP handlers and held to `chipbench/reference_structural.py` by the
op's own `check`: what the cell checks on the chip. With the gate off
the same corpus answers flat requests as it does with the gate on, and a
`?q=` gets HTTP 400 ("structural queries disabled ...").

Beside it: the generator's stated targets, that a group's span total
sits inside 0.70-0.90 of its power of two whatever the seed, the plain
reference against the program's own host evaluator (`eval_host`) on
random small trees, the `desc` join at the ingest cap's boundary (one
running max whatever the longest trace), and that the new spans and
counters are written by structural searches only.
"""

import base64
import json
import random
import urllib.parse
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from chipbench import costs_structural, reference_structural as rs
from chipbench.generators import otel_blocks as ob
from chipbench.generators import otel_calltree as oc
from tempo_tpu.observability import metrics as obs
from tempo_tpu.observability import tracing
from tempo_tpu.search import ir, structural
from tempo_tpu.search.columnar import ColumnarPages, PageGeometry
from tempo_tpu.search.data import SearchData, SpanData

from conftest import scan_batch

with open("chipbench/configs/tempo-search-calltree16.json") as _f:
    CONFIG = json.load(_f)
with open("chipbench/traffic/structural.json") as _f:
    TRAFFIC = json.load(_f)
TEMPLATES = {op["name"]: op for op in TRAFFIC["ops"]}
CORPUS = dict(CONFIG["corpus"], config_name="calltreetest",
              tenant="calltreetest", blocks=2, entries_per_block=4096)
FLAT = {
    "service-errors": {"tags": {"service.name": {"draw": "strata"},
                                "http.status_code": {"fixed": "500"}},
                       "variants": 2, "limit": 20},
    "service-exhaustive": {"tags": {"service.name": {"draw": "strata"}},
                           "exhaustive": True, "variants": 2, "limit": 20},
}


def _ask(api, request):
    path, _, qs = request["path"].partition("?")
    code, body = api.handle("GET", path, dict(urllib.parse.parse_qsl(qs)),
                            request["headers"])
    return code, body


def _response(code, body):
    return {"status": code, "body": base64.b64encode(
        json.dumps(body).encode()).decode()}


def _stable(body):
    """An answer without what a clock or a placement decides."""
    m = dict(body.get("metrics", {}))
    for k in ("inspectedBytesDevice", "deviceSeconds"):
        m.pop(k, None)
    return json.dumps(dict(body, metrics=m), sort_keys=True)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    from chipbench.ops import search
    from tempo_tpu.api import HTTPApi
    from tempo_tpu.db.tempodb import TempoDBConfig
    from tempo_tpu.modules import App, AppConfig

    root = tmp_path_factory.mktemp("calltree")
    with ThreadPoolExecutor(2) as pool:
        manifest = oc.generate(CORPUS, 2**31 + 44, str(root / "blocks"), pool)
    manifest["_pool"] = None
    flat = {name: search.build(params, manifest, np.random.default_rng(44))
            for name, params in FLAT.items()}
    gate_was = structural.STRUCTURAL.enabled

    def app_with(gate: bool):
        app = App(AppConfig(
            backend={"backend": "local",
                     "local": {"path": str(root / "blocks")}},
            wal_dir=str(root / f"wal-{gate}"),
            db=TempoDBConfig(auto_mesh=False,
                             search_structural_enabled=gate)))
        app.poll_tick()
        return app

    # the gate is the process's (the last TempoDB wins): off first
    off = app_with(False)
    api = HTTPApi(off, multitenancy=True)
    off_answers = {name: [_ask(api, r) for r in reqs]
                   for name, reqs in flat.items()}
    off_q = api.handle(
        "GET", "/api/search",
        {"q": json.dumps({"exists": {"kind": "client"}})},
        {"X-Scope-OrgID": manifest["tenant"]})
    off.shutdown()
    on = app_with(True)
    yield {"manifest": manifest, "api": HTTPApi(on, multitenancy=True),
           "app": on, "flat": flat, "off_answers": off_answers,
           "off_q": off_q}
    on.shutdown()
    structural.STRUCTURAL.enabled = gate_was


@pytest.mark.parametrize("name", sorted(TEMPLATES))
def test_served_structural_answers_equal_the_reference(served, name):
    from chipbench.ops import search_structural as op

    m = served["manifest"]
    template = json.loads(json.dumps(TEMPLATES[name]))
    if name == "store-fanout":
        # 8,192 traces have no p99.99 below their largest count
        template["draw"]["N"]["count_quantile"] = "0.99"
    requests = op.build(template, m, np.random.default_rng(44))
    assert len(requests) == template["variants"]
    matched = 0
    for request in requests:
        code, body = _ask(served["api"], request)
        ok, why = op.check(request, _response(code, body), m)
        assert ok, (request["path"], why)
        assert body["metrics"]["inspectedTraces"] == m["entries"]
        matched += op._expect(request, m)["matches"]
    # a template that matched nothing anywhere would hold nothing
    assert matched > 0, name


@pytest.mark.parametrize("name", sorted(FLAT))
def test_flat_answers_are_the_same_with_the_gate_off_and_on(served, name):
    from chipbench.ops import search

    m = served["manifest"]
    for request, (code, body) in zip(served["flat"][name],
                                     served["off_answers"][name]):
        ok, why = search.check(request, _response(code, body), m)
        assert ok, (request["path"], why)
        code_on, body_on = _ask(served["api"], request)
        assert code_on == code == 200
        assert _stable(body_on) == _stable(body)


def test_with_the_gate_off_a_structural_query_is_refused(served):
    code, body = served["off_q"]
    assert code == 400
    assert "structural queries disabled" in body["error"]


def test_spans_and_counters_are_written_by_structural_searches_only(served):
    from chipbench.ops import search_structural as op

    m, api = served["manifest"], served["api"]
    batcher = served["app"].reader_db.batcher
    collector = tracing.CollectExporter()
    tracing.set_tracer(tracing.Tracer(tracing.SyncProcessor(collector)))
    try:
        launches = {r: obs.structural_launches.value(rel=r)
                    for r in ("none", "child", "desc")}
        for request in served["flat"]["service-exhaustive"]:
            assert _ask(api, request)[0] == 200
        names = {s.name for s in collector.spans}
        assert "dispatch.execute" in names
        assert not names & {"structural.compile", "batcher.stage_spans"}
        assert not any({"rel", "join_scans", "span_tile"} & set(s.attributes)
                       for s in collector.spans)
        assert launches == {r: obs.structural_launches.value(rel=r)
                            for r in launches}
        # evicted, the group is staged again by the structural search
        for key in batcher.cache.snapshot()["entries"]:
            with batcher.cache.group_lock:
                batcher.cache._drop_hbm_locked(key)
        rows = {k: obs.structural_span_rows.value(kind=k)
                for k in ("live", "pad")}
        reordered = obs.structural_span_reorder_rows.value()
        order_s = obs.structural_span_order_seconds.value()
        request = op.build(
            dict(TEMPLATES["errors-below"], variants=1,
                 draw={"A": {"service": "strata"}}), m,
            np.random.default_rng(3))[0]
        # a predicate no test before this one prepared
        request["path"] = request["path"].replace("limit=20", "limit=19")
        assert _ask(api, request)[0] == 200
    finally:
        tracing.set_tracer(None)
    by_name = {}
    for s in collector.spans:
        by_name.setdefault(s.name, []).append(s)
    compiled = by_name["structural.compile"][0].attributes
    assert compiled["nodes"] == 4 and compiled["terms"] == 2
    staged = by_name["batcher.stage_spans"][0].attributes
    assert staged["span_rows"] - staged["pad_rows"] == m["spans"]
    # put again from the host tier: the layout was paid when the
    # group's host columns were stacked, not by this search
    assert "order_ms" not in staged
    assert obs.structural_span_order_seconds.value() == order_s
    assert obs.structural_span_reorder_rows.value() == reordered
    assert obs.structural_span_rows.value(kind="live") - rows["live"] \
        == m["spans"]
    assert obs.structural_span_rows.value(kind="pad") - rows["pad"] \
        == staged["pad_rows"]
    assert obs.structural_span_bytes.value() == staged["bytes"] > 0
    # `dispatch.compile` where this process had not launched the plan
    execute = [s.attributes for s in collector.spans
               if s.name in ("dispatch.execute", "dispatch.compile")
               and "rel" in s.attributes][-1]
    # one join by ancestor: one running max over the span axis
    assert execute["rel"] == "desc" and execute["join_scans"] == 1
    assert "join_trips" not in execute
    assert execute["span_rows"] == staged["span_rows"]
    # its tag leaves look their tables up by the tile of the span axis
    assert execute["span_tile"] == structural.SPAN_TILE
    assert obs.structural_launches.value(rel="desc") \
        == launches["desc"] + 1
    assert not hasattr(obs, "structural_join_trips")
    # the corpus is stored as an ingester stores it, a child mostly
    # before its parent: the layout moves most of its rows
    stored = [b for h in batcher.cache.snapshot()["host"].values()
              for b in h.blocks if b.has_spans]
    assert sum(b.n_spans for b in stored) == m["spans"]
    moved = sum(structural.span_preorder(
        b.span_parent, b.entry_span_begin.reshape(-1)[b.span_trace])[3]
        for b in stored)
    assert 0.5 * m["spans"] < moved <= m["spans"]


# ---------------------------------------------------------------------------
# the generator


def _spans_of(seed: int, blocks: int, entries: int = 65536) -> list:
    vocab, _table, gid, ids, params = oc.prepare(
        dict(CORPUS, blocks=blocks, entries_per_block=entries))
    out = []
    for i in range(blocks):
        vals, _s, _e, dur = ob.make_block(params, vocab, gid, seed, i)
        out.append(oc.make_spans(params, ids, vals, dur, seed, i))
    return out


@pytest.fixture(scope="module")
def eight_blocks():
    return _spans_of(2**31 + 7, 8)


def test_the_generator_is_a_function_of_the_seed():
    a, b = _spans_of(9, 1, 8192)[0], _spans_of(9, 1, 8192)[0]
    other = _spans_of(10, 1, 8192)[0]
    for k in ("count", "parent", "dur", "kind", "vals"):
        assert np.array_equal(a[k], b[k]), k
    assert not np.array_equal(a["count"], other["count"])


def test_the_generator_keeps_its_stated_targets(eight_blocks):
    count = np.concatenate([s["count"] for s in eight_blocks])
    assert 10 <= count.mean() <= 12
    assert np.median(count) <= 8
    assert np.percentile(count, 99) >= 100
    assert count.max() == 512
    depth = np.concatenate([
        np.maximum.reduceat(
            s["depth"], np.concatenate([[0], np.cumsum(s["count"])])[:-1])
        for s in eight_blocks])
    assert 3 <= np.median(depth) <= 4
    assert depth.max() >= 12
    for s in eight_blocks:
        par, dur = s["parent"], s["dur"]
        child = np.flatnonzero(par >= 0)
        # a child no longer than its parent and shorter wherever the
        # parent is not 0, 2-4 kvs a span
        assert (dur[child] <= dur[par[child]]).all()
        assert ((dur[child] < dur[par[child]]) | (dur[par[child]] == 0)).all()
        kvs = (s["vals"] >= 0).sum(axis=1)
        assert kvs.min() >= 2 and kvs.max() <= oc.SPAN_SLOTS
        assert set(np.unique(s["kind"])) == {1, 2, 3, 4, 5}
    # the cap cut a few traces in ten thousand
    assert 0 < sum(s["cut"] for s in eight_blocks) < len(count) / 1000


def test_the_generator_writes_a_trace_as_an_ingester_stores_it():
    """One batch a service in any order, a batch's spans as they ended:
    a parent lies before its child or behind it, and what
    `collect_span_rows` keeps of such a payload (its first 512 spans,
    parents resolved among them) is what the generator kept."""
    from tempo_tpu import tempopb
    from tempo_tpu.search.data import collect_span_rows

    vocab, table, gid, ids, params = oc.prepare(
        dict(CORPUS, blocks=1, entries_per_block=16384))
    vals, _s, _e, dur = ob.make_block(params, vocab, gid, 11, 0)
    kept = oc.make_spans(params, ids, vals, dur, 11, 0)
    whole = oc.make_spans(dict(params, max_spans=1 << 20), ids, vals, dur,
                          11, 0)
    assert kept["cut"] > 0 and kept["orphans"] > 0 and whole["cut"] == 0
    par = kept["parent"]
    child = np.flatnonzero(par >= 0)
    behind = (par[child] > child).mean()
    assert 0.5 < behind < 0.95           # children mostly end first
    begin = np.concatenate([[0], np.cumsum(kept["count"])])
    wbegin = np.concatenate([[0], np.cumsum(whole["count"])])
    svc_c = oc.K_SVC
    cut = np.flatnonzero(whole["count"] > kept["count"])
    for t in list(range(40)) + list(cut[:3]):
        a, b = wbegin[t], wbegin[t + 1]
        svc = whole["vals"][a:b, svc_c]
        # a service's spans are one run of the trace
        runs = 1 + int((svc[1:] != svc[:-1]).sum())
        assert runs == len(set(svc.tolist()))
        trace = tempopb.Trace()
        batch = None
        for i in range(a, b):
            if i == a or svc[i - a] != svc[i - a - 1]:
                batch = trace.batches.add()
                kv = batch.resource.attributes.add()
                kv.key = "service.name"
                kv.value.string_value = table[svc[i - a]]
                spans = batch.scope_spans.add().spans
            sp = spans.add()
            sp.span_id = int(i - a + 1).to_bytes(8, "big")
            if whole["parent"][i] >= 0:
                sp.parent_span_id = int(
                    whole["parent"][i] - a + 1).to_bytes(8, "big")
            sp.kind = int(whole["kind"][i])
            sp.start_time_unix_nano = 1
            sp.end_time_unix_nano = 1 + int(whole["dur"][i]) * 1_000_000
        rows = collect_span_rows(trace, max_spans=512, max_kvs=16)
        ka, kb = begin[t], begin[t + 1]
        assert len(rows) == kb - ka == min(b - a, 512)
        assert [r.parent for r in rows] == [
            -1 if p < 0 else int(p - ka) for p in par[ka:kb]]
        assert [r.kind for r in rows] == kept["kind"][ka:kb].tolist()
        assert [r.dur_ms for r in rows] == kept["dur"][ka:kb].tolist()


@pytest.mark.parametrize("seed", [1, 2, 3, 4, 5])
def test_a_groups_span_total_sits_inside_its_power_of_two(seed):
    totals = [len(s["parent"]) for s in _spans_of(seed, 8)]
    # a block's total is nearly a constant: so is every group's of
    # 8, 16, 32 or 64 blocks, all at the same share of its power of two
    assert max(totals) - min(totals) < 1e-3 * np.mean(totals)
    for blocks in (8, 16, 32, 64):
        total = np.mean(totals) * blocks
        assert 0.70 <= total / structural._pow2(int(total)) <= 0.90
    assert 0.70 <= sum(totals) / structural._pow2(sum(totals)) <= 0.90


# ---------------------------------------------------------------------------
# two evaluators that share no code agree


KEYS = ("env", "service.name")
SPAN_KEYS = ("name", "service.name")
VALUES = ("api", "auth", "cache", "db", "dev", "op0", "op1", "op2", "prod")


def _random_traces(rng: random.Random, n: int) -> list:
    out = []
    for i in range(n):
        sd = SearchData(trace_id=i.to_bytes(4, "big").rjust(16, b"\x00"))
        sd.start_s = 1_600_000_000 + i
        sd.end_s = sd.start_s + 1
        sd.dur_ms = rng.randint(1, 3000)
        sd.kvs = {"service.name": {rng.choice(VALUES[:4])},
                  "env": {rng.choice(("prod", "dev"))}}
        for s in range(rng.randint(1, 12)):
            sd.spans.append(SpanData(
                parent=-1 if s == 0 else rng.randrange(s),
                dur_ms=rng.randint(0, 400), kind=rng.randint(1, 5),
                kvs={"service.name": {rng.choice(VALUES[:4])},
                     "name": {rng.choice(VALUES[5:8])}}))
        out.append(sd)
    return out


def _as_block(entries: list) -> tuple:
    """(corpus, block) of `reference_structural` from SearchData."""
    table = sorted(VALUES)
    vid = {v: i for i, v in enumerate(table)}
    vals = np.full((len(KEYS), len(entries)), -1, dtype=np.int16)
    for e, sd in enumerate(entries):
        for k, key in enumerate(KEYS):
            vals[k, e] = vid[next(iter(sd.kvs[key]))]
    spans = [sp for sd in entries for sp in sd.spans]
    count = np.array([len(sd.spans) for sd in entries], dtype=np.int32)
    begin = np.repeat(np.concatenate([[0], np.cumsum(count)])[:-1], count)
    par = np.array([sp.parent for sp in spans], dtype=np.int32)
    svals = np.full((len(spans), len(SPAN_KEYS)), -1, dtype=np.int16)
    for i, sp in enumerate(spans):
        for k, key in enumerate(SPAN_KEYS):
            if key in sp.kvs:
                svals[i, k] = vid[next(iter(sp.kvs[key]))]
    corpus = {"table": table, "key_names": KEYS, "span_key_names": SPAN_KEYS}
    return corpus, {
        "vals": vals, "dur": np.array([sd.dur_ms for sd in entries]),
        "span_count": count,
        "span_parent": np.where(par >= 0, par + begin, -1).astype(np.int32),
        "span_dur": np.array([sp.dur_ms for sp in spans], dtype=np.uint32),
        "span_kind": np.array([sp.kind for sp in spans], dtype=np.int8),
        "span_vals": svals}


def _leaf(rng: random.Random) -> dict:
    kind = rng.choice(("tag", "tag", "dur", "kind", "not"))
    if kind == "tag":
        return {"tag": {"k": rng.choice(SPAN_KEYS + ("nope",)),
                        "v": rng.choice(("a", "op", "op1", "db", "", "c"))}}
    if kind == "dur":
        lo = rng.randint(0, 300)
        return {"dur": {"min_ms": lo, "max_ms": lo + rng.randint(0, 200)}}
    if kind == "kind":
        return {"kind": rng.choice(("server", "client", 1, 4, "consumer"))}
    return {"not": _leaf(rng)}


def _span(rng: random.Random) -> dict:
    if rng.random() < 0.3:
        return {rng.choice(("and", "or")): [_leaf(rng), _leaf(rng)]}
    return _leaf(rng)


def _query(rng: random.Random, kind: str) -> dict:
    if kind == "child":
        return {"child": {"parent": _span(rng), "child": _span(rng)}}
    if kind == "desc":
        return {"exists": {"desc": {"anc": _span(rng), "span": _span(rng)}}}
    if kind == "count":
        return {"count": {"of": _span(rng), "n": rng.randint(0, 4),
                          "op": rng.choice(ir.CMP_OPS)}}
    if kind == "quantile":
        return {"quantile": {"of": _span(rng), "ms": rng.randint(0, 400),
                             "q": rng.choice(("0.5", "0.9", "0.99", "1",
                                              "0.25", "0.001")),
                             "op": rng.choice(ir.CMP_OPS)}}
    return {rng.choice(("and", "or")): [
        {"exists": _span(rng)},
        {"not": {"tag": {"k": "env", "v": rng.choice(("prod", "d"))}}},
        {"dur": {"min_ms": rng.randint(0, 2000)}}]}


@pytest.mark.parametrize("kind", ["child", "desc", "count", "quantile",
                                  "boolean"])
def test_the_plain_reference_agrees_with_the_programs_host_evaluator(kind):
    rng = random.Random(f"calltree-{kind}")
    entries = _random_traces(rng, 200)
    corpus, block = _as_block(entries)
    matched = 0
    for _ in range(25):
        q = _query(rng, kind)
        got = rs.evaluate(q, corpus, block)
        expr = ir.parse(json.dumps(q))
        want = np.array([structural.eval_host(expr, sd) for sd in entries])
        assert np.array_equal(got, want), q
        matched += int(got.sum())
    assert matched > 0


# ---------------------------------------------------------------------------
# the `desc` join's trips: from the longest trace, not the padded axis


def _chain_trace(i: int, n_spans: int, rng: random.Random) -> SearchData:
    sd = SearchData(trace_id=i.to_bytes(4, "big").rjust(16, b"\x00"))
    sd.start_s, sd.end_s, sd.dur_ms = 1_600_000_000 + i, 1_600_000_001 + i, 9
    sd.kvs = {"service.name": {"api"}, "env": {"prod"}}
    for s in range(n_spans):
        sd.spans.append(SpanData(
            parent=s - 1, dur_ms=5, kind=2,
            kvs={"service.name": {rng.choice(VALUES[:4])},
                 "name": {"op0" if s == 0 else
                          "op2" if s == n_spans - 1 else "op1"}}))
    return sd


DESC = [
    # the chain's last span under its first: every ancestor but one apart
    {"exists": {"desc": {"anc": {"tag": {"k": "name", "v": "op0"}},
                         "span": {"tag": {"k": "name", "v": "op2"}}}}},
    {"exists": {"desc": {"anc": {"tag": {"k": "service.name", "v": "db"}},
                         "span": {"tag": {"k": "service.name", "v": "api"}}}}},
    {"count": {"of": {"desc": {
        "anc": {"and": [{"tag": {"k": "name", "v": "op1"}},
                        {"tag": {"k": "service.name", "v": "auth"}}]},
        "span": {"tag": {"k": "name", "v": "op"}}}}, "op": ">", "n": 3}},
]


@pytest.mark.parametrize("case", ["chain-511", "chain-600-cut-at-512",
                                  "longest-9-of-2^15-rows"])
def test_desc_answers_hold_at_the_ingest_caps_boundary(case):
    was = structural.STRUCTURAL.enabled
    structural.STRUCTURAL.enabled = True
    try:
        rng = random.Random(case)
        if case == "longest-9-of-2^15-rows":
            entries = [_chain_trace(i, rng.randint(1, 9), rng)
                       for i in range(3400)]
            entries[7] = _chain_trace(7, 9, rng)
            geo = PageGeometry(entries_per_page=1024, kv_per_entry=4)
        else:
            n = 511 if case == "chain-511" else 600
            entries = [_chain_trace(i, rng.randint(1, 6), rng)
                       for i in range(60)]
            entries[31] = _chain_trace(31, n, rng)
            # what the ingest cap keeps of a trace: its first 512 spans
            entries[31].spans = entries[31].spans[:512]
            geo = PageGeometry(entries_per_page=64, kv_per_entry=4)
        pages = ColumnarPages.build(entries, geo)
        corpus, block = _as_block(entries)
        from tempo_tpu import tempopb

        req = tempopb.SearchRequest()
        req.limit = 5000
        first = scan_batch([pages], req, top_k=4096,
                           structural=ir.parse(json.dumps(DESC[0])))
        batch, eng = first.batch, first.engine
        rows = int(batch.span_device["span_parent"].shape[0])
        assert batch.span_device["span_tile_block"].shape \
            == (rows // structural.SPAN_TILE,)
        assert (rows == 1 << 15) == (case == "longest-9-of-2^15-rows")
        # one program whatever the group's longest trace: nothing of
        # it is in the launch's statics
        assert not hasattr(batch, "span_max")
        last = np.asarray(batch.span_device["span_last"])
        live = np.asarray(batch.span_device["span_trace"]) >= 0
        longest = int((last - np.arange(rows))[live].max()) + 1
        assert longest == {"chain-511": 511, "chain-600-cut-at-512": 512,
                           "longest-9-of-2^15-rows": 9}[case]

        for q in DESC:
            want = int(rs.evaluate(q, corpus, block).sum())
            expr = ir.parse(json.dumps(q))
            got = scan_batch([pages], req, structural=expr, engine=eng,
                             batch=batch)
            assert int(got.count) == want, (case, q)
            plan = structural._LeafCollector().lower_trace(expr)
            assert structural.plan_joins(plan) == ("desc", 1)
        assert int(first.count) == int(rs.evaluate(DESC[0], corpus,
                                                   block).sum()) > 0
    finally:
        structural.STRUCTURAL.enabled = was


def _walk_order_trace(i: int, rng: random.Random, n_spans: int) -> SearchData:
    """A random tree written depth-first, siblings in a random order."""
    kids: dict = {0: []}
    for s in range(1, n_spans):
        kids.setdefault(rng.randrange(s), []).append(s)
        kids.setdefault(s, [])
    order, parent_of, stack = [], {}, [(0, -1)]
    while stack:
        node, par = stack.pop()
        parent_of[node] = par
        order.append(node)
        below = kids[node][:]
        rng.shuffle(below)
        stack.extend((k, node) for k in below)
    at = {node: j for j, node in enumerate(order)}
    sd = _chain_trace(i, 0, rng)
    for node in order:
        sd.spans.append(SpanData(
            parent=-1 if parent_of[node] < 0 else at[parent_of[node]],
            dur_ms=rng.randint(0, 400), kind=rng.randint(1, 5),
            kvs={"service.name": {rng.choice(VALUES[:4])},
                 "name": {rng.choice(VALUES[5:8])}}))
    return sd


def _stored_order_trace(i: int, rng: random.Random, n_spans: int,
                        children_first: bool) -> SearchData:
    """A random tree with its spans shuffled (or, `children_first`,
    every child before its parent): the orders an ingester may store."""
    sd = _walk_order_trace(i, rng, n_spans)
    order = list(range(n_spans))
    if children_first:
        order.reverse()
    else:
        rng.shuffle(order)
    at = {old: new for new, old in enumerate(order)}
    spans = [sd.spans[old] for old in order]
    for sp in spans:
        if sp.parent >= 0:
            sp.parent = at[sp.parent]
    sd.spans = spans
    return sd


@pytest.mark.parametrize("order", ["walk-order", "shuffled",
                                   "children-first"])
def test_desc_on_the_device_equals_the_reference_in_either_order(order):
    was = structural.STRUCTURAL.enabled
    structural.STRUCTURAL.enabled = True
    try:
        from tempo_tpu import tempopb

        rng = random.Random(order)
        entries = [_walk_order_trace(i, rng, rng.randint(1, 30))
                   if order == "walk-order" else
                   _stored_order_trace(i, rng, rng.randint(1, 30),
                                       order == "children-first")
                   for i in range(200)]
        pages = ColumnarPages.build(
            entries, PageGeometry(entries_per_page=64, kv_per_entry=4))
        corpus, block = _as_block(entries)
        req = tempopb.SearchRequest()
        req.limit = 5000
        staged = None
        for _ in range(12):
            q = _query(rng, "desc")
            got = scan_batch([pages], req, top_k=512,
                             structural=ir.parse(json.dumps(q)),
                             **({} if staged is None else
                                {"engine": staged.engine,
                                 "batch": staged.batch}))
            staged = staged or got
            assert int(got.count) == int(rs.evaluate(q, corpus,
                                                     block).sum()), q
        # walk order is the layout: staging moved nothing there
        cols = staged.batch.span_device
        n = sum(len(sd.spans) for sd in entries)
        assert np.array_equal(
            np.asarray(cols["span_dur"])[:n],
            pages.span_dur) == (order == "walk-order")
    finally:
        structural.STRUCTURAL.enabled = was


def test_plan_joins_names_the_relation_and_counts_the_scans():
    def plan_of(q):
        return structural._LeafCollector().lower_trace(
            ir.parse(json.dumps(q)))

    assert structural.plan_joins(None) == ("none", 0)
    assert structural.plan_joins(plan_of(DESC[0])) == ("desc", 1)
    child = {"child": {"parent": {"kind": 2}, "child": {"kind": 3}}}
    assert structural.plan_joins(plan_of(child)) == ("child", 0)
    both = {"and": [DESC[0], DESC[2], child]}
    assert structural.plan_joins(plan_of(both)) == ("desc", 2)
    # every span slot of a bucket with relations runs the arm
    assert structural.plan_joins(("bucket", 4, 2, True)) == ("desc", 4)
    assert structural.plan_joins(("bucket", 4, 2, False)) == ("none", 0)
    # no trip count left to read: the planner's weight of a `desc` is
    # a pass over the span axis, as `child`'s
    nb = structural.plan_node_bytes(plan_of(DESC[0]), 1 << 20, 4096)
    assert sorted(nb.values())[-1] < 1 << 26


def test_costs_count_each_column_a_plan_reads_once():
    kw = dict(spans=1000, entries=100, span_slots=4, kv_slots=16, n_keys=16,
              n_vals=9000)
    out = 4 * (2 + 2 * costs_structural.TOP_K)
    q = {name: op["q"] for name, op in TEMPLATES.items()}
    # tag leaves + the parent column + segments; entry columns 13 B
    joined = 1000 * (4 + 32 + 4 + 4) + 100 * (13 + 8) + out
    assert costs_structural.search_bytes(q["errors-below"], **kw) == joined
    assert costs_structural.search_bytes(q["direct-call"], **kw) == joined
    assert costs_structural.search_bytes(q["store-fanout"], **kw) \
        == 1000 * (4 + 32 + 4 + 1) + 100 * (13 + 8) + out
    # a quantile reads durations; a trace-scope tag the entry's kv slots
    assert costs_structural.search_bytes(q["slow-p90"], **kw) \
        == 1000 * (4 + 32 + 4 + 4) + 100 * (13 + 8 + 16 * 3) + out
    assert costs_structural.search_bytes(q["client-no-error-parent"], **kw) \
        == 1000 * (4 + 32 + 4 + 4 + 1) + 100 * (13 + 8) + out
    assert costs_structural.reads({"dur": {"min_ms": 1}}) == set()
