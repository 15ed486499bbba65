"""Structural query engine (ISSUE 14): IR parsing, the span-segment
substrate, and the differential contract — random IR trees over random
corpora must answer byte-for-byte identically through every engine path
(single / batched / coalesced / mesh / dist + both host routes) vs the
plain-python reference evaluator (`structural.eval_host`), packed
residency on and off, breaker-forced host routes included."""

from __future__ import annotations

import json
import random

import numpy as np
import pytest

from tempo_tpu import robustness, tempopb
from tempo_tpu.backend.local import LocalBackend
from tempo_tpu.db import TempoDB, TempoDBConfig
from tempo_tpu.search import ir, structural
from tempo_tpu.search import packing as packing_mod
from tempo_tpu.search.batcher import host_scan
from tempo_tpu.search.columnar import ColumnarPages, PageGeometry
from tempo_tpu.search.data import (
    SearchData,
    SpanData,
    decode_search_data,
    encode_search_data,
    search_data_matches,
)
from tempo_tpu.search.multiblock import MultiBlockEngine, compile_multi
from tempo_tpu.search.structural import (
    STRUCTURAL,
    STRUCTURAL_QUERY_TAG,
    compile_structural,
    eval_host,
    structural_query,
)

from conftest import scan_batch

E_GEO = PageGeometry(entries_per_page=64, kv_per_entry=8)

_SVCS = ["api", "db", "auth", "cache", "web"]
_OPS = ["op0", "op1", "op2"]


@pytest.fixture(autouse=True)
def _structural_on():
    """Each test runs with the gate ON (the default-off contract has its
    own tests) and leaves the process gate as it found it."""
    prev = STRUCTURAL.enabled
    prev_stack = STRUCTURAL.stack_enabled
    prev_shard = STRUCTURAL.shard_spans
    prev_bucket = STRUCTURAL.bucket_enabled
    prev_bucket_max = STRUCTURAL.bucket_max_nodes
    prev_remainder = STRUCTURAL.remainder_pages
    STRUCTURAL.enabled = True
    packing_prev = packing_mod.PACKING.enabled
    yield
    STRUCTURAL.enabled = prev
    STRUCTURAL.stack_enabled = prev_stack
    STRUCTURAL.shard_spans = prev_shard
    STRUCTURAL.bucket_enabled = prev_bucket
    STRUCTURAL.bucket_max_nodes = prev_bucket_max
    STRUCTURAL.remainder_pages = prev_remainder
    packing_mod.PACKING.enabled = packing_prev
    robustness.BREAKER.reset()


def _corpus(seed: int, n: int = 150, max_spans: int = 9):
    rng = random.Random(seed)
    entries = []
    for i in range(n):
        sd = SearchData(trace_id=i.to_bytes(2, "big").rjust(16, b"\x00"))
        sd.start_s = 1_600_000_000 + i
        sd.end_s = sd.start_s + rng.randint(0, 10)
        sd.dur_ms = rng.randint(1, 5000)
        sd.root_service = rng.choice(_SVCS)
        sd.kvs = {
            "service.name": {sd.root_service},
            "env": {"prod" if i % 2 else "dev"},
        }
        for _ in range(rng.randint(0, max_spans)):
            s = len(sd.spans)
            sd.spans.append(SpanData(
                parent=(-1 if s == 0 or rng.random() < 0.2
                        else rng.randrange(s)),
                dur_ms=rng.randint(1, 1000),
                kind=rng.randint(0, 5),
                kvs={"service.name": {rng.choice(_SVCS)},
                     "name": {rng.choice(_OPS)}},
            ))
        entries.append(sd)
    return entries


def _rand_span(rng: random.Random, depth: int) -> ir.SpanExpr:
    choices = ["tag", "dur", "kind"]
    if depth > 0:
        choices += ["and", "or", "not", "child", "desc"]
    op = rng.choice(choices)
    if op == "tag":
        return ir.SpanTag(rng.choice(["service.name", "name", "nope"]),
                          rng.choice(["a", "p", "op", "db", ""]))
    if op == "dur":
        lo = rng.randint(0, 800)
        return ir.SpanDur(lo, lo + rng.randint(0, 800))
    if op == "kind":
        return ir.SpanKind(rng.randint(0, 5))
    if op in ("and", "or"):
        args = tuple(_rand_span(rng, depth - 1)
                     for _ in range(rng.randint(1, 3)))
        return ir.SpanAnd(args) if op == "and" else ir.SpanOr(args)
    if op == "not":
        return ir.SpanNot(_rand_span(rng, depth - 1))
    if op == "child":
        return ir.ChildOf(_rand_span(rng, depth - 1),
                          _rand_span(rng, depth - 1))
    return ir.DescOf(_rand_span(rng, depth - 1),
                     _rand_span(rng, depth - 1))


def _rand_trace(rng: random.Random, depth: int = 2) -> ir.TraceExpr:
    choices = ["exists", "count", "quantile", "tag", "dur"]
    if depth > 0:
        choices += ["and", "or", "not"]
    op = rng.choice(choices)
    if op == "exists":
        return ir.Exists(_rand_span(rng, 2))
    if op == "count":
        return ir.Count(_rand_span(rng, 1),
                        rng.choice(ir.CMP_OPS), rng.randint(0, 4))
    if op == "quantile":
        qn, qd = rng.choice([(1, 2), (9, 10), (99, 100), (1, 4)])
        return ir.Quantile(_rand_span(rng, 1), qn, qd,
                           rng.choice(ir.CMP_OPS), rng.randint(0, 900))
    if op == "tag":
        return ir.TraceTag(rng.choice(["service.name", "env", "nope"]),
                           rng.choice(["a", "prod", "dev", ""]))
    if op == "dur":
        lo = rng.randint(0, 4000)
        return ir.TraceDur(lo, lo + rng.randint(0, 4000))
    if op in ("and", "or"):
        args = tuple(_rand_trace(rng, depth - 1)
                     for _ in range(rng.randint(1, 3)))
        return ir.TraceAnd(args) if op == "and" else ir.TraceOr(args)
    return ir.TraceNot(_rand_trace(rng, depth - 1))


def _reparam_span(e: ir.SpanExpr, rng: random.Random) -> ir.SpanExpr:
    """Same tree SHAPE (ops, arity, comparison operators), fresh leaf
    parameters — the 'N dashboards running the same saved query with
    different filters' load plan-shape stacking exists for."""
    if isinstance(e, ir.SpanTag):
        return ir.SpanTag(rng.choice(["service.name", "name", "nope"]),
                          rng.choice(["a", "p", "op", "db", ""]))
    if isinstance(e, ir.SpanDur):
        lo = rng.randint(0, 800)
        return ir.SpanDur(lo, lo + rng.randint(0, 800))
    if isinstance(e, ir.SpanKind):
        return ir.SpanKind(rng.randint(0, 5))
    if isinstance(e, ir.SpanAnd):
        return ir.SpanAnd(tuple(_reparam_span(a, rng) for a in e.args))
    if isinstance(e, ir.SpanOr):
        return ir.SpanOr(tuple(_reparam_span(a, rng) for a in e.args))
    if isinstance(e, ir.SpanNot):
        return ir.SpanNot(_reparam_span(e.arg, rng))
    if isinstance(e, ir.ChildOf):
        return ir.ChildOf(_reparam_span(e.parent, rng),
                          _reparam_span(e.child, rng))
    return ir.DescOf(_reparam_span(e.anc, rng),
                     _reparam_span(e.span, rng))


def _reparam(e: ir.TraceExpr, rng: random.Random) -> ir.TraceExpr:
    if isinstance(e, ir.TraceTag):
        return ir.TraceTag(rng.choice(["service.name", "env", "nope"]),
                           rng.choice(["a", "prod", "dev", ""]))
    if isinstance(e, ir.TraceDur):
        lo = rng.randint(0, 4000)
        return ir.TraceDur(lo, lo + rng.randint(0, 4000))
    if isinstance(e, ir.Exists):
        return ir.Exists(_reparam_span(e.of, rng))
    if isinstance(e, ir.Count):
        return ir.Count(_reparam_span(e.of, rng), e.op, rng.randint(0, 4))
    if isinstance(e, ir.Quantile):
        qn, qd = rng.choice([(1, 2), (9, 10), (99, 100), (1, 4)])
        return ir.Quantile(_reparam_span(e.of, rng), qn, qd, e.op,
                           rng.randint(0, 900))
    if isinstance(e, ir.TraceAnd):
        return ir.TraceAnd(tuple(_reparam(a, rng) for a in e.args))
    if isinstance(e, ir.TraceOr):
        return ir.TraceOr(tuple(_reparam(a, rng) for a in e.args))
    return ir.TraceNot(_reparam(e.arg, rng))


def _expected_ids(expr, entries) -> set:
    return {sd.trace_id for sd in entries if eval_host(expr, sd)}


def _scan_ids(batch, eng, mq, entries) -> tuple[int, set]:
    count, _ins, scores, idx = eng.scan(batch, mq)
    E = batch.blocks[0].geometry.entries_per_page
    got = set()
    for s, i in zip(scores.tolist(), idx.tolist()):
        if s < 0:
            break
        p, e = divmod(i, E)
        bi = int(batch.page_block[p])
        lp = p - batch.page_offset[bi]
        got.add(bytes(batch.blocks[bi].trace_ids[lp, e]))
    return int(count), got


def _mk_req(expr, limit: int = 4096) -> tempopb.SearchRequest:
    req = tempopb.SearchRequest()
    req.limit = limit
    structural.attach_query(req, expr)
    return req


# ---------------------------------------------------------------- IR


def test_ir_parse_roundtrip():
    src = ('{"and": [{"count": {"of": {"child": {"parent": {"tag": '
           '{"k": "service.name", "v": "api"}}, "child": {"dur": '
           '{"min_ms": 100}}}}, "op": ">", "n": 1}}, '
           '{"quantile": {"of": {"kind": "server"}, "q": "0.9", '
           '"op": ">=", "ms": 250}}]}')
    expr = ir.parse(src)
    again = ir.parse(ir.to_json(expr))
    assert again == expr
    # the quoted transport form round-trips too
    assert ir.parse_quoted(ir.quote(ir.to_json(expr))) == expr


@pytest.mark.parametrize("src,path_frag", [
    ("{", "$"),
    ('{"nope": 1}', "$"),
    ('{"and": []}', "$.and"),
    ('{"count": {"of": {"dur": {}}, "op": "~", "n": 1}}', "$.count.op"),
    ('{"exists": {"tag": {"k": "", "v": "x"}}}', "$.exists.tag.k"),
    ('{"quantile": {"of": {"dur": {}}, "q": "1.5", "ms": 1}}',
     "$.quantile.q"),
    ('{"exists": {"kind": "banana"}}', "$.exists.kind"),
    ('{"dur": {"min_ms": 10, "max_ms": 1}}', "$.dur"),
    ('{"and": [{"dur": {"bogus": 1}}]}', "$.and[0].dur"),
])
def test_ir_parse_errors_carry_json_path(src, path_frag):
    with pytest.raises(ir.IRSyntaxError) as e:
        ir.parse(src)
    assert path_frag in str(e.value)


def test_ir_quantile_q1_roundtrips():
    """q=1.0 must serialize to a re-parseable form ("1", never the
    float-format artifact "1.") — attach_query stows to_json output in
    the transport tag, so an unparseable form fails a VALID query."""
    for q in ("1.0", "1", "0.5", "0.999", "0.25"):
        src = ('{"quantile": {"of": {"dur": {"min_ms": 1}}, "q": "%s", '
               '"op": ">=", "ms": 10}}' % q)
        expr = ir.parse(src)
        again = ir.parse(ir.to_json(expr))
        assert (again.q_num * expr.q_den
                == expr.q_num * again.q_den), q  # same rational
        req = _mk_req(expr)
        assert structural_query(req) is not None


def test_ir_node_budget_enforced():
    deep = {"dur": {"min_ms": 1}}
    for _ in range(ir.MAX_NODES + 1):
        deep = {"not": deep}
    with pytest.raises(ir.IRSyntaxError) as e:
        ir.parse(json.dumps(deep))
    assert "limit" in str(e.value)


# ------------------------------------------------- wire + container


def test_search_data_span_codec_roundtrip_and_legacy_compat():
    sd = _corpus(3, n=5)[2]
    assert sd.spans  # seed chosen to carry spans
    sd2 = decode_search_data(encode_search_data(sd), sd.trace_id)
    assert [(s.parent, s.dur_ms, s.kind, s.kvs) for s in sd2.spans] == \
        [(s.parent, s.dur_ms, s.kind, s.kvs) for s in sd.spans]
    # legacy payload (no span section) decodes to spans == []
    legacy = SearchData(trace_id=sd.trace_id, start_s=1, end_s=2,
                        dur_ms=3, kvs={"a": {"b"}})
    dec = decode_search_data(encode_search_data(legacy), sd.trace_id)
    assert dec.spans == []
    # span-less encode is byte-identical to the legacy wire form
    assert encode_search_data(legacy) == encode_search_data(
        SearchData(trace_id=sd.trace_id, start_s=1, end_s=2, dur_ms=3,
                   kvs={"a": {"b"}}))


def test_columnar_span_segment_roundtrips():
    entries = _corpus(11, n=100)
    pages = ColumnarPages.build(entries, E_GEO)
    assert pages.has_spans
    # codec round-trip
    p2 = ColumnarPages.from_bytes(pages.to_bytes())
    for name, _ in ColumnarPages._SPAN_ARRAYS:
        assert np.array_equal(getattr(p2, name), getattr(pages, name)), name
    # to_entries (compaction) preserves span rows incl. parent links
    back = pages.to_entries()
    assert len(back) == len(entries)
    for orig, rt in zip(entries, back):
        assert [(s.parent, s.dur_ms, s.kind) for s in rt.spans] == \
            [(s.parent, s.dur_ms, s.kind) for s in orig.spans]
        for s_o, s_r in zip(orig.spans, rt.spans):
            assert s_r.kvs == s_o.kvs
    # gate-off build (no spans captured) stays byte-identical legacy
    legacy_entries = _corpus(11, n=100)
    for sd in legacy_entries:
        sd.spans = []
    legacy = ColumnarPages.build(legacy_entries, E_GEO)
    assert not legacy.has_spans
    assert b"span_trace" not in legacy.to_bytes()


def test_slice_pages_remaps_span_segment():
    entries = _corpus(13, n=200)
    pages = ColumnarPages.build(entries, E_GEO)
    E = E_GEO.entries_per_page
    sl = pages.slice_pages(1, 2)
    expr = ir.parse('{"count": {"of": {"tag": {"k": "name", "v": "op"}},'
                    ' "op": ">", "n": 2}}')
    eng = MultiBlockEngine(top_k=512)
    batch = eng.stage([sl])
    req = _mk_req(expr)
    mq = compile_multi([sl], req, cache_on=batch)
    mq.structural = compile_structural(expr, [sl], cache_on=batch)
    count, got = _scan_ids(batch, eng, mq, entries)
    want = _expected_ids(expr, entries[E:3 * E])
    assert got == want and count == len(want)


# the acceptance triple (ISSUE 14): a parent-child query, a descendant
# query, and a count(span) > N aggregate — asserted correct through
# EVERY engine path (batched/host in _check_paths; mesh, dist, single,
# and the serving path each run the triple below)
_ACCEPTANCE_TRIPLE = (
    '{"child": {"parent": {"tag": {"k": "service.name", "v": "api"}}, '
    '"child": {"dur": {"min_ms": 200}}}}',
    '{"desc": {"anc": {"tag": {"k": "service.name", "v": "db"}}, '
    '"span": {"kind": "client"}}}',
    '{"count": {"of": {"tag": {"k": "name", "v": "op"}}, "op": ">", '
    '"n": 3}}',
)


# ---------------------------------------------- engine-path identity


def _check_paths(entries, exprs, packed: bool, mesh=None, seed=0):
    """Compiled-vs-host identity over the batched device path AND the
    byte-identical host route, one staged batch, many queries."""
    packing_mod.PACKING.enabled = packed
    # two blocks with distinct dictionaries + one span-less block: the
    # assembly must handle group maps and absent segments
    half = len(entries) // 2
    b1 = ColumnarPages.build(entries[:half], E_GEO)
    b2 = ColumnarPages.build(entries[half:], E_GEO)
    spanless = [SearchData(trace_id=(10_000 + i).to_bytes(16, "big"),
                           start_s=1, end_s=2, dur_ms=100,
                           kvs={"env": {"prod"}}) for i in range(5)]
    b3 = ColumnarPages.build(spanless, E_GEO)
    blocks = [b1, b2, b3]
    eng = MultiBlockEngine(top_k=512, mesh=mesh)
    host = eng.stage_host(blocks)
    batch = eng.place(host)
    for expr in exprs:
        req = _mk_req(expr)
        mq = compile_multi(blocks, req, cache_on=batch)
        assert mq is not None
        mq.structural = compile_structural(
            expr, blocks, cache_on=batch, staged_dicts=batch.staged_dicts)
        want = _expected_ids(expr, entries + spanless)
        count, got = _scan_ids(batch, eng, mq, entries)
        assert got == want, (ir.to_json(expr), packed, "device")
        assert count == len(want)
        # breaker-style host route: host-only compile, CPU-pinned kernel
        mq_h = compile_multi(blocks, req, cache_on=batch, host_only=True)
        mq_h.structural = compile_structural(expr, blocks, host_only=True)
        hcount, _hi, hscores, hidx = host_scan(host, mq_h, 512)
        assert hcount == len(want), (ir.to_json(expr), packed, "host")
        E = E_GEO.entries_per_page
        hgot = set()
        for s, i in zip(hscores.tolist(), hidx.tolist()):
            if s < 0:
                break
            p, e = divmod(i, E)
            bi = int(host.page_block[p])
            lp = p - host.page_offset[bi]
            hgot.add(bytes(host.blocks[bi].trace_ids[lp, e]))
        assert hgot == want


def test_fixed_queries_all_paths_unpacked():
    entries = _corpus(21)
    exprs = [
        # the acceptance triple: parent-child, descendant, count
        ir.parse('{"child": {"parent": {"tag": {"k": "service.name", '
                 '"v": "api"}}, "child": {"dur": {"min_ms": 200}}}}'),
        ir.parse('{"desc": {"anc": {"tag": {"k": "service.name", '
                 '"v": "db"}}, "span": {"kind": "client"}}}'),
        ir.parse('{"count": {"of": {"tag": {"k": "name", "v": "op"}}, '
                 '"op": ">", "n": 3}}'),
        ir.parse('{"quantile": {"of": {"dur": {"min_ms": 1}}, '
                 '"q": "0.9", "op": ">=", "ms": 500}}'),
        ir.parse('{"and": [{"tag": {"k": "env", "v": "prod"}}, '
                 '{"not": {"exists": {"kind": 4}}}]}'),
    ]
    _check_paths(entries, exprs, packed=False)


def test_fixed_queries_all_paths_packed():
    entries = _corpus(22)
    exprs = [
        ir.parse('{"child": {"parent": {"tag": {"k": "service.name", '
                 '"v": "a"}}, "child": {"dur": {"min_ms": 100}}}}'),
        ir.parse('{"count": {"of": {"kind": "server"}, "op": ">=", '
                 '"n": 2}}'),
        ir.parse('{"and": [{"dur": {"min_ms": 1000}}, {"exists": '
                 '{"tag": {"k": "name", "v": "op1"}}}]}'),
    ]
    _check_paths(entries, exprs, packed=True)


@pytest.mark.parametrize("packed", [False, True])
def test_differential_fuzz_compiled_vs_host(packed):
    """The property: ANY random IR tree over ANY random corpus answers
    identically on the compiled device path, the host route, and the
    reference evaluator."""
    rng = random.Random(40_000 + packed)
    for round_i in range(6):
        entries = _corpus(500 + round_i, n=80)
        exprs = [_rand_trace(rng) for _ in range(5)]
        _check_paths(entries, exprs, packed=packed,
                     seed=round_i)


def _check_stacked(entries, template, rng, packed: bool, mesh=None,
                   n_variants: int = 5):
    """Plan-shape stacking differential: a random same-shape query
    group answers bit-for-bit identically fused (stack_queries + one
    launch), solo (scan_async) and on the host reference evaluator. Returns the group size actually stacked."""
    from tempo_tpu.search.engine import fetch_scan_out
    from tempo_tpu.search.multiblock import stack_queries

    packing_mod.PACKING.enabled = packed
    half = len(entries) // 2
    b1 = ColumnarPages.build(entries[:half], E_GEO)
    b2 = ColumnarPages.build(entries[half:], E_GEO)
    spanless = [SearchData(trace_id=(10_000 + i).to_bytes(16, "big"),
                           start_s=1, end_s=2, dur_ms=100,
                           kvs={"env": {"prod"}}) for i in range(5)]
    blocks = [b1, b2, ColumnarPages.build(spanless, E_GEO)]
    eng = MultiBlockEngine(top_k=512, mesh=mesh)
    batch = eng.stage(blocks)
    variants = [template] + [_reparam(template, rng)
                             for _ in range(n_variants - 1)]
    mqs = []
    for expr in variants:
        req = _mk_req(expr)
        mq = compile_multi(blocks, req, cache_on=batch)
        mq.structural = compile_structural(
            expr, blocks, cache_on=batch,
            staged_dicts=batch.staged_dicts)
        mq._expr = expr
        mqs.append(mq)
    # leaf dedup can shift a variant's plan (two leaves collapsing to
    # one term index): stack exactly the same-plan members — the same
    # grouping stack_group_key enforces in the coalescer
    base = mqs[0].structural.plan
    group = [mq for mq in mqs if mq.structural.plan == base]
    assert len(group) >= 2, "reparam produced no same-plan peer"
    cq = stack_queries(group)
    assert cq.structural is not None and cq.structural.plan == base
    counts, _ins, scores, idx = fetch_scan_out(
        eng.coalesced_scan_async(batch, cq, 512))
    all_entries = entries + spanless
    E = E_GEO.entries_per_page
    for qi, mq in enumerate(group):
        got = set()
        for s, i in zip(scores[qi].tolist(), idx[qi].tolist()):
            if s < 0:
                break
            p, e = divmod(i, E)
            if p >= batch.n_pages:
                continue
            bi = int(batch.page_block[p])
            if bi < 0:
                continue
            lp = p - batch.page_offset[bi]
            got.add(bytes(batch.blocks[bi].trace_ids[lp, e]))
        want = _expected_ids(mq._expr, all_entries)
        scount, sgot = _scan_ids(batch, eng, mq, all_entries)
        assert got == want == sgot, (ir.to_json(mq._expr), packed,
                                     len(got), len(want), len(sgot))
        assert int(counts[qi]) == len(want) == scount
    return len(group)


@pytest.mark.parametrize("packed", [False, True])
def test_differential_fuzz_stacked_plans(packed):
    """The stacking property: ANY random same-shape structural query
    group answers identically coalesced (one fused dispatch), solo, and
    on the reference evaluator — packed residency on and off."""
    rng = random.Random(60_000 + packed)
    for round_i in range(4):
        entries = _corpus(700 + round_i, n=80)
        template = _rand_trace(rng)
        _check_stacked(entries, template, rng, packed=packed)


def test_stacked_plans_on_mesh_with_sharded_spans():
    """Stacking composes with segment-aligned span sharding: the fused
    dispatch over sharded span columns answers identically to solo
    dispatches, the replicated layout, and the host evaluator."""
    import jax

    if len(jax.devices()) < 2:
        pytest.skip("needs multiple (forced host) devices")
    from tempo_tpu.parallel import make_mesh

    rng = random.Random(99)
    entries = _corpus(801, n=160)
    mesh = make_mesh()
    for template in [ir.parse(s) for s in _ACCEPTANCE_TRIPLE[:2]] \
            + [_rand_trace(rng)]:
        STRUCTURAL.shard_spans = True
        try:
            _check_stacked(entries, template, rng, packed=False,
                           mesh=mesh)
        finally:
            STRUCTURAL.shard_spans = False


def test_sharded_span_segment_layout_and_identity():
    """The reshard itself: trace-whole chunks, local coordinates, and
    byte-identical answers sharded vs replicated vs host (the mesh path
    runs it end to end when >1 device is available)."""
    import jax

    entries = _corpus(77, n=400)
    blocks = [ColumnarPages.build(entries, E_GEO)]
    eng = MultiBlockEngine(top_k=512)
    host = eng.stage_host(blocks)
    span_cat = host.span_cat
    assert span_cat is not None
    P_pages = int(host.page_block.shape[0])
    E = E_GEO.entries_per_page
    n_sh = 4
    STRUCTURAL.shard_spans = True
    try:
        sh = STRUCTURAL.shard_span_segment(span_cat, n_sh, P_pages, E)
    finally:
        STRUCTURAL.shard_spans = False
    assert sh is not None
    per_shard = sh["span_trace"].shape[0] // n_sh
    pp = P_pages // n_sh
    # every live span sits in the chunk of its trace's page shard, with
    # a local trace index and a parent inside the same chunk
    for s in range(n_sh):
        chunk = slice(s * per_shard, (s + 1) * per_shard)
        tr = sh["span_trace"][chunk]
        live = tr >= 0
        assert (tr[live] < pp * E).all()
        par = sh["span_parent"][chunk][live]
        assert ((par >= -1) & (par < per_shard)).all()
    # per-trace verdict identity vs the replicated layout: per-shard
    # span bytes shrink to ~1/P of the replicated staging
    rep_bytes = sum(int(v.nbytes) for k, v in span_cat.items()
                    if k.startswith("span_"))
    sh_bytes = sum(int(v.nbytes) for k, v in sh.items()
                   if k.startswith("span_")) // n_sh
    assert sh_bytes < rep_bytes
    # disabled gate: one attribute read, None (replicated layout kept)
    assert STRUCTURAL.shard_span_segment(span_cat, n_sh, P_pages, E) \
        is None


def test_serving_path_stacks_concurrent_same_plan_queries(tmp_path):
    """8 concurrent same-plan-shape structural searches through the
    FULL serving path fuse (dispatches/request well below 1 for the
    structural leg), byte-identical to the same queries run serially,
    and the stack metric + /debug ratio say so."""
    import threading

    from tempo_tpu.observability import metrics as obs

    entries = _corpus(91, n=120)
    db = _mkdb(tmp_path, entries,
               search_structural_stack_enabled=True,
               search_coalesce_window_s=0.05)
    svcs = ["api", "db", "auth", "cache", "web", "api", "db", "auth"]
    exprs = [ir.parse(
        '{"child": {"parent": {"tag": {"k": "service.name", "v": "%s"}},'
        ' "child": {"dur": {"min_ms": %d}}}}' % (svc, 50 + 50 * i))
        for i, svc in enumerate(svcs)]
    def canon(resp):
        # device_seconds is a wall-clock measurement — legitimately
        # different run to run; everything else must be byte-identical
        resp.metrics.device_seconds = 0
        return resp.SerializeToString()

    serial = []
    for e in exprs:
        r = _mk_req(e, limit=1000)
        serial.append(canon(db.search("t", r).response()))
    co = db.batcher.coalescer
    base_stacked = co.structural_stacked
    stacked0 = obs.structural_stack_events.value(result="stacked")
    out = [None] * len(exprs)
    barrier = threading.Barrier(len(exprs))

    def one(i):
        r = _mk_req(exprs[i], limit=1000)
        barrier.wait()
        out[i] = canon(db.search("t", r).response())

    threads = [threading.Thread(target=one, args=(i,))
               for i in range(len(exprs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for i in range(len(exprs)):
        assert out[i] == serial[i], f"query {i} diverged under stacking"
    assert co.structural_stacked > base_stacked, "no structural fusion"
    assert obs.structural_stack_events.value(result="stacked") > stacked0
    stats = co.stats()
    assert stats["structural_stack_ratio"] > 0
    # /debug/scan surfaces the same coalesce block
    dbg = db.batcher.debug_stats()
    assert dbg["coalesce"]["structural_stacked"] == co.structural_stacked


def test_stacking_disabled_keeps_solo_flush_and_counts_it(tmp_path):
    """The noop contract of the stacking gate: disabled keeps the exact
    solo-flush behavior and books result=solo_disabled."""
    from tempo_tpu.observability import metrics as obs

    entries = _corpus(92, n=60)
    db = _mkdb(tmp_path, entries)  # stack gate OFF
    assert STRUCTURAL.stack_enabled is False
    solo0 = obs.structural_stack_events.value(result="solo_disabled")
    expr = ir.parse(_ACCEPTANCE_TRIPLE[2])
    req = _mk_req(expr, limit=1000)
    got = {bytes.fromhex(m.trace_id)
           for m in db.search("t", req).response().traces}
    assert got == _expected_ids(expr, entries)
    assert obs.structural_stack_events.value(result="solo_disabled") \
        > solo0
    co = db.batcher.coalescer
    assert co.structural_stacked == 0


def test_mesh_dist_path_matches_host():
    import jax

    if len(jax.devices()) < 2:
        pytest.skip("needs multiple (forced host) devices")
    from tempo_tpu.parallel import make_mesh

    entries = _corpus(31)
    rng = random.Random(77)
    exprs = [ir.parse(s) for s in _ACCEPTANCE_TRIPLE] + [_rand_trace(rng)]
    _check_paths(entries, exprs, packed=False, mesh=make_mesh())


def test_one_block_batch_mesh_path():
    """One block as a one-block batch over the mesh: its pages shard,
    span columns replicate and the structural verdict enters the
    sharded scan page-sharded."""
    import jax

    if len(jax.devices()) < 2:
        pytest.skip("needs multiple (forced host) devices")
    from tempo_tpu.parallel import make_mesh

    entries = _corpus(45, n=100)
    pages = ColumnarPages.build(entries, E_GEO)
    staged = {}
    for src in (_ACCEPTANCE_TRIPLE):
        expr = ir.parse(src)
        got = scan_batch([pages], _mk_req(expr), top_k=512,
                         mesh=make_mesh(), structural=expr, **staged)
        staged = {"engine": got.engine, "batch": got.batch}
        assert got.batch.span_device is not None
        assert not got.batch.span_sharded
        want = _expected_ids(expr, entries)
        assert got.trace_ids == want and got.count == len(want), src


def test_one_block_batch_mesh_sharded_spans():
    """The mesh path with search_structural_shard_spans: span columns
    stage chunk-per-shard (span_sharded=True) and the acceptance triple
    answers byte-identically to the host reference."""
    import jax

    if len(jax.devices()) < 2:
        pytest.skip("needs multiple (forced host) devices")
    from tempo_tpu.parallel import make_mesh

    entries = _corpus(46, n=600)
    pages = ColumnarPages.build(entries, E_GEO)
    STRUCTURAL.shard_spans = True
    try:
        staged = {}
        for src in _ACCEPTANCE_TRIPLE:
            expr = ir.parse(src)
            got = scan_batch([pages], _mk_req(expr), top_k=1024,
                             mesh=make_mesh(), structural=expr, **staged)
            staged = {"engine": got.engine, "batch": got.batch}
            assert (got.batch.span_device is not None
                    and got.batch.span_sharded)
            want = _expected_ids(expr, entries)
            assert got.trace_ids == want and got.count == len(want), src
    finally:
        STRUCTURAL.shard_spans = False


def test_one_block_batch_path():
    entries = _corpus(41, n=90)
    pages = ColumnarPages.build(entries, E_GEO)
    staged = {}
    for src in _ACCEPTANCE_TRIPLE + (
            '{"count": {"of": {"child": {"parent": {"kind": "server"}, '
            '"child": {"dur": {"min_ms": 50}}}}, "op": ">=", "n": 1}}',):
        expr = ir.parse(src)
        req = _mk_req(expr)
        got = scan_batch([pages], req, top_k=512, structural=expr,
                         **staged)
        staged = {"engine": got.engine, "batch": got.batch}
        assert got.batch.span_device is not None
        want = _expected_ids(expr, entries)
        assert got.trace_ids == want and got.count == len(want), src

        # the host route (breaker fallback): byte-identical
        host = scan_batch([pages], req, top_k=512, structural=expr,
                          host_only=True)
        assert host.count == len(want), src
        assert host.trace_ids == want, src


# ---------------------------------------------- serving path (TempoDB)


def _mkdb(tmp_path, entries, **cfg_kw) -> TempoDB:
    cfg_kw.setdefault("auto_mesh", False)
    cfg_kw.setdefault("search_structural_enabled", True)
    be = LocalBackend(str(tmp_path / "blocks"))
    db = TempoDB(be, str(tmp_path / "wal"), TempoDBConfig(**cfg_kw))
    half = len(entries) // 2
    for chunk in (entries[:half], entries[half:]):
        db.write_block_direct(
            "t", [(sd.trace_id, encode_search_data(sd), sd.start_s,
                   sd.end_s) for sd in chunk],
            search_entries=chunk)
    return db


def test_tempodb_serving_path_with_coalescer_and_breaker_route(tmp_path):
    entries = _corpus(51, n=120)
    db = _mkdb(tmp_path, entries)
    expr = ir.parse('{"and": [{"child": {"parent": {"tag": {"k": '
                    '"service.name", "v": "a"}}, "child": {"dur": '
                    '{"min_ms": 100}}}}, {"tag": {"k": "env", '
                    '"v": ""}}]}')
    req = _mk_req(expr, limit=1000)
    req.explain = True
    want = _expected_ids(expr, entries)
    res = db.search("t", req)
    got = {bytes.fromhex(m.trace_id) for m in res.results()} \
        if hasattr(res, "results") else \
        {bytes.fromhex(m.trace_id) for m in res.response().traces}
    assert got == want
    # explain carries the compiled plan tree with per-node timings
    stats = json.loads(res.response().metrics.query_stats_json)
    ops = [n["op"] for n in stats["structural"]["nodes"]]
    assert "child" in ops and all("device_ms" in n
                                  for n in stats["structural"]["nodes"])
    # the acceptance triple through the serving (coalescer-enabled)
    # path too
    for src in _ACCEPTANCE_TRIPLE:
        e2 = ir.parse(src)
        r2 = _mk_req(e2, limit=1000)
        got2 = {bytes.fromhex(m.trace_id)
                for m in db.search("t", r2).response().traces}
        assert got2 == _expected_ids(e2, entries), src
    # breaker open: the whole serving path answers through the
    # byte-identical host route
    robustness.BREAKER.reset()
    robustness.BREAKER.threshold = 1
    robustness.BREAKER.record_fault("timeout", mode="batched")
    assert robustness.BREAKER.state == "open"
    req2 = _mk_req(expr, limit=1000)
    res2 = db.search("t", req2)
    got2 = {bytes.fromhex(m.trace_id) for m in res2.response().traces}
    assert got2 == want
    robustness.BREAKER.reset()


def test_live_and_fallback_paths_share_reference_semantics():
    """search_data_matches (live/WAL scans) and model.matches (proto
    fallback) both evaluate the host reference semantics."""
    entries = _corpus(61, n=20)
    expr = ir.parse('{"exists": {"tag": {"k": "name", "v": "op2"}}}')
    req = _mk_req(expr)
    for sd in entries:
        assert search_data_matches(sd, req) == eval_host(expr, sd)


# ------------------------------------------------------ HTTP surface


def test_http_api_structural_queries(tmp_path):
    from tempo_tpu.api.http import HTTPApi
    from tempo_tpu.modules import App, AppConfig
    from tempo_tpu.utils.test_data import make_trace

    app = App(AppConfig(
        wal_dir=str(tmp_path / "wal"),
        db=TempoDBConfig(search_structural_enabled=True,
                         auto_mesh=False)))
    api = HTTPApi(app)
    hdr = {"X-Scope-OrgID": "t1"}

    # parent-linked trace: root server span + slow child under it
    tid = b"\x01" * 16
    tr = tempopb.Trace()
    rs = tr.batches.add()
    kv = rs.resource.attributes.add()
    kv.key = "service.name"
    kv.value.string_value = "api"
    ss = rs.scope_spans.add()
    root = ss.spans.add()
    root.trace_id = tid
    root.span_id = b"\x0a" * 8
    root.name = "root-op"
    root.kind = 2
    root.start_time_unix_nano = 1_600_000_000_000_000_000
    root.end_time_unix_nano = root.start_time_unix_nano + 500_000_000
    child = ss.spans.add()
    child.trace_id = tid
    child.span_id = b"\x0b" * 8
    child.parent_span_id = root.span_id
    child.name = "child-op"
    child.kind = 3
    child.start_time_unix_nano = root.start_time_unix_nano
    child.end_time_unix_nano = child.start_time_unix_nano + 400_000_000
    app.push("t1", [rs])
    # a second, non-matching trace
    tid2 = b"\x02" * 16
    app.push("t1", list(make_trace(tid2, seed=5).batches))

    q = ('{"child": {"parent": {"tag": {"k": "service.name", '
         '"v": "api"}}, "child": {"dur": {"min_ms": 300}}}}')
    # live (recent) path
    code, body = api.handle("GET", "/api/search",
                            {"q": q, "limit": "10"}, hdr)
    assert code == 200, body
    assert [t["traceId"] for t in body.get("traces", [])] == [tid.hex()]
    # flushed backend path
    api.handle("GET", "/flush", {}, hdr)
    app.reader_db.poll()
    code, body = api.handle("GET", "/api/search",
                            {"q": q, "limit": "10", "explain": "1"}, hdr)
    assert code == 200, body
    assert [t["traceId"] for t in body.get("traces", [])] == [tid.hex()]
    assert "structural" in body.get("queryStats", {})

    # malformed IR: 400 with the JSON-path diagnostic, never a 500
    code, body = api.handle("GET", "/api/search",
                            {"q": '{"count": {"of": {"dur": {}}, '
                                  '"op": "~", "n": 1}}'}, hdr)
    assert code == 400 and "$.count.op" in body["error"]
    code, body = api.handle("GET", "/api/search", {"q": "{bogus"}, hdr)
    assert code == 400 and "structural" in body["error"]
    app.shutdown()


def test_http_gate_off_rejects_structural(tmp_path):
    from tempo_tpu.api.http import HTTPApi
    from tempo_tpu.modules import App, AppConfig

    app = App(AppConfig(wal_dir=str(tmp_path / "wal"),
                        db=TempoDBConfig(auto_mesh=False)))
    assert STRUCTURAL.enabled is False  # App configured the gate OFF
    api = HTTPApi(app)
    code, body = api.handle(
        "GET", "/api/search",
        {"q": '{"dur": {"min_ms": 1}}'}, {"X-Scope-OrgID": "t1"})
    assert code == 400 and "disabled" in body["error"]
    app.shutdown()


# ------------------------------------------------------ noop contract


def test_gate_off_is_true_noop(tmp_path):
    STRUCTURAL.enabled = False
    # extraction captures nothing; containers match the legacy bytes
    entries = _corpus(71, n=30)
    for sd in entries:
        sd.spans = []
    legacy = ColumnarPages.build(entries, E_GEO)
    assert not legacy.has_spans
    # stack_host stages no span columns when the gate is off
    eng = MultiBlockEngine(top_k=64)
    pages = ColumnarPages.build(_corpus(71, n=30), E_GEO)  # HAS spans
    host = eng.stage_host([pages])
    assert host.span_cat is None
    # the gated entry point reads one attribute and answers None for
    # legacy requests...
    req = tempopb.SearchRequest()
    req.tags["service.name"] = "api"
    assert structural_query(req) is None
    # ...and REFUSES a structural request against the disabled gate at
    # this shared altitude (gRPC and protocol paths included) — never a
    # silent legacy-scan superset
    from tempo_tpu.api.params import InvalidArgument

    req2 = tempopb.SearchRequest()
    req2.tags[STRUCTURAL_QUERY_TAG] = "ignored"
    with pytest.raises(InvalidArgument, match="disabled"):
        structural_query(req2)


def test_structural_query_parse_cache_and_invalid_tag():
    from tempo_tpu.api.params import InvalidArgument

    expr = ir.parse('{"dur": {"min_ms": 5}}')
    req = _mk_req(expr)
    assert structural_query(req) == expr
    assert structural_query(req) is structural_query(req)  # cached
    bad = tempopb.SearchRequest()
    bad.tags[STRUCTURAL_QUERY_TAG] = "%7Bnot-json"
    with pytest.raises(InvalidArgument):
        structural_query(bad)


def test_request_roundtrip_via_params():
    """The reserved tag survives the frontend <-> querier URL form."""
    from urllib.parse import parse_qs

    from tempo_tpu.api.params import (build_search_request,
                                      parse_search_request)

    expr = ir.parse('{"exists": {"tag": {"k": "service.name", '
                    '"v": "a b=c"}}}')
    req = _mk_req(expr, limit=7)
    qs = build_search_request(req)
    back = parse_search_request(
        {k: v[0] for k, v in parse_qs(qs).items()})
    assert structural_query(back) == expr
    assert back.limit == 7
