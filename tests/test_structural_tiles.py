"""The layout of the span axis. By the tile (ISSUE 45): staging starts
every block's spans on a multiple of `structural.SPAN_TILE`, so every
aligned tile holds live rows of one block, and a tag leaf over spans
looks its block's tables up once a tile. Inside a trace (ISSUE 46):
staging lays a trace's spans out depth first, whatever order the block
stores, and keeps each span's last descendant (`span_last`), so a
`desc` join is one running max. Pinned here: both halves of the layout
(replicated and sharded), the answers where a wrong tile's table or a
wrong subtree would give a wrong one, the rule for a loop of parents,
and that neither a lookup a row nor a loop over the span axis comes
back (the jaxpr)."""

from __future__ import annotations

import collections
import random

import jax
import numpy as np
import pytest

from tempo_tpu.observability import metrics as obs
from tempo_tpu.search import ir, structural
from tempo_tpu.search.columnar import ColumnarPages
from tempo_tpu.search.data import SearchData, SpanData
from tempo_tpu.search.engine import fetch_scan_out
from tempo_tpu.search.multiblock import (
    MultiBlockEngine,
    compile_multi,
    stack_host,
    stack_queries,
)
from tempo_tpu.search.structural import (
    SPAN_TILE,
    STRUCTURAL,
    BucketedStructural,
    compile_structural,
)

from test_structural import (  # noqa: F401 — _structural_on is autouse
    E_GEO,
    _expected_ids,
    _mk_req,
    _scan_ids,
    _structural_on,
)

E = E_GEO.entries_per_page


def _trace(i: int, spans: list, **kvs) -> SearchData:
    """Trace `i` with `spans` = [(parent, {key: value}), ...]."""
    sd = SearchData(trace_id=i.to_bytes(16, "big"))
    sd.start_s = 1_600_000_000 + i
    sd.end_s = sd.start_s + 1
    sd.dur_ms = 10 + i % 900
    sd.kvs = {k: {v} for k, v in (kvs or {"env": "prod"}).items()}
    sd.spans = [SpanData(parent=p, dur_ms=1 + (i * 7 + s) % 400,
                         kind=(i + s) % 6,
                         kvs={k: {v} for k, v in kv.items()})
                for s, (p, kv) in enumerate(spans)]
    return sd


def _block(first: int, n_traces: int, spans_each: int,
           services=("api", "db", "cache")) -> tuple:
    """(pages, entries): `n_traces` chains of `spans_each` spans."""
    entries = [
        _trace(first + i,
               [(s - 1, {"service.name": services[(i + s) % len(services)],
                         "name": f"op{(i + s) % 3}"})
                for s in range(spans_each)])
        for i in range(n_traces)]
    return ColumnarPages.build(entries, E_GEO), entries


def _layout_case(case: str) -> list:
    if case == "empty-block-between":
        return [_block(0, 100, 7)[0], _block(1000, 70, 0)[0],
                _block(2000, 90, 9)[0]]
    if case == "exact-multiple-of-the-tile":
        # 128 traces x 8 spans = 2 tiles to the row, then a neighbour
        # whose first row must land on the very next tile
        return [_block(0, 128, 8)[0], _block(1000, 30, 3)[0]]
    if case == "one-block":
        return [_block(0, 150, 5)[0]]
    if case == "group-under-one-tile":
        return [_block(0, 20, 3)[0]]
    if case == "four-small-blocks":
        return [_block(1000 * b, 60 + 10 * b, 2 + b)[0] for b in range(4)]
    raise AssertionError(case)


_LAYOUT_CASES = ["empty-block-between", "exact-multiple-of-the-tile",
                 "one-block", "group-under-one-tile", "four-small-blocks"]


def _live_rows(blocks: list, page_offset) -> list:
    """What staging must keep of the blocks' spans, in any layout: a
    sorted list of (global entry, position in the trace, position of
    the parent in the trace, dur, kind, kv ids)."""
    rows = []
    for bi, b in enumerate(blocks):
        if not b.has_spans:
            continue
        begin = b.entry_span_begin.reshape(-1)
        for r in range(b.n_spans):
            t = int(b.span_trace[r])
            par = int(b.span_parent[r])
            rows.append((t + int(page_offset[bi]) * E, r - int(begin[t]),
                         -1 if par < 0 else par - int(begin[t]),
                         int(b.span_dur[r]), int(b.span_kind[r]),
                         tuple(b.span_kv_key[r]), tuple(b.span_kv_val[r])))
    return sorted(rows)


@pytest.mark.parametrize("n_shards", [1, 2, 4])
@pytest.mark.parametrize("case", _LAYOUT_CASES)
def test_every_aligned_tile_holds_one_blocks_spans(case, n_shards):
    """Over stack_spans' product (n_shards 1) and shard_span_segment's:
    every aligned tile's live rows belong to `span_tile_block`'s block,
    the axis (a shard's chunk) is a power of two of at least one tile,
    and nothing of the blocks' spans is lost or moved between traces."""
    blocks = _layout_case(case)
    # as a mesh engine of `n_shards` stages: the page axis a power of
    # two of at least a page a shard (three shards may then be empty)
    pad_pages = structural._pow2(max(n_shards,
                                     sum(b.n_pages for b in blocks)))
    host = stack_host(blocks, pad_to=pad_pages)
    cols = host.span_cat
    assert int(host.page_block.shape[0]) == pad_pages
    shard_entries = pad_pages * E
    if n_shards > 1:
        STRUCTURAL.shard_spans = True
        cols = STRUCTURAL.shard_span_segment(cols, n_shards, pad_pages, E)
        shard_entries = pad_pages // n_shards * E
    S = int(cols["span_trace"].shape[0])
    per_shard = S // n_shards
    assert per_shard >= SPAN_TILE and per_shard & (per_shard - 1) == 0
    assert "span_block" not in cols
    tiles = cols["span_tile_block"]
    assert tiles.shape == (S // SPAN_TILE,) and tiles.dtype == np.int32

    trace = cols["span_trace"]
    live = trace >= 0
    shard = np.arange(S) // per_shard
    gtrace = trace + shard * shard_entries       # global flat entry
    block_of_row = host.page_block[gtrace[live] // E]
    assert np.array_equal(block_of_row,
                          np.repeat(tiles, SPAN_TILE)[live])
    # the rows themselves, by trace: begin/count find them, parents stay
    # inside the trace (and the shard's chunk)
    begin = cols["entry_span_begin"].reshape(-1)
    count = cols["entry_span_count"].reshape(-1)
    got = []
    for r in np.flatnonzero(live):
        g = int(gtrace[r])
        first = int(begin[g]) + (g // shard_entries) * per_shard
        assert first <= r < first + int(count[g])
        par = int(cols["span_parent"][r])
        if par >= 0:
            par += int(shard[r]) * per_shard
            assert first <= par < first + int(count[g])
        got.append((g, int(r) - first, -1 if par < 0 else par - first,
                    int(cols["span_dur"][r]), int(cols["span_kind"][r]),
                    tuple(cols["span_kv_key"][r]),
                    tuple(cols["span_kv_val"][r])))
    assert sorted(got) == _live_rows(blocks, host.page_offset)
    # what the alignment costs: under a tile a block that has spans
    with_spans = sum(1 for b in blocks if b.has_spans)
    used = sum(-(-b.n_spans // SPAN_TILE) * SPAN_TILE
               for b in blocks if b.has_spans)
    if n_shards == 1:
        assert S == structural._pow2(max(SPAN_TILE, used))
        assert used - int(live.sum()) < with_spans * SPAN_TILE


def test_span_tile_is_one_constant_of_the_right_form():
    assert SPAN_TILE % 128 == 0 and SPAN_TILE & (SPAN_TILE - 1) == 0
    assert 128 <= SPAN_TILE <= 1024


# ------------------------------------------------------------ answers


def _three_dictionaries() -> tuple:
    """Three blocks whose dictionaries disagree: the string `api` has a
    different value id in each, the key `name` a different key id, and
    the third block has no `name` at all; a span-less block between."""
    a, ea = _block(0, 90, 6, services=("api", "db", "cache"))
    eb = [_trace(1000 + i,
                 [(s - 1, {"service.name":
                           ("aaa-first", "api", "zeta", "db")[(i + s) % 4],
                           "name": ("a-op", "op1", "op0")[(i + s) % 3],
                           "a.early.key": f"v{i % 5}"})
                  for s in range(1 + i % 9)], env="dev", aa="00")
          for i in range(80)]
    ec = [_trace(2000 + i,
                 [(max(-1, s - 2), {"service.name":
                                    ("web", "api", "alpha")[(i + s) % 3]})
                  for s in range(2 + i % 4)], zone="z")
          for i in range(70)]
    spanless = [_trace(3000 + i, []) for i in range(5)]
    blocks = [a, ColumnarPages.build(eb, E_GEO),
              ColumnarPages.build(spanless, E_GEO),
              ColumnarPages.build(ec, E_GEO)]
    ids = [b.val_dict.index("api") for b in (blocks[0], blocks[1], blocks[3])]
    assert len(set(ids)) == 3, ids
    assert "name" not in blocks[3].key_dict
    assert (blocks[0].key_dict.index("name")
            != blocks[1].key_dict.index("name"))
    return blocks, ea + eb + spanless + ec


@pytest.fixture
def probe_masks(monkeypatch):
    """The device probe hands a hit mask over for any needle, where it
    would hand ranges over up to dict_probe.R_MAX runs of hits."""
    from tempo_tpu.search import dict_probe

    monkeypatch.setattr(dict_probe, "R_MAX", 0)


def _stage(blocks, mask: bool):
    """`mask`: the dictionaries go to the device and (under the
    `probe_masks` fixture) the leaves' membership is a hit mask."""
    eng = MultiBlockEngine(top_k=512,
                           device_probe_min_vals=1 if mask else 0)
    host = eng.stage_host(blocks)
    return eng, host, eng.place(host)


_NEEDLES = [("service.name", "api", 1), ("service.name", "a", 2),
            ("name", "op", 1), ("name", "", 1)]


@pytest.mark.parametrize("mask", [False, True], ids=["ranges", "hit-mask"])
@pytest.mark.parametrize("key,value,min_r", _NEEDLES)
def test_tag_leaf_rows_equal_a_numpy_reference_a_row(key, value, min_r,
                                                     mask, probe_masks):
    """`_tile_leaf`'s [S] verdicts against a reference that asks, ROW BY
    ROW, which block the row's trace lies in and reads that block's
    table row: a tile that read its neighbour's table differs here."""
    blocks, _entries = _three_dictionaries()
    eng, host, batch = _stage(blocks, mask)
    expr = ir.Exists(ir.SpanTag(key, value))
    st = compile_structural(expr, blocks, cache_on=batch,
                            staged_dicts=batch.staged_dicts)
    assert (st.val_hits is not None) == mask
    ref = compile_structural(expr, blocks, host_only=True)
    assert ref.val_hits is None and ref.val_ranges.shape[2] >= min_r
    cols = host.span_cat
    live = cols["span_trace"] >= 0
    want = np.zeros(live.shape, dtype=bool)
    for r in np.flatnonzero(live):
        b = int(host.page_block[int(cols["span_trace"][r]) // E])
        k = int(ref.term_keys[b, 0])
        for c in range(cols["span_kv_key"].shape[1]):
            v = int(cols["span_kv_val"][r, c])
            if int(cols["span_kv_key"][r, c]) == k and any(
                    lo <= v <= hi for lo, hi in ref.val_ranges[b, 0]):
                want[r] = True
    assert want.any() and not want[live].all()

    tables = st.device_tables()
    sctx = structural._span_ctx(batch.span_device, tables[2], tables[3])
    got = np.asarray(structural._tile_leaf(sctx, tables, 0))
    assert np.array_equal(got, want)


_ANSWER_QUERIES = [
    '{"exists": {"tag": {"k": "service.name", "v": "api"}}}',
    '{"exists": {"tag": {"k": "service.name", "v": "a"}}}',
    '{"count": {"of": {"tag": {"k": "name", "v": "op"}}, "op": ">", '
    '"n": 2}}',
    '{"exists": {"child": {"parent": {"tag": {"k": "service.name", '
    '"v": "api"}}, "child": {"tag": {"k": "name", "v": "op1"}}}}}',
    '{"exists": {"desc": {"anc": {"tag": {"k": "service.name", "v": "a"}},'
    ' "span": {"tag": {"k": "service.name", "v": "db"}}}}}',
    '{"quantile": {"of": {"tag": {"k": "service.name", "v": "api"}}, '
    '"q": "0.5", "op": ">=", "ms": 100}}',
]
# same SHAPE as the first two, other needles: their plans are equal, so
# they stack along the query axis as they are
_STACK_PEERS = [
    '{"exists": {"tag": {"k": "service.name", "v": "db"}}}',
    '{"exists": {"tag": {"k": "name", "v": "op"}}}',
    '{"exists": {"tag": {"k": "name", "v": "a-"}}}',
]
# three plans of one bucket (test_bucket_stacking's triple), every one
# with a tag leaf over spans
_BUCKET_TRIPLE = [
    '{"exists": {"child": {"parent": {"tag": {"k": "service.name", '
    '"v": "api"}}, "child": {"dur": {"min_ms": 50}}}}}',
    '{"exists": {"child": {"parent": {"tag": {"k": "service.name", '
    '"v": "a"}}, "child": {"kind": "server"}}}}',
    '{"exists": {"child": {"parent": {"dur": {"min_ms": 10}}, '
    '"child": {"tag": {"k": "name", "v": "op"}}}}}',
]


def _compiled(blocks, batch, src: str):
    expr = ir.parse(src)
    mq = compile_multi(blocks, _mk_req(expr), cache_on=batch)
    mq.structural = compile_structural(
        expr, blocks, cache_on=batch, staged_dicts=batch.staged_dicts)
    mq._expr = expr
    return mq


def _fused_ids(eng, batch, group: list) -> tuple:
    cq = stack_queries(group)
    counts, _ins, scores, idx = fetch_scan_out(
        eng.coalesced_scan_async(batch, cq, 512))
    out = []
    for qi in range(len(group)):
        got = set()
        for s, i in zip(scores[qi].tolist(), idx[qi].tolist()):
            if s < 0:
                break
            p, e = divmod(i, E)
            bi = int(batch.page_block[p])
            got.add(bytes(batch.blocks[bi].trace_ids[
                p - batch.page_offset[bi], e]))
        out.append((int(counts[qi]), got))
    return cq, out


@pytest.mark.parametrize("mask", [False, True], ids=["ranges", "hit-mask"])
@pytest.mark.parametrize("path", ["plan-solo", "plan-stacked",
                                  "bucket-program"])
def test_tag_leaf_answers_where_the_blocks_dictionaries_disagree(
        path, mask, probe_masks):
    """Verdicts equal `eval_host` on a batch where one string has three
    ids and one block lacks the key: a static plan alone, stacked along
    the query axis ([Q, B, T] tables), and as a bucket program's slots;
    R = 1 and R > 1 in every one; ranges and the hit mask."""
    blocks, entries = _three_dictionaries()
    eng, _host, batch = _stage(blocks, mask)
    if path == "plan-solo":
        for src in _ANSWER_QUERIES:
            mq = _compiled(blocks, batch, src)
            assert (mq.structural.val_hits is not None) == mask
            want = _expected_ids(mq._expr, entries)
            assert want and len(want) < len(entries), src
            assert _scan_ids(batch, eng, mq, entries) == (len(want), want)
        return
    srcs = (_ANSWER_QUERIES[:2] + _STACK_PEERS if path == "plan-stacked"
            else _BUCKET_TRIPLE)
    group = [_compiled(blocks, batch, src) for src in srcs]
    if path == "plan-stacked":
        assert len({mq.structural.plan for mq in group}) == 1
        assert mask or {mq.structural.val_ranges.shape[2]
                        for mq in group} == {1, 2}
    cq, fused = _fused_ids(eng, batch, group)
    assert isinstance(cq.structural, BucketedStructural) \
        == (path == "bucket-program")
    for mq, (count, got) in zip(group, fused):
        want = _expected_ids(mq._expr, entries)
        assert want, ir.to_json(mq._expr)
        assert (count, got) == (len(want), want), ir.to_json(mq._expr)


# ------------------------------------------------- no lookup by the row


# the five templates of chipbench/traffic/structural.json, by shape
_TEMPLATES = {
    "errors-below": '{"exists": {"desc": {"anc": {"tag": {"k": '
                    '"service.name", "v": "api"}}, "span": {"tag": {"k": '
                    '"name", "v": "op1"}}}}}',
    "direct-call": '{"exists": {"child": {"parent": {"tag": {"k": '
                   '"service.name", "v": "api"}}, "child": {"tag": {"k": '
                   '"service.name", "v": "db"}}}}}',
    "store-fanout": '{"count": {"of": {"and": [{"tag": {"k": "name", '
                    '"v": "op0"}}, {"kind": "client"}]}, "op": ">", '
                    '"n": 1}}',
    "slow-p90": '{"and": [{"quantile": {"of": {"tag": {"k": '
                '"service.name", "v": "api"}}, "q": "0.9", "op": ">=", '
                '"ms": 100}}, {"tag": {"k": "env", "v": "prod"}}]}',
    "client-no-error-parent": '{"and": [{"exists": {"and": [{"kind": '
                              '"client"}, {"dur": {"min_ms": 50}}, '
                              '{"not": {"tag": {"k": "name", "v": '
                              '"op2"}}}]}}, {"dur": {"min_ms": 20}}]}',
}


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for sub in (v if isinstance(v, (list, tuple)) else [v]):
            sub = getattr(sub, "jaxpr", sub)
            if hasattr(sub, "eqns"):
                yield sub


def _span_axis_gathers(jaxpr, S: int) -> int:
    """The gathers of `jaxpr` (and of every jaxpr nested in it) whose
    indices have `S` rows."""
    return sum(
        (eqn.primitive.name == "gather" and S in eqn.invars[1].aval.shape)
        + sum(_span_axis_gathers(sub, S) for sub in _sub_jaxprs(eqn))
        for eqn in jaxpr.eqns)


def _primitives(jaxpr) -> collections.Counter:
    """How often each primitive stands in `jaxpr` and the jaxprs nested
    in it."""
    n = collections.Counter(eqn.primitive.name for eqn in jaxpr.eqns)
    for eqn in jaxpr.eqns:
        for sub in _sub_jaxprs(eqn):
            n += _primitives(sub)
    return n


_NESTED_DESC = ('{"exists": {"desc": {"anc": {"tag": {"k": "service.name", '
                '"v": "api"}}, "span": {"desc": {"anc": {"kind": "server"}, '
                '"span": {"tag": {"k": "name", "v": "op1"}}}}}}}')


@pytest.mark.parametrize("name,gathers,scans", [
    ("errors-below", 0, 1),     # _descends: one running max, no lookup
    ("nested-desc", 0, 2),
    ("direct-call", 1, 0),      # the parent column, once
    ("store-fanout", 0, 0),
    ("slow-p90", 0, 0),
    ("client-no-error-parent", 0, 0),
])
def test_no_launch_gathers_by_the_span_row_or_loops(name, gathers, scans):
    """The guard that keeps a lookup a row and the doubling loop from
    coming back: in the jaxpr of each template's launch the only gather
    whose indices are as long as the span axis is `child`'s through the
    parent column; a `desc` is one `cummax` a node and the program
    holds no `while` and no `scan`; the leaves' lookups have one index
    a tile."""
    blocks, _entries = _three_dictionaries()
    eng, _host, batch = _stage(blocks, mask=False)
    mq = _compiled(blocks, batch, _TEMPLATES.get(name, _NESTED_DESC))
    st = mq.structural
    d = batch.device
    S = int(batch.span_device["span_trace"].shape[0])
    n_entries = int(np.prod(d["entry_valid"].shape))
    # no other axis of the launch may be as long as the span axis
    assert S not in (n_entries, S // SPAN_TILE) and S > SPAN_TILE
    assert S not in d["kv_key"].shape

    def verdicts(span_cols, tables):
        return structural.structural_entry_mask(
            d["kv_key"], d["kv_val"], d["entry_dur"], d["entry_valid"],
            d["page_block"], d.get("entry_dur_res"), span_cols, tables,
            plan=st.plan, widths=batch.widths)

    jaxpr = jax.make_jaxpr(verdicts)(batch.span_device,
                                     st.device_tables()).jaxpr
    assert _span_axis_gathers(jaxpr, S) == gathers
    prims = _primitives(jaxpr)
    assert prims["cummax"] == scans
    assert not prims["while"] and not prims["scan"]
    assert structural.plan_joins(st.plan) == (
        "desc" if scans else "child" if gathers else "none", scans)
    leaves = sum(op == "tag" for op in structural._plan_ops(st.plan))
    assert leaves >= (name != "client-no-error-parent")
    assert _span_axis_gathers(jaxpr, S // SPAN_TILE) == 3 * leaves


def test_a_bucket_programs_desc_arm_is_a_running_max_a_slot():
    """Opcode 8 of a slot program: every span slot of a bucket with
    relations computes the `desc` arm, one `cummax` each, beside
    `child`'s one lookup through the parent column; no loop."""
    blocks, _entries = _forest_blocks(5)
    eng, _host, batch = _stage(blocks, mask=False)
    group = [_compiled(blocks, batch, src) for src in _DESC_BUCKET]
    cq = stack_queries(group)
    bucket = cq.structural
    assert isinstance(bucket, BucketedStructural)
    n_slots = bucket.plan[1]
    assert bucket.plan[3] and n_slots >= 3
    assert structural.plan_joins(bucket.plan) == ("desc", n_slots)
    d = batch.device
    S = int(batch.span_device["span_trace"].shape[0])

    def verdicts(span_cols, tables):
        return structural.structural_entry_mask(
            d["kv_key"], d["kv_val"], d["entry_dur"], d["entry_valid"],
            d["page_block"], d.get("entry_dur_res"), span_cols, tables,
            plan=bucket.plan, widths=batch.widths)

    lane = tuple(None if t is None else t[0]
                 for t in bucket.device_tables())
    jaxpr = jax.make_jaxpr(verdicts)(batch.span_device, lane).jaxpr
    prims = _primitives(jaxpr)
    assert prims["cummax"] == n_slots
    assert not prims["while"] and not prims["scan"]
    assert _span_axis_gathers(jaxpr, S) == n_slots


def test_a_launch_says_its_tile(probe_masks):
    """`span_tile` beside `span_rows` on a structural launch."""
    from tempo_tpu.observability import profile

    blocks, _entries = _three_dictionaries()
    eng, _host, batch = _stage(blocks, mask=True)
    mq = _compiled(blocks, batch, _TEMPLATES["direct-call"])
    eng.scan(batch, mq)
    rec = profile.PROFILER.snapshot(recent=1)["recent"][-1]
    attrs = rec["attrs"]
    rows = int(batch.span_device["span_trace"].shape[0])
    assert attrs["span_rows"] == rows and attrs["span_tile"] == SPAN_TILE


def test_pad_rows_between_blocks_count_as_pad():
    """The rows between a block's end and the next tile are pad rows
    like those at the axis' end: `span_trace` -1, and no table row's
    key can match them (kv ids -1 against key ids >= 0 or the row's
    verdict masked by `s_valid`)."""
    blocks = _layout_case("empty-block-between")
    eng, host, batch = _stage(blocks, mask=False)
    cols = host.span_cat
    n0 = blocks[0].n_spans
    gap = slice(n0, -(-n0 // SPAN_TILE) * SPAN_TILE)
    assert gap.stop > gap.start
    assert (cols["span_trace"][gap] == -1).all()
    assert (cols["span_parent"][gap] == -1).all()
    assert (cols["span_kv_key"][gap] == -1).all()
    mq = _compiled(blocks, batch,
                   '{"exists": {"not": {"tag": {"k": "nope", "v": ""}}}}')
    tables = mq.structural.device_tables()
    sctx = structural._span_ctx(batch.span_device, None, None)
    got = np.asarray(structural._span_mask(
        mq.structural.plan[2], sctx, tables, batch.widths))
    assert np.array_equal(got, cols["span_trace"] >= 0)
    assert got.sum() == sum(b.n_spans for b in blocks)


# -------------------------------------- a trace's spans, depth first


_F_SVCS = ("api", "db", "auth", "cache")
_F_OPS = ("op0", "op1", "op2")


def _forest_trace(i: int, rng: random.Random, n_spans: int,
                  shape: str = "forest", keep: int | None = None):
    """Trace `i` with a random forest of `n_spans` spans in a random
    stored order (`shape` "chain": one chain, stored deepest first).
    `keep`: what the ingest cap keeps, the first `keep` stored spans; a
    kept span whose parent went has none (collect_span_rows). A span's
    `dur_ms` is its stored position: how the tests name a span."""
    if shape == "chain":
        parent = list(range(-1, n_spans - 1))
        order = list(range(n_spans))[::-1]
    else:
        # several roots a trace, parents drawn among the spans before
        parent = [-1 if s == 0 or rng.random() < 0.15 else rng.randrange(s)
                  for s in range(n_spans)]
        order = list(range(n_spans))
        rng.shuffle(order)
    at = {old: new for new, old in enumerate(order)}
    kept = order[:keep]
    spans = []
    for new, old in enumerate(kept):
        par = parent[old]
        par = at[par] if par >= 0 and at[par] < len(kept) else -1
        spans.append(SpanData(
            parent=par, dur_ms=new, kind=rng.randint(0, 5),
            kvs={"service.name": {rng.choice(_F_SVCS)},
                 "name": {rng.choice(_F_OPS)}}))
    sd = _trace(i, [])
    sd.spans = spans
    return sd


def _forest_blocks(seed: int) -> tuple:
    """(blocks, entries): random forests in random stored orders with
    empty traces between them, a chain of 512 stored deepest first, a
    trace of 600 cut at the cap of 512 (orphans), and a block without
    spans between two that have them."""
    rng = random.Random(seed)
    first = [_forest_trace(i, rng, rng.choice((0, 0, 1, 2, 5, 9, 17, 40)))
             for i in range(90)]
    first[11] = _forest_trace(11, rng, 512, shape="chain")
    second = [_forest_trace(1000 + i, rng, rng.randint(0, 25))
              for i in range(70)]
    second[3] = _forest_trace(1003, rng, 600, keep=512)
    spanless = [_trace(3000 + i, []) for i in range(5)]
    entries = [first, spanless, second]
    return ([ColumnarPages.build(e, E_GEO) for e in entries],
            first + spanless + second)


@pytest.mark.parametrize("n_shards", [1, 4])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_every_subtree_is_the_run_up_to_its_last_descendant(seed,
                                                            n_shards):
    """The layout inside a trace, over stack_spans' product and
    shard_span_segment's: every trace's run and span SET are what the
    block stores, the re-pointed parents are the stored tree's, every
    span's subtree is exactly the rows `[row, span_last[row]]`, and the
    parentless spans and every span's children keep stored order."""
    blocks, entries = _forest_blocks(seed)
    pad_pages = structural._pow2(max(n_shards,
                                     sum(b.n_pages for b in blocks)))
    host = stack_host(blocks, pad_to=pad_pages)
    cols = host.span_cat
    shard_entries, per_shard = pad_pages * E, cols["span_trace"].shape[0]
    if n_shards > 1:
        STRUCTURAL.shard_spans = True
        cols = STRUCTURAL.shard_span_segment(cols, n_shards, pad_pages, E)
        shard_entries //= n_shards
        per_shard = cols["span_trace"].shape[0] // n_shards
    begin = cols["entry_span_begin"].reshape(-1)
    count = cols["entry_span_count"].reshape(-1)
    parent, last = cols["span_parent"], cols["span_last"]
    by_id = {sd.trace_id: sd for sd in entries}
    seen = moved = 0
    for bi, b in enumerate(blocks):
        for lp, e in zip(*np.nonzero(b.entry_valid)):
            sd = by_id[bytes(b.trace_ids[lp, e])]
            g = (host.page_offset[bi] + int(lp)) * E + int(e)
            n = len(sd.spans)
            assert count[g] == n
            if not n:
                continue
            seen += 1
            # the run, in the rows of its shard's chunk
            lo = int(begin[g])
            chunk = (g // shard_entries) * per_shard
            run = slice(chunk + lo, chunk + lo + n)
            assert (cols["span_trace"][run] == g % shard_entries).all()
            stored = cols["span_dur"][run].tolist()   # new row -> stored
            assert sorted(stored) == list(range(n))
            moved += stored != list(range(n))
            assert cols["span_kind"][run].tolist() \
                == [sd.spans[o].kind for o in stored]
            # parents, as positions in the run: the stored tree's
            par = [p - lo if p >= 0 else -1 for p in parent[run].tolist()]
            assert [stored[p] if p >= 0 else -1 for p in par] \
                == [sd.spans[o].parent for o in stored]
            # subtrees: under row r lie exactly the rows (r, last[r]]
            below = [set() for _ in range(n)]
            for r in range(n - 1, -1, -1):
                p = par[r]
                assert p < r        # a parent lies before its child
                if p >= 0:
                    below[p] |= below[r] | {r}
            for r in range(n):
                end = int(last[run][r]) - lo
                assert below[r] == set(range(r + 1, end + 1)), (g, r)
            # stored order among the parentless and among siblings
            kids = collections.defaultdict(list)
            for r in range(n):
                kids[par[r]].append(stored[r])
            assert all(k == sorted(k) for k in kids.values())
    assert seen > 100 and moved > 50
    pad = cols["span_trace"] < 0
    assert (last[pad] == -1).all() and (parent[pad] == -1).all()


def test_the_order_counts_the_rows_it_moved():
    """`span_preorder` on a block stored depth first moves nothing; the
    counter says both, once a block stacked."""
    chains = _block(0, 40, 6)[0]            # stored as the layout wants
    shuffled = _forest_blocks(4)[0][0]
    before = {m: obs.structural_span_reorder_rows.value(moved=m)
              for m in ("yes", "no")}
    order_s = obs.structural_span_order_seconds.value()
    cols = STRUCTURAL.stack_spans(
        [chains, shuffled], E, chains.n_pages + shuffled.n_pages)
    assert obs.structural_span_order_seconds.value() > order_s
    assert cols["span_last"].shape == cols["span_trace"].shape
    run_begin = chains.entry_span_begin.reshape(-1)[chains.span_trace]
    perm, par, last, moved = structural.span_preorder(chains.span_parent,
                                                      run_begin)
    assert moved == 0 and np.array_equal(perm, np.arange(chains.n_spans))
    assert np.array_equal(par, chains.span_parent)
    assert np.array_equal(last[::6], np.arange(5, chains.n_spans, 6))
    moved = structural.span_preorder(
        shuffled.span_parent,
        shuffled.entry_span_begin.reshape(-1)[shuffled.span_trace])[3]
    assert 0 < moved <= shuffled.n_spans
    assert obs.structural_span_reorder_rows.value(moved="yes") \
        - before["yes"] == moved
    assert obs.structural_span_reorder_rows.value(moved="no") \
        - before["no"] == chains.n_spans + shuffled.n_spans - moved


_DESC_QUERIES = {
    "desc": '{"exists": {"desc": {"anc": {"tag": {"k": "service.name", '
            '"v": "api"}}, "span": {"tag": {"k": "name", "v": "op1"}}}}}',
    "desc-count": '{"count": {"of": {"desc": {"anc": {"kind": "server"}, '
                  '"span": {"tag": {"k": "service.name", "v": "db"}}}}, '
                  '"op": ">", "n": 2}}',
    "desc-in-desc": _NESTED_DESC,
    "desc-as-ancestor": '{"exists": {"desc": {"anc": {"desc": {"anc": '
                        '{"tag": {"k": "name", "v": "op0"}}, "span": '
                        '{"kind": "client"}}}, "span": {"tag": {"k": '
                        '"service.name", "v": "auth"}}}}}',
    "desc-under-not": '{"exists": {"and": [{"tag": {"k": "name", "v": '
                      '"op2"}}, {"not": {"desc": {"anc": {"tag": {"k": '
                      '"service.name", "v": "a"}}, "span": {"tag": {"k": '
                      '"name", "v": "op"}}}}}]}}',
    "child": '{"exists": {"child": {"parent": {"tag": {"k": '
             '"service.name", "v": "db"}}, "child": {"kind": "client"}}}}',
}
# three plans of one bucket with relations: opcode 8 and opcode 7 side
# by side in one fused launch
_DESC_BUCKET = [
    '{"exists": {"desc": {"anc": {"tag": {"k": "service.name", '
    '"v": "api"}}, "span": {"dur": {"min_ms": 3}}}}}',
    '{"exists": {"desc": {"anc": {"kind": "server"}, "span": {"tag": '
    '{"k": "name", "v": "op1"}}}}}',
    '{"exists": {"child": {"parent": {"dur": {"min_ms": 2}}, '
    '"child": {"tag": {"k": "name", "v": "op"}}}}}',
]


def _mesh_engine(layout: str):
    """(engine kwargs) for one chip, or a mesh of four with the span
    columns replicated or sharded by the trace."""
    if layout == "one-chip":
        return {}
    if len(jax.devices()) < 4:
        pytest.skip("needs four (forced host) devices")
    from tempo_tpu.parallel import make_mesh

    STRUCTURAL.shard_spans = layout == "mesh4-sharded"
    return {"mesh": make_mesh(4)}


@pytest.mark.parametrize("layout", ["one-chip", "mesh4-replicated",
                                    "mesh4-sharded"])
@pytest.mark.parametrize("path", ["plan-solo", "bucket-program"])
def test_desc_answers_equal_eval_host_in_every_layout(path, layout):
    """`desc` (alone, nested in a `desc` on either side, under `not`)
    and a bucket program's opcode 8 against `eval_host`, over forests
    in random stored orders with orphans, a chain of 512 and a trace
    cut at the cap: one chip's layout and both of the mesh's."""
    blocks, entries = _forest_blocks(7)
    eng = MultiBlockEngine(top_k=512, **_mesh_engine(layout))
    batch = eng.place(eng.stage_host(blocks))
    assert batch.span_sharded == (layout == "mesh4-sharded")
    if path == "plan-solo":
        for name, src in _DESC_QUERIES.items():
            mq = _compiled(blocks, batch, src)
            want = _expected_ids(mq._expr, entries)
            assert want and len(want) < len(entries), name
            assert _scan_ids(batch, eng, mq, entries) \
                == (len(want), want), name
        return
    group = [_compiled(blocks, batch, src) for src in _DESC_BUCKET]
    cq, fused = _fused_ids(eng, batch, group)
    assert isinstance(cq.structural, BucketedStructural)
    for mq, (count, got) in zip(group, fused):
        want = _expected_ids(mq._expr, entries)
        assert want and len(want) < len(entries), ir.to_json(mq._expr)
        assert (count, got) == (len(want), want), ir.to_json(mq._expr)


# ---------------------------------------------------- a loop of parents


def test_a_loop_is_cut_at_its_first_stored_span():
    # rows 0..2 a tree; 3 <-> 5 a 2-loop with 4 hanging off 5; 6 -> 8 ->
    # 7 -> 6 a 3-loop with 9 -> 10 hanging off 7; 11 names itself
    parent = np.array([-1, 0, 1, 5, 5, 3, 8, 6, 7, 7, 9, 11],
                      dtype=np.int32)
    cut, depth = structural.cut_parent_loops(parent)
    assert cut.tolist() == [-1, 0, 1, -1, 5, 3, -1, 6, 7, 7, 9, -1]
    assert depth.tolist() == [0, 1, 2, 0, 2, 1, 0, 1, 2, 2, 3, 0]
    assert parent[3] == 5           # the stored column is not written
    whole = np.array([-1, 0, 0, 2], dtype=np.int32)
    assert structural.cut_parent_loops(whole)[0] is whole
    # eval_host's walk that decides whether the helper is asked at all
    assert structural._has_parent_loop(parent.tolist())
    assert structural._has_parent_loop([-1, 2, 1])
    assert not structural._has_parent_loop(whole.tolist())
    assert not structural._has_parent_loop([3, 0, 1, -1, 2])    # a chain


def _loop_trace(i: int, rng: random.Random):
    """A 2-loop and a 3-loop of parents, each with a tree hanging off,
    beside a well-formed tree: what no ingester writes and a damaged
    block may hold."""
    sd = _forest_trace(i, rng, 14)
    par = [-1, 0, 1, 5, 5, 3, 8, 6, 7, 7, 9, 4, 10, 2]
    for sp, p in zip(sd.spans, par):
        sp.parent = p
    return sd


@pytest.mark.parametrize("layout", ["one-chip", "mesh4-sharded"])
def test_staging_and_eval_host_agree_on_a_block_with_parent_loops(layout):
    """The one rule (cut_parent_loops) where the parent column is read:
    staging ends on a block whose parent column holds loops, and the
    staged launch answers `desc` and `child` as `eval_host` does."""
    rng = random.Random(46)
    entries = [_loop_trace(i, rng) if i % 3 == 0
               else _forest_trace(i, rng, rng.randint(0, 12))
               for i in range(120)]
    pages = ColumnarPages.build(entries, E_GEO)
    # the block stores the loops as they came
    lo = int(pages.entry_span_begin[0, 0])
    assert pages.span_parent[lo + 3] == lo + 5 \
        and pages.span_parent[lo + 5] == lo + 3
    eng = MultiBlockEngine(top_k=512, **_mesh_engine(layout))
    batch = eng.place(eng.stage_host([pages]))
    answered = 0
    for name, src in _DESC_QUERIES.items():
        mq = _compiled([pages], batch, src)
        want = _expected_ids(mq._expr, entries)
        answered += bool(want)
        assert _scan_ids(batch, eng, mq, entries) == (len(want), want), name
    assert answered >= 5
    # the cut: under the 2-loop's first span lie the loop's other span
    # and what hangs off it, and nothing lies above it
    sd = entries[0]
    for sp in sd.spans:
        sp.kvs = {"service.name": {"x"}}
    sd.spans[3].kvs = {"service.name": {"first"}}
    sd.spans[11].kvs = {"service.name": {"leaf"}}
    q = '{"exists": {"desc": {"anc": {"tag": {"k": "service.name", "v": ' \
        '"%s"}}, "span": {"tag": {"k": "service.name", "v": "%s"}}}}}'
    assert structural.eval_host(ir.parse(q % ("first", "leaf")), sd)
    assert not structural.eval_host(ir.parse(q % ("leaf", "first")), sd)
    assert not structural.eval_host(ir.parse(q % ("x", "first")), sd)
