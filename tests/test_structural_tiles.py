"""The span axis by the tile (ISSUE 45): staging starts every block's
spans on a multiple of `structural.SPAN_TILE`, so every aligned tile
holds live rows of one block, and a tag leaf over spans looks its
block's tables up once a tile. Three things are pinned here: the layout
(replicated and sharded), the answers where a wrong tile's table would
give a wrong one, and that no lookup a row comes back (the jaxpr)."""

from __future__ import annotations

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
    sctx = structural._span_ctx(batch.span_device, tables[2], tables[3],
                                batch.span_max)
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


def _span_axis_gathers(jaxpr, S: int, in_loop: bool = False) -> list:
    """[outside loops, inside loop bodies]: the gathers of `jaxpr` (and
    of every jaxpr nested in it) whose indices have `S` rows."""
    n = [0, 0]
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather" \
                and S in eqn.invars[1].aval.shape:
            n[in_loop] += 1
        loop = in_loop or eqn.primitive.name in ("while", "scan")
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    m = _span_axis_gathers(sub, S, loop)
                    n[0] += m[0]
                    n[1] += m[1]
    return n


@pytest.mark.parametrize("name,want", [
    ("errors-below", [1, 2]),       # _descends: one before, two a trip
    ("direct-call", [1, 0]),        # the parent column, once
    ("store-fanout", [0, 0]),
    ("slow-p90", [0, 0]),
    ("client-no-error-parent", [0, 0]),
])
def test_no_tag_leaf_gathers_by_the_span_row(name, want):
    """The guard that keeps a lookup a row from coming back: in the
    jaxpr of each template's launch, the gathers whose indices are as
    long as the span axis are the joins' own and no other; the leaves'
    have one index a tile."""
    blocks, _entries = _three_dictionaries()
    eng, _host, batch = _stage(blocks, mask=False)
    mq = _compiled(blocks, batch, _TEMPLATES[name])
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
            plan=st.plan, widths=batch.widths, span_max=batch.span_max)

    jaxpr = jax.make_jaxpr(verdicts)(batch.span_device,
                                     st.device_tables()).jaxpr
    assert _span_axis_gathers(jaxpr, S) == want
    tile_gathers = _span_axis_gathers(jaxpr, S // SPAN_TILE)
    leaves = sum(op == "tag" for op in structural._plan_ops(st.plan))
    assert leaves >= (name != "client-no-error-parent")
    assert sum(tile_gathers) == 3 * leaves
    # the host's reckoning of the same launch, and the counter's
    assert structural.leaf_lookup_rows(
        st.plan, st.device_tables(), batch.span_device) \
        == 3 * leaves * (S // SPAN_TILE)
    before = obs.structural_leaf_lookup_rows.value()
    eng.scan(batch, mq)
    assert obs.structural_leaf_lookup_rows.value() - before \
        == 3 * leaves * (S // SPAN_TILE)


def test_a_launch_says_its_tile_and_its_lookup_rows(probe_masks):
    """`span_tile` and `leaf_lookup_rows` beside `span_rows` on a
    structural launch: over the span rows, lookups / SPAN_TILE."""
    from tempo_tpu.observability import profile

    blocks, _entries = _three_dictionaries()
    eng, _host, batch = _stage(blocks, mask=True)
    mq = _compiled(blocks, batch, _TEMPLATES["direct-call"])
    eng.scan(batch, mq)
    rec = profile.PROFILER.snapshot(recent=1)["recent"][-1]
    attrs = rec["attrs"]
    rows = int(batch.span_device["span_trace"].shape[0])
    assert attrs["span_rows"] == rows and attrs["span_tile"] == SPAN_TILE
    # two leaves of three lookups, and the tiles' groups for the mask
    assert attrs["leaf_lookup_rows"] * SPAN_TILE == 7 * rows


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
    sctx = structural._span_ctx(batch.span_device, None, None,
                                batch.span_max)
    got = np.asarray(structural._span_mask(
        mq.structural.plan[2], sctx, tables, batch.widths))
    assert np.array_equal(got, cols["span_trace"] >= 0)
    assert got.sum() == sum(b.n_spans for b in blocks)
