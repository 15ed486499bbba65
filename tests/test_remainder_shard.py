"""Remainder-shard mesh layout (ISSUE 16): under
search_structural_remainder_pages the staged page axis pads to the
minimal multiple of the shard count instead of the next pow2 — the last
shard owns the ragged tail, described to the dist kernels by the static
`shard_tail` jit key. Byte-identical to the pow2/replicated layout
(pad entries were already invalid); only the staged footprint and the
compiled layout descriptor change."""

from __future__ import annotations

import numpy as np
import pytest

from tempo_tpu.search import ir
from tempo_tpu.search.columnar import ColumnarPages, PageGeometry
from tempo_tpu.search.multiblock import MultiBlockEngine, compile_multi
from tempo_tpu.search.structural import STRUCTURAL, compile_structural
from test_structural import (  # noqa: F401 — _structural_on is autouse
    _ACCEPTANCE_TRIPLE,
    E_GEO,
    _corpus,
    _expected_ids,
    _mk_req,
    _scan_ids,
    _structural_on,
)

# small pages make ragged page counts cheap to build
G_SMALL = PageGeometry(entries_per_page=8, kv_per_entry=8)


def test_remainder_pad_minimal_multiple_invariants():
    """Page counts 1, n-1, n+1, and primes pad to the minimal multiple
    of the shard count: zero over-pad beyond the ragged tail."""
    STRUCTURAL.remainder_pages = True
    try:
        for n in (2, 3, 4, 5, 8):
            for total in (1, n - 1, n + 1, 2, 3, 5, 7, 11, 13, 17, 23):
                pad = STRUCTURAL.remainder_pad(total, n)
                assert pad % n == 0, (total, n)
                assert pad >= max(total, n)
                # the whole point: never more than one ragged tail
                assert pad - total < n, (total, n, pad)
    finally:
        STRUCTURAL.remainder_pages = False
    # disabled gate: one attribute read, None (pow2 layout kept)
    assert STRUCTURAL.remainder_pad(9, 8) is None


def test_stage_host_minimal_padding_cuts_staged_bytes():
    """A 17-page batch on 8 shards stages 24 pages under the gate, not
    the 32 the pow2 layout takes — measured on the staged arrays."""
    entries = _corpus(11, n=130)  # 130 entries / 8 per page = 17 pages
    blocks = [ColumnarPages.build(entries, G_SMALL)]
    assert sum(b.n_pages for b in blocks) == 17
    eng = MultiBlockEngine(top_k=128)
    eng.n_shards = 8  # host-side layout: no mesh needed
    off = eng.stage_host(blocks)
    assert int(off.page_block.shape[0]) == 32
    STRUCTURAL.remainder_pages = True
    try:
        on = eng.stage_host(blocks)
    finally:
        STRUCTURAL.remainder_pages = False
    assert int(on.page_block.shape[0]) == 24
    assert on.cat_nbytes < off.cat_nbytes
    # the tail pages are pad: no block owns them
    assert (np.asarray(on.page_block)[17:] < 0).all()


def test_span_segment_rebases_on_ragged_layout():
    """Segment-aligned span sharding composes with the minimal-multiple
    page axis: every live span still lands in its trace's shard chunk
    with chunk-local coordinates, including on the short last shard."""
    entries = _corpus(12, n=260)  # 33 pages -> minimal 40 on 8 shards
    blocks = [ColumnarPages.build(entries, G_SMALL)]
    eng = MultiBlockEngine(top_k=128)
    eng.n_shards = 8
    STRUCTURAL.remainder_pages = True
    try:
        host = eng.stage_host(blocks)
    finally:
        STRUCTURAL.remainder_pages = False
    P_pages = int(host.page_block.shape[0])
    assert P_pages == 40
    span_cat = host.span_cat
    assert span_cat is not None
    n_sh = 8
    E = G_SMALL.entries_per_page
    STRUCTURAL.shard_spans = True
    try:
        sh = STRUCTURAL.shard_span_segment(span_cat, n_sh, P_pages, E)
    finally:
        STRUCTURAL.shard_spans = False
    assert sh is not None
    per_shard = sh["span_trace"].shape[0] // n_sh
    pp = P_pages // n_sh
    total_live = 0
    for s in range(n_sh):
        chunk = slice(s * per_shard, (s + 1) * per_shard)
        tr = sh["span_trace"][chunk]
        live = tr >= 0
        total_live += int(live.sum())
        assert (tr[live] < pp * E).all()
        par = sh["span_parent"][chunk][live]
        assert ((par >= -1) & (par < per_shard)).all()
    # nothing dropped by the reshard
    assert total_live == int((span_cat["span_trace"] >= 0).sum())


def _device_ids(entries, geo, mesh, *, remainder: bool):
    """Stage + scan the acceptance triple; returns per-expr result
    sets, counts, and the staged page-axis length."""
    blocks = [ColumnarPages.build(entries, geo)]
    eng = MultiBlockEngine(top_k=512, mesh=mesh)
    STRUCTURAL.remainder_pages = remainder
    try:
        batch = eng.stage(blocks)
    finally:
        STRUCTURAL.remainder_pages = False
    out = []
    all_entries = list(entries)
    for src in _ACCEPTANCE_TRIPLE:
        expr = ir.parse(src)
        req = _mk_req(expr)
        mq = compile_multi(blocks, req, cache_on=batch)
        mq.structural = compile_structural(
            expr, blocks, cache_on=batch,
            staged_dicts=batch.staged_dicts)
        STRUCTURAL.remainder_pages = remainder
        try:
            count, got = _scan_ids(batch, eng, mq, all_entries)
        finally:
            STRUCTURAL.remainder_pages = False
        assert got == _expected_ids(expr, all_entries), (src, remainder)
        out.append((count, got))
    return out, int(batch.device["kv_key"].shape[0])


def test_mesh_remainder_layout_byte_identical():
    """The mesh leg: a non-multiple page count staged remainder-style
    (shard_tail in the jit key) answers identically to the pow2 layout
    and the host reference, with fewer staged pages."""
    import jax

    if len(jax.devices()) < 2:
        pytest.skip("needs multiple (forced host) devices")
    from tempo_tpu.parallel import make_mesh

    entries = _corpus(13, n=130)  # 17 pages on 8 shards: 24 vs 32
    mesh = make_mesh()
    got_off, pages_off = _device_ids(entries, G_SMALL, mesh,
                                     remainder=False)
    got_on, pages_on = _device_ids(entries, G_SMALL, mesh,
                                   remainder=True)
    assert got_on == got_off
    assert pages_on < pages_off, (pages_on, pages_off)


def test_mesh_remainder_layout_with_sharded_spans():
    """Remainder layout + segment-aligned span sharding together: the
    short last shard's rebased spans answer identically."""
    import jax

    if len(jax.devices()) < 2:
        pytest.skip("needs multiple (forced host) devices")
    from tempo_tpu.parallel import make_mesh

    entries = _corpus(14, n=260)
    mesh = make_mesh()
    STRUCTURAL.shard_spans = True
    try:
        got_off, _ = _device_ids(entries, G_SMALL, mesh,
                                 remainder=False)
        got_on, _ = _device_ids(entries, G_SMALL, mesh, remainder=True)
    finally:
        STRUCTURAL.shard_spans = False
    assert got_on == got_off


def test_one_block_batch_remainder_descriptor_byte_identical():
    """One block as a one-block batch over the mesh: under the gate its
    pages stage minimally and the ragged tail enters the jit key as
    shard_tail — results stay byte-identical to the gate-off compile."""
    import jax

    if len(jax.devices()) < 2:
        pytest.skip("needs multiple (forced host) devices")
    from conftest import scan_batch
    from tempo_tpu.parallel import make_mesh

    entries = _corpus(15, n=130)
    pages = ColumnarPages.build(entries, G_SMALL)
    staged_pages = {}
    for remainder in (False, True):
        STRUCTURAL.remainder_pages = remainder
        try:
            staged = {}
            for src in _ACCEPTANCE_TRIPLE:
                expr = ir.parse(src)
                got = scan_batch([pages], _mk_req(expr), top_k=512,
                                 mesh=make_mesh(), structural=expr,
                                 **staged)
                staged = {"engine": got.engine, "batch": got.batch}
                want = _expected_ids(expr, entries)
                assert got.trace_ids == want and got.count == len(want), \
                    (src, remainder)
            staged_pages[remainder] = int(
                got.batch.device["kv_key"].shape[0])
        finally:
            STRUCTURAL.remainder_pages = False
    assert staged_pages[True] <= staged_pages[False]
