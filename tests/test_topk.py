"""masked_topk against a full sort: scores AND flat indices.

The reference is numpy's `lexsort` on (-score, flat index): the first k
of a full sort by (score descending, flat index ascending), the one tie
rule every path of `masked_topk` keeps (equal start seconds resolve to
the lowest flat index). The rows path engages by shape
(`topk_row_width`), so most cases here are just above its threshold of
32,768 scores. The order is `latest_k`'s stable sort and not
`lax.top_k`'s: on the TPU a batched `lax.top_k` did not keep the lower
index among equal scores (PERF.md section 6, PR 25), which the CPU
these tests run on cannot show.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tempo_tpu.search import engine
from tempo_tpu.search.engine import latest_k, masked_topk, topk_row_width


def reference(mask, start, k):
    score = np.where(mask, np.minimum(start, 2**31 - 1).astype(np.int64),
                     -1).reshape(-1)
    k = min(k, score.size)
    order = np.lexsort((np.arange(score.size), -score))[:k]
    return score[order].astype(np.int32), order.astype(np.int32)


def check(mask, start, k, rows: bool):
    """One input through the kernel; `rows` says which path its shape
    must take (a case that silently fell to the other path would test
    nothing)."""
    n = mask.size
    assert bool(topk_row_width(n, min(k, n))) == rows
    scores, idx = jax.jit(masked_topk, static_argnums=2)(
        jnp.asarray(mask), jnp.asarray(start), k)
    want_s, want_i = reference(mask, start, k)
    np.testing.assert_array_equal(np.asarray(scores), want_s)
    np.testing.assert_array_equal(np.asarray(idx), want_i)


def _random(n, seed, density, span):
    rng = np.random.default_rng(seed)
    return (rng.random(n) < density,
            rng.integers(1_600_000_000, 1_600_000_000 + span, n,
                         dtype=np.uint32))


def _one_second(n):
    return np.ones(n, dtype=bool), np.full(n, 1_600_000_000, np.uint32)


def _straddle(n, k, w):
    """k - 1 entries above a tie of four that lies across two rows, the
    lower-indexed of which must win the last place."""
    mask = np.zeros(n, dtype=bool)
    start = np.full(n, 1_600_000_000, np.uint32)
    top = np.arange(k - 1) * w + 7          # one per row, rows 0..k-2
    mask[top] = True
    start[top] = 1_700_000_000 + np.arange(k - 1)
    tie = np.array([200 * w + 5, 200 * w + 90, 150 * w + 100, 150 * w + 3])
    mask[tie] = True
    start[tie] = 1_650_000_000
    return mask, start


CASES = {
    # the cell's shape class scaled down: P pages of 1,024 entries
    "pages8x1024": lambda: (*_random(8 * 1024, 1, 0.3, 3000), 128, False),
    "pages64x1024": lambda: (*_random(64 * 1024, 2, 0.3, 3000), 128, True),
    "pages64x1024_2d": lambda: tuple(
        a.reshape(64, 1024) for a in _random(64 * 1024, 3, 0.05, 500)
    ) + (128, True),
    "n_not_multiple_of_w": lambda: (*_random(40_001, 4, 0.5, 2000), 20, True),
    "last_row_short_and_best": lambda: (
        np.arange(40_001) >= 39_990,
        np.arange(40_001, dtype=np.uint32) + 1_600_000_000, 20, True),
    "k_ge_n": lambda: (*_random(50, 5, 0.5, 20), 128, False),
    "k_one": lambda: (*_random(70_000, 6, 0.2, 5), 1, True),
    "nothing_matches": lambda: (
        np.zeros(65_536, dtype=bool), _random(65_536, 7, 0, 9)[1], 128, True),
    "fewer_than_k_match": lambda: (
        np.arange(65_536) % 9_973 == 17, _random(65_536, 8, 0, 9)[1],
        128, True),
    "all_match_one_second": lambda: (*_one_second(65_536), 128, True),
    "all_match_one_second_direct": lambda: (*_one_second(4_096), 128, False),
    "tie_straddles_two_rows": lambda: (*_straddle(65_536, 128, 128), 128,
                                       True),
    "dense_ties": lambda: (*_random(131_072, 9, 0.9, 3), 128, True),
    "large_k_falls_back": lambda: (*_random(65_536, 10, 0.5, 100), 256,
                                   False),
    "start_beyond_int32": lambda: (
        np.ones(40_000, dtype=bool),
        np.where(np.arange(40_000) % 1_000 == 0, 2**32 - 1,
                 1_600_000_000).astype(np.uint32), 20, True),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_masked_topk_equals_full_sort(case):
    mask, start, k, rows = CASES[case]()
    check(mask, start, k, rows)


@pytest.mark.parametrize("seed", range(4))
def test_rows_and_direct_paths_agree(seed, monkeypatch):
    """The same data down both branches: index for index."""
    mask, start = _random(65_536 + 77 * seed, seed, (0.001, 0.1, 0.6, 1.0)[seed],
                          (1, 40, 4000, 10**6)[seed])
    rows_out = jax.jit(masked_topk, static_argnums=2)(
        jnp.asarray(mask), jnp.asarray(start), 128)
    monkeypatch.setattr(engine, "topk_row_width", lambda n, k: 0)
    direct_out = jax.jit(masked_topk, static_argnums=2)(
        jnp.asarray(mask), jnp.asarray(start), 128)
    for a, b in zip(rows_out, direct_out):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    want = reference(mask, start, 128)
    np.testing.assert_array_equal(np.asarray(rows_out[1]), want[1])


@pytest.mark.parametrize("q", [1, 2, 4, 8, 24])
def test_masked_topk_under_vmap(q):
    """The coalesced kernels' use: Q masks over one start column; the
    row gather becomes a batched gather."""
    rng = np.random.default_rng(q)
    n = 40 * 1024
    start = rng.integers(1_600_000_000, 1_600_000_300, n, dtype=np.uint32)
    masks = rng.random((q, n)) < rng.random((q, 1)) ** 3
    masks[0] = False
    scores, idx = jax.jit(jax.vmap(
        lambda m: masked_topk(m, jnp.asarray(start).reshape(40, 1024), 128)))(
            jnp.asarray(masks).reshape(q, 40, 1024))
    for i in range(q):
        want_s, want_i = reference(masks[i], start, 128)
        np.testing.assert_array_equal(np.asarray(scores[i]), want_s)
        np.testing.assert_array_equal(np.asarray(idx[i]), want_i)


@pytest.mark.parametrize("shape", [(64,), (3, 64), (2, 3, 40)])
def test_latest_k_orders_by_score_then_index(shape):
    """Along the last axis, batched over the rest. `idx` need not be
    the position (the mesh merge hands it global flat indices), only
    ascending among equal scores."""
    rng = np.random.default_rng(len(shape))
    score = rng.integers(-1, 4, shape).astype(np.int32)
    idx = np.broadcast_to(np.arange(shape[-1], dtype=np.int32) * 3 + 5,
                          shape)
    got_s, got_i = latest_k(jnp.asarray(score), jnp.asarray(idx), 16)
    for at in np.ndindex(*shape[:-1]):
        order = np.lexsort((idx[at], -score[at].astype(np.int64)))[:16]
        np.testing.assert_array_equal(np.asarray(got_s[at]), score[at][order])
        np.testing.assert_array_equal(np.asarray(got_i[at]), idx[at][order])


def test_mesh_merge_keeps_the_tie_rule():
    """Every entry of five blocks in one start second: the mesh kernel's
    merge of its shards' candidates returns the single-device kernel's
    indices, the lowest flat ones."""
    from tempo_tpu import tempopb
    from tempo_tpu.parallel import make_mesh
    from tempo_tpu.search.columnar import ColumnarPages, PageGeometry
    from tempo_tpu.search.data import SearchData
    from tempo_tpu.search.multiblock import MultiBlockEngine, compile_multi

    blocks = []
    for b in range(5):
        entries = []
        for i in range(100):
            sd = SearchData(trace_id=bytes([b, i]).rjust(16, b"\x00"))
            sd.start_s, sd.end_s, sd.dur_ms = 1_600_000_000, 1_600_000_005, 7
            sd.root_service, sd.root_name = "svc", "GET /"
            sd.kvs = {"service.name": {"svc"}}
            entries.append(sd)
        blocks.append(ColumnarPages.build(entries, PageGeometry(32, 8)))
    req = tempopb.SearchRequest()
    req.limit = 64
    mq = compile_multi(blocks, req)
    single = MultiBlockEngine(top_k=64)
    _, _, s_scores, s_idx = single.scan(single.stage(blocks), mq)
    dist = MultiBlockEngine(top_k=64, mesh=make_mesh())
    _, _, d_scores, d_idx = dist.scan(dist.stage(blocks), mq)
    np.testing.assert_array_equal(d_scores, s_scores)
    np.testing.assert_array_equal(d_idx, s_idx)
    assert s_idx.tolist() == list(range(64))


@pytest.mark.parametrize("n,k,w", [
    (4096 * 1024, 128, 128),      # a full group of the share16 cells
    (64 * 1024, 128, 128),        # one block
    (4096 * 1024, 1024, 128),     # a limit of a thousand
    (1 << 24, 128, 256),
    (32_768, 128, 0),             # small inputs sort directly
    (64 * 1024, 256, 0),          # candidates would be half the input
    (50, 50, 0),
])
def test_topk_row_width(n, k, w):
    assert topk_row_width(n, k) == w
    if w:
        assert w % 128 == 0 and 4 * k * w <= n and -(-n // w) >= k


def test_book_topk_counts_and_names_the_path():
    from tempo_tpu.observability import metrics as obs
    from tempo_tpu.observability import profile

    before = {p: obs.topk_dispatches.value(path=p)
              for p in ("rows", "direct")}
    rec = profile.Dispatch(profile.PROFILER, "batched")
    engine.book_topk(rec, 4096 * 1024, 128)
    assert rec.attrs["topk"] == "rows:128"
    engine.book_topk(rec, 2048, 128)
    assert rec.attrs["topk"] == "direct"
    engine.book_topk(profile.NOOP_DISPATCH, 64 * 1024, 128)
    assert obs.topk_dispatches.value(path="rows") == before["rows"] + 2
    assert obs.topk_dispatches.value(path="direct") == before["direct"] + 1
