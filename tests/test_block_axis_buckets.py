"""A group's block count is bucketed out of the scan program's jit key
(search/multiblock.py `block_bucket`): the per-block query tables of a
launch carry a block axis padded to the next power of two, as the page
axis beside it is, and the pad rows are key id -1, a pruned block's
sentinel, which no page's `page_block` names. Held here, on the test's
own arrays: a group of 3, 5, 9, 17 or 49 blocks answers, solo and fused,
as a numpy walk of those arrays and as the host route do, in match set
and `inspected`; pad rows match nothing, `exhaustive` or not; groups of
5 and 7 blocks launch under ONE jit key and groups of 7 and 9 under two;
a full 64-block group's tables are the exact-count tables, so its
lowered program is the text the exact-count form gave (the cells'
persistent-cache keys), and a 49-block tail lowers to that same text."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tempo_tpu import tempopb
from tempo_tpu.observability import metrics as obs
from tempo_tpu.observability import profile
from tempo_tpu.search import multiblock
from tempo_tpu.search.batcher import host_scan
from tempo_tpu.search.columnar import ColumnarPages, PageGeometry
from tempo_tpu.search.data import SearchData
from tempo_tpu.search.engine import fetch_scan_out
from tempo_tpu.search.multiblock import (
    MultiBlockEngine,
    batch_scan_kernel,
    block_bucket,
    compile_multi,
    stack_queries,
)
from tempo_tpu.search.pipeline import EXHAUSTIVE_SEARCH_TAG

from tests.conftest import BatchScan

SIZES = (3, 5, 9, 17, 49)
GEOMETRY = PageGeometry(32, 8)
TOP_K = 1024
N_SERVICES = 6
STATUS = ("200", "404", "500")


class Group:
    """`n` blocks from the test's own arrays: per block and entry a
    service, a status, a duration and a start; block b lacks service
    b % N_SERVICES altogether (its dictionary prunes that needle), and
    blocks hold 20 to 70 entries, so 1 to 3 pages."""

    def __init__(self, n: int, seed: int = 0, entries: int | None = None):
        rng = np.random.default_rng([seed, n])
        self.svc, self.status, self.dur, self.start = [], [], [], []
        self.blocks = []
        for b in range(n):
            m = entries or int(rng.integers(20, 71))
            svc = rng.integers(0, N_SERVICES - 1, m)
            svc = np.where(svc >= b % N_SERVICES, svc + 1, svc)
            status = rng.integers(0, len(STATUS), m)
            dur = rng.integers(1, 30_000, m)
            start = 1_600_000_000 + rng.permutation(m) + 100 * b
            self.svc.append(svc)
            self.status.append(status)
            self.dur.append(dur)
            self.start.append(start)
            sds = []
            for i in range(m):
                sd = SearchData(trace_id=self.trace_id(b, i))
                sd.start_s = int(start[i])
                sd.end_s = sd.start_s + 1
                sd.dur_ms = int(dur[i])
                sd.root_service = f"svc-{svc[i]}"
                sd.root_name = "GET /"
                sd.kvs = {"service.name": {sd.root_service},
                          "http.status_code": {STATUS[status[i]]}}
                sds.append(sd)
            self.blocks.append(ColumnarPages.build(sds, GEOMETRY))
        self.entries = sum(len(s) for s in self.svc)

    @staticmethod
    def trace_id(block: int, entry: int) -> bytes:
        return block.to_bytes(4, "big") + entry.to_bytes(4, "big") + b"bucketed"

    def walk(self, svc=None, status=None, min_ms=0) -> set:
        """The matches, by a walk of the arrays: trace ids."""
        out = set()
        for b in range(len(self.blocks)):
            m = self.dur[b] >= min_ms
            if svc is not None:
                m &= self.svc[b] == svc
            if status is not None:
                m &= self.status[b] == STATUS.index(status)
            out.update(self.trace_id(b, int(i)) for i in np.flatnonzero(m))
        return out


def _req(svc=None, status=None, min_ms=0, exhaustive=False):
    req = tempopb.SearchRequest()
    if svc is not None:
        req.tags["service.name"] = f"svc-{svc}"
    if status is not None:
        req.tags["http.status_code"] = status
    if exhaustive:
        req.tags[EXHAUSTIVE_SEARCH_TAG] = "1"
    req.min_duration_ms = min_ms
    req.limit = TOP_K
    return req


# (walk's arguments, exhaustive): two terms, one term under exhaustive
# (no block is pruned by its dictionary), a needle two fifths of the
# blocks lack, a duration alone
QUERIES = (
    (dict(svc=1, status="500"), False),
    (dict(svc=2), True),
    (dict(svc=0, min_ms=5_000), False),
    (dict(min_ms=29_000), False),
)


def _ids(batch, scores, idx) -> set:
    """The matches among the top-k rows, as trace ids (`batch` a
    BlockBatch or the host route's HostBatch)."""
    return BatchScan(None, batch, True, (0, 0, np.asarray(scores),
                                         np.asarray(idx))).trace_ids


@pytest.fixture(scope="module")
def groups():
    return {n: Group(n) for n in SIZES}


@pytest.mark.parametrize("n", SIZES)
def test_tables_carry_the_bucket_and_pad_rows_are_pruned_rows(n, groups):
    g = groups[n]
    B = block_bucket(n)
    assert B >= n and B & (B - 1) == 0 and B < 2 * n
    for kw, exhaustive in QUERIES:
        mq = compile_multi(g.blocks, _req(exhaustive=exhaustive, **kw))
        assert mq.term_keys.shape[0] == mq.val_ranges.shape[0] == B
        # a pad row is what a pruned block's row is: no key, no range
        assert (mq.term_keys[n:] == -1).all()
        assert (mq.val_ranges[n:] == (1, 0)).all()
    # and a block axis at a power of two pads nothing
    assert block_bucket(64) == 64 and block_bucket(256) == 256


@pytest.mark.parametrize("Q", [1, 2, 4], ids=["solo", "fused2", "fused4"])
@pytest.mark.parametrize("n", SIZES)
def test_a_group_of_any_size_answers_as_the_walk_and_the_host_route(
        n, Q, groups):
    """stage_host + compile_multi + batch_scan_kernel over a group whose
    block count is no power of two: count, match set and `inspected`
    equal the numpy walk's and the host route's."""
    g = groups[n]
    eng = MultiBlockEngine(top_k=TOP_K)
    host = eng.stage_host(g.blocks)
    batch = eng.place(host)
    queries = QUERIES[:Q] if Q > 1 else QUERIES
    mqs = [compile_multi(g.blocks, _req(exhaustive=ex, **kw), cache_on=batch)
           for kw, ex in queries]
    if Q == 1:
        got = [eng.scan(batch, mq) for mq in mqs]
    else:
        counts, inspected, scores, idx = fetch_scan_out(
            eng.coalesced_scan_async(batch, stack_queries(mqs), TOP_K))
        got = [(int(counts[i]), inspected, scores[i], idx[i])
               for i in range(Q)]
    for (kw, _ex), mq, (count, inspected, scores, idx) in zip(
            queries, mqs, got):
        want = g.walk(**kw)
        assert 0 < len(want) < TOP_K
        assert count == len(want)
        assert int(inspected) == g.entries
        assert _ids(batch, scores, idx) == want
        h_count, h_inspected, h_scores, h_idx = host_scan(host, mq, TOP_K)
        assert (h_count, h_inspected) == (count, int(inspected))
        assert _ids(host, h_scores, h_idx) == want


@pytest.mark.parametrize("n", SIZES)
def test_pad_rows_match_nothing_even_under_exhaustive(n, groups):
    """An exhaustive request matches every entry that holds the value
    and nothing else: the pad rows add no match and no inspected entry,
    solo and fused beside a pad query."""
    g = groups[n]
    eng = MultiBlockEngine(top_k=TOP_K)
    batch = eng.stage(g.blocks)
    mq = compile_multi(g.blocks, _req(svc=3, exhaustive=True),
                       cache_on=batch)
    want = g.walk(svc=3)
    count, inspected, scores, idx = eng.scan(batch, mq)
    assert (count, int(inspected)) == (len(want), g.entries)
    assert _ids(batch, scores, idx) == want
    # three members: the query axis pads to four, the block axis to B
    counts, inspected, scores, idx = fetch_scan_out(
        eng.coalesced_scan_async(batch, stack_queries([mq] * 3), TOP_K))
    assert counts.tolist() == [len(want)] * 3 + [0]
    assert int(inspected) == g.entries


@pytest.fixture
def jit_keys(monkeypatch):
    """The scan program's jit keys from here on, on a profiler that has
    seen none."""
    profile.configure(enabled=True, fence=False)
    profile.PROFILER.reset()
    seen = set()
    monkeypatch.setattr(multiblock, "_SCAN_JIT_KEYS", seen)
    yield seen
    profile.PROFILER.reset()


@pytest.mark.parametrize("a,b,keys", [(5, 7, 1), (7, 9, 2), (9, 16, 1),
                                      (3, 4, 1), (17, 33, 2)])
def test_groups_of_one_bucket_share_a_jit_key(a, b, keys, jit_keys):
    """Blocks of one page each and a page axis that lands in one bucket
    whatever the count, so only the block axis can tell two groups
    apart: it does so by bucket, not by count."""
    eng = MultiBlockEngine(top_k=TOP_K)
    pages = {}
    rows0 = {k: obs.launch_table_rows.value(kind=k) for k in ("real", "pad")}
    for n in (a, b):
        g = Group(n, seed=1, entries=GEOMETRY.entries_per_page)
        # the page axis in one bucket for both: pad the smaller up
        batch = eng.place(multiblock.stack_host(g.blocks, pad_to=64))
        pages[n] = batch.device["kv_key"].shape
        for kw, ex in QUERIES[:2]:
            mq = compile_multi(g.blocks, _req(exhaustive=ex, **kw),
                               cache_on=batch)
            count, *_ = eng.scan(batch, mq)
            assert count == len(g.walk(**kw))
    assert pages[a] == pages[b]
    # two predicates of two shapes (T = 2 and T = 1) a bucket
    assert len(jit_keys) == 2 * keys
    assert obs.scan_jit_keys.value() == len(jit_keys)
    real = obs.launch_table_rows.value(kind="real") - rows0["real"]
    pad = obs.launch_table_rows.value(kind="pad") - rows0["pad"]
    assert real == 2 * (a + b)
    assert pad == 2 * (block_bucket(a) - a + block_bucket(b) - b)


def _lowered(n_blocks: int, rows: int, fused: int | None) -> str:
    """The scan program's lowered text for a group of `n_blocks` one-page
    blocks whose tables carry `rows` rows, solo or fused over `fused`
    members, from shapes alone."""
    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    P = block_bucket(n_blocks)      # stage_host's page bucket
    E, C, T, R = GEOMETRY.entries_per_page, GEOMETRY.kv_per_entry, 2, 1
    cols = (S((P, E, C), jnp.int8), S((P, E, C), jnp.int16),
            S((P, E), jnp.uint32), S((P, E), jnp.uint32),
            S((P, E), jnp.uint32), S((P, E), jnp.bool_), S((P,), jnp.int32))
    if fused is None:
        tables = (S((rows, T), jnp.int32), S((rows, T, R, 2), jnp.int32),
                  None, *[S((), jnp.uint32)] * 4)
        packed = None
    else:
        packed = (fused, rows, T, R)
        tables = (S((multiblock._packed_slots(packed)[-1][1],), jnp.int32),
                  *[None] * 6)
    return batch_scan_kernel.lower(*cols, *tables, n_terms=T, top_k=128,
                                   packed=packed).as_text()


@pytest.mark.parametrize("fused", [None, 2], ids=["solo", "fused2"])
def test_a_full_group_lowers_to_the_exact_count_text(fused):
    """64 blocks are their own bucket: the tables `compile_multi` makes
    for a full group have the rows the exact-count form gave them, so
    the program text, and with it the persistent-cache key of every cell
    whose groups fill, is what it was. A 49-block tail is given 64 rows
    and the full group's page bucket: the same text, no key of its own."""
    g = Group(64, seed=2, entries=8)
    mq = compile_multi(g.blocks, _req(svc=1, status="500"))
    assert mq.term_keys.shape == (64, 2) == (len(g.blocks), 2)
    exact = _lowered(64, rows=64, fused=fused)
    assert _lowered(64, rows=block_bucket(64), fused=fused) == exact
    assert _lowered(49, rows=block_bucket(49), fused=fused) == exact
    # what the tail compiled before: a text of its own
    assert _lowered(49, rows=49, fused=fused) != exact
