"""A launch comes back as ONE int32 array (search/engine.py `pack_out`
on the device, `unpack_out` on the host: the only place the layout is
written): count, inspected, scores [k], idx [k] and the ?agg= counts
[K] behind them, a row a member for a fused launch. Until PR 41 a
launch returned four arrays, started four async copies and was fetched
by two `int()` and two `np.asarray`, eight runtime calls under the
interpreter lock, eight launches a scan search (PERF.md section 6).
Held here: the round trip is exact for solo and fused launches at every
Q, k and K; a launch starts one copy and its drain makes one fetch, on
every route (solo, fused, ?agg=, mesh), and the counter says so; a
fused group's members each read their own row of an array fetched
once; a fetch that faults reaches every member once; the host route
answers in the same form."""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tempo_tpu.observability import metrics as obs
from tempo_tpu.observability import tracing
from tempo_tpu.parallel import make_mesh
from tempo_tpu.search import batcher as batcher_mod
from tempo_tpu.search import coalescer as coalescer_mod
from tempo_tpu.search import engine as engine_mod
from tempo_tpu.search import multiblock as multiblock_mod
from tempo_tpu.search.analytics import ANALYTICS
from tempo_tpu.search.batcher import BlockBatcher, host_scan
from tempo_tpu.search.coalescer import _FusedOut, _FusedSlice
from tempo_tpu.search.engine import (fetch_scan_out, pack_out, resolve_top_k,
                                     start_fetch, unpack_out)
from tempo_tpu.search.multiblock import (MultiBlockEngine, compile_multi,
                                         stack_queries)

from tests.test_coalesce import _blocks, _jobs, _mk_req

MODES = ("batched", "coalesced", "mesh")


def _parts(rng, Q, k, K):
    """A launch's results as `_scan_pages` hands them to pack_out:
    every value of either sign and up to the int32 edges (a layout that
    cast or narrowed anything would show it). Q = 0: a solo launch."""
    lead = (Q,) if Q else ()
    i32 = np.iinfo(np.int32)

    def draw(*shape):
        a = rng.integers(i32.min, i32.max, shape, dtype=np.int32,
                         endpoint=True)
        a.reshape(-1)[:2] = (i32.min, i32.max)
        return a

    agg = (draw(*lead, K),) if K else ()
    return (draw(*lead) if Q else np.int32(rng.integers(0, i32.max)),
            np.int32(rng.integers(0, i32.max)), draw(*lead, k),
            draw(*lead, k), *agg)


@pytest.mark.parametrize("K", [0, 24])
@pytest.mark.parametrize("k", [128, 512])
@pytest.mark.parametrize("Q", [0, 2, 3, 4, 5, 8])
def test_the_packed_output_round_trip_is_exact(Q, k, K):
    """parts -> one int32 array on the device -> host views: each part
    comes back in shape and every bit; the width is 2 + 2k (+ K), one
    row a member; what comes back are views of the one fetched array."""
    parts = _parts(np.random.default_rng(1000 * Q + k + K), Q, k, K)
    packed = jax.jit(pack_out)(*(jnp.asarray(p) for p in parts))
    assert packed.dtype == jnp.int32
    assert packed.shape == ((Q,) if Q else ()) + (2 + 2 * k + K,)
    host = np.asarray(packed)
    got = unpack_out(host, K)
    assert len(got) == len(parts) == (5 if K else 4)
    count, inspected, *rows = got
    assert type(inspected) is int and inspected == int(parts[1])
    if Q:
        np.testing.assert_array_equal(count, parts[0])
        # `inspected` is a property of the pages: every row repeats it
        assert (host[:, 1] == inspected).all()
    else:
        assert type(count) is int and count == int(parts[0])
    for g, p in zip(rows, parts[2:]):
        assert g.dtype == np.int32 and g.shape == p.shape
        np.testing.assert_array_equal(g, p)
        assert np.shares_memory(g, host)


def test_a_short_batch_keeps_fewer_than_top_k_and_still_unpacks():
    """masked_topk keeps min(top_k, entries): the row's width, not the
    launch's static k, says how many scores came back."""
    blocks = _blocks(1, entries=40)
    eng = MultiBlockEngine(top_k=128)
    batch = eng.stage(blocks)
    mq = compile_multi(blocks, _mk_req({"service.name": "svc-1"}, limit=20))
    out = eng.scan_async(batch, mq)
    n = batch.device["entry_valid"].size
    assert n < 128 and out.shape == (2 + 2 * n,)
    count, inspected, scores, idx = fetch_scan_out(out)
    assert inspected == 40 and scores.shape == idx.shape == (n,)
    assert count == int((scores >= 0).sum()) > 0


class _Copies:
    """Stands where a launch's output array goes, counting the async
    copies `start_fetch` starts on it."""

    def __init__(self):
        self.copies = 0

    def copy_to_host_async(self):
        self.copies += 1


def test_start_fetch_starts_one_copy_and_survives_a_refusal():
    out = _Copies()
    start_fetch(out)
    assert out.copies == 1

    class Refuses:
        def copy_to_host_async(self):
            raise RuntimeError("no async copy on this backend")

    start_fetch(Refuses())  # the blocking fetch still works: no raise


@pytest.fixture
def fetches(monkeypatch):
    """Every `start_fetch` the batcher and the coalescer make and every
    blocking fetch the engine makes, with what each was given."""
    seen = {"copies": [], "fetches": []}
    real_start, real_fetch = batcher_mod.start_fetch, engine_mod.fetch_scan_out

    def start(out):
        seen["copies"].append(out)
        return real_start(out)

    def fetch(out, n_agg=0):
        seen["fetches"].append(out)
        return real_fetch(out, n_agg)

    monkeypatch.setattr(batcher_mod, "start_fetch", start)
    monkeypatch.setattr(coalescer_mod, "start_fetch", start)
    monkeypatch.setattr(multiblock_mod, "fetch_scan_out", fetch)
    return seen


def _moved(before):
    return {m: obs.launch_out_fetches.value(mode=m) - before[m]
            for m in MODES}


def _at():
    return {m: obs.launch_out_fetches.value(mode=m) for m in MODES}


@pytest.mark.parametrize("coalesce", [True, False])
def test_a_launch_starts_one_copy_and_its_drain_makes_one_fetch(
        coalesce, fetches):
    """A served search of several groups: every launch hands
    `start_fetch` ONE device array and every drain fetches ONE, through
    the coalescer's solo flush and through the direct path alike;
    `tempo_search_launch_out_fetches_total` rises by one a launch."""
    blocks = _blocks(4)
    b = BlockBatcher(max_batch_pages=blocks[0].n_pages * 2,
                     **({} if coalesce else {"coalesce_max_queries": 1}))
    assert (b.coalescer is not None) == coalesce
    req = _mk_req({"service.name": "svc-1"}, limit=100)
    before, d0 = _at(), obs.scan_dispatches.value(mode="batched")
    res = b.search(_jobs(blocks), req)
    launches = b.last_dispatches
    # (block ids decide where the groups are cut: two at least)
    assert launches >= 2 and res.metrics.inspected_traces == 800
    assert obs.scan_dispatches.value(mode="batched") - d0 == launches
    assert _moved(before) == {"batched": launches, "coalesced": 0, "mesh": 0}
    assert len(fetches["copies"]) == len(fetches["fetches"]) == launches
    for out in fetches["copies"] + fetches["fetches"]:
        assert isinstance(out, jax.Array) and out.dtype == jnp.int32
        assert out.shape == (2 + 2 * resolve_top_k(b.engine.top_k, 100),)


def test_a_fused_groups_drain_makes_one_fetch_for_all_its_members(fetches):
    """Members released together fuse: one launch, one copy, one fetch
    (by whichever member drains first), every answer its solo one; the
    syncs that found the array fetched say `out_fetches` 0 and the one
    that fetched says nothing."""
    blocks = _blocks(3, entries=150)
    jobs = _jobs(blocks)
    N = 4
    reqs = [_mk_req({"service.name": f"svc-{i}"}, limit=20)
            for i in range(N)]
    solo = BlockBatcher(coalesce_max_queries=1)
    want = [solo.search(list(jobs), r).response().SerializeToString()
            for r in reqs]
    b = BlockBatcher(coalesce_window_s=5.0, coalesce_max_queries=N)
    b.search(list(jobs), reqs[0])     # staged, and the solo key compiled
    del fetches["copies"][:], fetches["fetches"][:]
    before = _at()
    exporter = tracing.CollectExporter()
    tracing.set_tracer(tracing.Tracer(tracing.SyncProcessor(exporter)))
    try:
        got, barrier = [None] * N, threading.Barrier(N)

        def one(i):
            barrier.wait()
            with tracing.start_span("test.search"):
                got[i] = b.search(list(jobs), reqs[i]
                                  ).response().SerializeToString()

        threads = [threading.Thread(target=one, args=(i,)) for i in range(N)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
    finally:
        tracing.set_tracer(None)
    assert got == want
    # the window never waits for a peer: whoever arrives first may
    # flush alone, the others meet in one fused launch
    moved = _moved(before)
    launches = moved["batched"] + moved["coalesced"]
    assert moved["coalesced"] == 1 and moved["mesh"] == 0
    assert len(fetches["copies"]) == len(fetches["fetches"]) == launches
    assert {id(o) for o in fetches["copies"]} \
        == {id(o) for o in fetches["fetches"]}
    (fused,) = [o for o in fetches["fetches"] if o.ndim == 2]
    members = N - (launches - 1)
    assert fused.shape == (1 << (members - 1).bit_length(), 2 + 2 * 128)
    syncs = [s for s in exporter.spans if s.name == "batcher.sync"]
    assert sorted(s.attributes.get("out_fetches", 1) for s in syncs) \
        == [0] * (N - launches) + [1] * launches
    assert sum("out_fetches" in s.attributes for s in syncs) == N - launches


def test_an_agg_launch_rides_the_same_array(tmp_path, fetches):
    """?agg= counts come behind the rest of the row: the drain of a
    launch that reduces still fetches ONE host array (the choice of
    PR 41: no second output), solo and fused."""
    import json

    from tests.test_analytics import (_corpus, _mk_req as agg_req, _mkdb,
                                      _pred, _ref_series)
    from tempo_tpu.search.analytics import agg_response

    entries = _corpus(41, n=120)
    db = _mkdb(tmp_path, entries)
    try:
        before = _at()
        resp = db.search("t", agg_req({"env": "prod"}, limit=1000)).response()
        assert json.loads(resp.metrics.agg_json) == agg_response(
            _ref_series(entries, _pred({"env": "prod"})))
        launches = db.batcher.last_dispatches
        assert launches >= 1
        assert _moved(before)["batched"] == launches
        assert len(fetches["fetches"]) == launches
        cache = db.batcher.cache
        (gkey, *_rest) = cache.snapshot()["entries"]
        K = ANALYTICS.stage_for_batch(cache.resident(gkey).batch).n_keys
        for out in fetches["fetches"]:
            assert out.ndim == 1 and out.shape[0] > 2 + K
            assert (out.shape[0] - 2 - K) % 2 == 0
    finally:
        ANALYTICS.configure(enabled=False)


def test_a_mesh_launch_leaves_the_shard_map_as_one_replicated_array():
    """On a mesh the packing happens inside the shard_map: ONE array,
    whole on every device (one transfer where there were four), counted
    under `mesh`, the answer the one-device engine's."""
    blocks = _blocks(3)
    req = _mk_req({"service.name": "svc-2"}, limit=50)
    one = MultiBlockEngine(top_k=128)
    want = one.scan(one.stage(blocks), compile_multi(blocks, req))
    eng = MultiBlockEngine(top_k=128, mesh=make_mesh(4))
    batch = eng.stage(blocks)
    mqs = [compile_multi(blocks, r, cache_on=batch)
           for r in (req, _mk_req({"http.status_code": "500"}, limit=50))]
    before = _at()
    out = eng.scan_async(batch, mqs[0])
    assert isinstance(out, jax.Array) and out.is_fully_replicated
    assert out.shape == (2 + 2 * 128,) and len(out.devices()) == 4
    got = eng.fetch(out, mqs[0])
    assert got[:2] == want[:2]
    np.testing.assert_array_equal(got[2], want[2])
    np.testing.assert_array_equal(got[3], want[3])
    cq = stack_queries(mqs)
    fused = eng.coalesced_scan_async(batch, cq, 128)
    assert fused.is_fully_replicated and fused.shape == (2, 2 + 2 * 128)
    counts, inspected, scores, idx = eng.fetch(fused, cq)
    assert (int(counts[0]), inspected) == want[:2]
    np.testing.assert_array_equal(scores[0], want[2])
    np.testing.assert_array_equal(idx[0], want[3])
    assert _moved(before) == {"batched": 0, "coalesced": 0, "mesh": 2}


class _Engine:
    """An engine whose fetch fails, counting how often it is asked."""

    def __init__(self, exc):
        self.exc, self.asked = exc, 0

    def fetch(self, out, q):
        self.asked += 1
        raise self.exc


def test_a_faulted_fused_fetch_reaches_every_member_once():
    """The claimer's fetch dies: the engine was asked ONCE, and every
    member's slice raises that same fault, the claimer's and those
    parked on it alike, every time it is read (each member's drain then
    answers its own query on the host route)."""
    fault = RuntimeError("device lost mid-copy")
    eng = _Engine(fault)
    shared = _FusedOut(object(), eng, object())
    slices = [_FusedSlice(shared, qi) for qi in range(4)]
    raised = [None] * 4
    barrier = threading.Barrier(4)

    def drain(qi):
        barrier.wait()
        try:
            slices[qi].fetch()
        except RuntimeError as e:
            raised[qi] = e

    threads = [threading.Thread(target=drain, args=(qi,)) for qi in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not any(t.is_alive() for t in threads)
    assert eng.asked == 1 and all(e is fault for e in raised)
    with pytest.raises(RuntimeError) as again:
        slices[0].fetch()
    assert again.value is fault and eng.asked == 1


def test_the_host_route_answers_in_the_same_form():
    """`batcher.host_scan` runs the same program pinned to the CPU and
    takes its one array apart the same way: the drain's tuple, value
    for value the device route's."""
    blocks = _blocks(2)
    eng = MultiBlockEngine(top_k=128)
    req = _mk_req({"service.name": "svc-3"}, limit=20, min_duration_ms=500)
    want = eng.scan(eng.stage(blocks), compile_multi(blocks, req))
    host = eng.stage_host(blocks)
    mq = compile_multi(blocks, req, cache_on=host, host_only=True)
    before = _at()
    got = host_scan(host, mq, resolve_top_k(eng.top_k, mq.limit))
    assert got[:2] == want[:2] and type(got[0]) is type(got[1]) is int
    np.testing.assert_array_equal(got[2], want[2])
    np.testing.assert_array_equal(got[3], want[3])
    # no launch of the device route, so nothing on its counter
    assert _moved(before) == dict.fromkeys(MODES, 0)
