"""Where a mesh launch's query parameters live, and what the lock holds.

A launch over a mesh runs inside the process-wide collective lock
(`parallel/mesh.py locked_collective`). Its replicated operands (the
`P()` entries of the dist kernels' in_specs: term tables, bounds, the
fused launch's stacked tables) are put on every device of the mesh
before the lock is taken, once per predicate, so the locked section is
the enqueue of an already-placed call. Held here on the virtual CPU
devices: nothing is transferred under the lock, the parameters have the
sharding the launch wants and are cached by it, the counter and the span
attribute say `placed` once and `reused` thereafter, and the batcher
charges a replicated table once per device.
"""

import contextlib
from concurrent.futures import ThreadPoolExecutor

import jax
import numpy as np
import pytest

from tempo_tpu.observability import metrics as obs
from tempo_tpu.observability import tracing
from tempo_tpu.parallel import make_mesh
from tempo_tpu.parallel import mesh as mesh_mod
from tempo_tpu.search.engine import device_scalar, query_device_params

from test_mesh_served import _tied_blocks, ask, corpus, make_app  # noqa: F401


@pytest.fixture
def guarded_lock(monkeypatch):
    """`locked_collective` with `jax.transfer_guard("disallow")` around
    what it holds: an operand the call has to move first (an implicit
    transfer) raises instead. Yields the list of launches that entered."""
    entered = []
    real = mesh_mod.locked_collective

    @contextlib.contextmanager
    def guarded(rec=None):
        with real(rec), jax.transfer_guard("disallow"):
            entered.append(rec)
            yield

    monkeypatch.setattr(mesh_mod, "locked_collective", guarded)
    return entered


def _tied_queries(blocks, limit=64):
    from tempo_tpu import tempopb
    from tempo_tpu.search.multiblock import compile_multi

    mqs = []
    for parity in ("odd", "even"):
        req = tempopb.SearchRequest()
        req.limit = limit
        req.tags["parity"] = parity
        mqs.append(compile_multi(blocks, req))
    return mqs


@pytest.mark.parametrize("kind", ("solo", "fused"))
def test_a_mesh_launch_transfers_nothing_under_the_lock(kind, guarded_lock,
                                                        monkeypatch):
    """With a predicate's parameters resident on the mesh, a solo and a
    fused launch run their locked call under
    `jax.transfer_guard("disallow")`: every operand is already where the
    executable reads it. On the parent's placement (PR 26:
    `query_device_params` made uncommitted arrays on device 0 whatever
    the engine's mesh, the fused path `jnp.asarray`ed its tables) this
    test fails: jit re-places each of them on the four devices inside
    the call, and the guard raises `Disallowed device-to-device
    transfer`. The second half puts that placement back and expects the
    raise."""
    from tempo_tpu.search.engine import fetch_scan_out
    from tempo_tpu.search.multiblock import MultiBlockEngine, stack_queries

    blocks = _tied_blocks(5, 100)
    eng = MultiBlockEngine(top_k=64, mesh=make_mesh(4))
    batch = eng.stage(blocks)
    mqs = _tied_queries(blocks)

    def launch():
        if kind == "solo":
            # the first launch places, the second finds them resident
            eng.scan(batch, mqs[0])
            return [int(eng.scan(batch, mqs[0])[0])]
        counts = fetch_scan_out(
            eng.coalesced_scan_async(batch, stack_queries(mqs), 64))[0]
        return [int(c) for c in counts]

    assert launch() == ([250] if kind == "solo" else [250, 250])
    assert guarded_lock and all(r is not None for r in guarded_lock)

    # the parent's placement: device 0, uncommitted
    from tempo_tpu.search import multiblock

    monkeypatch.setattr(
        multiblock, "query_device_params",
        lambda mq, mesh=None: query_device_params(mq, None))
    monkeypatch.setattr(
        MultiBlockEngine, "_place_params",
        lambda self, tables: tuple(
            None if t is None else jax.numpy.asarray(t) for t in tables))
    with pytest.raises(Exception, match="Disallowed .* transfer"):
        launch()


def test_a_fused_mesh_launch_puts_one_array(guarded_lock, monkeypatch):
    """A fused launch's seven tables reach the mesh as ONE replicated
    array, put in `build`, before the lock (the parent put seven: 28
    transfers on four devices); the counter counts arrays, not device
    copies; the locked call moves nothing."""
    from tempo_tpu.search.engine import fetch_scan_out
    from tempo_tpu.search.multiblock import MultiBlockEngine, stack_queries

    blocks = _tied_blocks(5, 100)
    mesh = make_mesh(4)
    eng = MultiBlockEngine(top_k=64, mesh=mesh)
    batch = eng.stage(blocks)
    cq = stack_queries(_tied_queries(blocks))
    put, real = [], mesh_mod.put_replicated

    def counting(m, tree):
        assert not guarded_lock, "put under the collective lock"
        put.extend(t for t in jax.tree_util.tree_leaves(tree)
                   if isinstance(t, np.ndarray))
        return real(m, tree)

    monkeypatch.setattr(mesh_mod, "put_replicated", counting)
    before = obs.launch_param_puts.value(mode="mesh")
    counts = fetch_scan_out(
        eng.coalesced_scan_async(batch, cq, 64))[0]
    assert [int(c) for c in counts] == [250, 250]
    assert len(put) == 1 and put[0] is cq.packed
    assert obs.launch_param_puts.value(mode="mesh") - before == 1
    assert len(guarded_lock) == 1


def test_served_mesh_searches_under_the_guard_equal_the_reference(
        corpus, tmp_path, guarded_lock):  # noqa: F811
    """The served path on a mesh of four, every locked call under the
    transfer guard: solo launches, then all requests at once (fused
    launches over the same groups), each answer held to the plain
    reference, none answered by the host after a fault."""
    from chipbench.ops import search as op
    from tempo_tpu.api import HTTPApi

    app = make_app(corpus, 4, tmp_path)
    before = {m: obs.scan_dispatches.value(mode=m, shards=4)
              for m in ("batched", "coalesced")}
    fallback = obs.scan_dispatches.value(mode="host_fallback")
    faults = obs.device_faults.value()
    try:
        api = HTTPApi(app, multitenancy=True)
        answers = [ask(api, r) for r in corpus["requests"]]
        with ThreadPoolExecutor(len(corpus["requests"])) as pool:
            answers += list(pool.map(lambda r: ask(api, r),
                                     corpus["requests"]))
    finally:
        app.shutdown()
    for r, a in zip(corpus["requests"] * 2, answers):
        ok, why = op.check(r, a, corpus["manifest"])
        assert ok, (r["path"], why)
    launches = sum(obs.scan_dispatches.value(mode=m, shards=4) - before[m]
                   for m in before)
    assert launches > 0 and len(guarded_lock) == launches
    assert obs.scan_dispatches.value(mode="host_fallback") == fallback
    assert obs.device_faults.value() == faults


def _query():
    from tempo_tpu import tempopb
    from tempo_tpu.search.multiblock import compile_multi

    blocks = _tied_blocks(2, 20)
    req = tempopb.SearchRequest()
    req.tags["parity"] = "odd"
    req.min_duration_ms = 3
    return compile_multi(blocks, req)


@pytest.mark.parametrize("placements", (
    (None,), (2,), (4,), (None, 4), (4, None, 4)),
    ids=("off-a-mesh", "mesh-of-2", "mesh-of-4", "off-then-on",
         "on-off-on"))
def test_query_device_params_live_where_the_launch_runs(placements):
    """`query_device_params` by placement, with ONE query object through
    all of `placements` in turn: off a mesh single-device arrays as
    before (uncommitted, the default device), on a mesh every array
    replicated over that mesh; a second call is the same tuple; and the
    by-value scalar memo never hands a device-0 scalar to a mesh."""
    mq = _query()
    want = (np.asarray(mq.term_keys), np.asarray(mq.val_ranges),
            mq.dur_lo, min(mq.dur_hi, 0xFFFFFFFF),
            mq.win_start, min(mq.win_end, 0xFFFFFFFF))
    device_scalar(mq.dur_lo)           # the value is memoised off a mesh
    for n in placements:
        mesh = None if n is None else make_mesh(n)
        params = query_device_params(mq, mesh)
        assert query_device_params(mq, mesh) is params
        assert len(params) == 6
        for got, w in zip(params, want):
            np.testing.assert_array_equal(np.asarray(got), w)
            if mesh is None:
                assert len(got.sharding.device_set) == 1
                assert not got.committed
            else:
                assert got.sharding == mesh_mod.replicated(mesh)
                assert got.committed and got.is_fully_replicated
                assert len(got.sharding.device_set) == n
        # scalars are shared by value AND placement
        assert device_scalar(mq.dur_lo, mesh) is params[2]


def test_off_a_mesh_the_params_are_what_they_were():
    """The one-chip path: the same constructors as ever (`jnp.asarray`
    of the tables, `jnp.uint32` scalars from the by-value memo), dtypes
    unchanged."""
    mq = _query()
    params = query_device_params(mq)
    assert [str(a.dtype) for a in params] == ["int32", "int32"] + ["uint32"] * 4
    assert [a.shape for a in params[2:]] == [()] * 4
    assert params[2] is device_scalar(mq.dur_lo)
    assert getattr(mq, "_device_params") is params


def test_placed_once_then_reused_and_charged_once_per_device(
        corpus, tmp_path):  # noqa: F811
    """One memoised predicate searched four times on a mesh of four: per
    group the first launch reads `placed`, every later one `reused`
    (`tempo_search_mesh_param_placements_total`, and `params` on the
    launch's `dispatch.execute`); the batcher charges the predicate's
    tables four times their one-device bytes. Off a mesh the counter
    does not move and the span has no `params`."""
    from chipbench.ops import search as op
    from tempo_tpu.api import HTTPApi

    request = next(r for r in corpus["requests"]
                   if r["ref"].get("exhaustive"))
    bytes_by_shards = {}
    for shards in (1, 4):
        # 12 pages a group on either: the same groups, so the same
        # tables, and only the placement differs
        app = make_app(corpus, shards, tmp_path / str(shards),
                       pages=12 // shards)
        collector = tracing.CollectExporter()
        tracing.set_tracer(tracing.Tracer(tracing.SyncProcessor(collector)))
        before = {r: obs.mesh_param_placements.value(result=r)
                  for r in ("placed", "reused")}
        try:
            api = HTTPApi(app, multitenancy=True)
            for _ in range(4):
                ok, why = op.check(request, ask(api, request),
                                   corpus["manifest"])
                assert ok, why
            cache = app.reader_db.batcher.cache
            memo = [pre for c in map(cache.resident,
                                     cache.snapshot()["entries"])
                    for pre in c.query_cache.values()
                    if pre.get("device_params") is not None]
        finally:
            tracing.set_tracer(None)
            app.shutdown()
        moved = {r: obs.mesh_param_placements.value(result=r) - before[r]
                 for r in before}
        executes = [s for s in collector.spans
                    if s.name in ("dispatch.execute", "dispatch.compile")
                    and s.attributes.get("jit_cache")]
        groups = len(memo)
        assert groups >= 2 and executes
        bytes_by_shards[shards] = sorted(
            pre["device_params_bytes"] for pre in memo)
        if shards == 1:
            assert moved == {"placed": 0, "reused": 0}
            assert all("params" not in s.attributes for s in executes)
            continue
        # a group's launches: one placed, three reused
        assert moved == {"placed": groups, "reused": 3 * groups}
        # a mesh launch has two spans of the stage: the kernel call
        # under the lock, and the fence after it
        said = [s.attributes["params"] for s in executes]
        assert sorted(said) == (["placed"] * 2 * groups
                                + ["reused"] * 6 * groups)
        for pre in memo:
            assert all(a.sharding == mesh_mod.replicated(app.reader_db.mesh)
                       for a in pre["device_params"])
    assert bytes_by_shards[4] == [4 * b for b in bytes_by_shards[1]]
