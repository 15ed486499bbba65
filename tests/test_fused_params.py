"""A fused launch's seven per-query tables travel as ONE int32 buffer
(search/multiblock.py `stack_queries` lays it out, `unpack_queries`
takes it apart on the device in front of the scan): every small
host-to-device transfer pays a fixed per-call cost, and seven of them
were the largest named piece of a scan search's host time (PERF.md
section 6, PR 39). Held here: the round trip is exact, bit for bit, for
every table at every (Q, T, R) bucket; a fused launch through the packed
operand answers as its members do solo, with and without a member that
brings a hit mask; the launch transfers one host array in `build` and
nothing in its kernel call; a solo launch's parameters are counted once,
when they are put."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tempo_tpu.observability import metrics as obs
from tempo_tpu.search import dict_probe, multiblock, pipeline
from tempo_tpu.search.engine import fetch_scan_out, resolve_top_k
from tempo_tpu.search.multiblock import (
    MultiBlockEngine,
    MultiQuery,
    _packed_slots,
    compile_multi,
    stack_queries,
    unpack_queries,
)

from tests.test_coalesce import _blocks, _mk_req

BLOCKS = 5
# the bounds a uint32 must carry through an int32 buffer as they went
EDGES = (0, 1, 2**31 - 1, 2**31, 2**31 + 1, 0xFFFFFFFF)


def _member(rng, T, R, bounds):
    """A MultiQuery of T real terms and R ranges a term over BLOCKS
    blocks, every table random (ids of either sign: -1 is the pruned
    key, and a buffer that value-cast anything would show it)."""
    return MultiQuery(
        term_keys=rng.integers(-1, 2**31 - 1, (BLOCKS, max(1, T)),
                               dtype=np.int32),
        val_ranges=rng.integers(-2**31, 2**31 - 1,
                                (BLOCKS, max(1, T), R, 2), dtype=np.int32),
        dur_lo=bounds[0], dur_hi=bounds[1], win_start=bounds[2],
        win_end=bounds[3], limit=20, n_terms=T)


@pytest.mark.parametrize("R", [1, 2, 16, 32, 512])
@pytest.mark.parametrize("T", [0, 1, 2, 3])
@pytest.mark.parametrize("Qn", [2, 3, 5])
def test_the_packed_round_trip_is_exact(Qn, T, R):
    """host tables -> one buffer -> device -> seven tables: each equals
    what `stack_queries` stacked, in dtype, shape and every bit; the
    layout is a function of (Q, B, T, R) alone; pad queries and pad
    terms stay dead."""
    rng = np.random.default_rng(1000 * Qn + 10 * T + R)
    # members of fewer terms and narrower ranges than the widest: the
    # stack pads them
    mqs = [_member(rng, T if i == 0 else max(0, T - i % 2),
                   R if i == 0 else max(1, R >> (i % 3)),
                   [EDGES[(i + j) % len(EDGES)] for j in range(4)])
           for i in range(Qn)]
    cq = stack_queries(mqs)
    Q, B, Tp, Rp = cq.dims
    assert (Q, B) == (1 << (Qn - 1).bit_length(), BLOCKS)
    assert Tp >= max(1, T) and Rp >= R and Tp & (Tp - 1) == Rp & (Rp - 1) == 0
    assert cq.packed.dtype == np.int32 and cq.packed.ndim == 1
    assert cq.packed.size == _packed_slots(cq.dims)[-1][1] \
        == Q * B * Tp * (1 + 2 * Rp) + Q * Tp + 4 * Q
    host = (cq.term_keys, cq.val_ranges, cq.term_active, cq.dur_lo,
            cq.dur_hi, cq.win_start, cq.win_end)
    # no second copy of a table: each is a view of the one buffer
    for t in host[:2] + host[3:]:
        assert np.shares_memory(t, cq.packed)
    got = jax.jit(unpack_queries, static_argnums=1)(
        jnp.asarray(cq.packed), cq.dims)
    want_dtypes = ["int32", "int32", "bool"] + ["uint32"] * 4
    for g, h, dt in zip(got, host, want_dtypes):
        assert str(g.dtype) == str(h.dtype) == dt and g.shape == h.shape
        np.testing.assert_array_equal(np.asarray(g), h)
    # and what was stacked is what the members brought
    for qi, mq in enumerate(mqs):
        t_n, r_n = mq.term_keys.shape[1], mq.val_ranges.shape[2]
        np.testing.assert_array_equal(got[0][qi, :, :t_n], mq.term_keys)
        np.testing.assert_array_equal(got[1][qi, :, :t_n, :r_n],
                                      mq.val_ranges)
        assert np.asarray(got[2][qi]).tolist() == (
            [True] * mq.n_terms + [False] * (Tp - mq.n_terms))
        assert [int(g[qi]) for g in got[3:]] == [
            mq.dur_lo, mq.dur_hi, mq.win_start, mq.win_end]
        # a pad term's key is the pruned one and its ranges hold nothing
        assert (np.asarray(got[0][qi, :, t_n:]) == -1).all()
        assert (np.asarray(got[1][qi, :, t_n:]) == (1, 0)).all()
        assert (np.asarray(got[1][qi, :, :, r_n:]) == (1, 0)).all()
    for qi in range(Qn, Q):
        # a pad query: no key, no active term, an empty duration range
        assert (np.asarray(got[0][qi]) == -1).all()
        assert not np.asarray(got[2][qi]).any()
        assert int(got[3][qi]) == 1 and int(got[4][qi]) == 0


@pytest.fixture
def masks(monkeypatch):
    """Every device probe's product leaves as a hit mask
    (`dict_probe.R_MAX` at 0), the compile cache empty around it."""
    monkeypatch.setattr(dict_probe, "R_MAX", 0)
    pipeline._COMPILE_CACHE.clear()
    yield
    pipeline._COMPILE_CACHE.clear()


def _reqs():
    return [
        _mk_req({"service.name": "svc-1"}, limit=20),
        _mk_req({"service.name": "svc-2", "http.status_code": "500"},
                limit=50, min_duration_ms=100),
        _mk_req({"http.status_code": "404"}, limit=5,
                max_duration_ms=25_000),
    ]


@pytest.fixture
def kernel_calls(monkeypatch):
    """Every launch's kernel call under `jax.transfer_guard("disallow")`
    (an operand the call would have to move first raises): the operands
    and statics each was given, and the program's name."""
    calls = []

    def guarded(name, real):
        def call(*tables, **statics):
            calls.append((tables, statics, name))
            with jax.transfer_guard("disallow"):
                return real(*tables, **statics)
        # mask_scan_kernel traces batch_scan_kernel's body by this
        call.__wrapped__ = real.__wrapped__
        return call

    for name in ("batch_scan_kernel", "mask_scan_kernel"):
        monkeypatch.setattr(multiblock, name,
                            guarded(name, getattr(multiblock, name)))
    return calls


@pytest.mark.parametrize("member", ["ranges", "val_hits"])
def test_a_fused_launch_answers_as_its_members_solo(member, request,
                                                    kernel_calls):
    """Counts, scores and flat indices of every member through the
    packed operand equal its solo launch's; the pad lane counts
    nothing. `val_hits`: one member brings a hit mask (its two operands
    travel as they did), the others host ranges."""
    if member == "val_hits":
        request.getfixturevalue("masks")
    blocks = _blocks(3)
    eng = MultiBlockEngine(top_k=128, device_probe_min_vals=(
        1 if member == "val_hits" else None))
    batch = eng.stage(blocks)
    reqs = _reqs()
    mqs = [compile_multi(blocks, r,
                         cache_on=batch if i == 0 else None)
           for i, r in enumerate(reqs)]
    assert (mqs[0].val_hits is not None) == (member == "val_hits")
    assert all(mq.val_hits is None for mq in mqs[1:])
    serial = [eng.scan(batch, mq) for mq in mqs]
    assert any(c for c, *_ in serial)
    del kernel_calls[:]
    cq = stack_queries(mqs)
    k = max(resolve_top_k(eng.top_k, mq.limit) for mq in mqs)
    counts, inspected, scores, idx = fetch_scan_out(
        eng.coalesced_scan_async(batch, cq, k))
    assert counts.shape == (4,) and counts[3] == 0
    for qi, (c, ins, s, i) in enumerate(serial):
        assert int(counts[qi]) == c and inspected == ins
        kq = s.shape[0]
        np.testing.assert_array_equal(scores[qi][:kq], s)
        np.testing.assert_array_equal(idx[qi][:kq], i)
    # one kernel call, given the buffer where the seven tables were
    (tables, statics, _name), = kernel_calls
    assert statics["packed"] == cq.dims
    buf, *rest = tables[7:14]
    assert rest == [None] * 6 and buf.shape == cq.packed.shape
    assert (tables[14] is not None) == (member == "val_hits")


@pytest.mark.parametrize("member", ["ranges", "val_hits"])
def test_a_fused_launch_transfers_one_array_in_build(member, request,
                                                     kernel_calls,
                                                     monkeypatch):
    """Off a mesh a fused launch hands `_place_params` ONE host array
    for its seven tables (the parent handed it seven) and the counter
    says so; the kernel call moves nothing. A member's hit mask is on
    the device already: its block -> group rows are the one more."""
    if member == "val_hits":
        request.getfixturevalue("masks")
    blocks = _blocks(2)
    eng = MultiBlockEngine(top_k=128, device_probe_min_vals=(
        1 if member == "val_hits" else None))
    batch = eng.stage(blocks)
    mqs = [compile_multi(blocks, r, cache_on=batch) for r in _reqs()[:2]]
    placed = []
    real = MultiBlockEngine._place_params

    def counting(self, tables):
        placed.append([t for t in tables if isinstance(t, np.ndarray)])
        return real(self, tables)

    monkeypatch.setattr(MultiBlockEngine, "_place_params", counting)
    before = {m: obs.launch_param_puts.value(mode=m)
              for m in ("batched", "coalesced", "mesh")}
    cq = stack_queries(mqs)
    fetch_scan_out(eng.coalesced_scan_async(batch, cq, 128))
    moved = {m: obs.launch_param_puts.value(mode=m) - before[m]
             for m in before}
    want = 1 if member == "ranges" else 2
    assert moved == {"batched": 0, "coalesced": want, "mesh": 0}
    (host,), (_call,) = placed, kernel_calls
    assert len(host) == want and host[0] is cq.packed
    assert all(isinstance(t, jax.Array) for t in _call[0] if t is not None)


def test_a_solo_launch_counts_its_parameters_when_it_puts_them(kernel_calls):
    """`tempo_search_launch_param_puts_total{mode="batched"}`: the two
    tables and the bounds the by-value memo did not hold on a
    predicate's first launch, nothing on its second."""
    blocks = _blocks(2)
    eng = MultiBlockEngine(top_k=128)
    batch = eng.stage(blocks)
    # bounds no other test of this process has memoised
    mq = compile_multi(blocks, _mk_req(
        {"service.name": "svc-3"}, min_duration_ms=39_001,
        max_duration_ms=39_002, start=1_600_039_001, end=1_600_039_002))
    reads = []
    for _ in range(2):
        at = obs.launch_param_puts.value(mode="batched")
        eng.scan(batch, mq)
        reads.append(obs.launch_param_puts.value(mode="batched") - at)
    assert reads == [6, 0] and len(kernel_calls) == 2
    # the same bounds under another predicate: memoised by value
    again = compile_multi(blocks, _mk_req(
        {"service.name": "svc-4"}, min_duration_ms=39_001,
        max_duration_ms=39_002, start=1_600_039_001, end=1_600_039_002))
    at = obs.launch_param_puts.value(mode="batched")
    eng.scan(batch, again)
    assert obs.launch_param_puts.value(mode="batched") - at == 2
