"""What every launch of the scan shares, around the one program
(`multiblock.batch_scan_kernel`): the exact top-k (`masked_topk`,
`latest_k`) and its booking, the query parameters' residency on the
device(s), the single-sync fetch of a launch's outputs, and the CPU pin
of the host route.

The scan replaces the reference's per-entry FlatBuffer scan loops
(tempodb/search/backend_search_block.go:247-295, pipeline.go:86-97,
tempofb/searchdata_util.go:47-100) with one fused, jit-compiled kernel
over the dense columnar page layout:

  1. per kv-slot term match: (kv_key == term_key) & (kv_val in ranges)
     — value membership is an OR of inclusive [lo,hi] id-range compares;
     the host dictionary prefilter resolves substring semantics into
     sorted id sets and collapses them to ranges (pipeline.ids_to_ranges;
     a bitmap-gather variant measured 35ms/1M entries vs <5ms for ranges —
     gathers serialize on the VPU)
  2. kv → entry reduction: `any` over the per-entry kv-capacity axis —
     a lane reduction, NOT a scatter (scatters serialize on the VPU;
     this is the layout lesson baked into columnar.py)
  3. AND across terms (fori_loop, T static)
  4. duration / time-window compares on entry columns
  5. count + top-k by start time on device; only the top-k indices
     travel back to host

Shapes are static per (page-bucket, T, top_k) so XLA compiles once per
bucket and reuses; everything is int32/uint32/bool — VPU-native, no MXU
(this workload is bandwidth-bound; the win is fusion + vector width).
"""

from __future__ import annotations

import threading
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from tempo_tpu.observability import metrics as obs

DEFAULT_TOP_K = 128


def cpu_pinned():
    """Context pinning kernel execution to the CPU backend — the host
    route's execution context (batcher.host_scan) and the live tier's
    (live_tier.scan_search_data). Two consumers ride the host route: the breaker's fallback when the device is wedged, and the
    owner-routing layer's non-owner serve (search/ownership.py — a
    process that doesn't own a block group answers from here instead of
    staging a duplicate HBM copy). Platforms without a reachable cpu
    backend degrade to the default device (still correct; the point of
    the pin is to avoid a wedged accelerator)."""
    import contextlib

    try:
        cpu = jax.devices("cpu")[0]
    except Exception:  # noqa: BLE001 — odd platform sets
        cpu = None
    return (jax.default_device(cpu) if cpu is not None
            else contextlib.nullcontext())


def pack_out(count, inspected, scores, idx, *agg):
    """A launch's results as ONE int32 array, on the device, the last
    thing the scan program traces: a row [count, inspected, scores [k],
    idx [k]] of 2 + 2k words, and behind them the ?agg= counts [K] of a
    launch that reduces. A fused launch's results carry a leading [Q]
    (`inspected` alone does not: it is a property of the pages) and
    make one row a member, [Q, 2 + 2k (+ K)], `inspected` repeated.
    Every part is int32 as the scan makes it (masked_topk's clamped
    start seconds and flat indices, integer sums), so nothing is cast
    and nothing narrows. unpack_out is the same layout read on the
    host; these two functions are the only place it is written.

    The barrier keeps the scan in front of it the program it was when
    these parts were its outputs: without it the TPU compiler, seeing
    count and scores meet in one consumer, fuses the count into the
    mask's last pass, makes the scores in a pass of their own over a
    copy of entry_start, and a launch moves 1.6x the bytes behind its
    term loop (compiled for a v5e, PR 41; tests/test_scan_kernel_v5e.py
    holds the ops equal). With it the concatenate is one more fusion
    over 2 + 2k words."""
    count, inspected, scores, idx, *agg = jax.lax.optimization_barrier(
        (count, inspected, scores, idx, *agg))
    return jnp.concatenate(
        (count[..., None],
         jnp.broadcast_to(inspected, count.shape)[..., None],
         scores, idx, *agg), axis=-1)


def unpack_out(host: np.ndarray, n_agg: int = 0) -> tuple:
    """pack_out's array on the host, taken apart into views of it (no
    copy): (count, inspected, scores [k], idx [k]) of a solo launch's
    row, (counts [Q], inspected, scores [Q, k], idx [Q, k]) of a fused
    launch's rows; `inspected`, and a solo launch's count, as Python
    ints. `n_agg` is the K of a launch that reduced (?agg=), whose
    counts [K] ([Q, K]) come fifth; k is what the row's width leaves
    (masked_topk keeps min(top_k, entries))."""
    k = (host.shape[-1] - 2 - n_agg) // 2
    if host.ndim == 1:
        count, inspected = int(host[0]), int(host[1])
    else:
        count, inspected = host[:, 0], int(host[0, 1])
    out = (count, inspected, host[..., 2:2 + k], host[..., 2 + k:2 + 2 * k])
    if n_agg:
        out += (host[..., 2 + 2 * k:],)
    return out


def start_fetch(out) -> None:
    """Kick off the device→host copy of a launch's one output array
    without blocking: issued at dispatch time, the transfer overlaps
    later kernel work and the drain's fetch finds the bytes there."""
    try:
        out.copy_to_host_async()
    except Exception:  # noqa: BLE001 — fetch still works, just sync
        pass


def fetch_scan_out(out, n_agg: int = 0) -> tuple:
    """A launch's output, solo or fused, as host values (unpack_out's
    tuple): ONE blocking fetch of its one array, the launch's single
    synchronization point. A fused group's demux slices the host array,
    one D2H wait for the whole group, not Q."""
    return unpack_out(np.asarray(out), n_agg)


def resolve_top_k(base: int, limit: int) -> int:
    """top_k must cover the request limit or results get silently
    truncated below it; bucket to pow2 to bound recompiles. Shared by
    the solo and the coalesced dispatch so the SAME (limit → k) mapping
    keys every jit cache."""
    k = max(1, base)
    while k < limit:
        k *= 2
    return k


def topk_row_width(n: int, k: int) -> int:
    """Row width W of masked_topk's rows path for `n` scores of which
    `k` are kept, or 0 where it sorts the input directly: a pure
    function of the static shape, so the kernel and the host-side
    booking (book_topk) read the same choice. W is the power of two
    nearest sqrt(n / k) from below, at least the 128 lanes: n / W row
    maxima and k * W candidates are then about equal, 2 * sqrt(n * k)
    elements sorted instead of n. Inputs of up to 32,768 scores, and a
    k whose candidates would pass a quarter of the input, are sorted
    whole."""
    if n <= 32768 or k < 1:
        return 0
    w = 128
    while 4 * w * w * k <= n:
        w *= 2
    return w if 4 * k * w <= n else 0


def book_topk(rec, n: int, k: int) -> None:
    """Count one kernel launch under the top-k path its shape takes
    (`n` scores per masked_topk call: a shard's share under a mesh) and
    name the path on the launch's `dispatch.execute` span."""
    w = topk_row_width(n, min(k, n))
    obs.topk_dispatches.inc(path="rows" if w else "direct")
    rec.set(topk=f"rows:{w}" if w else "direct")


def latest_k(score, idx, k: int):
    """The first k of `score`, with their `idx`, in the order (score
    descending, idx ascending), along the last axis. One stable sort by
    score: equal scores keep the order they came in, so callers hand
    them over with `idx` ascending among equals. Stability is asked of
    the sort and not hoped of `lax.top_k`, which keeps the lower
    position only where it lowers to a stable sort: the TPU compiler
    splits a batched one (the fused kernels' under vmap) into
    value-only sorts that do not."""
    neg, idx = jax.lax.sort((-score, idx), num_keys=1, is_stable=True)
    return -neg[..., :k], idx[..., :k]


def masked_topk(mask, entry_start, top_k: int):
    """Top-k most recent matches (by start second); score -1 marks
    non-matches. Returns (scores i32 [k], flat idx i32 [k]), exactly
    the first k of a full sort by (score descending, flat index
    ascending): equal start seconds resolve to the lowest flat index,
    on every path, device or host route.

    A sort of the whole input is what a large one is spared. The flat
    scores are viewed as rows of W (topk_row_width) and a tournament on
    the row maxima picks k rows, by (maximum descending, row
    ascending). Every element of another row is at or below that row's
    maximum, which each of the k chosen rows matches or beats with an
    element that, on a tie, has the lower flat index: so the answer
    lies within the chosen k * W elements, and is the first k of them
    in the same order (taken row by row in ascending order, which is
    flat-index order)."""
    score = jnp.where(
        mask, jnp.minimum(entry_start, jnp.uint32(2**31 - 1)).astype(jnp.int32),
        jnp.int32(-1),
    ).reshape(-1)
    n = score.shape[0]
    k = min(top_k, n)
    w = topk_row_width(n, k)
    if not w:
        return latest_k(score, jnp.arange(n, dtype=jnp.int32), k)
    rows2d = jnp.pad(score, (0, -n % w), constant_values=-1).reshape(-1, w)
    _, rows = latest_k(jnp.max(rows2d, axis=1),
                       jnp.arange(rows2d.shape[0], dtype=jnp.int32), k)
    rows = jnp.sort(rows)
    flat = rows[:, None] * w + jnp.arange(w, dtype=jnp.int32)
    return latest_k(rows2d[rows].reshape(-1), flat.reshape(-1), k)


_SCALAR_CACHE: OrderedDict = OrderedDict()
_scalar_lock = threading.Lock()
_SCALAR_CACHE_MAX = 512


def device_scalar(v: int, mesh=None):
    """uint32 scalar as a device array, memoized by VALUE across
    dispatches and queries. Every compiled query uploads four of these
    (duration/window bounds) and the common values — 0 and UINT32_MAX
    for unbounded requests — recur on essentially every query, and each
    put is its own host→device transfer with a fixed per-call cost, so
    re-putting the same four scalars per query is avoidable overhead.
    Bounded LRU; jit treats equal-valued scalars identically, so sharing
    is invisible to the cache keys.

    `mesh`: where the launch that reads it runs. A scalar for a mesh is
    put on every device of it (parallel.mesh.put_replicated) and
    memoized under (value, mesh): a device-0 scalar handed to a mesh
    launch would be re-placed inside the locked call."""
    return _device_scalar(v, mesh)[0]


def _device_scalar(v: int, mesh) -> tuple:
    """device_scalar's (array, did this call transfer it)."""
    v = int(v)
    key = v if mesh is None else (v, mesh)
    with _scalar_lock:
        hit = _SCALAR_CACHE.get(key)
        if hit is not None:
            _SCALAR_CACHE.move_to_end(key)
            return hit, False
    if mesh is None:
        arr = jnp.uint32(v)
    else:
        from tempo_tpu.parallel.mesh import put_replicated

        arr = put_replicated(mesh, np.uint32(v))
    with _scalar_lock:
        _SCALAR_CACHE[key] = arr
        while len(_SCALAR_CACHE) > _SCALAR_CACHE_MAX:
            _SCALAR_CACHE.popitem(last=False)
    return arr, True


def query_device_params(mq, mesh=None):
    """Query params (term tables + the four scalar bounds) as device
    arrays, uploaded ONCE per query and cached on the MultiQuery — one
    search fans out over many groups with the same query, and every
    small H2D transfer pays a fixed per-call cost. The scalar bounds
    additionally memoize BY VALUE across queries (device_scalar), so a
    fresh query with the default unbounded window re-uploads nothing
    but its term tables.

    `mesh`: the mesh the query's launches run over, None off a mesh.
    Off a mesh the arrays are what they always were: uncommitted, on
    the default device. On a mesh they are put on every device of it
    once (parallel.mesh.put_replicated): the scan's in_specs want them
    replicated, and an array that is not is re-placed on every device
    by every launch, inside the collective lock. The cache holds the
    arrays of the last placement asked for, and their own sharding says
    which that was: a query that moves between an engine with a mesh
    and one without gets the right arrays from each. The host arrays
    that upload transferred (the two tables and the bounds the by-value
    memo did not hold) are counted beside it, `_device_params_puts`: the
    launch that made it books them
    (tempo_search_launch_param_puts_total)."""
    from tempo_tpu.parallel.mesh import placed_for, put_replicated

    cached = getattr(mq, "_device_params", None)
    if cached is not None and placed_for(cached[0], mesh):
        return cached
    bounds = (mq.dur_lo, min(mq.dur_hi, 0xFFFFFFFF),
              mq.win_start, min(mq.win_end, 0xFFFFFFFF))
    if mesh is None:
        tables = (jnp.asarray(mq.term_keys), jnp.asarray(mq.val_ranges))
    else:
        tables = put_replicated(
            mesh, (np.asarray(mq.term_keys), np.asarray(mq.val_ranges)))
    scalars = [_device_scalar(v, mesh) for v in bounds]
    cached = tables + tuple(arr for arr, _put in scalars)
    object.__setattr__(mq, "_device_params_puts",
                       len(tables) + sum(put for _arr, put in scalars))
    object.__setattr__(mq, "_device_params", cached)
    return cached
