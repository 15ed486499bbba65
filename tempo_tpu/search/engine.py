"""The JAX scan engine — the north-star hot path on device.

Replaces the reference's per-entry FlatBuffer scan loops
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

import functools
import threading
from collections import OrderedDict
from dataclasses import dataclass

import time

import jax
import jax.numpy as jnp
import numpy as np

from tempo_tpu.observability import metrics as obs
from tempo_tpu.observability import profile

from .columnar import ColumnarPages
from .pipeline import CompiledQuery
from . import packing
from .packing import duration_ok, mask_select, unpack_ids

DEFAULT_TOP_K = 128


@dataclass
class StagedPages:
    """A block's columnar arrays resident on device (the HBM cache tier),
    plus the host-side bits needed to render results."""
    device: dict          # name -> jnp array, page axis padded to bucket
    n_pages: int          # real (unpadded) page count
    pages: ColumnarPages  # host container (dicts, trace ids, header)
    # dict_probe.DeviceDict when the value dictionary cleared the
    # device-probe threshold at staging time — query compilation then
    # runs the substring probe ON DEVICE (pipeline._device_probe_tags)
    # instead of the host memmem walk
    staged_dict: object = None
    # packed-residency width descriptor (search/packing.py) — static
    # per staged block, part of the scan kernel's jit shape key; None
    # = the unpacked legacy layout
    widths: tuple | None = None
    # structural-engine span columns on device (search/structural.py),
    # staged only when search_structural_enabled AND the container
    # carries spans; None keeps the legacy kernel signature pytree
    span_device: dict | None = None


DEVICE_ARRAYS = ("kv_key", "kv_val", "entry_start", "entry_end",
                 "entry_dur", "entry_valid")


def _bucket(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


def cpu_pinned():
    """Context pinning kernel execution to the CPU backend — the host
    route's execution context, shared by the batched (batcher.host_scan)
    and single-block (backend_search_block.host_scan_single) paths so
    their byte-identity-critical plumbing cannot diverge. Two consumers
    ride it: the breaker's fallback when the device is wedged, and the
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


def pad_page_axis(pages: ColumnarPages, target: int) -> dict:
    """Numpy arrays with the page axis padded to `target` rows; padding is
    invalid entries / -1 kv slots."""
    out = {}
    P = pages.n_pages
    for name in DEVICE_ARRAYS:
        arr = getattr(pages, name)
        if target > P:
            pad = np.zeros((target - P,) + arr.shape[1:], dtype=arr.dtype)
            if name in ("kv_key", "kv_val"):
                pad -= 1
            arr = np.concatenate([arr, pad], axis=0)
        out[name] = arr
    return out


def stage(pages: ColumnarPages, page_bucket: int | None = None,
          probe_min_vals: int | None = None) -> StagedPages:
    """Move a block's columns to device, padding the page axis to a
    power-of-two bucket so jit compiles once per bucket.

    `probe_min_vals`: value-dictionary size at which the packed
    dictionary bytes stage alongside the columns for the on-device
    substring probe (None = dict_probe.DEVICE_PROBE_MIN_VALS; <= 0
    disables). The threshold is applied HERE, at staging time — query
    compilation just uses whatever was staged."""
    B = page_bucket or _bucket(pages.n_pages)
    host = pad_page_axis(pages, B)
    widths = None
    if packing.PACKING.enabled:
        # packed residency: the single-block staging packs the SAME
        # per-column widths the batched stack_host would choose for a
        # one-block batch (search/packing.py)
        widths = packing.PACKING.plan_widths(
            len(pages.key_dict), len(pages.val_dict), pages.max_dur_ms())
        if widths is not None:
            host = packing.pack_columns(host, widths)
    from .structural import STRUCTURAL

    span_host = None
    if STRUCTURAL.enabled:
        # structural span segment rides the same staging (gate off =
        # zero extra work and the identical device pytree)
        span_host = STRUCTURAL.stage_single(pages, B)
    t0 = time.perf_counter()
    dev = {k: jnp.asarray(v) for k, v in host.items()}
    span_dev = (None if span_host is None
                else {k: jnp.asarray(v) for k, v in span_host.items()})
    profile.observe_stage("h2d", "single", time.perf_counter() - t0,
                          nbytes=sum(int(v.nbytes) for v in host.values())
                          + (0 if span_host is None else
                             sum(int(v.nbytes)
                                 for v in span_host.values())))
    sd = stage_block_dict(pages, probe_min_vals)
    return StagedPages(device=dev, n_pages=pages.n_pages, pages=pages,
                       staged_dict=sd, widths=widths,
                       span_device=span_dev)


def stage_block_dict(pages: ColumnarPages, probe_min_vals: int | None,
                     n_shards: int = 1, mesh=None):
    """DeviceDict for one block's value dictionary when it clears the
    device-probe threshold, else None. Shared by the single-block stage,
    the batched stack_host staging, and the distributed engine
    (n_shards/mesh shard the value axis).

    The static threshold is the FLOOR: below it (or <= 0) the probe
    stays on host unconditionally. Above it, the offload planner — when
    enabled — can veto the staging ("host" decision), so a CPU-bound
    process never uploads hundreds of MB of dictionary bytes the probe
    kernel would lose on anyway; planner disabled keeps the static
    behavior exactly."""
    from . import dict_probe, planner
    from .pipeline import _dict_fingerprint

    mv = (dict_probe.DEVICE_PROBE_MIN_VALS if probe_min_vals is None
          else probe_min_vals)
    if mv <= 0 or len(pages.val_dict) < mv:
        return None
    fp = _dict_fingerprint(pages, pages.key_dict, pages.val_dict)
    if planner.stage_veto(pages, fp, n_shards=n_shards):
        return None
    return dict_probe.stage_val_dict(pages.val_dict, n_shards=n_shards,
                                     mesh=mesh, fingerprint=fp,
                                     cache_on=pages)


def entry_match_mask(kv_key, kv_val, entry_start, entry_end, entry_dur,
                     entry_valid, term_keys, val_ranges,
                     dur_lo, dur_hi, win_start, win_end, *, n_terms: int,
                     val_hits=None, entry_dur_res=None, widths=None):
    """The core predicate: [P,E] bool mask of matching entries. Shared by
    the single-device kernel and the shard_map distributed kernel (each
    shard evaluates it over its local page slice).

    Value membership is an OR over inclusive [lo,hi] id ranges — pure
    broadcast compares, no gather (pipeline.ids_to_ranges explains why).

    `val_hits` (bool [T, v_pad], device): the on-device dictionary
    probe's per-term value hit mask (search/dict_probe.py). When present
    the membership test is a mask LOOKUP — one [P,E,C] gather per term —
    and the range tables are the never-match padding; the probe result
    never crossed the host boundary. (bench.py's high-cardinality phases
    re-validate the lookup-vs-range tradeoff each round.)

    `widths` (STATIC at every call site) + `entry_dur_res`: the
    packed-residency column descriptor (search/packing.py) — the kv
    unpack runs inside the term body so the widening shifts/masks fuse
    into the compares; no unpacked copy materializes in HBM."""
    kw, vw, dw = widths if widths is not None else (None, None, None)
    mask = entry_valid
    if n_terms:
        def term_body(t, acc):
            kk = unpack_ids(kv_key, kw)              # fused widen
            vv = unpack_ids(kv_val, vw)
            k = term_keys[t]
            keym = kk == k                           # [P,E,C]
            if val_hits is not None:
                safe_v = jnp.maximum(vv, 0).astype(jnp.int32)
                valm = mask_select(val_hits[t], safe_v) & (vv >= 0)
            else:
                lo = val_ranges[t, :, 0]                 # [R]
                hi = val_ranges[t, :, 1]
                v = vv[..., None]                        # [P,E,C,1]
                valm = ((v >= lo) & (v <= hi)).any(-1)   # [P,E,C], fused over R
            hit = jnp.any(keym & valm, axis=-1)      # [P,E] lane reduction
            return acc & hit

        mask = jax.lax.fori_loop(0, n_terms, term_body, mask)

    mask = mask & duration_ok(entry_dur, entry_dur_res, dur_lo, dur_hi, dw)
    mask = mask & (entry_end.astype(jnp.uint32) >= win_start.astype(jnp.uint32))
    mask = mask & (entry_start.astype(jnp.uint32) <= win_end.astype(jnp.uint32))
    return mask


def start_fetch(arrays) -> None:
    """Kick off device→host copies without blocking: issuing the async
    copies at dispatch time collapses N blocking fetches into one wait
    and overlaps the transfer with later kernel work."""
    for a in arrays:
        copy = getattr(a, "copy_to_host_async", None)
        if copy is not None:
            try:
                copy()
            except Exception:  # noqa: BLE001 — fetch still works, just sync
                pass


def fetch_scan_out(out):
    """(count, inspected, scores, idx[, agg]) device arrays → host
    values with a single synchronization point. The optional trailing
    aggregate histogram (?agg= dispatches) rides the same sync."""
    start_fetch(out)
    count, inspected, scores, idx, *ext = out
    fetched = (int(count), int(inspected), np.asarray(scores),
               np.asarray(idx))
    if ext:
        return fetched + (np.asarray(ext[0]),)
    return fetched


def resolve_top_k(base: int, limit: int) -> int:
    """top_k must cover the request limit or results get silently
    truncated below it; bucket to pow2 to bound recompiles. Shared by
    the single-block, multi-block and coalesced dispatch paths so the
    SAME (limit → k) mapping keys every jit cache."""
    k = max(1, base)
    while k < limit:
        k *= 2
    return k


def fetch_coalesced_out(out):
    """Query-axis variant of fetch_scan_out: (counts [Q], inspected,
    scores [Q,k], idx [Q,k][, agg [Q,K]]) device arrays → host values
    with a single synchronization point. The per-query demux slices the
    host arrays — one D2H wait for the whole coalesced group, not Q."""
    start_fetch(out)
    counts, inspected, scores, idx, *ext = out
    fetched = (np.asarray(counts), int(inspected),
               np.asarray(scores), np.asarray(idx))
    if ext:
        return fetched + (np.asarray(ext[0]),)
    return fetched


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


@functools.partial(jax.jit, static_argnames=("n_terms", "top_k", "widths",
                                             "plan"))
def scan_kernel(kv_key, kv_val, entry_start, entry_end, entry_dur,
                entry_valid, term_keys, val_ranges, dur_lo, dur_hi,
                win_start, win_end, val_hits=None, entry_dur_res=None,
                span_cols=None, s_tables=None,
                *, n_terms: int, top_k: int, widths=None, plan=None):
    """Returns (match_count i32, inspected i32, topk_scores i32 [k],
    topk_flat_idx i32 [k]) — flat index = page * E + entry. `val_hits`
    (None, bool [T, v_pad], or packed uint32 words) selects the
    device-probe membership path; jit treats None as pytree structure,
    so each variant compiles once. `widths` is the static packed-
    residency descriptor (search/packing.py); `plan` + span_cols/
    s_tables are the structural query lowering (search/structural.py) —
    its [P,E] verdicts AND into the same mask, one fused dispatch."""
    mask = entry_match_mask(
        kv_key, kv_val, entry_start, entry_end, entry_dur, entry_valid,
        term_keys, val_ranges, dur_lo, dur_hi, win_start, win_end,
        n_terms=n_terms, val_hits=val_hits, entry_dur_res=entry_dur_res,
        widths=widths,
    )
    if plan is not None:
        from .structural import structural_entry_mask

        page_block = jnp.zeros(entry_valid.shape[0], dtype=jnp.int32)
        mask = mask & structural_entry_mask(
            kv_key, kv_val, entry_dur, entry_valid, page_block,
            entry_dur_res, span_cols, s_tables, plan=plan, widths=widths)
    count = jnp.sum(mask, dtype=jnp.int32)
    inspected = jnp.sum(entry_valid, dtype=jnp.int32)
    top_scores, top_idx = masked_topk(mask, entry_start, top_k)
    return count, inspected, top_scores, top_idx


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
    v = int(v)
    key = v if mesh is None else (v, mesh)
    with _scalar_lock:
        hit = _SCALAR_CACHE.get(key)
        if hit is not None:
            _SCALAR_CACHE.move_to_end(key)
            return hit
    if mesh is None:
        arr = jnp.uint32(v)
    else:
        from tempo_tpu.parallel.mesh import put_replicated

        arr = put_replicated(mesh, np.uint32(v))
    with _scalar_lock:
        _SCALAR_CACHE[key] = arr
        while len(_SCALAR_CACHE) > _SCALAR_CACHE_MAX:
            _SCALAR_CACHE.popitem(last=False)
    return arr


class ScanEngine:
    """Single-device scan orchestration: staging cache + kernel dispatch +
    host-side result rendering. The distributed variant lives in
    tempo_tpu.parallel.dist_search."""

    def __init__(self, top_k: int = DEFAULT_TOP_K):
        self.top_k = top_k

    def _resolve_top_k(self, cq: CompiledQuery) -> int:
        return resolve_top_k(self.top_k, cq.limit)

    @staticmethod
    def query_device_params(cq: CompiledQuery, mesh=None):
        """Query params as device arrays, uploaded ONCE per query and
        cached on the CompiledQuery — one search fans out over many
        blocks/pages with the same query, and every small H2D transfer
        pays a fixed per-call cost. The scalar bounds additionally
        memoize BY
        VALUE across queries (device_scalar), so a fresh query with the
        default unbounded window re-uploads nothing but its term
        tables.

        `mesh`: the mesh the query's launches run over, None off a mesh.
        Off a mesh the arrays are what they always were: uncommitted, on
        the default device. On a mesh they are put on every device of it
        once (parallel.mesh.put_replicated): the dist kernels' in_specs
        want them replicated, and an array that is not is re-placed on
        every device by every launch, inside the collective lock. The
        cache holds the arrays of the last placement asked for, and
        their own sharding says which that was: a query that moves
        between an engine with a mesh and one without gets the right
        arrays from each."""
        from tempo_tpu.parallel.mesh import placed_for, put_replicated

        cached = getattr(cq, "_device_params", None)
        if cached is not None and placed_for(cached[0], mesh):
            return cached
        bounds = (cq.dur_lo, min(cq.dur_hi, 0xFFFFFFFF),
                  cq.win_start, min(cq.win_end, 0xFFFFFFFF))
        if mesh is None:
            tables = (jnp.asarray(cq.term_keys), jnp.asarray(cq.val_ranges))
        else:
            tables = put_replicated(
                mesh, (np.asarray(cq.term_keys), np.asarray(cq.val_ranges)))
        cached = tables + tuple(device_scalar(v, mesh) for v in bounds)
        object.__setattr__(cq, "_device_params", cached)
        return cached

    def scan_staged_async(self, sp: StagedPages, cq: CompiledQuery,
                          _rec=profile.NOOP_DISPATCH):
        """Dispatch the kernel without forcing device→host transfers;
        returns device arrays (count, inspected, scores, idx). Use when
        pipelining many blocks/queries — convert only at the end.

        `_rec`: a profile.Dispatch record when the caller owns one (the
        sync scan_staged wrapper); the default noop keeps this enqueue
        hot loop free of per-call profiling cost."""
        d = sp.device
        with _rec.stage("build"):
            tk, vr, dlo, dhi, ws, we = self.query_device_params(cq)
        vh = getattr(cq, "val_hits", None)
        widths = getattr(sp, "widths", None)
        # structural plan (search/structural.py): compiled against this
        # block and attached to the CompiledQuery; None = the legacy
        # pytree, same executables as before
        st = getattr(cq, "structural", None)
        plan = None if st is None else st.plan
        s_tables = None if st is None else st.device_tables()
        span_cols = getattr(sp, "span_device", None) if st is not None \
            else None
        k = self._resolve_top_k(cq)
        miss = _rec.compile_check(
            ("scan_kernel", d["kv_key"].shape, str(d["kv_key"].dtype),
             str(d["kv_val"].dtype), vr.shape,
             None if vh is None else (tuple(vh.shape), str(vh.dtype)),
             widths, cq.n_terms, k,
             None if st is None else st.shape_sig(),
             None if span_cols is None else
             tuple(sorted((n, tuple(a.shape))
                          for n, a in span_cols.items()))))
        book_topk(_rec, d["entry_valid"].size, k)
        with _rec.stage("compile" if miss else "execute"):
            out = scan_kernel(
                d["kv_key"], d["kv_val"],
                d["entry_start"], d["entry_end"], d["entry_dur"],
                d["entry_valid"],
                tk, vr, dlo, dhi, ws, we, vh, d.get("entry_dur_res"),
                span_cols, s_tables,
                n_terms=cq.n_terms, top_k=k, widths=widths, plan=plan,
            )
            _rec.fence(out)
        return out

    def scan_staged(self, sp: StagedPages, cq: CompiledQuery):
        # watchdog-bounded (robustness.GUARD): a hang/backend error here
        # books a breaker fault and raises DeviceFault instead of
        # wedging the caller; a disabled breaker makes this a direct
        # call (the noop contract)
        from tempo_tpu.robustness import GUARD

        return GUARD.run("single", lambda: self._scan_staged_sync(sp, cq))

    def _scan_staged_sync(self, sp: StagedPages, cq: CompiledQuery):
        with profile.dispatch("single") as rec:
            out = self.scan_staged_async(sp, cq, _rec=rec)
            with rec.stage("d2h"):
                res = fetch_scan_out(out)
            rec.add_bytes(d2h=res[2].nbytes + res[3].nbytes + 8)
            # scan_bytes feeds the planner's per-byte scan rate (physical
            # staged bytes — packed when packed residency is on)
            rec.set(n_pages=sp.n_pages,
                    scan_bytes=sum(int(a.nbytes)
                                   for a in sp.device.values()))
        return res

    def scan(self, pages: ColumnarPages, cq: CompiledQuery):
        return self.scan_staged(stage(pages), cq)

    # ---- host-side result rendering ----

    def results(self, sp: StagedPages, cq: CompiledQuery,
                scores: np.ndarray, idx: np.ndarray) -> list:
        """Map top-k flat indices back to TraceSearchMetadata."""
        from tempo_tpu import tempopb

        pages = sp.pages
        E = pages.geometry.entries_per_page
        out = []
        limit = cq.limit
        for s, i in zip(scores.tolist(), idx.tolist()):
            if s < 0 or len(out) >= limit:
                break
            p, e = divmod(i, E)
            if p >= pages.n_pages:
                continue
            m = tempopb.TraceSearchMetadata()
            m.trace_id = bytes(pages.trace_ids[p, e]).hex()
            m.start_time_unix_nano = int(pages.entry_start[p, e]) * 1_000_000_000
            m.duration_ms = int(pages.entry_dur[p, e])
            svc = int(pages.entry_root_svc[p, e])
            name = int(pages.entry_root_name[p, e])
            if svc >= 0:
                m.root_service_name = pages.val_dict[svc]
            if name >= 0:
                m.root_trace_name = pages.val_dict[name]
            out.append(m)
        return out
