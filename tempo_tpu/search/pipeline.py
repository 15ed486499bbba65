"""Host-side query compilation: dictionaries in, device predicate out.

Role-equivalent to the reference's search pipeline (tempodb/search/
pipeline.go:20-183) and tag probes (pkg/tempofb/searchdata_util.go:47-100),
re-cut for the dictionary-encoded columnar layout: the substring match
(`bytes.Contains`) is evaluated ONCE per (block, query) over the block's
value dictionary on the host — cheap, exact — producing the value-id sets
the device kernel tests membership against. A term whose key or value set
is empty prunes the whole block before any device work (the reference's
MatchesBlock header rollup, backend_search_block.go:202-210).
"""

from __future__ import annotations

import bisect
import hashlib
import threading
from collections import OrderedDict
from dataclasses import dataclass

import numpy as np

from tempo_tpu import tempopb
from tempo_tpu.observability import metrics as obs

INT32_SENTINEL = np.int32(2**31 - 1)
UINT32_MAX = 0xFFFFFFFF

# Hidden debug flag (reference tempodb/search/pipeline.go:14
# SecretExhaustiveSearchTag): a request carrying this tag forces a FULL
# traversal — block pruning and result-limit early-quit are suppressed so
# every page of every block is scanned. The remaining (non-secret) tag
# predicates still apply, as in the reference where the secret tag adds a
# filter without dropping the others. In-band, undocumented, for
# benchmarking scans.
EXHAUSTIVE_SEARCH_TAG = "x-dbg-exhaustive"


def is_exhaustive(req: tempopb.SearchRequest) -> bool:
    return EXHAUSTIVE_SEARCH_TAG in req.tags


@dataclass
class CompiledQuery:
    term_keys: np.ndarray   # int32 [T]
    term_vals: np.ndarray   # int32 [T, V] sorted, padded with INT32_SENTINEL
    val_ranges: np.ndarray  # int32 [T, R, 2] inclusive [lo,hi] id ranges,
                            # padded with [1,0] (never matches)
    dur_lo: int
    dur_hi: int
    win_start: int
    win_end: int
    limit: int
    # device-probe product (search/dict_probe.py) of a term whose hits
    # are more than dict_probe.R_MAX runs of the sorted dictionary: bool
    # [T, v_pad] value hit mask, resident on device. When set,
    # val_ranges is the never-match padding and the scan kernels test
    # membership with a mask lookup instead of range compares. With
    # fewer runs the device probe fills val_ranges as the host does and
    # this stays None.
    val_hits: object = None

    @property
    def n_terms(self) -> int:
        return int(self.term_keys.shape[0])


def ids_to_ranges(ids: np.ndarray) -> np.ndarray:
    """Collapse a sorted id set into inclusive [lo,hi] runs. Sorted
    dictionaries make substring hits clumpy (all values sharing a prefix
    are contiguous), so R is typically far below V — and the device tests
    ranges with pure compares, the TPU-friendly alternative to a
    membership gather (gathers serialize on the VPU; measured 35ms vs
    <5ms per 1M entries)."""
    if ids.size == 0:
        return np.zeros((0, 2), dtype=np.int32)
    breaks = np.nonzero(np.diff(ids) > 1)[0]
    lo = np.concatenate([[0], breaks + 1])
    hi = np.concatenate([breaks, [ids.size - 1]])
    return np.stack([ids[lo], ids[hi]], axis=1).astype(np.int32)


def block_header_skip_reason(header: dict,
                             req: tempopb.SearchRequest) -> str | None:
    """Why the header rollup prunes this block — None when it doesn't.
    The reason string feeds the per-query stats' skipped-blocks
    breakdown (search/query_stats.py): an operator reading an explain
    must be able to tell "out of the time window" from "no span that
    long" without re-deriving it."""
    if is_exhaustive(req):
        return None  # debug flag: never prune
    if req.start and header.get("max_end_s", UINT32_MAX) < req.start:
        return "time_range"
    if req.end and header.get("min_start_s", 0) > req.end:
        return "time_range"
    if req.min_duration_ms and header.get("max_dur_ms", UINT32_MAX) < req.min_duration_ms:
        return "duration"
    if req.max_duration_ms and header.get("min_dur_ms", 0) > req.max_duration_ms:
        return "duration"
    return None


NATIVE_SCAN_THRESHOLD = 50_000


def substring_value_ids(val_dict: list, needle: str,
                        packed: tuple | None = None) -> np.ndarray:
    """Ids of dictionary values containing `needle` — the host-side answer
    to bytes.Contains semantics (SURVEY.md §7 hard parts). Small
    dictionaries scan vectorized in numpy; huge ones (the 10M-distinct-
    values BASELINE config) go through the native C++ memmem scan over a
    packed byte dictionary (`packed` = (bytes, int64 offsets), cacheable
    per block via ColumnarPages.packed_val_dict)."""
    if not needle:
        return np.arange(len(val_dict), dtype=np.int32)
    if not val_dict:
        return np.zeros(0, dtype=np.int32)
    if len(val_dict) >= NATIVE_SCAN_THRESHOLD:
        from tempo_tpu.ops import native

        if native.available():
            if packed is None:
                packed = pack_val_dict(val_dict)
            buf, offsets = packed
            return native.substr_scan(buf, offsets, needle.encode("utf-8"))
    arr = np.array(val_dict, dtype=np.str_)
    hits = np.char.find(arr, needle) >= 0
    return np.nonzero(hits)[0].astype(np.int32)


def pack_val_dict(val_dict: list) -> tuple:
    """(concatenated utf-8 bytes, int64 offsets[n+1]) for the native scan."""
    blobs = [v.encode("utf-8") for v in val_dict]
    offsets = np.zeros(len(blobs) + 1, dtype=np.int64)
    np.cumsum([len(b) for b in blobs], out=offsets[1:])
    return b"".join(blobs), offsets


_PRUNED = "pruned"  # cache sentinel: block provably cannot match these tags
_COMPILE_CACHE_MAX = 128     # distinct tag-sets kept per dictionary
_COMPILE_CACHE_DICTS = 4096  # distinct dictionaries tracked
# entries whose probe product is a DEVICE hit mask pin HBM (~v_pad bytes
# per term — 10 MB/term at 10M values), so they get a much tighter
# per-dictionary bound than the host-only entries. Bit-packed masks
# (search/packing.py, 8x fewer bytes per entry) afford an 8x deeper
# bound at the same HBM charge — more distinct tag-sets stay compiled.
_PROBE_CACHE_MAX = 8
_PROBE_CACHE_MAX_PACKED = 64
_COMPILE_CACHE: OrderedDict = OrderedDict()
_compile_cache_lock = threading.Lock()


class _MaskBytes:
    """HBM pinned by device hit masks, by who holds them: `probe_cache`,
    the [T, v_pad] products in _COMPILE_CACHE (at most _PROBE_CACHE_MAX a
    dictionary; no batch owns them, so no budget is charged), and
    `memo`, the [G, T, Vmax] stacks the batcher's prepare memo keeps (a
    copy, charged to its staged batch under search_batch_cache_bytes).
    Range products are a few ints on the host and count nowhere."""

    def __init__(self):
        self._lock = threading.Lock()
        self._held = {"probe_cache": 0, "memo": 0}
        self._peak = 0
        for holder in self._held:       # a 0 from the start, not a gap
            obs.probe_mask_bytes.set(0, held_by=holder)
        obs.probe_mask_peak_bytes.set(0)

    def add(self, holder: str, nbytes: int) -> None:
        if not nbytes:
            return
        with self._lock:
            self._held[holder] += nbytes
            obs.probe_mask_bytes.set(self._held[holder], held_by=holder)
            total = sum(self._held.values())
            if total > self._peak:
                self._peak = total
                obs.probe_mask_peak_bytes.set(total)


MASK_BYTES = _MaskBytes()


def _mask_nbytes(product) -> int:
    """Device bytes of a cached probe product's hit mask (0: pruned, or
    a range product)."""
    if isinstance(product, str) or product is None or product[3] is None:
        return 0
    return int(product[3].nbytes)


def _dict_fingerprint(cache_on, key_dict: list, val_dict: list) -> bytes:
    """Content digest of the dictionaries, computed once per container
    OUTSIDE the cache lock (a 1M-value dictionary hashes for ~100ms — it
    must not serialize every other thread's compiles). sha256, not
    hash(): a 64-bit collision would silently serve another dictionary's
    compiled term ids, an undetectable wrong-results failure.

    Containers decoded from the encoding/v2 search object carry the
    digest of their ENCODED dictionary sections (`_dict_section_sha`,
    columnar.from_bytes — one C-speed pass over contiguous bytes at
    build, zero cost at open), so the first cache touch skips the
    ~100ms-per-1M-values python walk; synthetic/test containers built
    in memory fall back to it."""
    fp = getattr(cache_on, "_dict_fingerprint", None)
    if fp is None:
        fp = getattr(cache_on, "_dict_section_sha", None)
        if fp is None:
            h = hashlib.sha256()
            for part in key_dict:
                h.update(part.encode("utf-8", "surrogatepass"))
                h.update(b"\x00")
            h.update(b"\x01")
            for part in val_dict:
                h.update(part.encode("utf-8", "surrogatepass"))
                h.update(b"\x00")
            fp = h.digest()
        cache_on._dict_fingerprint = fp
    return fp


def _tags_sig(req) -> tuple:
    """Cache key for the dictionary-probe part of query compilation: only
    the tag terms (and the exhaustive flag) touch the dictionaries —
    duration/window/limit are scalar passthroughs. The structural
    reserved tag is excluded like the exhaustive flag (it is not a term;
    its OWN compilation caches separately in search/structural.py), so
    structural variants of one base predicate share the probe product.
    The ?agg= reserved tag is likewise not a term — the aggregate stage
    is batch-scoped, never per-predicate."""
    from .analytics import AGG_QUERY_TAG
    from .structural import STRUCTURAL_QUERY_TAG

    return (tuple(sorted((k, v) for k, v in req.tags.items()
                         if k not in (EXHAUSTIVE_SEARCH_TAG,
                                      STRUCTURAL_QUERY_TAG,
                                      AGG_QUERY_TAG))),
            is_exhaustive(req))


def compile_query(key_dict: list, val_dict: list,
                  req: tempopb.SearchRequest,
                  packed_vals: tuple | None = None,
                  cache_on=None, staged_dict=None,
                  host_only: bool = False,
                  probed: list | None = None) -> CompiledQuery | None:
    """Returns None when the block provably cannot match (key absent from
    the key dictionary, or no dictionary value satisfies a term). Under the
    exhaustive debug flag blocks are never pruned: an unsatisfiable term
    compiles to an empty value-range set (scanned, matches nothing).

    `cache_on`: a host container object (the block's ColumnarPages) to
    memoize the dictionary-probe product on, keyed by (dictionary
    CONTENT, tag terms) — the serving path compiles every query against
    every block's dictionaries (O(blocks) per query, VERDICT r2 #1);
    blocks are immutable, so repeated tag-sets hit, and blocks that
    SHARE dictionaries (the common production shape: the same services/
    status codes tenant-wide) share one probe. Bounded LRU per
    dictionary; the fingerprint is computed once per container.

    `staged_dict`: a dict_probe.DeviceDict for this value dictionary —
    when present the substring probe runs ON DEVICE (staging-time
    routing already applied the `search_device_probe_min_vals`
    threshold) and the compiled query carries what the device found:
    ranges like the host's where every term's hits are at most
    dict_probe.R_MAX runs of the sorted dictionary, else the [T, v_pad]
    hit mask. The cache key is unchanged, so repeated tag-sets skip all
    probe work on either path; a cached host-path product is served to
    a device-capable caller (and vice versa) — both are exact, only
    the kernel's membership test differs.

    `probed`: where given, gets one (path, product) for this
    dictionary, path `cached`, `device` or `host`, product None where
    the block was pruned (compile_multi's `dict_probe.probe` span).

    `host_only`: the breaker's host-fallback path — the probe must not
    touch the device AT ALL: staged dictionaries are ignored, and a
    CACHED product carrying a device hit mask is treated as a miss
    (reading its arrays would hang on the very wedged device the
    fallback is escaping); the fresh host product overwrites it."""
    if host_only:
        staged_dict = None
    sig = None
    fp = None
    if cache_on is not None:
        sig = _tags_sig(req)
        fp = _dict_fingerprint(cache_on, key_dict, val_dict)
        with _compile_cache_lock:
            cache = _COMPILE_CACHE.get(fp)
            if cache is None:
                cache = _COMPILE_CACHE[fp] = OrderedDict()
                _COMPILE_CACHE.move_to_end(fp)
                while len(_COMPILE_CACHE) > _COMPILE_CACHE_DICTS:
                    _COMPILE_CACHE.popitem(last=False)
            hit = cache.get(sig)
            if hit is not None:
                cache.move_to_end(sig)
        if hit is not None and not isinstance(hit, str) \
                and hit[3] is not None:
            # the cached product is a DEVICE hit mask: unusable while
            # the breaker blocks the device (or on the explicit host
            # path) — recompile through host and overwrite it
            from tempo_tpu.robustness import BREAKER

            from . import packing

            if host_only or BREAKER.blocking():
                hit = None
            elif packing.is_packed_mask(hit[3]) != packing.PACKING.enabled:
                # minted under the other packed-residency gate state:
                # treat as a miss so one assembled batch never mixes
                # mask formats (the fresh product overwrites it)
                hit = None
        if hit is not None:
            # _PRUNED can only come from a non-exhaustive probe (the
            # exhaustive flag is part of the signature)
            pruned = isinstance(hit, str)
            _book_probe("cached", None if pruned else hit, probed)
            return None if pruned else _from_probe(hit, req)

    path, out = _probe_tags(key_dict, val_dict, req, packed_vals,
                            staged_dict=staged_dict, fp=fp)
    _book_probe(path, out, probed)
    if sig is not None:
        from . import packing

        with _compile_cache_lock:
            cache = _COMPILE_CACHE.get(fp)
            if cache is not None:
                freed = _mask_nbytes(cache.get(sig))
                cache[sig] = _PRUNED if out is None else out
                while len(cache) > _COMPILE_CACHE_MAX:
                    freed += _mask_nbytes(cache.popitem(last=False)[1])
                # device hit masks pin HBM: keep only the newest few
                # (range products, the device's too, are a few ints and
                # share the host entries' bound above; only a mask coming
                # in can push the masks past theirs). Bit-packed masks
                # are 8x smaller, so they get an 8x deeper bound at the
                # same HBM charge.
                if _mask_nbytes(out):
                    masks = [s for s, o in cache.items()
                             if _mask_nbytes(o)
                             and not packing.is_packed_mask(o[3])]
                    while len(masks) > _PROBE_CACHE_MAX:
                        freed += _mask_nbytes(cache.pop(masks.pop(0), None))
                    packed = [s for s, o in cache.items()
                              if _mask_nbytes(o)
                              and packing.is_packed_mask(o[3])]
                    while len(packed) > _PROBE_CACHE_MAX_PACKED:
                        freed += _mask_nbytes(
                            cache.pop(packed.pop(0), None))
                MASK_BYTES.add("probe_cache", _mask_nbytes(out) - freed)
    return None if out is None else _from_probe(out, req)


def _book_probe(path: str, product, probed: list | None) -> None:
    obs.dict_probes.inc(path=path)
    if probed is not None:
        probed.append((path, product))


def probe_summary(probed: list) -> dict:
    """What one compile over a group's distinct dictionaries did, as the
    attributes of its `dict_probe.probe` span: `path` is the dearest
    taken (device, then host, then cached), `membership` is `mask` once
    one product is a hit mask, `runs_max` the most runs a term has in
    a range product (a mask's are past dict_probe.R_MAX by definition)."""
    by = {"device": 0, "host": 0, "cached": 0}
    terms = runs_max = 0
    membership = "range"
    for path, product in probed:
        by[path] += 1
        if product is None:
            continue
        terms = max(terms, int(product[0].shape[0]))
        if product[3] is not None:
            membership = "mask"
        elif product[2].size:
            vr = product[2]
            runs_max = max(runs_max,
                           int((vr[..., 0] <= vr[..., 1]).sum(axis=1).max()))
    path = next((p for p in ("device", "host") if by[p]), "cached")
    return dict(by, path=path, dicts=len(probed), terms=terms,
                runs_max=runs_max, membership=membership)


def _from_probe(probe, req) -> CompiledQuery:
    term_keys, term_vals, val_ranges, val_hits = probe
    return CompiledQuery(
        term_keys=term_keys,
        term_vals=term_vals,
        val_ranges=val_ranges,
        val_hits=val_hits,
        dur_lo=req.min_duration_ms or 0,
        dur_hi=req.max_duration_ms or UINT32_MAX,
        win_start=req.start or 0,
        win_end=req.end or UINT32_MAX,
        limit=req.limit or 20,
    )


def _device_probe_tags(terms, key_dict, staged_dict, exhaustive):
    """Device-path value probe: ONE vmapped kernel call for all terms;
    the only host sync fetches, per term, any_hits (prune decisions)
    and the runs of its hits over the sorted ids (a few ints). Where
    every term has at most dict_probe.R_MAX runs the product carries
    them as val_ranges and no mask, exactly what the host path makes,
    and the scan tests membership by compares; otherwise the mask stays
    on the device and the scan gathers from it. Returns the probe
    product or None (pruned). Raises ValueError when a needle exceeds
    the kernel's unroll bound — the caller falls back to the exact
    host scan."""
    from . import dict_probe

    term_key_ids = []
    needles = []
    for k, v in terms:
        i = bisect.bisect_left(key_dict, k)
        if i >= len(key_dict) or key_dict[i] != k:
            if not exhaustive:
                return None
            i = -1
        term_key_ids.append(i)
        nb = v.encode("utf-8")
        if len(nb) > dict_probe.MAX_NEEDLE_BYTES:
            raise ValueError("needle too long for device probe")
        needles.append(nb)
    import jax

    hits, *small = dict_probe.probe_values(staged_dict, needles)
    any_host, n_runs, bounds = jax.device_get(small)
    if not exhaustive:
        for t, ki in enumerate(term_key_ids):
            if ki >= 0 and not any_host[t]:
                return None  # no dictionary value satisfies this term
    T = len(term_key_ids)
    term_keys = np.asarray(term_key_ids, dtype=np.int32)
    term_vals = np.full((T, 1), INT32_SENTINEL, dtype=np.int32)
    # missing keys (exhaustive only) must contribute an all-false row
    # regardless of what the probe said for their needle
    key_ok = term_keys >= 0
    runs_max = int(np.where(key_ok, n_runs, 0).max())
    if runs_max <= dict_probe.R_MAX:
        R = dict_probe._pow2(max(1, runs_max))
        val_ranges = np.tile(np.array([1, 0], dtype=np.int32), (T, R, 1))
        val_ranges[key_ok, :bounds.shape[1]] = bounds[key_ok, :R]
        return term_keys, term_vals, val_ranges, None
    if not key_ok.all():
        import jax.numpy as jnp

        hits = hits & jnp.asarray(key_ok)[:, None]
    from . import packing

    if packing.PACKING.enabled:
        # packed residency: the compile-cache product (and everything
        # assembled from it) carries uint32 bit-words instead of 1-byte
        # bools — 8x fewer HBM bytes pinned per cached tag-set; the
        # scan kernels select the bit in-register (packing.mask_select)
        hits = packing.PACKING.pack_hits(hits)
    val_ranges = np.tile(np.array([1, 0], dtype=np.int32), (T, 1, 1))
    return term_keys, term_vals, val_ranges, hits


def _use_device_probe(staged_dict, terms, fp) -> bool:
    """Placement for a staged dictionary's substring probe. Static path
    (planner disabled): staged == device, exactly the pre-planner
    behavior. Planner enabled: the cost model chooses — its "host"
    verdict falls through to the exact host scan even though the packed
    bytes sit in HBM (both paths are exact; only the time moves). The
    decision memoizes through the compile cache: one verdict per
    (dictionary, tag-set), shared by every block of the group and every
    member of a coalesced dispatch."""
    from tempo_tpu.robustness import BREAKER

    from . import dict_probe, planner

    if BREAKER.blocking():
        # device circuit breaker open/half-open: the probe stays on the
        # exact host path even though the packed bytes sit in HBM —
        # results are identical, only the time moves (and the host walk
        # finishes, which a wedged device dispatch would not)
        return False
    p = planner.PLANNER
    if not p.enabled:
        return True
    lmax = max(len(v.encode("utf-8")) for _, v in terms)
    if lmax > dict_probe.MAX_NEEDLE_BYTES:
        return False  # host fallback regardless — no decision burned
    packed = staged_dict.packed
    T = len(terms)
    Lp = dict_probe._pow2(max(1, lmax))
    # the probe kernel's jit signature (dict_probe.probe_value_hits) —
    # lets the planner predict whether a device choice pays a compile
    shape_key = ("probe", staged_dict.mesh is not None,
                 tuple(packed.buf.shape), tuple(packed.off.shape), T, Lp)
    d = p.decide_probe(
        n_vals=packed.n_vals, dict_bytes=packed.real_bytes, n_terms=T,
        resident=True, packed=True, staged_bytes=staged_dict.nbytes,
        n_shards=(packed.n_shards if staged_dict.mesh is not None else 1),
        shape_key=shape_key, fp=packed.fingerprint or fp, site="compile")
    return d.target == "device"


def _probe_tags(key_dict: list, val_dict: list, req,
                packed_vals: tuple | None, staged_dict=None, fp=None):
    """The expensive, tags-only part of compilation: binary-search keys,
    then either the host substring scan folded to range sets, or the
    device probe (staged_dict present, and — when the offload planner is
    enabled — the cost model picks device) yielding ranges or a device
    hit mask. Returns (path, product): `device` or `host`, and
    (term_keys, term_vals, val_ranges, val_hits) or None (pruned)."""
    from .analytics import AGG_QUERY_TAG
    from .structural import STRUCTURAL_QUERY_TAG

    exhaustive = is_exhaustive(req)
    terms = sorted((k, v) for k, v in req.tags.items()
                   if k not in (EXHAUSTIVE_SEARCH_TAG,
                                STRUCTURAL_QUERY_TAG,
                                AGG_QUERY_TAG))
    if staged_dict is not None and terms \
            and _use_device_probe(staged_dict, terms, fp):
        from tempo_tpu.robustness import GUARD, DeviceFault

        try:
            # watchdog-bounded like every other device dispatch: a probe
            # kernel that hangs or errors books a breaker fault and the
            # EXACT host scan below answers instead (byte-identical)
            return "device", GUARD.run(
                "dict_probe",
                lambda: _device_probe_tags(terms, key_dict, staged_dict,
                                           exhaustive))
        except ValueError:
            pass  # oversized needle: exact host path below
        except DeviceFault:
            pass  # wedged/erroring probe: fault booked, host path below
    if terms:
        # the host memmem walk is PR4's motivating cost (312ms at 10M
        # distinct values) — record it under its own mode so the stage
        # histogram shows host-vs-device probe cost side by side, and
        # feed the offload planner's host-side rate (with the dictionary
        # fingerprint, so predicted-vs-actual error resolves)
        import time as _time

        from tempo_tpu.observability import profile
        from . import planner

        # bytes are estimated unconditionally (O(256) sample): a
        # planner-DISABLED deployment's /debug/profile dump must still
        # carry the host-probe byte totals, or the offline calibration
        # replay (scripts/calibrate_offload.py) — whose whole point is
        # deciding if the planner is worth enabling — falls back to the
        # hardcoded default host rate instead of this host's measured one
        nb = len(terms) * planner.dict_bytes_est(val_dict)
        t0 = _time.perf_counter()
        try:
            return "host", _host_probe_tags(terms, key_dict, val_dict,
                                            packed_vals, exhaustive)
        finally:
            dt = _time.perf_counter() - t0
            profile.observe_stage("build", "host_probe", dt, nbytes=nb)
            planner.PLANNER.observe("host_probe", dt, nbytes=nb, fp=fp)
            from . import query_stats

            qs = query_stats.current()
            if qs is not None:
                # the host memmem walk is HOST work this query paid for
                # — the per-query bytes-by-placement split counts it
                qs.add_host_probe(dt, nb)
                qs.add_inspected(nbytes=nb, placement="host")
    return "host", _host_probe_tags(terms, key_dict, val_dict, packed_vals,
                                    exhaustive)


def _host_probe_tags(terms, key_dict, val_dict, packed_vals, exhaustive):
    term_key_ids = []
    term_val_sets = []
    for k, v in terms:
        i = bisect.bisect_left(key_dict, k)
        if i >= len(key_dict) or key_dict[i] != k:
            if not exhaustive:
                return None
            term_key_ids.append(-1)
            term_val_sets.append(np.zeros(0, dtype=np.int32))
            continue
        ids = substring_value_ids(val_dict, v, packed=packed_vals)
        if ids.size == 0 and not exhaustive:
            return None
        term_key_ids.append(i)
        term_val_sets.append(np.sort(ids))

    T = len(term_key_ids)
    if T:
        vmax = max(s.size for s in term_val_sets)
        V = 1
        while V < vmax:
            V *= 2
        term_vals = np.full((T, V), INT32_SENTINEL, dtype=np.int32)
        range_sets = [ids_to_ranges(s) for s in term_val_sets]
        rmax = max(r.shape[0] for r in range_sets)
        R = 1
        while R < rmax:
            R *= 2
        # pad with [1,0] — an empty range no value id satisfies
        val_ranges = np.tile(np.array([1, 0], dtype=np.int32), (T, R, 1))
        for t, (s, r) in enumerate(zip(term_val_sets, range_sets)):
            term_vals[t, :s.size] = s
            val_ranges[t, :r.shape[0]] = r
        term_keys = np.asarray(term_key_ids, dtype=np.int32)
    else:
        term_keys = np.zeros(0, dtype=np.int32)
        term_vals = np.zeros((0, 1), dtype=np.int32)
        val_ranges = np.zeros((0, 1, 2), dtype=np.int32)

    # host path: no device hit mask (val_hits slot keeps the probe
    # product a uniform 4-tuple across both paths)
    return term_keys, term_vals, val_ranges, None
