"""Device-side aggregate analytics (docs/search-analytics.md).

Two faces of one reduction idiom, both gated by
``storage.search_analytics_enabled`` (default off — every hook is one
attribute read, contract-checked like the other gates):

**Ingest side.** The metrics generator's native summary feed (fixed
56-byte rows, modules/generator.py) is a per-span Python walk on the
push-ack path: per row, a tuple build, a dict probe, a bisect, a float
divide, two lock round-trips. With the gate on, the whole micro-batch
decodes in one numpy structured view and the (series, latency-bucket)
tallies compute as ONE dense count kernel — sort the composite keys,
``searchsorted`` the key-space edges, diff — the scatter-free counting
idiom the scan kernels already use (no scatter on the VPU hot path).
The host then drains per-SERIES deltas into the exact same
``ManagedRegistry`` handles the walk would have fed: integer bucket/call
counts arrive as bulk adds, and the float latency sums fold sequentially
per series in row order, so the registry state is byte-identical to the
per-span walk (differential-fuzzed in tests/test_analytics.py).

Latency binning runs on-device WITHOUT int64 (JAX x32): the nanosecond
duration splits into two int31 limbs and each static bucket edge becomes
an integer threshold pair ``T = min{n : float64(n/1e9) > edge}`` — the
unrolled two-limb compare reproduces ``bisect_left(LATENCY_BUCKETS_S,
dur_ns/1e9)`` exactly. The threshold tuple is a static descriptor in the
jit key, like ``widths``/``plan``; rows pad to pow2 tiers (the live-tier
``_HotStage`` pattern) so successive micro-batches re-enter one compiled
kernel.

**Query side.** ``?agg=red`` rides the search request as a reserved
in-band tag (the structural-query idiom) and compiles onto the fused
scan kernels as one more static plan stage: the final verdict mask (term
predicates AND the structural plan, when present) gates which traces
contribute, and the same dense-count reduction produces group-by-service
calls/errors/latency-histogram answers in the SAME dispatch — single,
coalesced, mesh/dist, and the breaker's host route all return
byte-identical integer counts by construction. The per-entry composite
key ``(service, ms-bucket, error)`` stages once per batch from columns
the host already holds (``entry_root_svc``, ``entry_dur``, the
``error=true`` kv pair every container records for error-status spans).
"""

from __future__ import annotations

import bisect
import functools
import json
import threading
import time

import numpy as np

from tempo_tpu.observability import metrics as obs

# reserved in-band tag carrying the ?agg= spec across the frontend <->
# querier round-trip (the STRUCTURAL_QUERY_TAG / EXHAUSTIVE_SEARCH_TAG
# idiom: excluded from term compilation, probe signatures, and trace
# matching)
AGG_QUERY_TAG = "x-agg-q"

# query-side latency bucket edges in INTEGER milliseconds — the ingest
# edges (generator.LATENCY_BUCKETS_S) times 1000, kept integral because
# entry_dur is already ms and 0.002*1000 is 2.0000000000000004 in
# float64; integer edges make the query-side histogram order-free and
# byte-identical across every dispatch path
MS_BUCKETS = (2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096,
              8192, 16384)
_NB1Q = len(MS_BUCKETS) + 1         # query-side bins incl. +Inf


def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


# ---------------------------------------------------------------------------
# exact two-limb duration thresholds (ingest side)

@functools.lru_cache(maxsize=4)
def _dur_thresholds(buckets: tuple) -> tuple:
    """Integer-nanosecond bucket thresholds: for each float edge ``b``,
    ``T = min{n : float64(n/1e9) > b}`` — so ``dur_ns >= T`` is exactly
    ``dur_ns/1e9 > b``, and the bin index ``sum_b [dur >= T_b]`` equals
    ``bisect_left(buckets, dur_ns/1e9)``. Returned as (hi, lo) int31
    limb pairs for the x32 device kernel (hi = T >> 31)."""
    out = []
    for b in buckets:
        n = int(b * 1e9)
        while n > 0 and n / 1e9 > b:
            n -= 1
        while n / 1e9 <= b:
            n += 1
        out.append((n >> 31, n & 0x7FFFFFFF))
    return tuple(out)


@functools.lru_cache(maxsize=4)
def _dur_thresholds_full(buckets: tuple) -> tuple:
    """The same thresholds as full integers — the host fallback's int64
    compare needs no limbs."""
    return tuple((hi << 31) | lo
                 for hi, lo in _dur_thresholds(buckets))


# ---------------------------------------------------------------------------
# the dense count kernel (shared by both ingest reductions)

def _jax():
    import jax
    import jax.numpy as jnp

    return jax, jnp


@functools.lru_cache(maxsize=1)
def _count_kernel():
    jax, jnp = _jax()

    @functools.partial(jax.jit,
                       static_argnames=("n_keys", "tier", "buckets"))
    def analytics_count_kernel(cols, *, n_keys: int, tier: int,
                               buckets):
        """Dense (series, latency-bucket) counts in one pass.

        ``cols`` is one staged int32 [3, tier] array — series index,
        duration hi limb, duration lo limb — pow2-padded (``tier`` is
        the static capacity descriptor, the live-tier idiom, so
        micro-batches within a tier re-enter this compiled kernel).
        Pad rows carry the sentinel series index ``n_keys``, which
        lands one past the counted key range. ``buckets`` is the
        static two-limb threshold descriptor; ``n_keys`` the
        pow2-padded series capacity. Counting is sort +
        searchsorted-diff: scatter-free, the layout the VPU wants."""
        nb1 = len(buckets) + 1
        series_idx, dur_hi, dur_lo = cols[0], cols[1], cols[2]
        b = jnp.zeros(series_idx.shape, dtype=jnp.int32)
        for thi, tlo in buckets:
            ge = (dur_hi > thi) | ((dur_hi == thi) & (dur_lo >= tlo))
            b = b + ge.astype(jnp.int32)
        key = jnp.minimum(series_idx * nb1 + b,
                          jnp.int32(n_keys * nb1))
        skey = jax.lax.sort(key)
        edges = jnp.searchsorted(
            skey, jnp.arange(n_keys * nb1 + 1, dtype=jnp.int32))
        return (edges[1:] - edges[:-1]).astype(jnp.int32)

    return analytics_count_kernel


# native summary-row layout (modules/generator.py _ROW, "<6IQQ8s8s");
# sid/pid decode as void8 so .tobytes() preserves trailing zero bytes —
# the pairing-store keys must match struct's full-width "8s" bytes
_ROW_DT = np.dtype([("ti", "<u4"), ("svc", "<u4"), ("name", "<u4"),
                    ("kind", "<u4"), ("status", "<u4"), ("flags", "<u4"),
                    ("start", "<u8"), ("end", "<u8"),
                    ("sid", "V8"), ("pid", "V8")])


# ---------------------------------------------------------------------------
# query-side staging

class AggStage:
    """Per-batch staged aggregation descriptor: the batch-global service
    table and the per-entry composite key column the kernels count.

    ``entry_agg[p, e] = (svc_gid * NB1 + ms_bucket) * 2 + err`` — int32,
    valid range [0, n_keys); the kernel writes the sentinel ``n_keys``
    for entries the verdict mask rejects. The service axis pads to pow2
    so the static ``agg`` jit key takes log-many values per geometry."""

    __slots__ = ("services", "n_keys", "host", "_device", "_lock")

    def __init__(self, services: tuple, host: np.ndarray):
        self.services = services
        self.n_keys = _pow2(max(1, len(services))) * _NB1Q * 2
        self.host = host
        self._device = None
        self._lock = threading.Lock()

    def device(self, sharding=None):
        """Memoized device placement, where the batch's engine reads
        its page-sharded operands: `sharding` is the engine's page
        sharding on a mesh (the keys shard with their pages, as the
        dist kernels' in_specs say), None off one (the default
        device). A batch is staged by one engine, so one placement."""
        with self._lock:
            if self._device is None:
                if sharding is None:
                    _, jnp = _jax()

                    self._device = jnp.asarray(self.host)
                else:
                    from tempo_tpu.parallel.mesh import put

                    self._device = put(self.host, sharding)
            return self._device

    def cpu(self):
        """Host-route placement, staged under cpu_pinned by the
        caller (host_scan memoizes the result on the HostBatch)."""
        _, jnp = _jax()

        return jnp.asarray(self.host)

    def decode(self, counts: np.ndarray) -> dict:
        """Dense [n_keys] counts -> {service: {calls, errors, hist}}.
        Integer-only, so every dispatch path decodes identically."""
        s_pad = self.n_keys // (_NB1Q * 2)
        c = np.asarray(counts).reshape(s_pad, _NB1Q, 2)
        series = {}
        for i, svc in enumerate(self.services):
            sub = c[i]
            calls = int(sub.sum())
            if not calls:
                continue
            series[svc] = {
                "calls": calls,
                "errors": int(sub[:, 1].sum()),
                "hist": [int(x) for x in sub.sum(axis=1)],
            }
        return series


def agg_response(series: dict) -> dict:
    """The ?agg=red response payload (docs/search-analytics.md)."""
    return {"type": "red", "buckets_ms": list(MS_BUCKETS),
            "series": series}


def merge_agg(into: dict | None, other: dict | None) -> dict | None:
    """Integer merge of two agg payloads (sub-response fan-in)."""
    if other is None:
        return into
    if into is None:
        return other
    dst = into["series"]
    for svc, s in other["series"].items():
        d = dst.get(svc)
        if d is None:
            dst[svc] = s
        else:
            d["calls"] += s["calls"]
            d["errors"] += s["errors"]
            d["hist"] = [a + b for a, b in zip(d["hist"], s["hist"])]
    return into


def attach_agg(req, spec: str) -> None:
    """Validate an ?agg= spec and stow it in the reserved tag. Raises
    ValueError on anything but the supported grammar (params.py maps it
    to a 400)."""
    spec = (spec or "").strip().lower()
    if spec != "red":
        raise ValueError(
            f"unsupported agg spec {spec!r} (supported: 'red')")
    req.tags[AGG_QUERY_TAG] = spec


def agg_requested(req) -> bool:
    return AGG_QUERY_TAG in req.tags


def _block_entry_agg(pages, svc_index: dict) -> np.ndarray:
    """One block's per-entry composite keys (numpy, host side)."""
    lut = np.empty(len(pages.val_dict) + 1, dtype=np.int64)
    unknown = svc_index[""]
    for i, v in enumerate(pages.val_dict):
        lut[i] = svc_index.get(v, unknown)
    lut[-1] = unknown                     # entry_root_svc == -1
    gids = lut[pages.entry_root_svc]
    bins = np.searchsorted(np.asarray(MS_BUCKETS, dtype=np.int64),
                           pages.entry_dur.astype(np.int64), side="left")
    err = np.zeros(pages.entry_dur.shape, dtype=np.int64)
    kid = bisect.bisect_left(pages.key_dict, "error")
    vid = bisect.bisect_left(pages.val_dict, "true")
    if (kid < len(pages.key_dict) and pages.key_dict[kid] == "error"
            and vid < len(pages.val_dict)
            and pages.val_dict[vid] == "true"):
        err = ((pages.kv_key == kid)
               & (pages.kv_val == vid)).any(axis=-1).astype(np.int64)
    return ((gids * _NB1Q + bins) * 2 + err).astype(np.int32)


def build_agg_stage(blocks, pad_pages: int, entries_per_page: int) \
        -> AggStage:
    """Stage the batch-global composite-key column: one sorted service
    table over every member block's root services (plus the "" unknown
    slot), then per-block id remaps — all host numpy, one pass."""
    names = {""}
    for b in blocks:
        ids = np.unique(b.entry_root_svc[b.entry_valid])
        for i in ids.tolist():
            if i >= 0:
                names.add(b.val_dict[i])
    services = tuple(sorted(names))
    svc_index = {s: i for i, s in enumerate(services)}
    arr = np.zeros((pad_pages, entries_per_page), dtype=np.int32)
    po = 0
    for b in blocks:
        arr[po:po + b.n_pages] = _block_entry_agg(b, svc_index)
        po += b.n_pages
    return AggStage(services, arr)


# ---------------------------------------------------------------------------
# the process-wide engine

class AnalyticsEngine:
    """Process-wide singleton (the LiveTier/STRUCTURAL model): the most
    recent TempoDB's configure() wins; every hook gate-checks
    ``enabled`` first, so the default-off deployment pays one attribute
    read per push and per search."""

    def __init__(self):
        self.enabled = False
        self.min_rows = 1
        self._lock = threading.Lock()

    def configure(self, enabled: bool = False, min_rows: int = 1) -> None:
        with self._lock:
            self.min_rows = max(1, int(min_rows))
            # set LAST: a concurrent hook that observes enabled sees the
            # settled knobs
            self.enabled = bool(enabled)

    # ------------------------------------------------------------------
    # ingest side

    def consume_blob(self, procs, strs, blob, off: int, n_rows: int,
                     tids) -> bool:
        """Batched replacement for the generator processors' per-row
        walk over one native summary blob. Returns True when the blob
        was fully consumed (series updated byte-identically to the
        walk); False hands the blob back to the classic path — unknown
        processor types and tiny blobs stay on the walk."""
        if not self.enabled:
            return False
        from tempo_tpu.modules.generator import (ServiceGraphProcessor,
                                                 SpanMetricsProcessor)

        spm = sgp = None
        for p in procs:
            if type(p) is SpanMetricsProcessor and spm is None:
                spm = p
            elif type(p) is ServiceGraphProcessor and sgp is None:
                sgp = p
            else:
                return False
        if n_rows < self.min_rows:
            return False
        t0 = time.perf_counter()
        r = np.frombuffer(blob, dtype=_ROW_DT, count=n_rows, offset=off)
        if spm is not None:
            self._consume_spanmetrics(spm, strs, r)
        if sgp is not None:
            self._consume_servicegraph(sgp, strs, r, tids)
        from . import planner

        planner.PLANNER.observe("analytics", time.perf_counter() - t0,
                                nbytes=n_rows * _ROW_DT.itemsize)
        return True

    # -- spanmetrics ---------------------------------------------------

    def _consume_spanmetrics(self, spm, strs, r) -> None:
        from tempo_tpu.modules.generator import LATENCY_BUCKETS_S

        n = len(r)
        dur = np.maximum(
            r["end"].astype(np.int64) - r["start"].astype(np.int64), 0)
        svc = r["svc"].astype(np.int64)
        name = r["name"].astype(np.int64)
        kind = r["kind"].astype(np.int64)
        status = r["status"].astype(np.int64)
        # one packed int64 composite key beats np.unique(axis=0)'s void
        # view by ~5x at these sizes; the radix widths come from the
        # batch itself (overflow falls back to the 2-D unique)
        ms = [int(c.max()) + 1 if n else 1
              for c in (svc, name, kind, status)]
        if ms[0] * ms[1] * ms[2] * ms[3] < (1 << 62):
            packed = ((svc * ms[1] + name) * ms[2] + kind) * ms[3] + status
            uk, inverse = np.unique(packed, return_inverse=True)
            uniq = np.empty((len(uk), 4), dtype=np.int64)
            q, uniq[:, 3] = np.divmod(uk, ms[3])
            q, uniq[:, 2] = np.divmod(q, ms[2])
            uniq[:, 0], uniq[:, 1] = np.divmod(q, ms[1])
        else:
            cols = np.stack([svc, name, kind, status], axis=1)
            uniq, inverse = np.unique(cols, axis=0, return_inverse=True)
        # the string table may repeat strings: two distinct (svc, name)
        # index pairs can resolve to one logical series — remap to the
        # canonical group or the registry would split it
        canon: dict[tuple, int] = {}
        g_keys: list[tuple] = []
        g_of_uniq = np.empty(len(uniq), dtype=np.int64)
        for gi, u in enumerate(uniq):
            sk = (strs[int(u[0])], strs[int(u[1])], int(u[2]), int(u[3]))
            j = canon.get(sk)
            if j is None:
                j = canon[sk] = len(g_keys)
                g_keys.append(sk)
            g_of_uniq[gi] = j
        gids = g_of_uniq[inverse.reshape(-1)]
        G = len(g_keys)

        counts = self._count(gids, dur, n_keys=_pow2(G),
                             buckets=LATENCY_BUCKETS_S)
        nb1 = len(LATENCY_BUCKETS_S) + 1
        counts2 = counts.reshape(-1, nb1)

        # per-series float latency values, ROW ORDER preserved within
        # each series (stable sort) — the sequential host fold is what
        # keeps the histogram _sums byte-identical to the walk
        order = np.argsort(gids, kind="stable")
        starts = np.searchsorted(gids[order], np.arange(G + 1))
        vals = (dur.astype(np.float64) / 1e9)[order]
        # last-occurrence order reproduces the walk's final LRU order
        last = np.zeros(G, dtype=np.int64)
        np.maximum.at(last, gids, np.arange(n, dtype=np.int64))
        for g in np.argsort(last, kind="stable").tolist():
            c, h = spm._series_touch(g_keys[g])
            c.inc(int(starts[g + 1] - starts[g]))
            h.observe_bulk(counts2[g].tolist(),
                           vals[starts[g]:starts[g + 1]].tolist())

    # -- service graph -------------------------------------------------

    def _consume_servicegraph(self, sgp, strs, r, tids) -> None:
        now = time.monotonic()
        kind = r["kind"]
        cand = np.nonzero((kind == 2) | (kind == 3))[0]
        if cand.size:
            self._servicegraph_rows(sgp, strs, r, tids, cand, now)
        sgp._maybe_expire(now)

    def _servicegraph_rows(self, sgp, strs, r, tids, cand, now) -> None:
        from tempo_tpu import tempopb

        kind_c = r["kind"][cand].astype(np.int64)
        sid_u = np.frombuffer(r["sid"][cand].tobytes(), dtype="<u8")
        pid_u = np.frombuffer(r["pid"][cand].tobytes(), dtype="<u8")
        # the pairing id: a client's own span id, a server's parent id
        id_u = np.where(kind_c == 3, sid_u, pid_u)
        # canonical trace gid — duplicate trace-id BYTES in tids
        # collapse to one pairing key, exactly as the walk's tuples do
        tid_gid_of: dict[bytes, int] = {}
        tid_gids = np.empty(max(1, len(tids)), dtype=np.int64)
        for i, t in enumerate(tids):
            tid_gids[i] = tid_gid_of.setdefault(bytes(t),
                                                len(tid_gid_of))
        ti_c = r["ti"][cand].astype(np.int64)
        tg = tid_gids[ti_c]
        uid, id_inv = np.unique(id_u, return_inverse=True)
        _, ginv, gcount = np.unique(
            tg * len(uid) + id_inv.reshape(-1),
            return_inverse=True, return_counts=True)
        ginv = ginv.reshape(-1)
        nG = len(gcount)
        order = np.argsort(ginv, kind="stable")
        bounds = np.zeros(nG + 1, dtype=np.int64)
        np.cumsum(gcount, out=bounds[1:])
        ksum = np.bincount(ginv, weights=kind_c,
                           minlength=nG).astype(np.int64)
        # clean groups — exactly one client + one server, nothing
        # mid-pairing in the store — pair IN-BATCH with no store
        # round-trip; everything else replays the walk's _pair_collect
        # in row order, so overwrite/capacity semantics stay the walk's
        clean = (gcount == 2) & (ksum == 5)

        status_c = r["status"][cand].astype(np.int64)
        start_c = r["start"][cand].astype(np.int64)
        end_c = r["end"][cand].astype(np.int64)
        svc_c = r["svc"][cand].astype(np.int64)

        g_clean = np.nonzero(clean)[0]
        if g_clean.size and sgp._store:
            keep = np.ones(len(g_clean), dtype=bool)
            with sgp._lock:
                store = sgp._store
                for i, g in enumerate(g_clean.tolist()):
                    j = int(order[bounds[g]])
                    key = (tids[int(ti_c[j])],
                           int(id_u[j]).to_bytes(8, "little"))
                    if key in store:
                        keep[i] = False
            if not keep.all():
                clean[g_clean[~keep]] = False
                g_clean = g_clean[keep]

        # canonical service gid over the batch's string-table ids (the
        # table may repeat strings — same remap as spanmetrics)
        canon: dict[str, int] = {}
        names: list[str] = []
        lut = np.zeros(int(svc_c.max()) + 1 if cand.size else 1,
                       dtype=np.int64)
        for i in np.unique(svc_c).tolist():
            s = strs[i]
            gi = canon.get(s)
            if gi is None:
                gi = canon[s] = len(names)
                names.append(s)
            lut[i] = gi

        n_clean = len(g_clean)
        lo = bounds[g_clean]
        a = order[lo]
        b = order[lo + 1]
        a_cl = kind_c[a] == 3
        jc = np.where(a_cl, a, b)
        js = np.where(a_cl, b, a)

        extra = []   # replayed emissions: (pos, c_svc, s_svc, c_st,
        #              s_st, c_start, c_end)
        if not clean.all():
            for g in np.nonzero(~clean)[0].tolist():
                for j in order[bounds[g]:bounds[g + 1]].tolist():
                    side = "client" if kind_c[j] == 3 else "server"
                    key = (tids[int(ti_c[j])],
                           int(id_u[j]).to_bytes(8, "little"))
                    em = sgp._pair_collect(
                        key, side, strs[int(svc_c[j])],
                        (int(status_c[j]), int(start_c[j]),
                         int(end_c[j])), now)
                    if em is not None:
                        extra.append((j,) + em)
        total = n_clean + len(extra)
        if not total:
            return
        pos = np.empty(total, dtype=np.int64)
        cg = np.empty(total, dtype=np.int64)
        sg = np.empty(total, dtype=np.int64)
        c_st = np.empty(total, dtype=np.int64)
        s_st = np.empty(total, dtype=np.int64)
        dur = np.empty(total, dtype=np.int64)
        if n_clean:
            # a pair emits where its SECOND row lands — positions
            # restore the walk's emission order, which the per-edge
            # float latency fold depends on
            pos[:n_clean] = np.maximum(jc, js)
            cg[:n_clean] = lut[svc_c[jc]]
            sg[:n_clean] = lut[svc_c[js]]
            c_st[:n_clean] = status_c[jc]
            s_st[:n_clean] = status_c[js]
            dur[:n_clean] = np.maximum(end_c[jc] - start_c[jc], 0)
        for k, (j, e_c_svc, e_s_svc, e_c_st, e_s_st, e_cs,
                e_ce) in enumerate(extra):
            t = n_clean + k
            pos[t] = j
            for svc_str, dst in ((e_c_svc, cg), (e_s_svc, sg)):
                gi = canon.get(svc_str)
                if gi is None:
                    gi = canon[svc_str] = len(names)
                    names.append(svc_str)
                dst[t] = gi
            c_st[t] = e_c_st
            s_st[t] = e_s_st
            dur[t] = max(e_ce - e_cs, 0)
        o = np.argsort(pos, kind="stable")
        cg, sg, c_st, s_st, dur = cg[o], sg[o], c_st[o], s_st[o], dur[o]
        ERR = tempopb.Status.STATUS_CODE_ERROR
        failed = ((c_st == ERR) | (s_st == ERR)).astype(np.int64)
        uek, einv = np.unique(cg * len(names) + sg, return_inverse=True)
        einv = einv.reshape(-1)
        from tempo_tpu.modules.generator import LATENCY_BUCKETS_S

        E = len(uek)
        counts = self._count(einv * 2 + failed, dur,
                             n_keys=_pow2(2 * E),
                             buckets=LATENCY_BUCKETS_S)
        nb1 = len(LATENCY_BUCKETS_S) + 1
        counts2 = counts.reshape(-1, nb1)
        req_n = np.bincount(einv, minlength=E)
        fail_n = np.bincount(einv, weights=failed,
                             minlength=E).astype(np.int64)
        order_e = np.argsort(einv, kind="stable")
        starts_e = np.searchsorted(einv[order_e], np.arange(E + 1))
        vals = (dur.astype(np.float64) / 1e9)[order_e]
        for e, ek in enumerate(uek.tolist()):
            labels = dict(client=names[ek // len(names)],
                          server=names[ek % len(names)])
            sgp.requests.inc(int(req_n[e]), **labels)
            if fail_n[e]:
                sgp.failed.inc(int(fail_n[e]), **labels)
            bins = (counts2[2 * e] + counts2[2 * e + 1]).tolist()
            sgp.latency.observe_bulk(
                bins, vals[starts_e[e]:starts_e[e + 1]].tolist(),
                **labels)

    # -- the shared dense count ---------------------------------------

    def _count(self, sidx: np.ndarray, dur: np.ndarray, n_keys: int,
               buckets: tuple) -> np.ndarray:
        """Dense (series, bucket) counts for one micro-batch: the device
        kernel behind the breaker/watchdog, with a byte-identical
        integer numpy fallback (counts are exact either way — the route
        only changes where the sort ran)."""
        from tempo_tpu.robustness import BREAKER, GUARD, DeviceFault

        nb1 = len(buckets) + 1
        K = n_keys * nb1
        thr = _dur_thresholds(tuple(buckets))
        out = None
        route = "host"
        # two-limb keys cover dur < 2^62 ns (~146 years) — beyond that
        # the int64 host path answers (still exact)
        if (BREAKER.allow_device()
                and (dur.size == 0 or int(dur.max()) < (1 << 62))):
            try:
                out = GUARD.run(
                    "analytics",
                    lambda: self._count_device(sidx, dur, n_keys, thr))
                route = "device"
            except DeviceFault:
                out = None
        if out is None:
            full = _dur_thresholds_full(tuple(buckets))
            b = np.zeros(len(dur), dtype=np.int64)
            for t in full:
                b += dur >= t
            key = sidx.astype(np.int64) * nb1 + b
            out = np.bincount(key, minlength=K)[:K]
        obs.search_analytics_dispatches.labels(route=route).inc()
        return out.astype(np.int64)

    def _count_device(self, sidx, dur, n_keys: int, thr: tuple):
        _, jnp = _jax()

        n = len(sidx)
        tier = _pow2(max(1, n))
        cols = np.empty((3, tier), dtype=np.int32)
        cols[0, :n] = sidx
        cols[0, n:] = n_keys       # sentinel: pad rows land past range
        cols[1, :n] = dur >> 31
        cols[2, :n] = dur & 0x7FFFFFFF
        cols[1:, n:] = 0
        obs.search_analytics_staged_bytes.set(cols.nbytes)
        out = _count_kernel()(jnp.asarray(cols), n_keys=n_keys,
                              tier=tier, buckets=thr)
        return np.asarray(out)

    # ------------------------------------------------------------------
    # query side

    def stage_for_batch(self, batch) -> AggStage:
        """Memoized per-batch staging of the composite-key column for
        the host route's HostBatch (a resident group's column is part of
        its cache entry: GroupCache.agg_staged); a BlockBatch works too
        — both carry .blocks, and the page count comes from the staged
        arrays so pads line up."""
        st = getattr(batch, "_agg_stage", None)
        if st is None:
            d = getattr(batch, "device", None) or getattr(
                batch, "cat", None)
            pad_pages = int(d["entry_valid"].shape[0])
            epp = batch.blocks[0].geometry.entries_per_page
            st = build_agg_stage(batch.blocks, pad_pages, epp)
            batch._agg_stage = st     # benign race: idempotent content
        return st


ANALYTICS = AnalyticsEngine()
