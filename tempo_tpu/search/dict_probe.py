"""Device-resident dictionary probe: the substring prefilter on chip.

The host-side dictionary probe (pipeline.substring_value_ids — numpy
char.find, or the native memmem walk) sits serially in front of every
fresh (block, tag-set) dispatch; at BASELINE high cardinality it is the
dominant cost (~312 ms at 10M distinct values, a CPU-container host
timing) in front of the device scan. This module moves the probe to where the
columns already live — the near-data-processing move of "Near Data
Processing in Taurus Database" / the predicate-offload pattern of
"GPU-Augmented OLAP Execution Engine" (PAPERS.md): evaluate the filter
on device and stop shipping intermediate id-sets across the host
boundary.

Layout (staged once per block, cached with the batch):

  buf  u8  [S, N]    packed utf-8 dictionary bytes, value-contiguous,
                     zero-padded; S = probe shards (mesh size, else 1)
  pos  i32 [S, N]    position→value-id map: shard-LOCAL value id owning
                     each byte, -1 on padding
  off  i32 [S, V+1]  per-value byte offsets into the shard's buffer
                     (pad values collapse to empty ranges)

The kernel is gather-free on the match side: a needle of length L is a
rolling-window equality, unrolled over needle chars as L shifted compares
of the whole buffer (`buf[j:j+N] == needle[j]`) ANDed together — pure
vector compares at full VPU width. A window must not span a value
boundary, which the same unroll enforces through the position map
(`pos[i+j] == pos[i]`). The per-byte match vector segment-reduces into a
per-value hit mask via cumsum + offset differencing (`hits[v] =
cumsum(match)[off[v+1]] - cumsum(match)[off[v]] > 0`) — a deterministic
segment reduction with one [V]-sized gather over a monotone index,
instead of an [N]-sized scatter (scatters serialize on the VPU,
columnar.py's layout lesson).

Mesh sharding splits the dictionary along the VALUE axis: each device
probes its contiguous value range and the per-shard hit masks all_gather
into the replicated global mask — the same collective shape the scan
uses for its results (multiblock._merge_shards).

The probe yields a [T, V] bool hit mask and, in the same dispatch, the
mask's runs over the sorted value ids (_hit_runs: how many a term, and
the first R_MAX as inclusive [lo, hi] ids). Where every term's hits are
at most R_MAX runs (an id typed whole, a prefix, a fragment of a few
letters) the product that leaves here is those ranges, a few ints: the
block is scanned by the range compares exactly as a host-compiled block
is, no mask is kept and the launch gathers nothing. Past R_MAX the mask
stays on the device and multiblock.multi_entry_mask tests membership
with a lookup in it, one gather for every slot of every entry and term.
On a v5e (PERF.md section 6, PR 33) that gather costs 0.73 s a term for
4,096 pages where the compares cost 1.9 ms at one range and 120 ms at
1,024: the mask is the fallback, not the fast path.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from tempo_tpu.observability import profile

# Dictionaries below this many distinct values keep the exact host path
# (numpy / native memmem): the probe there is microseconds-to-low-ms and
# staging dictionary bytes to HBM would cost more than it saves. Mirrors
# pipeline.NATIVE_SCAN_THRESHOLD, which hands the HOST scan to the native
# memmem walk at the same scale. Plumbed as TempoDBConfig
# `search_device_probe_min_vals`; <= 0 disables device probing.
DEVICE_PROBE_MIN_VALS = 50_000

# Needles longer than this fall back to the host scan for the whole
# query: the kernel unrolls one shifted compare per needle byte, so the
# unroll factor is bounded to keep compiles small. Tag needles are
# short in practice (service names, ids); 64 bytes covers them.
MAX_NEEDLE_BYTES = 64

# A term whose hits are at most this many runs of the sorted dictionary
# (a run: consecutive value ids that all hit; a prefix of sorted strings
# is always ONE) leaves the probe as inclusive [lo, hi] id ranges, a few
# ints, and its block is scanned by the range compares like any
# host-compiled block; past it the [T, v_pad] mask stays on the device
# and the scan gathers from it. Set from a v5e (scripts/membership_bench
# .py, PR 33: a solo launch of 4,096 pages x 1,024 entries x 16 slots,
# int32 ids, ms at T = 1 | 2 terms): R = 1 1.9 | 2.4, 16 5.0 | 8.5, 64 8.8 |
# 16.3, 256 31 | 61, 512 61 | 120, 1,024 120 | 238; the mask 731 | 1,853.
# The mask's gather (one lookup a slot and term) loses to R pairs of
# compares at every R measured, by 6.1x at the last, so the constant is
# the largest R measured, not a crossing. Past 64 the compares grow by
# 0.117 ms a range, which would meet the mask near 6,000: open.
R_MAX = 1024


def _pow2(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


@dataclass
class PackedDeviceDict:
    """Host-side staging product for one distinct value dictionary."""
    n_vals: int            # real value count
    n_shards: int          # S — probe shards (mesh size at stage time)
    v_shard: int           # padded values per shard; v_pad = S * v_shard
    buf: np.ndarray        # uint8 [S, N]
    pos: np.ndarray        # int32 [S, N] local value id per byte, -1 pad
    off: np.ndarray        # int32 [S, v_shard + 1]
    n_real: np.ndarray     # int32 [S] real values in each shard
    fingerprint: bytes     # pipeline._dict_fingerprint of the source dict

    @property
    def v_pad(self) -> int:
        return self.n_shards * self.v_shard

    @property
    def nbytes(self) -> int:
        return int(self.buf.nbytes + self.pos.nbytes + self.off.nbytes
                   + self.n_real.nbytes)

    @property
    def real_bytes(self) -> int:
        """Unpadded dictionary byte length (the host scan's work unit;
        the offload planner's host-cost input). Derived from the shard
        offsets — no dictionary walk."""
        hit = getattr(self, "_real_bytes", None)
        if hit is None:
            S = self.n_shards
            hit = int(self.off[np.arange(S), self.n_real].sum())
            self._real_bytes = hit
        return hit


@dataclass
class DeviceDict:
    """A PackedDeviceDict's arrays resident on device(s)."""
    packed: PackedDeviceDict
    device: dict           # name -> jnp array (buf/pos/off/n_real)
    mesh: object = None    # the mesh the arrays were placed for (or None)

    @property
    def v_pad(self) -> int:
        return self.packed.v_pad

    @property
    def n_vals(self) -> int:
        return self.packed.n_vals

    @property
    def nbytes(self) -> int:
        return int(sum(int(a.nbytes) for a in self.device.values()))


def pack_device_dict(val_dict: list, n_shards: int = 1,
                     fingerprint: bytes = b"") -> PackedDeviceDict:
    """Pack a sorted value dictionary for the device probe, split into
    `n_shards` contiguous value ranges (the mesh's value-axis split; 1
    when unsharded). Byte and value axes pad to power-of-two buckets so
    the probe kernel compiles once per (size-bucket, needle-bucket)."""
    import time as _time

    t_pack0 = _time.perf_counter()
    n_vals = len(val_dict)
    S = max(1, int(n_shards))
    v_shard = _pow2(max(1, -(-n_vals // S)))
    blobs = [v.encode("utf-8") for v in val_dict]
    lens = np.fromiter((len(b) for b in blobs), dtype=np.int64,
                       count=n_vals)
    shard_bytes = []
    for s in range(S):
        lo, hi = s * v_shard, min((s + 1) * v_shard, n_vals)
        shard_bytes.append(int(lens[lo:hi].sum()) if lo < hi else 0)
    N = _pow2(max(1, max(shard_bytes)))
    if max(shard_bytes) >= 2**31:
        raise ValueError("dictionary shard exceeds int32 byte addressing")
    buf = np.zeros((S, N), dtype=np.uint8)
    pos = np.full((S, N), -1, dtype=np.int32)
    off = np.zeros((S, v_shard + 1), dtype=np.int32)
    n_real = np.zeros(S, dtype=np.int32)
    for s in range(S):
        lo, hi = s * v_shard, min((s + 1) * v_shard, n_vals)
        if lo >= hi:
            continue
        n_real[s] = hi - lo
        ln = lens[lo:hi]
        ends = np.cumsum(ln)
        nb = int(ends[-1])
        off[s, 1:hi - lo + 1] = ends
        off[s, hi - lo + 1:] = nb  # pad values: empty [nb, nb) ranges
        if nb:
            blob = b"".join(blobs[lo:hi])
            buf[s, :nb] = np.frombuffer(blob, dtype=np.uint8)
            pos[s, :nb] = np.repeat(
                np.arange(hi - lo, dtype=np.int32), ln)
    out = PackedDeviceDict(n_vals=n_vals, n_shards=S, v_shard=v_shard,
                           buf=buf, pos=pos, off=off, n_real=n_real,
                           fingerprint=fingerprint)
    from . import planner

    # pack cost is part of a non-resident device decision: feed the
    # planner's rate (noop when the planner is disabled)
    planner.PLANNER.observe("pack", _time.perf_counter() - t_pack0,
                            nbytes=out.real_bytes)
    return out


def place_device_dict(packed: PackedDeviceDict, mesh=None,
                      sharding=None) -> DeviceDict:
    """H2D for a packed dictionary. With a mesh the shard axis (axis 0)
    splits across devices; `sharding` overrides (multi-host staging uses
    make_array_from_callback upstream)."""
    import time

    t0 = time.perf_counter()
    host = {"buf": packed.buf, "pos": packed.pos, "off": packed.off,
            "n_real": packed.n_real}
    if sharding is not None:
        dev = {k: jax.device_put(v, sharding) for k, v in host.items()}
    elif mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        from tempo_tpu.parallel.mesh import SCAN_AXIS, put

        dev = put(host, NamedSharding(mesh, P(SCAN_AXIS)))
    else:
        dev = {k: jnp.asarray(v) for k, v in host.items()}
    profile.observe_stage("h2d", "dict_probe", time.perf_counter() - t0,
                          nbytes=packed.nbytes)
    return DeviceDict(packed=packed, device=dev, mesh=mesh)


def stage_val_dict(val_dict: list, n_shards: int = 1, mesh=None,
                   fingerprint: bytes = b"",
                   cache_on=None) -> DeviceDict:
    """pack + place, memoizing the HOST packing on `cache_on` (the
    immutable ColumnarPages) so an HBM-evicted batch re-uploads with one
    H2D copy, not a re-pack of 10M strings."""
    packed = None
    if cache_on is not None:
        hit = getattr(cache_on, "_device_dict_packed", None)
        if hit is not None and hit.n_shards == max(1, int(n_shards)):
            packed = hit
    if packed is None:
        packed = pack_device_dict(val_dict, n_shards=n_shards,
                                  fingerprint=fingerprint)
        if cache_on is not None:
            cache_on._device_dict_packed = packed
    return place_device_dict(packed, mesh=mesh)


# ---------------------------------------------------------------------------
# kernels


def _cumsum_pow2(x):
    """jnp.cumsum of an int32 [N], N a power of two, in two levels: along
    the rows of its [N / 1024, 1024] view, then over the rows' totals.
    The same sums; the reason is the compiler: one scan over the 1 MiB
    of a 53,000-value dictionary takes the TPU toolchain 18 s to compile
    here and 32-36 s beside a serving process on a v5e's host, past the
    30 s watchdog of the dispatch that first runs it (a device fault and
    a host probe after every cold start); two short scans take under one."""
    n = x.shape[0]
    b = min(n, 1024)
    rows = jnp.cumsum(x.reshape(n // b, b), axis=1)
    before = jnp.cumsum(rows[:, -1]) - rows[:, -1]
    return (rows + before[:, None]).reshape(n)


def _probe_core(buf, pos, off, n_real, needles, lens, empties,
                *, n_needle_max: int):
    """hits bool [T, V] over ONE shard's byte buffer.

    buf u8 [N], pos i32 [N] (local value id, -1 pad), off i32 [V+1],
    n_real i32 scalar, needles u8 [T, Lp], lens i32 [T], empties bool [T].
    """
    N = buf.shape[0]
    V = off.shape[0] - 1
    # window reads run to i + L - 1: extend with bytes that can never
    # match (pos sentinel -2 differs from both real ids and -1 padding)
    buf_ext = jnp.concatenate(
        [buf, jnp.zeros((n_needle_max,), dtype=buf.dtype)])
    pos_ext = jnp.concatenate(
        [pos, jnp.full((n_needle_max,), -2, dtype=pos.dtype)])

    def one_term(needle, ln, empty):
        acc = pos >= 0  # windows must start on a real dictionary byte
        for j in range(n_needle_max):  # static unroll: shifted compares
            active = jnp.int32(j) < ln
            ok = ((buf_ext[j:j + N] == needle[j])
                  & (pos_ext[j:j + N] == pos))  # same-value boundary check
            acc = acc & (ok | ~active)
        # segment-reduce match positions into per-value hits: cumsum +
        # offset differencing (one monotone [V] gather, no scatter)
        c = jnp.concatenate([
            jnp.zeros((1,), jnp.int32),
            _cumsum_pow2(acc.astype(jnp.int32)),
        ])
        hits = (c[off[1:]] - c[off[:-1]]) > 0
        # empty needle: every real value matches (host semantics —
        # including zero-length values, which own no byte positions)
        hits = jnp.where(empty, jnp.arange(V, dtype=jnp.int32) < n_real,
                         hits)
        return hits

    return jax.vmap(one_term)(needles, lens, empties)


def _hit_runs(hits, r_max: int):
    """The runs of a hit mask over the sorted value ids: (n_runs int32
    [T], bounds int32 [T, r_max, 2]) with the first `r_max` runs as
    inclusive [lo, hi] ids and [1, 0] (matches nothing) behind them:
    pipeline.ids_to_ranges on the device. The r-th run starts where the
    count of 0->1 edges reaches r and ends where the count of 1->0
    edges does: two cumsums and 2 x r_max binary searches a term."""
    h = hits.astype(jnp.int32)
    prev = jnp.pad(h, ((0, 0), (1, 0)))[:, :-1]
    nxt = jnp.pad(h, ((0, 0), (0, 1)))[:, 1:]
    starts = jnp.cumsum(h & (1 - prev), axis=1)
    ends = jnp.cumsum(h & (1 - nxt), axis=1)
    r = jnp.arange(1, r_max + 1, dtype=jnp.int32)
    find = jax.vmap(lambda c: jnp.searchsorted(c, r, side="left"))
    n_runs = starts[:, -1]
    real = r[None, :] <= n_runs[:, None]
    lo = jnp.where(real, find(starts), 1).astype(jnp.int32)
    hi = jnp.where(real, find(ends), 0).astype(jnp.int32)
    return n_runs, jnp.stack([lo, hi], axis=-1)


@functools.partial(jax.jit, static_argnames=("n_needle_max", "r_max"))
def probe_kernel(buf, pos, off, n_real, needles, lens, empties,
                 *, n_needle_max: int, r_max: int = R_MAX):
    """Single-device probe over [S, ...] staged arrays — EVERY shard is
    probed (vmapped) and reassembled in shard order, so a dictionary
    packed for an S-way mesh but placed unsharded (place_batch's
    mismatch fallback) still yields the full [T, v_pad] mask, just
    without the parallelism. Returns (hits bool [T, v_pad],
    any_hits bool [T], n_runs int32 [T], bounds int32 [T, r_max, 2]):
    the mask, and the same hits as runs of the sorted ids (_hit_runs)."""
    local = jax.vmap(
        lambda b, p, o, nr: _probe_core(b, p, o, nr, needles, lens,
                                        empties,
                                        n_needle_max=n_needle_max)
    )(buf, pos, off, n_real)                           # [S, T, v_shard]
    hits = jnp.swapaxes(local, 0, 1).reshape(needles.shape[0], -1)
    return (hits, hits.any(axis=1), *_hit_runs(hits, r_max))


@functools.partial(jax.jit,
                   static_argnames=("mesh", "n_needle_max", "r_max"))
def dist_probe_kernel(mesh, buf, pos, off, n_real, needles, lens, empties,
                      *, n_needle_max: int, r_max: int = R_MAX):
    """Mesh probe: the dictionary's value axis is split across shards
    (axis 0 of the staged arrays); every device probes its value range
    and the local masks all_gather into the replicated global [T, v_pad]
    mask — same collective shape as the scan's result funnel. The runs
    are read off the gathered mask, on every device alike."""
    from jax.sharding import PartitionSpec as P
    from tempo_tpu.parallel.mesh import SCAN_AXIS, shard_map_compat

    def shard_fn(buf, pos, off, n_real, needles, lens, empties):
        local = _probe_core(buf[0], pos[0], off[0], n_real[0],
                            needles, lens, empties,
                            n_needle_max=n_needle_max)     # [T, v_shard]
        all_h = jax.lax.all_gather(local, SCAN_AXIS)       # [S, T, vs]
        hits = jnp.swapaxes(all_h, 0, 1).reshape(local.shape[0], -1)
        return (hits, hits.any(axis=1), *_hit_runs(hits, r_max))

    return shard_map_compat(
        shard_fn, mesh=mesh,
        in_specs=(P(SCAN_AXIS),) * 4 + (P(),) * 3,
        out_specs=(P(),) * 4,
        # all_gather output is identical on every shard; the replication
        # checker can't infer it through the gather (same stance as
        # multiblock.batch_scan_kernel)
        check=False,
    )(buf, pos, off, n_real, needles, lens, empties)


def probe_value_hits(ddev: DeviceDict, needles: list[bytes]):
    """The mask half of `probe_values`: (hits, any_hits)."""
    return probe_values(ddev, needles)[:2]


def probe_values(ddev: DeviceDict, needles: list[bytes]):
    """Run the device probe for a list of utf-8 needles against a staged
    dictionary. Returns (hits [T, v_pad] bool, any_hits [T] bool, n_runs
    [T] int32, bounds [T, R_MAX, 2] int32) DEVICE arrays — nothing
    synchronizes to host here; callers fetch any_hits and the runs (a
    few bytes) when they decide on pruning and on the membership path.

    Raises ValueError for needles longer than MAX_NEEDLE_BYTES — callers
    fall back to the exact host scan for that query."""
    T = len(needles)
    if T == 0:
        raise ValueError("probe_values needs at least one needle")
    lmax = max(len(n) for n in needles)
    if lmax > MAX_NEEDLE_BYTES:
        raise ValueError(f"needle exceeds {MAX_NEEDLE_BYTES} bytes")
    with profile.dispatch("dict_probe") as rec:
        with rec.stage("build"):
            Lp = _pow2(max(1, lmax))
            arr = np.zeros((T, Lp), dtype=np.uint8)
            lens = np.zeros(T, dtype=np.int32)
            empties = np.zeros(T, dtype=bool)
            for t, nb in enumerate(needles):
                arr[t, :len(nb)] = np.frombuffer(nb, dtype=np.uint8)
                lens[t] = len(nb)
                empties[t] = len(nb) == 0
        d = ddev.device
        rec.add_bytes(h2d=arr.nbytes + lens.nbytes + empties.nbytes)
        miss = rec.compile_check(
            ("probe", ddev.mesh is not None, d["buf"].shape,
             d["off"].shape, T, Lp))
        stage = "compile" if miss else "execute"
        # probe_bytes/fp: the offload planner's device-rate feed — it
        # listens on finished dispatch records (mode=dict_probe) and
        # needs the work size (terms × staged bytes) plus the dictionary
        # identity to resolve predicted-vs-actual error
        rec.set(n_vals=ddev.n_vals, n_terms=T,
                probe_bytes=T * ddev.nbytes,
                fp=(ddev.packed.fingerprint.hex()[:16]
                    if ddev.packed.fingerprint else None))
        if ddev.mesh is not None:
            from tempo_tpu.parallel.mesh import (
                locked_collective, put_replicated,
            )

            # the needles on every device of the mesh BEFORE the lock,
            # like every collective launch's replicated operands: the
            # lock holds the enqueue, not an upload
            with rec.stage("build"):
                needle_args = put_replicated(ddev.mesh,
                                             (arr, lens, empties))
            # collective dispatch: serialize with every other shard_map
            # enqueue in the process (the probe fires during query
            # compile, concurrent with scan dispatches on the same
            # devices — an interleaved per-device queue deadlocks the
            # collectives)
            with locked_collective(rec):
                with rec.stage(stage):
                    out = dist_probe_kernel(
                        ddev.mesh, d["buf"], d["pos"], d["off"],
                        d["n_real"], *needle_args, n_needle_max=Lp,
                        r_max=R_MAX)
            # fence after releasing the collective lock (lock-order
            # suite: no blocking wait under dispatch_lock); the stage
            # timer accumulates so kernel time books to the same stage
            with rec.stage(stage):
                rec.fence(out)
            return out
        with rec.stage(stage):
            out = probe_kernel(d["buf"], d["pos"], d["off"], d["n_real"],
                               jnp.asarray(arr), jnp.asarray(lens),
                               jnp.asarray(empties), n_needle_max=Lp,
                               r_max=R_MAX)
            rec.fence(out)
        return out


def hits_to_ids(hits_row) -> np.ndarray:
    """Host-side view of one term's hit mask as a sorted id array — the
    parity bridge to pipeline.substring_value_ids for tests/bench.
    Accepts both mask formats: bool rows and the packed-residency
    uint32 bit-words (search/packing.py)."""
    a = np.asarray(hits_row)
    if a.dtype == np.uint32:
        from .packing import unpack_mask_words

        a = unpack_mask_words(a, a.shape[-1] * 32)
    return np.nonzero(a)[0].astype(np.int32)
