"""Multi-block batched scanning.

The reference searches blocks one job at a time (10 MiB page ranges per
job, searchsharding.go); on TPU the economics invert — kernel dispatch
has fixed cost and HBM is huge, so MANY blocks batch into ONE kernel
call: block page-arrays concatenate along the page axis (geometry is
uniform per (E, C) bucket), a per-page block-id column maps results back,
and the query compiles once against a MERGED dictionary space.

Dictionary merging: each block has private key/val dictionaries. Rather
than re-encoding blocks to a global dictionary (expensive write-side),
the query compiles per block — per-page TERM COLUMNS: for block b and
term t, the key id and value ranges differ; we build [P_total] per-term
key-id arrays and range tables indexed by each page's block, so the
kernel's compares stay uniform. This is the context-parallel analog of
SURVEY.md §5 long-context: the corpus axis (blocks × pages) is the
sequence axis, sharded over the mesh.
"""

from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from tempo_tpu import tempopb
from tempo_tpu.observability import metrics as obs
from tempo_tpu.observability import profile
from tempo_tpu.observability import tracing

from .columnar import ColumnarPages
from .dict_probe import _pow2
from .engine import (
    DEFAULT_TOP_K,
    book_topk,
    fetch_scan_out,
    latest_k,
    masked_topk,
    pack_out,
    query_device_params,
    resolve_top_k,
)
from . import packing
from .packing import duration_ok, mask_select_grouped, unpack_ids
from .pipeline import (
    CompiledQuery,
    compile_query,
    ids_to_ranges,
    INT32_SENTINEL,
    UINT32_MAX,
)

import copy
import functools
import itertools
import math


# Ranges a term past which a member fuses apart from narrower ones
# (QueryCoalescer.submit): a fused launch pads every member to its
# widest, and a fused launch of four costs 6.1 ms for 4,096 pages at 64
# ranges, 17.8 at 512 (scripts/membership_bench.py on a v5e, PR 36).
WIDE_RANGES = 64

# Ranges a term from which the scan tests an ENTRY's value for the
# term's key against them, and under which it tests every slot of the
# entry against all of them in one pass (multi_entry_mask): the lowest
# power of two at which the entry form is a tenth faster solo at one and
# two terms and fused. The same bench, ms a launch by slot -> by entry at
# 1 term | 2 terms | fused x 4: 4 ranges 1.90 | 2.58 | 4.65 -> 2.20 |
# 3.05 | 4.92; 8 ranges 3.21 | 5.32 | 11.04 -> 2.12 | 3.07 | 4.89; 16 4.75
# | 8.27 | 15.76 -> 2.13 | 2.95 | 4.75; 512 60.5 | 119.8 | 246.8 -> 5.44 |
# 9.47 | 17.75 (by slot the compares ran 64 at a time past 64); one range
# 1.89 | 2.46 | 3.18, by slot as ever. Past 128, 0.0065 ms a range and
# term where by slot it was 0.117.
ENTRY_RANGES = 8
# Ranges the entry form compares at a time, and the least it does: a
# narrower table is padded with sentinels. Under 32 the TPU compiler
# took the kv columns through a two-term loop with their slots on the
# lanes, a copy of both for every launch (17.0 ms at 2 .. 16 ranges and
# two terms, 2.9 at 32: tests/test_scan_kernel_v5e.py holds it). 128 at
# a time read 5.36 ms at 512 ranges, 64 at a time 6.05.
_RANGE_BLOCK = 128
_RANGE_BLOCK_MIN = 32

# every key the scan program's launches gave `compile_check` since the
# process started (the gauge tempo_search_scan_jit_keys is its size)
_SCAN_JIT_KEYS: set = set()


def compares_by(n_ranges: int) -> str:
    """Which of multi_entry_mask's two range forms a launch of that
    many ranges a term traces: `slot` or `entry`."""
    return "entry" if n_ranges >= ENTRY_RANGES else "slot"


def block_bucket(n_blocks: int) -> int:
    """Rows of a launch's per-block tables for a group of `n_blocks`:
    the next power of two, as the page axis beside it is padded
    (MultiBlockEngine.stage_host). The block count of a group follows
    the blocklist; the rows are a jit shape of the scan program, so
    they come in log2 sizes. Rows past the group's blocks are pad rows:
    key id -1, the sentinel of a pruned block, and no page's
    `page_block` names one."""
    return _pow2(max(1, int(n_blocks)))


@dataclass
class BlockBatch:
    """Several blocks' pages stacked along the page axis on device."""
    device: dict                    # arrays [P_total, ...]
    page_block: np.ndarray          # int32 [P_total] block index per page
    blocks: list                    # list[ColumnarPages]
    page_offset: list               # start page of each block in the stack
    # dict fingerprint -> dict_probe.DeviceDict for every DISTINCT value
    # dictionary that cleared the device-probe threshold at staging time:
    # query compilation then runs the substring probe on device against
    # these instead of the host memmem walk. Staged with the batch,
    # accounted in `nbytes`, re-uploaded with it after an HBM eviction.
    staged_dicts: dict = field(default_factory=dict)
    # packed-residency width descriptor (search/packing.py): static per
    # batch, part of every consuming kernel's jit shape key; None = the
    # unpacked legacy layout
    widths: tuple | None = None
    # what the unpacked layout would pin for these page arrays — the
    # logical side of the physical/logical accounting split (equal to
    # device_nbytes when widths is None)
    logical_device_nbytes: int = 0
    # structural-engine span columns on device (search/structural.py):
    # staged with the batch only when search_structural_enabled AND some
    # block carries spans; None keeps the legacy kernel pytree exactly
    span_device: dict | None = None
    # True = span columns are in the segment-aligned SHARDED layout
    # (search_structural_shard_spans): chunk-per-shard span axis with
    # shard-local coordinates, so the dist kernels evaluate the
    # structural mask inside shard_map. Static at every consuming call
    # site — part of the jit shape key like `widths`
    span_sharded: bool = False
    # (start, end) on the span clock of the span columns' own put,
    # fenced (place_batch): what `batcher.stage_spans` is written from
    span_put_ns: tuple = ()

    @property
    def n_pages(self) -> int:
        return int(self.page_block.shape[0])

    @property
    def device_nbytes(self) -> int:
        """Physical HBM pinned by the stacked page arrays alone (packed
        bytes when widths is set; span columns included — they are
        resident with the batch)."""
        hit = getattr(self, "_device_nbytes", None)
        if hit is None:
            hit = self._device_nbytes = int(
                sum(int(a.nbytes) for a in self.device.values())
                + sum(int(a.nbytes)
                      for a in (self.span_device or {}).values()))
        return hit

    @property
    def nbytes(self) -> int:
        """HBM pinned by this batch: the stacked page arrays PLUS the
        staged dictionary arrays — the cache budget must see both or a
        high-cardinality tenant's dictionaries become unaccounted
        residents. Physical (packed) bytes: that is what the budget
        buys, and why packing fits ~2x more blocks per budget."""
        return (self.device_nbytes
                + int(sum(d.nbytes for d in self.staged_dicts.values())))

    @property
    def logical_nbytes(self) -> int:
        """The unpacked-layout equivalent of `nbytes` (dictionaries are
        already byte buffers — same on both sides of the split)."""
        return (int(self.logical_device_nbytes or self.device_nbytes)
                + int(sum(d.nbytes for d in self.staged_dicts.values())))


@dataclass
class HostBatch:
    """The host-RAM half of a staged batch: stacked (padded) numpy arrays
    ready for a device put. This is the overflow tier between the object
    store and HBM — an HBM-evicted batch re-stages from here with ONE
    H2D copy, skipping IO + decompress + restack (VERDICT r3 #2). Under
    owner-routed HBM it is also the NON-owner serving tier (host_scan
    runs over these arrays), which is why an ownership rebalance drops
    only the HBM half: the host copy keeps serving routed-away queries."""
    cat: dict                       # stacked host arrays incl. page_block
    page_block: np.ndarray
    # list[ColumnarPages], for result rendering + query compile. Where
    # `cat` owns its memory in the plain layout these are copies that
    # read the stacked columns from `cat` (`_blocks_over`), so the entry
    # does not pin the containers' decoded buffers a second time
    blocks: list
    page_offset: list
    # dict fingerprint -> dict_probe.PackedDeviceDict: the host half of
    # the device-probe staging, packed once per distinct dictionary and
    # kept with the batch so an HBM-evicted batch re-uploads with one
    # H2D copy, not a re-pack of 10M strings
    packed_dicts: dict = field(default_factory=dict)
    # packed-residency descriptor + logical bytes of the stacked copies
    # (see BlockBatch) — the host tier stages the SAME packed format, so
    # an HBM re-stage is one H2D put of the packed arrays and the
    # host-fallback scan runs the packed kernel directly
    widths: tuple | None = None
    cat_logical_nbytes: int = 0
    # structural span columns, host tier (see BlockBatch.span_device):
    # the host-fallback scan runs the same structural kernel over these
    span_cat: dict | None = None
    # bytes of `blocks`' columns that are views of `cat`: counted once
    aliased_nbytes: int = 0
    # the prepare memo of the group's last staged batch, kept across an
    # HBM eviction (group_cache._keep_memo_locked) and taken back by the
    # next stage: host objects only, small beside the columns, uncharged
    query_memo: object | None = None
    # the host route's own prepare memo (host-only compiles: range
    # tables, no device state), read and kept through the group cache's
    # `memo_get` / `memo_put` like a resident entry's
    query_cache: OrderedDict = field(default_factory=OrderedDict)

    @property
    def cat_nbytes(self) -> int:
        """Physical bytes of the stacked copies alone (the H2D unit)."""
        return int(sum(a.nbytes for a in self.cat.values())
                   + sum(a.nbytes
                         for a in (self.span_cat or {}).values()))

    @property
    def logical_nbytes(self) -> int:
        """`nbytes` with the stacked copies at the unpacked layout —
        the logical side of the host-tier accounting split."""
        return int((self.cat_logical_nbytes or self.cat_nbytes)
                   + sum(b.nbytes for b in self.blocks)
                   - self.aliased_nbytes
                   + sum(d.nbytes for d in self.packed_dicts.values()))

    @property
    def nbytes(self) -> int:
        # the entry pins BOTH the stacked copies and what each block
        # keeps beside them (needed for result rendering + query
        # compile) — budget against real RAM, not just the cat arrays,
        # or a 32 GB budget pins ~64 GB (code-review r4)
        return int(self.cat_nbytes
                   + sum(b.nbytes for b in self.blocks)
                   - self.aliased_nbytes
                   + sum(d.nbytes for d in self.packed_dicts.values()))

    @property
    def n_pages(self) -> int:
        # duck-types with BlockBatch so the host-fallback scan renders
        # results through the same MultiBlockEngine.results
        return int(self.page_block.shape[0])


def _pack_batch_dicts(blocks: list[ColumnarPages],
                      probe_min_vals: int | None,
                      n_shards: int = 1) -> dict:
    """fp -> PackedDeviceDict for every DISTINCT value dictionary above
    the device-probe threshold (None = dict_probe default; <= 0
    disables). Packing memoizes on the immutable block container, so an
    evicted batch restacked from the same blocks packs nothing.

    With the offload planner enabled, dictionaries above the floor get a
    per-GROUP stage-time decision (once per distinct dictionary per
    staged batch — a fused multi-query dispatch over this batch then
    inherits one verdict, never re-plans per member): a "host" verdict
    skips the pack+stage entirely, so the HBM and H2D investment is only
    made where the cost model says the device probe pays it back. The
    verdict is frozen into the staged batch until it re-stages (HBM
    eviction, blocklist churn) — the same lifetime every other staging
    property has."""
    from . import dict_probe, planner
    from .pipeline import _dict_fingerprint

    mv = (dict_probe.DEVICE_PROBE_MIN_VALS if probe_min_vals is None
          else probe_min_vals)
    out: dict = {}
    if mv <= 0:
        return out
    S = max(1, int(n_shards))
    vetoed: set = set()  # host verdicts memoize like device ones: ONE
    # decision per distinct dictionary per staged batch, even when many
    # blocks share a vetoed dictionary (no per-block ring/metric spam)
    for b in blocks:
        if len(b.val_dict) < mv:
            continue
        fp = _dict_fingerprint(b, b.key_dict, b.val_dict)
        if fp in out or fp in vetoed:
            continue
        if planner.stage_veto(b, fp, n_shards=S):
            vetoed.add(fp)
            continue
        hit = getattr(b, "_device_dict_packed", None)
        packed_ok = hit is not None and hit.n_shards == S
        if packed_ok:
            out[fp] = hit
        else:
            out[fp] = b._device_dict_packed = dict_probe.pack_device_dict(
                b.val_dict, n_shards=S, fingerprint=fp)
    return out


# the columns stack_host stacks without changing a value (the kv pair
# narrowed, the rest as they are) in the plain layout
_STACKED = ("kv_key", "kv_val", "entry_start", "entry_end", "entry_dur",
            "entry_valid")


def _blocks_over(cat: dict, blocks: list[ColumnarPages],
                 page_offset: list) -> tuple[list, int]:
    """The blocks as the host tier keeps them: each a shallow copy (the
    memos set on the container come along) whose stacked columns are
    views of `cat` and whose other arrays are its own. A decoded
    container's arrays all view ONE buffer (`ColumnarPages.from_bytes`),
    so a block kept as it came pins that buffer whole beside the stacked
    copy: 10.7 MB a block of 65,536 entries for the 1.5 MB still read
    from it, 3.7x the group's HBM bytes in host RAM. Returns the blocks
    and the bytes they share with `cat`."""
    out, aliased = [], 0
    for b, off in zip(blocks, page_offset):
        slim = copy.copy(b)
        for name, _ in b._ARRAYS + b._SPAN_ARRAYS:
            arr = getattr(b, name)
            if arr is None:
                continue
            if name in _STACKED:
                view = cat[name][off:off + b.n_pages]
                if view.ndim == 3:      # a group pads to its widest block
                    view = view[:, :, :arr.shape[2]]
                aliased += view.nbytes
            else:
                view = arr if arr.base is None else arr.copy()
            setattr(slim, name, view)
        out.append(slim)
    return out, aliased


def stack_host(blocks: list[ColumnarPages],
               pad_to: int | None = None,
               probe_min_vals: int | None = 0,
               n_shards: int = 1) -> HostBatch:
    """Concatenate uniform-geometry blocks along the page axis on host.

    `probe_min_vals` routes value dictionaries at/above that size into
    the packed device-probe staging (`HostBatch.packed_dicts`); the
    default 0 keeps direct/test callers dictionary-free — the serving
    path (MultiBlockEngine.stage_host) passes its configured
    threshold."""
    E = blocks[0].geometry.entries_per_page
    C = C0 = max(b.geometry.kv_per_entry for b in blocks)
    n_keys = max(len(b.key_dict) for b in blocks)
    n_vals = max(len(b.val_dict) for b in blocks)
    # packed residency (search/packing.py): choose per-column storage
    # widths from the recorded dictionary cardinalities + the duration
    # rollup. Gate off = widths None = the legacy layout below,
    # byte-identical, one attribute read.
    widths = None
    if packing.PACKING.enabled:
        widths = packing.PACKING.plan_widths(
            n_keys, n_vals, max(b.max_dur_ms() for b in blocks))
        if widths is not None and "u4" in widths[:2] and C % 2:
            C += 1  # nibble packing pairs slots; both kv columns must
            # unpack to one slot count (extra slot is pad, never matches)
    # narrow the kv columns to the smallest dtype the dictionaries allow:
    # the kernel compares against int32 term tables with XLA promoting
    # inline (no widened copy materializes), so the RESIDENT format can
    # be this narrow — the kv pair is ~70% of a batch's bytes, and both
    # HBM footprint and the bytes an evicted group's re-stage moves
    # over H2D shrink proportionally (on a v5e the allocator holds
    # 1.04-1.16x the logical bytes of these [pages, 1024, 4] int8
    # columns: chip_smoke.py prints the ratio). Dtype chosen BEFORE
    # stacking so concatenate produces the narrow array directly (no
    # full-width transient); packed widths likewise transform per block
    # before stacking.
    def _narrow(n):
        return (np.int8 if n <= 127          # -1 sentinel stays in range
                else np.int16 if n <= 32_767 else np.int32)
    kv_dtype = {"kv_key": _narrow(n_keys), "kv_val": _narrow(n_vals)}
    kv_width = None if widths is None else {"kv_key": widths[0],
                                            "kv_val": widths[1]}
    arrays = {name: [] for name in ("kv_key", "kv_val", "entry_start",
                                    "entry_end", "entry_dur", "entry_valid")}
    page_block = []
    page_offset = []
    total = 0
    for bi, b in enumerate(blocks):
        if b.geometry.entries_per_page != E:
            raise ValueError("blocks must share entries_per_page to batch")
        page_offset.append(total)
        P = b.n_pages
        for name in arrays:
            arr = getattr(b, name)
            if name in ("kv_key", "kv_val"):
                if kv_width is None:
                    arr = arr.astype(kv_dtype[name], copy=False)
                    if arr.shape[2] < C:
                        pad = np.full((P, E, C - arr.shape[2]), -1,
                                      dtype=kv_dtype[name])
                        arr = np.concatenate([arr, pad], axis=2)
                else:
                    if arr.shape[2] < C:
                        pad = np.full((P, E, C - arr.shape[2]), -1,
                                      dtype=arr.dtype)
                        arr = np.concatenate([arr, pad], axis=2)
                    arr = packing.pack_ids_array(arr, kv_width[name])
            arrays[name].append(arr)
        page_block.extend([bi] * P)
        total += P
    one_view = len(blocks) == 1 and not (pad_to and pad_to > total)
    if one_view:
        # single-block fast path: the block already matches the bucket
        # shape, so the concatenate below would be a pure copy of every
        # column — serve views of the (possibly just-transformed)
        # arrays instead
        cat = {k: v[0] for k, v in arrays.items()}
    else:
        cat = {k: np.concatenate(v, axis=0) for k, v in arrays.items()}
    page_block = np.asarray(page_block, dtype=np.int32)

    if widths is not None:
        # duration column: exact uint16, or uint16 buckets + residual
        # (packing.pack_duration) — packed BEFORE page padding so the
        # pad rows below are valid zero buckets
        q, res = packing.pack_duration(cat["entry_dur"], widths[2])
        cat["entry_dur"] = q
        if res is not None:
            cat["entry_dur_res"] = res

    if pad_to and pad_to > total:
        extra = pad_to - total
        for name, arr in cat.items():
            pad = np.zeros((extra,) + arr.shape[1:], dtype=arr.dtype)
            if name in ("kv_key", "kv_val") and widths is None:
                pad -= 1  # packed layouts pad with code 0 (= id -1)
            cat[name] = np.concatenate([arr, pad], axis=0)
        page_block = np.concatenate([
            page_block, np.full(extra, -1, dtype=np.int32)
        ])

    cat["page_block"] = page_block
    from .structural import STRUCTURAL

    span_cat = None
    if STRUCTURAL.enabled:
        # structural span segments stack alongside the page columns —
        # gate off is one attribute read and the identical layout
        span_cat = STRUCTURAL.stack_spans(blocks, E,
                                          int(page_block.shape[0]))
    entries_padded = int(page_block.shape[0]) * E
    packed_dicts = _pack_batch_dicts(blocks, probe_min_vals,
                                     n_shards=n_shards)
    aliased = 0
    if widths is None and not one_view:
        # `cat` is a copy in the blocks' own ids (not the one-block view,
        # not a packed layout): the blocks can read from it
        blocks, aliased = _blocks_over(cat, blocks, page_offset)
    return HostBatch(cat=cat, page_block=page_block, blocks=blocks,
                     page_offset=page_offset, packed_dicts=packed_dicts,
                     widths=widths, span_cat=span_cat,
                     aliased_nbytes=aliased,
                     cat_logical_nbytes=(
                         packing.logical_nbytes(entries_padded, C0,
                                                n_keys, n_vals)
                         + int(page_block.nbytes)))


def place_batch(host: HostBatch, sharding=None, mesh=None) -> BlockBatch:
    """H2D: put a host-stacked batch on device(s). `mesh` shards staged
    probe dictionaries along the value axis when they were packed for
    that mesh size (engine.stage_host packs with the engine's shard
    count); any mismatch places them unsharded — still correct, the
    probe just runs on one device."""
    import time

    from . import dict_probe

    from tempo_tpu.robustness import FAULTS

    if FAULTS.active:
        FAULTS.hit("h2d_delay")  # slow or hung staging put
    mode = "mesh" if sharding is not None else "batched"
    t0 = time.perf_counter()
    cat = host.cat
    if sharding is not None:
        from tempo_tpu.parallel import mesh as mesh_mod

        # multi-host: each process transfers ONLY its devices' page
        # slices — the per-host staging of the local shard (mesh.put)
        dev = mesh_mod.put(cat, sharding)
    else:
        dev = {k: jnp.asarray(v) for k, v in cat.items()}
    # fenced: a put returns before the bytes are on the device, and an
    # unfenced stamp times the enqueue. Nothing can scan the batch
    # before they are, so the wait is moved here, not added
    jax.block_until_ready(dev)
    # page-array H2D only; the dictionary placement below times itself
    # (mode=dict_probe) inside place_device_dict
    profile.observe_stage("h2d", mode, time.perf_counter() - t0,
                          nbytes=sum(int(v.nbytes) for v in cat.values()))
    span_dev = None
    span_sharded = False
    span_put_ns = ()
    if host.span_cat is not None:
        from .structural import STRUCTURAL

        t_span = tracing.now_ns()
        span_host = host.span_cat
        if sharding is not None and STRUCTURAL.shard_spans:
            # segment-aligned span sharding: each trace's contiguous
            # span run lands whole on its page's shard, coordinates
            # rebased shard-local — the host tier KEEPS the replicated
            # layout (host_scan's byte-identical fallback), only the
            # device placement reshards
            E = host.blocks[0].geometry.entries_per_page
            n_sh = int(sharding.mesh.devices.size)
            sh = STRUCTURAL.shard_span_segment(
                span_host, n_sh, int(host.page_block.shape[0]), E)
            if sh is not None:
                span_host = sh
                span_sharded = True
        if sharding is not None and span_sharded:
            from jax.sharding import NamedSharding, PartitionSpec as P
            from tempo_tpu.parallel.mesh import SCAN_AXIS

            # every sharded span array (span axis AND the [P, E] entry
            # range columns) splits on its leading axis, aligned with
            # the page sharding — per-shard span HBM ~1/P of replicated
            span_dev = mesh_mod.put(
                span_host, NamedSharding(sharding.mesh, P(SCAN_AXIS)))
        elif sharding is not None and jax.process_count() > 1:
            # span columns REPLICATE (the legacy layout): parent
            # pointers and segment ranges index the GLOBAL span axis,
            # and the dist kernels evaluate the structural mask outside
            # shard_map then hand the [P,E] verdicts to the sharded scan
            span_dev = mesh_mod.put_replicated(sharding.mesh, span_host)
        else:
            span_dev = {k: jnp.asarray(v)
                        for k, v in span_host.items()}
        # fenced like the page arrays above: nothing scans the batch
        # before its span columns are there, and the stamp is the put's
        jax.block_until_ready(span_dev)
        span_put_ns = (t_span, tracing.now_ns())
    staged = {}
    for fp, pd in host.packed_dicts.items():
        dict_mesh = (mesh if mesh is not None and pd.n_shards > 1
                     and pd.n_shards == int(mesh.devices.size) else None)
        staged[fp] = dict_probe.place_device_dict(pd, mesh=dict_mesh)
    return BlockBatch(device=dev, page_block=host.page_block,
                      blocks=host.blocks, page_offset=host.page_offset,
                      staged_dicts=staged, widths=host.widths,
                      logical_device_nbytes=host.cat_logical_nbytes,
                      span_device=span_dev, span_sharded=span_sharded,
                      span_put_ns=span_put_ns)


def stack_blocks(blocks: list[ColumnarPages], pad_to: int | None = None,
                 sharding=None, probe_min_vals: int | None = 0,
                 mesh=None, n_shards: int = 1) -> BlockBatch:
    """Concatenate uniform-geometry blocks along the page axis and place
    on device. With `sharding` (a NamedSharding over the page axis) the
    stacked arrays shard across the mesh instead of the default device."""
    return place_batch(stack_host(blocks, pad_to=pad_to,
                                  probe_min_vals=probe_min_vals,
                                  n_shards=n_shards),
                       sharding=sharding, mesh=mesh)


@dataclass
class MultiQuery:
    """Per-block compiled query folded into block-indexed tables."""
    # B is the group's block count in its bucket (block_bucket): rows
    # past the blocks are pad rows, key id -1 like a pruned block's
    term_keys: np.ndarray    # int32 [B, T] key id per (block, term); -1 = prune
    val_ranges: np.ndarray   # int32 [B, T, R, 2]
    dur_lo: int
    dur_hi: int
    win_start: int
    win_end: int
    limit: int
    n_terms: int
    # device-probe product (search/dict_probe.py) where some dictionary
    # answered with a hit mask (a term of more than dict_probe.R_MAX
    # runs): bool [G, T, Vmax] per-dictionary-GROUP value hit masks on
    # device, and the int32 [B] block -> group row map (-1 = this block
    # compiled to ranges, on the host or from the device's runs; its
    # val_ranges row applies). None where every block compiled to
    # ranges: the launch then takes no mask and gathers nothing.
    val_hits: object = None
    block_group: np.ndarray | None = None
    # (start, end, [(path, product)]) of the compile over the group's
    # distinct dictionaries: the batcher's `dict_probe.probe` span
    # (pipeline.probe_summary makes its attributes, in a traced search)
    probes: tuple | None = None
    # compiled structural predicate (structural.CompiledStructural):
    # static plan + dynamic tables ANDed into the entry mask by the
    # kernels; None = the legacy pytree and executables exactly
    structural: object = None
    # staged ?agg= stage (analytics.AggStage) — batch-scoped composite
    # keys + service table; None = no aggregate stage compiled in
    agg_stage: object = None


def _dict_groups(blocks: list[ColumnarPages], cache_on=None):
    """(fp_of, rep_idx, rows_of): which blocks share which dictionary.
    Query-INDEPENDENT, so it memoizes on `cache_on` (the immutable
    stacked batch): a novel tag-set at 10K blocks then costs
    distinct-dict probes + numpy assembly, not a 10K python loop —
    the dominant share of the r4 cold-tags host cost (VERDICT r4 #3)."""
    from .pipeline import _dict_fingerprint

    if cache_on is not None:
        hit = getattr(cache_on, "_dict_groups", None)
        if hit is not None:
            return hit
    fp_of: list[bytes] = []
    rep_idx: dict[bytes, int] = {}
    rows_of: dict[bytes, list[int]] = {}  # fp → block rows, same pass —
    # a per-group flatnonzero rescan would be O(dicts × B), quadratic
    # exactly when every block has its own dictionary
    for i, b in enumerate(blocks):
        fp = _dict_fingerprint(b, b.key_dict, b.val_dict)
        fp_of.append(fp)
        rep_idx.setdefault(fp, i)
        rows_of.setdefault(fp, []).append(i)
    out = (fp_of, rep_idx, rows_of)
    if cache_on is not None:
        cache_on._dict_groups = out
    return out


def compile_multi(blocks: list[ColumnarPages], req: tempopb.SearchRequest,
                  skip: list[bool] | None = None,
                  cache_on=None, host_only: bool = False) -> MultiQuery | None:
    """Compile the request against every block's dictionaries; blocks that
    prune get key id -1 (no page of theirs can match). `skip[i]` marks
    blocks already pruned by their header rollup — they stay in the batch
    (staging is query-independent) and are masked back to the -1 sentinel
    after assembly. `cache_on`: immutable object (the stacked batch) that
    memoizes the per-block dictionary grouping across queries.
    `host_only`: the breaker's host-fallback compile — no staged
    dictionary is consulted and cached device-resident probe products
    are bypassed (see compile_query)."""
    from tempo_tpu.ops import native
    from .pipeline import NATIVE_SCAN_THRESHOLD

    use_packed = bool(req.tags) and native.available()
    # one probe per DISTINCT dictionary, not per block: a 10K-block
    # tenant usually cycles a handful of dictionary contents (same
    # services/status codes everywhere)
    fp_of, rep_idx, rows_of = _dict_groups(blocks, cache_on=cache_on)
    # dictionaries the batch staged for the on-device probe (BlockBatch
    # .staged_dicts, keyed by the same fingerprints): their substring
    # scan runs on device and yields a hit mask instead of host ranges
    staged_dicts = getattr(cache_on, "staged_dicts", None) or {}
    compiled: dict[bytes, CompiledQuery | None] = {}
    probed: list = []
    t_probe = tracing.now_ns()
    for fp, i in rep_idx.items():
        b = blocks[i]
        compiled[fp] = compile_query(
            b.key_dict, b.val_dict, req,
            packed_vals=(b.packed_val_dict()
                         if use_packed and len(b.val_dict) >= NATIVE_SCAN_THRESHOLD
                         else None),
            cache_on=b,  # blocks are immutable: repeated tag-sets skip
                         # the O(dict) probe (VERDICT r2 #1 host cost)
            staged_dict=None if host_only else staged_dicts.get(fp),
            host_only=host_only, probed=probed,
        )
    probes = (t_probe, tracing.now_ns(), probed)
    per_block: list[CompiledQuery | None] = [
        None if (skip is not None and skip[i]) else compiled[fp_of[i]]
        for i in range(len(blocks))
    ]
    if all(cq is None for cq in per_block):
        return None
    # term count comes from the compiled queries, not len(req.tags): the
    # exhaustive debug tag is not itself a predicate, so raw-tag counting
    # would leave an unmatchable extra -1 key per block
    T = max((cq.n_terms for cq in per_block if cq is not None), default=0)
    # the block axis in its bucket: rows past len(blocks) keep the -1
    # key and the empty range they are filled with here
    B = block_bucket(len(blocks))
    rmax = 1
    for cq in per_block:
        if cq is not None and cq.n_terms:
            rmax = max(rmax, cq.val_ranges.shape[1])
    R = 1
    while R < rmax:
        R *= 2
    term_keys = np.full((B, max(1, T)), -1, dtype=np.int32)
    val_ranges = np.tile(np.array([1, 0], dtype=np.int32), (B, max(1, T), R, 1))
    # assemble per distinct dictionary: one row-broadcast per group
    # instead of a python loop over every (block, term)
    for fp, cq in compiled.items():
        if cq is None or not cq.n_terms:
            continue
        rows = np.asarray(rows_of[fp], dtype=np.int64)
        # clamp to the assembled width: T/R are sized over the UNSKIPPED
        # blocks' queries; a dictionary whose every row is header-skipped
        # may compile wider, and its rows get masked below anyway
        t_n = min(cq.n_terms, term_keys.shape[1])
        r_n = min(cq.val_ranges.shape[1], val_ranges.shape[2])
        term_keys[rows[:, None], np.arange(t_n)] = cq.term_keys[:t_n]
        val_ranges[rows[:, None, None],
                   np.arange(t_n)[:, None],
                   np.arange(r_n)] = cq.val_ranges[:t_n, :r_n]
    # device-probed dictionary groups: stack their [T, v_pad] hit masks
    # along a GROUP axis (pad T and V to the assembled/maximum widths —
    # device ops, nothing syncs to host) and map each block row to its
    # group; -1 rows keep the host range path, so a batch can mix
    # device-probed high-cardinality blocks with host-compiled small ones
    probe_fps = [fp for fp, cq in compiled.items()
                 if cq is not None and cq.n_terms
                 and cq.val_hits is not None]
    val_hits = block_group = None
    if probe_fps:
        Tp = max(1, T)
        # one assembled mask table must be format-uniform: a compile-
        # cache product minted before a packed-residency gate flip can
        # still be bool while its peers are bit-packed words — pack the
        # stragglers (cheap device op) rather than stacking mixed dtypes
        hs = {fp: compiled[fp].val_hits for fp in probe_fps}
        if any(packing.is_packed_mask(h) for h in hs.values()):
            hs = {fp: packing.pack_mask_words(h) for fp, h in hs.items()}
        Vm = max(int(h.shape[1]) for h in hs.values())
        padded = []
        for fp in probe_fps:
            h = hs[fp]
            h = jnp.pad(h, ((0, Tp - h.shape[0]), (0, Vm - h.shape[1])))
            padded.append(h)
        val_hits = jnp.stack(padded)                       # [G, Tp, Vm]
        block_group = np.full(B, -1, dtype=np.int32)
        for g, fp in enumerate(probe_fps):
            block_group[np.asarray(rows_of[fp], dtype=np.int64)] = g

    if skip is not None and any(skip):
        # header-pruned rows back to the unmatchable sentinel (their
        # dict group was assembled wholesale above)
        sk = np.flatnonzero(np.asarray(skip, dtype=bool))
        term_keys[sk] = -1
        val_ranges[sk] = np.array([1, 0], dtype=np.int32)
        if block_group is not None:
            block_group[sk] = -1  # term_keys -1 + range path: can't match

    any_cq = next(cq for cq in per_block if cq is not None)
    return MultiQuery(
        term_keys=term_keys, val_ranges=val_ranges,
        dur_lo=any_cq.dur_lo, dur_hi=any_cq.dur_hi,
        win_start=any_cq.win_start, win_end=any_cq.win_end,
        limit=any_cq.limit, n_terms=T,
        val_hits=val_hits, block_group=block_group, probes=probes,
    )


def _packed_slots(dims: tuple):
    """(start, stop, shape) of a fused launch's seven per-query tables
    in its one int32 buffer, in the order the scan takes them:
    term_keys, val_ranges, term_active, dur_lo, dur_hi, win_start,
    win_end. A pure function of the static (Q, B, T, R) the launch is
    keyed on: the host lays the tables out by it (stack_queries), the
    device takes them apart by it (unpack_queries)."""
    Q, B, T, R = dims
    shapes = ((Q, B, T), (Q, B, T, R, 2), (Q, T), (Q,), (Q,), (Q,), (Q,))
    ends = list(itertools.accumulate(math.prod(s) for s in shapes))
    return [(end - math.prod(s), end, s) for s, end in zip(shapes, ends)]


def _packed_views(dims: tuple):
    """The buffer and the seven tables as views of it: the tag tables
    int32, term_active int32 (0 | 1), the four bounds uint32 over the
    same bits, never value-cast (a dur_hi of 0xFFFFFFFF comes back as
    it went)."""
    slots = _packed_slots(dims)
    buf = np.empty(slots[-1][1], dtype=np.int32)
    views = [buf[a:b].reshape(shape) for a, b, shape in slots]
    return buf, views[:3] + [v.view(np.uint32) for v in views[3:]]


def unpack_queries(packed, dims: tuple) -> tuple:
    """A fused launch's seven tables from its one buffer, on the
    device: static slices, reshapes, a compare for term_active and a
    bit-cast for the bounds, traced in front of the scan they feed."""
    views = [packed[a:b].reshape(shape)
             for a, b, shape in _packed_slots(dims)]
    return (views[0], views[1], views[2] != 0,
            *(jax.lax.bitcast_convert_type(v, jnp.uint32)
              for v in views[3:]))


@dataclass
class CoalescedQuery:
    """Several requests' MultiQueries stacked along a new QUERY axis for
    one fused dispatch over a shared staged batch — the continuous-
    batching shape: predicate tables become [Q, B, ...] and the kernel
    computes per-query masks + per-query top-k in a single launch.

    The seven tables are views of `packed`, the ONE host buffer the
    launch puts on the device (_packed_slots is the layout): every
    small host-to-device transfer pays a fixed per-call cost, and seven
    of them were the largest piece of a fused launch's `build`."""
    term_keys: np.ndarray    # int32 [Q, B, T]
    val_ranges: np.ndarray   # int32 [Q, B, T, R, 2]
    term_active: np.ndarray  # bool [Q, T] — False = padding term (no-op)
    dur_lo: np.ndarray       # uint32 [Q]
    dur_hi: np.ndarray       # uint32 [Q]
    win_start: np.ndarray    # uint32 [Q]
    win_end: np.ndarray      # uint32 [Q]
    n_terms: int             # padded (static) term count
    n_queries: int           # REAL queries; padding rows match nothing
    packed: np.ndarray       # int32 [N]: the seven tables, as laid out
    # device-probe product stacked along the query axis: bool
    # [Q, G, T, Vmax] hit masks + int32 [Q, B] block->group rows (a
    # member query that compiled through the host path gets an all -1
    # row — its range tables apply). None when no member probed.
    val_hits: object = None
    block_group: np.ndarray | None = None
    # plan-shape stacking (structural.StackedStructural): ONE shared
    # static plan + [Q, ...]-stacked structural parameter tables. None
    # = the legacy pytree and executables exactly.
    structural: object = None
    # batch-scoped ?agg= stage (analytics.AggStage), shared across the
    # query axis — set when any member requested aggregation
    agg_stage: object = None

    @property
    def dims(self) -> tuple:
        """The static (Q, B, T, R) `packed` is laid out by."""
        return (*self.term_keys.shape, self.val_ranges.shape[3])


def stack_queries(mqs: list[MultiQuery]) -> CoalescedQuery:
    """Stack compiled queries over the SAME block batch along the query
    axis. Every shape axis (Q, T, R) pads to a power of two so the jit
    cache keys on predicate SHAPE buckets, never predicate values —
    different tag-sets share one compiled executable.

    Structural queries stack too, when EVERY member carries one and all
    plans are the identical static descriptor (the coalescer's
    stack_group_key guarantees this grouping): their parameter tables
    stack along the same query axis (structural.stack_structural) and
    the shared plan stays one jit key. A mixed group — some structural,
    some not, or differing plans — is a caller bug and raises rather
    than silently dropping a predicate.

    Pad semantics: extra terms of a real query are inactive (neutral-TRUE
    in the AND); whole pad QUERIES get an empty duration window
    (dur_lo=1 > dur_hi=0) so their mask is all-false and their top-k is
    all sentinel — dead lanes, not wrong results (structural pad lanes
    replay member 0's tables behind that same all-false legacy mask)."""
    Qn = len(mqs)
    sts = [getattr(mq, "structural", None) for mq in mqs]
    stacked_st = None
    if any(st is not None for st in sts):
        from .structural import (STRUCTURAL, canonical_bucket,
                                 stack_bucketed, stack_structural)

        if any(st is None for st in sts):
            # plan-shape grouping happens UPSTREAM (stack_group_key);
            # a mixed stack here would silently drop a predicate
            raise ValueError(
                "coalesced structural queries must all share one plan")
        if all(st.plan == sts[0].plan for st in sts[1:]):
            # same exact plan: the exact-descriptor stack (bucketing
            # adds nothing when the plans already share one jit key)
            stacked_st = stack_structural(sts, _pow2(Qn))
        else:
            # mixed plans fuse ONLY through the bucket canonicalization
            # (the bucket_group_key grouping contract): every member
            # must land in the same bucket descriptor
            buckets = {canonical_bucket(st.plan,
                                        STRUCTURAL.bucket_max_nodes)
                       for st in sts}
            if len(buckets) != 1 or None in buckets:
                raise ValueError(
                    "coalesced structural queries must share one plan "
                    "or canonicalize into one bucket shape")
            stacked_st = stack_bucketed(sts, _pow2(Qn), buckets.pop())
    B = mqs[0].term_keys.shape[0]
    Q = _pow2(Qn)
    T = _pow2(max(1, max(mq.n_terms for mq in mqs)))
    R = _pow2(max(mq.val_ranges.shape[2] for mq in mqs))
    packed, (term_keys, val_ranges, active, dur_lo, dur_hi, win_start,
             win_end) = _packed_views((Q, B, T, R))
    term_keys[...] = -1
    val_ranges[...] = (1, 0)
    active[...] = 0
    dur_lo[...] = 1                           # pad: empty dur range
    dur_hi[...] = 0
    win_start[...] = 0
    win_end[...] = 0
    for qi, mq in enumerate(mqs):
        if mq.term_keys.shape[0] != B:
            raise ValueError("coalesced queries must share one batch")
        t_n = mq.term_keys.shape[1]
        r_n = mq.val_ranges.shape[2]
        term_keys[qi, :, :t_n] = mq.term_keys
        val_ranges[qi, :, :t_n, :r_n] = mq.val_ranges
        active[qi, :mq.n_terms] = 1
        dur_lo[qi] = mq.dur_lo
        dur_hi[qi] = min(mq.dur_hi, 0xFFFFFFFF)
        win_start[qi] = mq.win_start
        win_end[qi] = min(mq.win_end, 0xFFFFFFFF)
    # device-probe members: stack their [G, T, V] group masks along the
    # query axis (device pads/stack — the probe product stays on chip
    # through the fused dispatch); host-path and pad queries carry all-
    # false masks behind an all -1 block_group row, so they never read it
    val_hits = block_group = None
    if any(mq.val_hits is not None for mq in mqs):
        probed = [mq for mq in mqs if mq.val_hits is not None]
        # format-uniform like compile_multi: members compiled across a
        # packed-residency gate flip pack up before stacking
        hits = {id(mq): mq.val_hits for mq in probed}
        if any(packing.is_packed_mask(h) for h in hits.values()):
            hits = {k: packing.pack_mask_words(h) for k, h in hits.items()}
        Gm = max(int(h.shape[0]) for h in hits.values())
        Vm = max(int(h.shape[2]) for h in hits.values())
        dt = next(iter(hits.values())).dtype
        zero = jnp.zeros((Gm, T, Vm), dtype=dt)
        block_group = np.full((Q, B), -1, dtype=np.int32)
        rows = []
        for qi in range(Q):
            mq = mqs[qi] if qi < Qn else None
            if mq is None or mq.val_hits is None:
                rows.append(zero)
                continue
            h = hits[id(mq)]
            rows.append(jnp.pad(h, ((0, Gm - h.shape[0]),
                                    (0, T - h.shape[1]),
                                    (0, Vm - h.shape[2]))))
            block_group[qi] = mq.block_group
        val_hits = jnp.stack(rows)                  # [Q, Gm, T, Vm]
    aggs = [mq for mq in mqs if getattr(mq, "agg_stage", None) is not None]
    return CoalescedQuery(
        term_keys=term_keys, val_ranges=val_ranges, term_active=active != 0,
        dur_lo=dur_lo, dur_hi=dur_hi, win_start=win_start, win_end=win_end,
        n_terms=T, n_queries=Qn, packed=packed, val_hits=val_hits,
        block_group=block_group, structural=stacked_st,
        # members share one batch, so their AggStage is the same
        # memoized object — any requester turns the stage on
        agg_stage=aggs[0].agg_stage if aggs else None)


def multi_entry_mask(kv_key, kv_val, entry_start, entry_end, entry_dur,
                     entry_valid, page_block, term_keys, val_ranges,
                     dur_lo, dur_hi, win_start, win_end, *, n_terms: int,
                     term_active=None, val_hits=None, block_group=None,
                     entry_dur_res=None, widths=None):
    """THE predicate: [P,E] bool mask of matching entries. Term
    columns are selected per page through the page_block index: key id
    and ranges become [P]-indexed gathers over the SMALL [B,...] tables
    (cheap — B entries, not 8M). Value membership is an OR over
    inclusive [lo,hi] id ranges — pure broadcast compares, no gather
    (pipeline.ids_to_ranges explains why). On a mesh each shard
    evaluates it over its local page slice.

    `term_active` ([T] bool, optional): the query-coalescing pad axis —
    queries stacked along a query axis share one static n_terms, so a
    query with fewer real terms marks the excess inactive and they drop
    out of the AND (neutral-TRUE). This is distinct from the -1 key
    sentinel, which means 'term exists but this block can never match
    it' (neutral-FALSE for the block).

    `val_hits` (bool [G, T, Vmax]) + `block_group` (int32 [P-indexable
    [B]]): the device-probe product — pages of a block mapped to group
    g >= 0 test value membership with a hit-mask lookup on that group's
    row (one gather per term); group -1 pages keep the range compares,
    so device-probed and host-compiled blocks mix in one batch.

    `widths` (STATIC at every call site — part of the jit shape key) +
    `entry_dur_res`: the packed-residency descriptor (search/packing.py).
    The kv unpack runs INSIDE the term body so the widening shifts/masks
    fuse into the compares of each pass over the columns — no unpacked
    copy materializes in HBM; packed (uint32-word) hit masks select
    their bit in-register the same way."""
    kw, vw, dw = widths if widths is not None else (None, None, None)
    safe_block = jnp.maximum(page_block, 0)
    mask = entry_valid & (page_block >= 0)[:, None]
    if n_terms:
        if val_hits is not None:
            bg_page = block_group[safe_block]              # [P]
            probe_page = (bg_page >= 0)[:, None, None]     # [P,1,1]
            safe_g = jnp.maximum(bg_page, 0)

        def in_ranges(v, lo, hi):
            return ((v >= lo[:, None, None, :]) &
                    (v <= hi[:, None, None, :])).any(-1)   # [P,E,C]

        def entry_in_ranges(keym, vv, lo, hi):
            """Select, then compare: [P,E], does a value of the term's
            key lie in a range. A term names one key and an entry has a
            slot for each value of it, so a pass takes the largest value
            not yet taken from the slots where `keym` holds (-1 where
            none is left: ids are >= 0, pads -1, no range holds -1) and
            tests that one value against the page's ranges, a block at
            a time. Passes repeat while some entry of the launch has a
            second value left: one where a key has one value an entry,
            k for a k-valued key, never more than C. The count is read
            on the device from the slots; under a fused launch's vmap
            the loop runs until every member is done, on a mesh each
            shard loops over its own pages."""
            vv = vv.astype(jnp.int32)
            if lo.shape[1] < _RANGE_BLOCK_MIN:
                # sentinels ([1, 0] holds nothing) up to the least block
                pad = ((0, 0), (0, _RANGE_BLOCK_MIN - lo.shape[1]))
                lo = jnp.pad(lo, pad, constant_values=1)
                hi = jnp.pad(hi, pad, constant_values=0)
            step = min(lo.shape[1], _RANGE_BLOCK)

            def holds(v):                                  # [P,E]
                # [P, step, E]: the entries stay on the lanes, as the kv
                # columns have them, and the ranges are reduced across
                # vregs; with the ranges on the lanes the same compares
                # took 12.0 ms where these take 5.4 (R = 512)
                def some(i, m):
                    l, h = (jax.lax.dynamic_slice_in_dim(b, i * step, step, 1)
                            [:, :, None] for b in (lo, hi))
                    return m | ((v[:, None, :] >= l) &
                                (v[:, None, :] <= h)).any(1)

                return jax.lax.fori_loop(0, lo.shape[1] // step, some,
                                         jnp.zeros(v.shape, dtype=bool))

            def take(state):
                below, hit, _ = state
                left = jnp.where(keym & (vv < below[..., None]), vv, -1)
                # the largest value left and how many are, in one pass
                # over the slots
                v, n = jax.lax.reduce(
                    (left, (left >= 0).astype(jnp.int32)),
                    (jnp.int32(-1), jnp.int32(0)),
                    lambda a, b: (jnp.maximum(a[0], b[0]), a[1] + b[1]),
                    (2,))
                return v, hit | holds(v), (n > 1).any()

            return jax.lax.while_loop(
                lambda state: state[2], take,
                (jnp.full(vv.shape[:2], jnp.iinfo(jnp.int32).max),
                 jnp.zeros(vv.shape[:2], dtype=bool), jnp.bool_(True)))[1]

        def mask_hits(vv, t):
            safe_v = jnp.maximum(vv, 0).astype(jnp.int32)
            return (mask_select_grouped(val_hits, safe_g[:, None, None],
                                        t, safe_v)
                    & (vv >= 0))                           # [P,E,C]

        def term_body(t, acc):
            kk = unpack_ids(kv_key, kw)                    # fused widen
            vv = unpack_ids(kv_val, vw)
            k_per_page = term_keys[safe_block, t]          # [P]
            keym = kk == k_per_page[:, None, None]         # [P,E,C]
            lo = val_ranges[safe_block, t, :, 0]           # [P,R]
            hi = val_ranges[safe_block, t, :, 1]
            if compares_by(lo.shape[1]) == "slot":
                valm = in_ranges(vv[..., None], lo, hi)
                if val_hits is not None:
                    valm = jnp.where(probe_page, mask_hits(vv, t), valm)
                hit = jnp.any(keym & valm, axis=-1)        # [P,E]
            else:
                hit = entry_in_ranges(keym, vv, lo, hi)
                if val_hits is not None:
                    hit = jnp.where(
                        probe_page[..., 0],
                        jnp.any(keym & mask_hits(vv, t), axis=-1), hit)
            if term_active is not None:
                hit = hit | ~term_active[t]
            return acc & hit

        mask = jax.lax.fori_loop(0, n_terms, term_body, mask)

    mask = mask & duration_ok(entry_dur, entry_dur_res, dur_lo, dur_hi, dw)
    mask = mask & (entry_end.astype(jnp.uint32) >= win_start.astype(jnp.uint32))
    mask = mask & (entry_start.astype(jnp.uint32) <= win_end.astype(jnp.uint32))
    return mask


def agg_entry_counts(mask, entry_agg, n_keys: int):
    """Dense aggregate counts over the verdict mask: entries the final
    mask accepts contribute their staged composite key (see
    search/analytics.py — (service, latency-bucket, error) for
    ?agg=red), rejected entries take the sentinel ``n_keys`` one past
    the counted range, and sort + searchsorted-diff produces the [K]
    histogram — the scatter-free dense-count idiom, fused into the
    same dispatch as the scan's mask."""
    key = jnp.where(mask, entry_agg, jnp.int32(n_keys)).reshape(-1)
    skey = jax.lax.sort(key)
    edges = jnp.searchsorted(skey,
                             jnp.arange(n_keys + 1, dtype=jnp.int32))
    return (edges[1:] - edges[:-1]).astype(jnp.int32)


def _scan_pages(kv_key, kv_val, entry_start, entry_end, entry_dur,
                entry_valid, page_block, term_keys, val_ranges, term_active,
                dur_lo, dur_hi, win_start, win_end, val_hits, block_group,
                entry_dur_res, struct_mask, span_cols, s_tables, entry_agg,
                *, n_terms: int, top_k: int, widths, plan, agg):
    """The scan of the pages one device holds: per query the verdict
    mask, its count, the top-k of its matches and, where `agg` (static,
    the dense key-space size K) is set, the ?agg= counts the same mask
    gates. Returns (count, inspected, scores [k], flat idx [k][, agg
    [K]]); `inspected` is a property of the pages, not of a query.

    `term_active` decides the query axis: None is one query and its
    tables are traced as they come; a [Q, T] array means every
    per-query table ([Q, ...]-stacked, stack_queries) carries a leading
    query axis and vmap lifts the verdict over it — count, scores, idx
    and agg gain that axis, the page arrays are closed over and shared.

    The structural predicate (static `plan`) reaches the verdict one of
    two ways: `struct_mask`, verdicts already evaluated by the caller
    (the mesh's replicated span layout), or `span_cols` + `s_tables`,
    evaluated here over these pages — compiled from the plan, never
    interpreted, in the same dispatch."""
    def one_query(tk, vr, ta, dlo, dhi, ws, we, vh, bg, sm, st_t):
        mask = multi_entry_mask(
            kv_key, kv_val, entry_start, entry_end, entry_dur, entry_valid,
            page_block, tk, vr, dlo, dhi, ws, we, n_terms=n_terms,
            term_active=ta, val_hits=vh, block_group=bg,
            entry_dur_res=entry_dur_res, widths=widths)
        if sm is not None:
            mask = mask & sm
        if st_t is not None:
            from .structural import structural_entry_mask

            # span_cols and entry_agg close over: staged with the batch,
            # the same for every query
            mask = mask & structural_entry_mask(
                kv_key, kv_val, entry_dur, entry_valid, page_block,
                entry_dur_res, span_cols, st_t, plan=plan, widths=widths)
        count = jnp.sum(mask, dtype=jnp.int32)
        # traced HERE though it reads no query table: between count and
        # top-k the TPU compiler fuses this reduce with the mask's first
        # `and`; traced ahead of the queries it is one more pass over
        # [P, E] in the solo program (PERF.md section 6, PR 28)
        inspected = jnp.sum(entry_valid & (page_block >= 0)[:, None],
                            dtype=jnp.int32)
        scores, idx = masked_topk(mask, entry_start, top_k)
        if agg is not None:
            return (count, inspected, scores, idx,
                    agg_entry_counts(mask, entry_agg, agg))
        return count, inspected, scores, idx

    # a None table is an empty pytree: no leaf to map
    queries = (term_keys, val_ranges, term_active, dur_lo, dur_hi,
               win_start, win_end, val_hits, block_group, struct_mask,
               s_tables)
    if term_active is None:
        return one_query(*queries)
    # `inspected` is traced once and comes out of the vmap unbatched
    return jax.vmap(one_query, out_axes=(
        0, None, 0, 0) + ((0,) if agg is not None else ()))(*queries)


def _drop_shard_tail(entry_valid, pages_total: int, shard_tail: int):
    """The remainder-shard layout (static `shard_tail`, part of the jit
    key like `widths`): the trailing `shard_tail` pad pages live on the
    last shard(s); their entries are already invalid, so this mask is
    byte-identical — it RECORDS the ragged tail in the compiled
    layout."""
    from tempo_tpu.parallel.mesh import SCAN_AXIS

    pp = entry_valid.shape[0]
    gpage = (jax.lax.axis_index(SCAN_AXIS).astype(jnp.int32) * pp
             + jnp.arange(pp, dtype=jnp.int32))
    return entry_valid & (gpage < jnp.int32(pages_total - shard_tail))[:, None]


def _merge_shards(count, inspected, scores, idx, agg_counts, *,
                  local_flat: int, top_k: int):
    """The mesh's Results funnel (reference results.go:38-141) as
    collectives riding ICI: counts and inspected psum, each shard's
    top-k candidates all_gather into the global top-k, the per-shard
    ?agg= counts psum to the global histogram (integer adds: bit-equal
    to one device's). Every array may carry a leading query axis;
    latest_k sorts along the last."""
    from tempo_tpu.parallel.mesh import SCAN_AXIS

    def gather(x):
        # [..., k] -> [..., S * k]: shard after shard, each in
        # masked_topk's order, so equal start seconds come in ascending
        # global index and the merge keeps it
        g = jax.lax.all_gather(x, SCAN_AXIS)
        return jnp.moveaxis(g, 0, -2).reshape(*x.shape[:-1], -1)

    shard = jax.lax.axis_index(SCAN_AXIS).astype(jnp.int32)
    gidx = idx + shard * local_flat
    count = jax.lax.psum(count, SCAN_AXIS)
    inspected = jax.lax.psum(inspected, SCAN_AXIS)
    all_scores = gather(scores)
    all_idx = gather(gidx)
    top = latest_k(all_scores, all_idx, min(top_k, all_scores.shape[-1]))
    return (count, inspected, *top,
            *(jax.lax.psum(a, SCAN_AXIS) for a in agg_counts))


@functools.partial(jax.jit,
                   static_argnames=("mesh", "n_terms", "top_k", "widths",
                                    "plan", "span_sharded", "shard_tail",
                                    "agg", "packed"))
def batch_scan_kernel(kv_key, kv_val, entry_start, entry_end, entry_dur,
                      entry_valid, page_block, term_keys, val_ranges,
                      term_active, dur_lo, dur_hi, win_start, win_end,
                      val_hits=None, block_group=None, entry_dur_res=None,
                      span_cols=None, s_tables=None, entry_agg=None,
                      *, mesh=None, n_terms: int, top_k: int, widths=None,
                      plan=None, span_sharded=False, shard_tail: int = 0,
                      agg=None, packed=None):
    """THE scan program: every block batch, on one device or a mesh,
    for one query or a fused group. Returns ONE int32 array, count,
    inspected, scores [k], flat idx [k] and the ?agg= counts [K] where
    `agg` is set, as engine.pack_out lays them out (unpack_out reads
    them on the host); flat idx = page * E + entry over the whole
    stacked page axis. What it is given decides what it traces, and
    each of the four is its own program:

      - `term_active` None: one query, no query axis anywhere, the
        output one row; else the per-query tables are [Q, ...]-stacked,
        count, scores, idx and agg carry a leading [Q] (_scan_pages)
        and the output is one row a member. The page arrays are read
        once per term loop regardless of Q.
      - `mesh` None: the whole page axis on the default device, no
        shard_map; else the stacked page axis (blocks x pages — the
        corpus 'sequence' axis, SURVEY.md §5) splits across the mesh's
        scan axis, the query tables replicate, each shard scans its
        slice and _merge_shards reduces, packed inside the shard_map
        so that one replicated array leaves it — one jit call.

    On a mesh the structural predicate (plan + span_cols/s_tables) has
    two placements, selected by the STATIC `span_sharded` flag (part of
    the jit key, like `widths`):

      - replicated span columns (legacy): the mask evaluates OUTSIDE
        the shard_map — parent pointers index the global span axis,
        which a page shard cannot see — and its [P, E] verdicts ([Q, P,
        E] for a fused group) enter the sharded region as one more
        operand split on the page axis;
      - segment-aligned sharded span columns
        (search_structural_shard_spans): each trace's span run lives
        whole on its page's shard in shard-local coordinates, so the
        `child` gather and `desc` running max evaluate INSIDE the
        shard over the local chunk — parent joins scale with the mesh,
        per-shard span HBM ~1/P, and only the per-trace verdict feeds
        the collectives.

    `packed` (STATIC, the (Q, B, T, R) of a fused launch): the seven
    per-query tables come as ONE int32 buffer in `term_keys`' place
    (CoalescedQuery.packed; the other six are None) and are taken apart
    here, in front of the scan and outside the shard_map: one operand
    to put on the device, or to replicate over the mesh, where there
    were seven. None: the tables come one by one, as a solo launch's
    resident parameters do."""
    if packed is not None:
        (term_keys, val_ranges, term_active, dur_lo, dur_hi, win_start,
         win_end) = unpack_queries(term_keys, packed)
    scan = functools.partial(_scan_pages, n_terms=n_terms, top_k=top_k,
                             widths=widths, plan=plan, agg=agg)
    if mesh is None:
        return pack_out(*scan(
            kv_key, kv_val, entry_start, entry_end, entry_dur,
            entry_valid, page_block, term_keys, val_ranges, term_active,
            dur_lo, dur_hi, win_start, win_end, val_hits, block_group,
            entry_dur_res, None, span_cols, s_tables, entry_agg))

    from jax.sharding import PartitionSpec as P
    from tempo_tpu.parallel.mesh import SCAN_AXIS, shard_map_compat

    fused = term_active is not None
    struct_mask = None
    if plan is not None and not span_sharded:
        from .structural import structural_entry_mask

        def verdicts(st_t):
            return structural_entry_mask(
                kv_key, kv_val, entry_dur, entry_valid, page_block,
                entry_dur_res, span_cols, st_t, plan=plan, widths=widths)

        struct_mask = (jax.vmap(verdicts) if fused else verdicts)(s_tables)
        span_cols = s_tables = None
    pages_total = int(kv_key.shape[0])
    local_flat = pages_total // mesh.devices.size * entry_valid.shape[1]

    def shard_fn(kv_key, kv_val, entry_start, entry_end, entry_dur,
                 entry_valid, page_block, *rest):
        if shard_tail:
            entry_valid = _drop_shard_tail(entry_valid, pages_total,
                                           shard_tail)
        count, inspected, scores, idx, *agg_counts = scan(
            kv_key, kv_val, entry_start, entry_end, entry_dur, entry_valid,
            page_block, *rest)
        return pack_out(*_merge_shards(
            count, inspected, scores, idx, agg_counts,
            local_flat=local_flat, top_k=top_k))

    return shard_map_compat(
        shard_fn, mesh=mesh,
        # seven page arrays split on the page axis; nine query tables
        # replicated (a None leaf makes its spec a no-op); then
        # entry_dur_res, struct_mask (its page axis second behind a
        # query axis), the sharded span columns and, last, entry_agg
        # split with their pages, s_tables between them replicated
        in_specs=(P(SCAN_AXIS),) * 7 + (P(),) * 9
        + (P(SCAN_AXIS), P(None, SCAN_AXIS) if fused else P(SCAN_AXIS),
           P(SCAN_AXIS), P(), P(SCAN_AXIS)),
        # the one packed array, the same on every shard: all_gather +
        # latest_k yields identical values on each, but the replication
        # checker can't infer it through the gather
        out_specs=P(),
        check=False,
    )(kv_key, kv_val, entry_start, entry_end, entry_dur, entry_valid,
      page_block, term_keys, val_ranges, term_active, dur_lo, dur_hi,
      win_start, win_end, val_hits, block_group, entry_dur_res,
      struct_mask, span_cols, s_tables, entry_agg)


@functools.partial(jax.jit,
                   static_argnames=("mesh", "n_terms", "top_k", "widths",
                                    "plan", "span_sharded", "shard_tail",
                                    "agg", "packed"))
def mask_scan_kernel(*tables, **statics):
    """batch_scan_kernel for a launch that takes a hit mask (`val_hits`):
    the same body under a program name of its own, so that a device
    trace tells the launches that gather (one lookup for every slot of
    every entry and term) from those that only compare."""
    return batch_scan_kernel.__wrapped__(*tables, **statics)


class MultiBlockEngine:
    """Batched scan over many blocks in one kernel dispatch; with a mesh,
    the batch shards across devices (the serving-path union of the
    reference's job fan-out and the Results merge)."""

    def __init__(self, top_k: int = DEFAULT_TOP_K, mesh=None,
                 device_probe_min_vals: int | None = None):
        from tempo_tpu.parallel import mesh as mesh_mod

        self.top_k = top_k
        self.mesh = mesh
        self.n_shards = int(mesh.devices.size) if mesh is not None else 1
        # value-dictionary size at which staging also packs+uploads the
        # dictionary bytes for the on-device substring probe (None =
        # dict_probe.DEVICE_PROBE_MIN_VALS; <= 0 keeps every probe on
        # the exact host path). Config: search_device_probe_min_vals.
        self.device_probe_min_vals = device_probe_min_vals
        # the PROCESS-WIDE collective-ordering lock (parallel.mesh
        # .dispatch_lock — see its comment): shared with every other
        # collective dispatch site, including the dictionary probe that
        # fires during query compilation on another thread.
        # Single-device dispatches need no ordering and skip the lock.
        self._dispatch_lock = mesh_mod.dispatch_lock

    def stage_host(self, blocks: list[ColumnarPages]) -> HostBatch:
        """Stack a batch on host, padded for this engine's device layout.

        The padded page count buckets to a power of two (shard-aligned):
        group sizes vary freely with the blocklist, and each distinct
        page count is a separate XLA compile (~20-40s on TPU) — pow2
        bucketing caps the shape count at log2 for <2x masked waste.

        Under the remainder-shard layout
        (search_structural_remainder_pages) the page axis pads only to
        the minimal multiple of the shard count instead: the last shard
        owns the ragged tail (the trailing pad pages), described to the
        dist kernels by the static `shard_tail` jit key — a 9-page
        batch on 8 shards stages 9 pages, not 16."""
        from .structural import STRUCTURAL

        total = sum(b.n_pages for b in blocks)
        pad_to = None
        if STRUCTURAL.remainder_pages:
            pad_to = STRUCTURAL.remainder_pad(total, self.n_shards)
        if pad_to is None:
            pad_to = max(1, self.n_shards)
            while pad_to < total:
                pad_to *= 2
        return stack_host(blocks, pad_to=pad_to,
                          probe_min_vals=self.device_probe_min_vals,
                          n_shards=self.n_shards)

    def _shard_tail(self, batch: BlockBatch, d: dict) -> int:
        """Static ragged-tail descriptor for the dist kernels: the
        count of trailing pad pages, nonzero ONLY under the
        remainder-shard gate. The pow2 layout keeps shard_tail=0 even
        though it pads too — keying the jit cache on every distinct
        tail would reintroduce exactly the per-page-count compiles the
        pow2 bucketing exists to cap."""
        from .structural import STRUCTURAL

        if self.mesh is None or not STRUCTURAL.remainder_pages:
            return 0
        return int(d["kv_key"].shape[0]) - int(batch.n_pages)

    def pages_per_shard(self, batch: BlockBatch) -> int:
        """Staged pages of the group, padding included, that each device
        of the mesh reads (all of them off a mesh)."""
        return int(batch.device["kv_key"].shape[0]) // self.n_shards

    @property
    def _page_sharding(self):
        """Where a launch reads its page-sharded operands (None off a
        mesh: the default device)."""
        if self.mesh is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec as P
        from tempo_tpu.parallel.mesh import SCAN_AXIS

        return NamedSharding(self.mesh, P(SCAN_AXIS))

    def place(self, host: HostBatch) -> BlockBatch:
        """H2D of a host-stacked batch (sharded over the mesh if any)."""
        if self.mesh is None:
            return place_batch(host)
        return place_batch(host, sharding=self._page_sharding,
                           mesh=self.mesh)

    def place_agg(self, stage):
        """A group's ?agg= key column (analytics.AggStage) where this
        engine's launches read their page arrays, fenced as `place` is:
        its caller times the put."""
        return jax.block_until_ready(stage.device(self._page_sharding))

    def _place_params(self, tables: tuple) -> tuple:
        """A launch's per-query tables (None entries stay None) as
        device arrays where its kernel reads them: the default device,
        or every device of the mesh. On a mesh this is the rule of
        parallel.mesh.put_replicated: placed in the `build` stage,
        before the collective lock is taken."""
        if self.mesh is None:
            # device_put and not jnp.asarray: the same 0.27 ms of a
            # thread for a small table, but it does not wait for a large
            # one (1 MB of wide ranges: 0.28 ms against 1.16,
            # scripts/fused_params_bench.py on a v5e, PR 39)
            return tuple(None if t is None else jax.device_put(t)
                         for t in tables)
        from tempo_tpu.parallel.mesh import put_replicated

        return put_replicated(self.mesh, tables)

    def _book_params(self, rec, reused: bool) -> None:
        """Say on the launch's record and counter whether its query
        parameters were already resident on the mesh (`reused`) or this
        launch put them there (`placed`). Off a mesh there is nothing
        to say: the question is a mesh's."""
        if self.mesh is None:
            return
        result = "reused" if reused else "placed"
        obs.mesh_param_placements.inc(result=result)
        rec.set(params=result)

    def stage(self, blocks: list[ColumnarPages]) -> BlockBatch:
        """Stack + place a batch on device(s)."""
        return self.place(self.stage_host(blocks))

    def scan_async(self, batch: BlockBatch, mq: MultiQuery):
        """Dispatch one query without device→host sync; returns the
        launch's one device array (engine.pack_out: one row)."""
        def place():
            # uploaded once per MultiQuery, to where this engine's
            # launches read them, and resident from then on
            resident = getattr(mq, "_device_params", None)
            params = query_device_params(mq, self.mesh)
            tk, vr, *bounds = params
            puts = 0 if params is resident else mq._device_params_puts
            vh, bg = mq.val_hits, None
            if vh is not None:
                vh, bg = self._place_params((vh, mq.block_group))
                puts += 1
            return (tk, vr, None, *bounds, vh, bg), params is resident, puts

        return self._launch(
            "batched", batch, mq, place,
            top_k=resolve_top_k(self.top_k, mq.limit),
            tables_key=(mq.val_ranges.shape,), kernel="multi")

    def scan(self, batch: BlockBatch, mq: MultiQuery):
        return self.fetch(self.scan_async(batch, mq), mq)

    def fetch(self, out, q) -> tuple:
        """The drain's end of a launch of `q` (a MultiQuery or a
        CoalescedQuery): the ONE blocking fetch of its output array,
        taken apart on the host (engine.unpack_out's tuple) and counted
        under the launch's mode (tempo_search_launch_out_fetches_total,
        the way out's mirror of launch_param_puts)."""
        agg_stage = q.agg_stage
        fetched = fetch_scan_out(
            out, 0 if agg_stage is None else agg_stage.n_keys)
        obs.launch_out_fetches.inc(mode=self._mode(
            "batched" if isinstance(q, MultiQuery) else "coalesced"))
        return fetched

    def _mode(self, mode: str) -> str:
        """What a launch is booked as: on a mesh every launch is a
        `mesh` launch."""
        return mode if self.mesh is None else "mesh"

    def coalesced_scan_async(self, batch: BlockBatch, cq: CoalescedQuery,
                             top_k: int):
        """Fused multi-query dispatch without device→host sync; returns
        the launch's one device array, a row a member (engine.pack_out:
        [Q, 2 + 2k (+ K)]). `top_k` is the GROUP k — max over the
        coalesced requests' resolved k, so every member's limit is
        covered. Where a member asks for the ?agg= counts every row of
        the group carries them (stack_queries); the served path launches
        ?agg= members solo (QueryCoalescer.submit says why)."""
        def place():
            # the stacked tables of THIS fused launch, one host buffer:
            # one transfer to where the launch reads them. A member's
            # hit mask is on the device already; its block -> group
            # rows are a second, small host array
            vh = cq.val_hits
            buf, vh, bg = self._place_params((
                cq.packed, vh, None if vh is None else cq.block_group))
            return ((buf, *[None] * 6, vh, bg), False,
                    1 if vh is None else 2)

        st = cq.structural
        return self._launch(
            "coalesced", batch, cq, place, top_k=top_k, packed=cq.dims,
            tables_key=(cq.term_keys.shape, cq.val_ranges.shape),
            h2d=cq.packed.nbytes
            + (0 if st is None else sum(
                int(getattr(t, "nbytes", 0)) for t in st.tables
                if t is not None)),
            kernel="coalesced", queries=cq.n_queries)

    def _launch(self, mode: str, batch: BlockBatch, q, place, *, top_k: int,
                tables_key: tuple, h2d: int = 0, packed: tuple | None = None,
                **attrs):
        """THE launch of batch_scan_kernel on the device(s): `q` is a
        MultiQuery or a CoalescedQuery, `place()` puts its nine
        per-query tables where the launch reads them (a fused launch's
        first seven as one buffer, laid out by `packed`) and says
        whether they were resident already and how many host arrays it
        transferred. `mode` names the launch off a mesh; on one every
        launch is a `mesh` launch.

        Watchdog-bounded (robustness.GUARD): a hung or erroring
        dispatch surfaces as DeviceFault (breaker fault booked) instead
        of wedging the submitter — to every member's future of a fused
        launch — and the batcher's drain answers through the
        byte-identical host path. Guard inactive = direct call."""
        from tempo_tpu.robustness import GUARD

        mode = self._mode(mode)

        def run():
            with profile.dispatch(mode) as rec:
                d = batch.device
                with rec.stage("build"):
                    tables, resident, puts = place()
                    if puts:
                        obs.launch_param_puts.inc(puts, mode=mode)
                    vh = tables[7]
                    # structural plan (search/structural.py): static
                    # plan in the jit key, dynamic tables uploaded once
                    # per query (one shared plan and [Q, ...]-stacked
                    # tables for a fused group)
                    st = q.structural
                    plan = None if st is None else st.plan
                    s_tables = None if st is None else st.device_tables(
                        self.mesh)
                    span_cols = (batch.span_device if st is not None
                                 else None)
                    # ?agg= reduction (search/analytics.py): the staged
                    # per-entry composite keys ride the dispatch; the
                    # dense key-space size is the static plan-stage
                    # descriptor
                    agg_stage = q.agg_stage
                    agg = None if agg_stage is None else agg_stage.n_keys
                    entry_agg = (None if agg_stage is None else
                                 agg_stage.device(self._page_sharding))
                self._book_params(rec, resident)
                rec.add_bytes(h2d=h2d)
                widths = batch.widths
                span_sharded = bool(st is not None and batch.span_sharded)
                shard_tail = self._shard_tail(batch, d)
                jit_key = (
                    attrs["kernel"], self.mesh is not None,
                    d["kv_key"].shape, str(d["kv_key"].dtype),
                    str(d["kv_val"].dtype), *tables_key,
                    None if vh is None else (tuple(vh.shape),
                                             str(vh.dtype)),
                    widths, q.n_terms, top_k,
                    None if st is None else st.shape_sig(), span_sharded,
                    shard_tail, agg,
                    None if span_cols is None else
                    tuple(sorted((n, tuple(a.shape))
                                 for n, a in span_cols.items())))
                miss = rec.compile_check(jit_key)
                if miss:
                    _SCAN_JIT_KEYS.add(jit_key)
                    obs.scan_jit_keys.set(len(_SCAN_JIT_KEYS))
                stage = "compile" if miss else "execute"
                membership = "range" if vh is None else "mask"
                members = attrs.get("queries", 1)
                obs.scan_membership.inc(members, path=membership)
                # the block axis of the tables: the group's blocks and
                # the pad rows that fill their bucket, once a member
                blocks = len(batch.blocks)
                bucket = int(q.term_keys.shape[-2])
                obs.launch_table_rows.inc(members * blocks, kind="real")
                rec.set(**attrs, scan_bytes=batch.device_nbytes,
                        shards=self.n_shards, membership=membership,
                        pages_per_shard=self.pages_per_shard(batch),
                        blocks=blocks)
                if bucket > blocks:
                    # said only where there are pad rows: a served
                    # search's self-trace keeps 64 pairs in key order
                    # and every key before `service.name` costs it one
                    obs.launch_table_rows.inc(members * (bucket - blocks),
                                              kind="pad")
                    rec.set(blocks_bucket=bucket)
                if agg is not None:
                    # a launch that reduces alone says over how many
                    # keys; its key rows are the staged group's entries,
                    # pad pages included, once a member
                    obs.agg_launches.inc(mode=mode)
                    obs.agg_key_rows.inc(members * int(entry_agg.size))
                    rec.set(agg_keys=agg)
                if span_cols is not None:
                    # a structural launch alone says how it joins and
                    # over how many span rows (pad rows included): no
                    # flat search carries these (PERF.md section 7 h11)
                    from .structural import plan_joins

                    rows = int(span_cols["span_parent"].shape[0])
                    tiles = int(span_cols["span_tile_block"].shape[0])
                    rel, scans = plan_joins(plan)
                    obs.structural_launches.inc(rel=rel)
                    rec.set(rel=rel, join_scans=scans, span_rows=rows,
                            span_tile=rows // tiles)
                if q.n_terms:
                    # a launch without tag terms compares no range
                    compare = compares_by(q.val_ranges.shape[-2])
                    obs.scan_range_compare.inc(members, by=compare)
                    rec.set(compare=compare)
                book_topk(rec, d["entry_valid"].size // self.n_shards,
                          top_k)

                def call():
                    kernel = (batch_scan_kernel if vh is None
                              else mask_scan_kernel)
                    return kernel(
                        d["kv_key"], d["kv_val"], d["entry_start"],
                        d["entry_end"], d["entry_dur"], d["entry_valid"],
                        d["page_block"], *tables, d.get("entry_dur_res"),
                        span_cols, s_tables, entry_agg, mesh=self.mesh,
                        n_terms=q.n_terms, top_k=top_k, widths=widths,
                        plan=plan, span_sharded=span_sharded,
                        shard_tail=shard_tail, agg=agg, packed=packed)

                if self.mesh is None:
                    with rec.stage(stage):
                        out = call()
                        rec.fence(out)
                    return out
                from tempo_tpu.parallel import mesh as mesh_mod

                # see __init__: collective ordering; time queued behind
                # other dispatches lands in the lock_wait stage
                with mesh_mod.locked_collective(rec):
                    with rec.stage(stage):
                        out = call()
                # fence AFTER releasing the collective lock: a fenced
                # wait under dispatch_lock would serialize every other
                # mesh dispatch behind this kernel's completion (the
                # blocking-under-lock class the analysis suite flags).
                # Stage timers accumulate, so the fenced wait still
                # books into the same compile/execute stage.
                with rec.stage(stage):
                    rec.fence(out)
                return out

        return GUARD.run(mode, run)

    @staticmethod
    def results(batch: BlockBatch, mq: MultiQuery,
                scores: np.ndarray, idx: np.ndarray) -> list:
        """Map top-k flat indices back to TraceSearchMetadata; `batch`
        is a BlockBatch or the HostBatch of a host-route scan."""
        E = batch.blocks[0].geometry.entries_per_page
        out = []
        for s, i in zip(scores.tolist(), idx.tolist()):
            if s < 0 or len(out) >= mq.limit:
                break
            p, e = divmod(i, E)
            if p >= batch.n_pages:
                continue
            bi = int(batch.page_block[p])
            if bi < 0:
                continue
            pages = batch.blocks[bi]
            lp = p - batch.page_offset[bi]
            m = tempopb.TraceSearchMetadata()
            m.trace_id = bytes(pages.trace_ids[lp, e]).hex()
            m.start_time_unix_nano = int(pages.entry_start[lp, e]) * 1_000_000_000
            m.duration_ms = int(pages.entry_dur[lp, e])
            svc = int(pages.entry_root_svc[lp, e])
            name = int(pages.entry_root_name[lp, e])
            if svc >= 0:
                m.root_service_name = pages.val_dict[svc]
            if name >= 0:
                m.root_trace_name = pages.val_dict[name]
            out.append(m)
        return out
