"""Packed HBM residency: bit-width-adaptive columns, unpacked in-register.

The fused scan is a linear pass over staged dictionary-id columns, so
spans/sec/chip is bounded by HBM bytes moved — and the HBM budget caps
how many blocks stay resident (the dominant latency lever: PR 11's
ownership bench measured 42% vs 78% hit ratio). Yet every value-id
column stages at the width of the WIDEST case even when a block's
dictionary has 200 distinct values. This module narrows the RESIDENT
format to what each batch's recorded dictionary cardinality actually
needs — compressed near-data execution in the Taurus sense (arxiv
2506.20010), with the pack-at-stage / unpack-in-kernel split of the
GPU-offloaded OLAP engines' compressed-scan layout (arxiv 2601.19911):

  kv id columns   code = id + 1 (pad -1 → 0) stored uint8/uint16/uint32,
                  or 4-bit two-codes-per-byte for ≤15-value dictionaries
  duration        exact uint16 when the block rollup's max fits; else
                  uint16 buckets ``dur >> s`` plus a small residual —
                  the kernel's range compare is exact on bucket interior
                  and reconstructs the full uint32 ONLY for rows sitting
                  on a boundary bucket
  probe hit masks the dict-probe product ([T, v_pad] bool) bit-packs to
                  uint32 words, 8x fewer HBM bytes pinned per cached
                  compile product (32x fewer bits than the 1-byte bools)

Kernels take a static per-column width descriptor (``widths`` — part of
the jit shape key, so compile-cache keys stay value-independent) and
widen with shifts/masks fused into the existing compares: no separate
decompression pass, no extra HBM round trip. The term tables, compile
cache and all query-side products stay in the id domain, so packed and
unpacked batches share every compiled predicate.

Gate: ``search_packed_residency`` (TempoDBConfig + YAML), default off —
a TRUE noop: call sites read one attribute (``PACKING.enabled``) and
take the byte-identical legacy path. Enabled vs disabled is also
byte-identical (the unpack is exact); only the resident bytes move.
"""

from __future__ import annotations

import functools

import numpy as np

# width descriptors for the kv id columns; "u4" packs two 4-bit codes
# per byte (id+1, pad 0), the rest are plain code arrays of that width
_KV_DTYPES = {"u8": np.uint8, "u16": np.uint16, "u32": np.uint32}


class PackedResidency:
    """Process-wide gate (module singleton ``PACKING``, the OWNERSHIP /
    PLANNER idiom): TempoDBConfig flips ``enabled``; staging sites
    consult ``plan_widths``/``pack_hits``, which are self-gated so the
    disabled path is one attribute read."""

    __slots__ = ("enabled",)

    def __init__(self) -> None:
        self.enabled = False

    def plan_widths(self, n_keys: int, n_vals: int, max_dur_ms: int):
        """The width descriptor for a batch: (key_width, val_width,
        dur_width) chosen from the recorded dictionary cardinalities and
        the header duration rollup, or None (= the unpacked legacy
        layout) when the gate is off. Static per staged batch — it is
        part of every consuming kernel's jit shape key."""
        if not self.enabled:
            return None
        return (width_for_cardinality(n_keys),
                width_for_cardinality(n_vals),
                dur_width(max_dur_ms))

    def pack_hits(self, hits):
        """Bit-pack a device-probe hit mask (bool [..., v_pad] → uint32
        words [..., v_pad/32]) when the gate is on; identity when off."""
        if not self.enabled:
            return hits
        return pack_mask_words(hits)


PACKING = PackedResidency()


def configure(enabled: bool | None = None) -> PackedResidency:
    """Apply config (TempoDBConfig.search_packed_residency) to the
    process gate — most recent TempoDB wins, the PROFILER idiom."""
    if enabled is not None:
        PACKING.enabled = bool(enabled)
    return PACKING


# ---------------------------------------------------------------------------
# width selection (host side)


def width_for_cardinality(n: int) -> str:
    """Narrowest storage for a dictionary of `n` distinct ids. Codes are
    id+1 with 0 reserved for the pad slot, so the boundaries sit at
    15/16, 255/256 and 65535/65536 (n values need n+1 codes)."""
    if n <= 15:
        return "u4"
    if n <= 255:
        return "u8"
    if n <= 65_535:
        return "u16"
    return "u32"


def dur_width(max_dur_ms: int) -> str:
    """Duration storage for a batch whose header rollup caps durations
    at `max_dur_ms`: exact uint16 when it fits; else uint16 buckets
    ``dur >> s`` with the smallest shift that fits, plus a residual
    column holding the shifted-out low bits (uint8 when s <= 8)."""
    m = max(0, int(max_dur_ms))
    if m <= 0xFFFF:
        return "u16"
    return f"q{m.bit_length() - 16}"


def legacy_kv_itemsize(n: int) -> int:
    """Bytes/slot the UNPACKED layout uses for a dictionary of `n` ids
    (multiblock.stack_host's signed narrowing with its -1 sentinel) —
    the logical-bytes baseline the packed accounting reports against."""
    return 1 if n <= 127 else (2 if n <= 32_767 else 4)


# ---------------------------------------------------------------------------
# host-side packing (numpy, at stack/stage time)


def pack_ids_array(arr: np.ndarray, w: str) -> np.ndarray:
    """Pack an int32 id array (-1 = pad) into width `w` codes (id+1,
    pad 0). For "u4" the last axis must be even; two codes share a byte
    (low nibble = even slot)."""
    codes = arr.astype(np.int32, copy=False) + 1
    if w == "u4":
        lo = codes[..., 0::2]
        hi = codes[..., 1::2]
        return (lo | (hi << 4)).astype(np.uint8)
    return codes.astype(_KV_DTYPES[w])


def pack_duration(arr: np.ndarray, dw: str):
    """(quantized, residual-or-None) for a uint32 duration column under
    descriptor `dw`. "u16" is an exact narrowing (the batch rollup
    proved every duration fits); "q<s>" stores ``dur >> s`` uint16
    buckets plus the shifted-out low bits so the kernel can reconstruct
    exactly at bucket boundaries."""
    if dw == "u16":
        return arr.astype(np.uint16), None
    s = int(dw[1:])
    a = arr.astype(np.uint32, copy=False)
    res_dt = np.uint8 if s <= 8 else np.uint16
    return (a >> s).astype(np.uint16), (a & ((1 << s) - 1)).astype(res_dt)


def logical_nbytes(n_entries_padded: int, kv_slots: int, n_keys: int,
                   n_vals: int) -> int:
    """Bytes the UNPACKED layout would pin for this many (padded)
    entries: narrowed kv columns + uint32 start/end/dur + bool valid.
    The physical/logical split the accounting gauges report — identical
    to physical when the gate is off."""
    kv = n_entries_padded * kv_slots * (legacy_kv_itemsize(n_keys)
                                        + legacy_kv_itemsize(n_vals))
    return int(kv + n_entries_padded * (4 + 4 + 4 + 1))


# ---------------------------------------------------------------------------
# in-kernel unpack (jnp; `w`/`dw`/`widths` are static at every call
# site — the jit-purity checker enforces that no tracer reaches a width
# descriptor parameter)


def unpack_ids(arr, w):
    """int32 id view (-1 = pad) of a packed kv column — the widening
    shifts/masks fuse into the consuming compare (no separate
    decompression pass materializes in HBM)."""
    import jax.numpy as jnp

    if w is None:
        return arr
    if w == "u4":
        lo = arr & jnp.uint8(0x0F)
        hi = arr >> 4
        codes = jnp.stack([lo, hi], axis=-1)
        codes = codes.reshape(arr.shape[:-1] + (arr.shape[-1] * 2,))
        return codes.astype(jnp.int32) - 1
    return arr.astype(jnp.int32) - 1


def duration_ok(entry_dur, entry_dur_res, dur_lo, dur_hi, dw):
    """The duration range predicate under descriptor `dw`. Quantized
    widths compare uint16 buckets against the query bounds' buckets —
    exact on the bucket interior — and reconstruct the full uint32
    (bucket << s | residual) ONLY for rows that hit a boundary bucket,
    where the bucket compare is ambiguous."""
    import jax.numpy as jnp

    lo = dur_lo.astype(jnp.uint32)
    hi = dur_hi.astype(jnp.uint32)
    if dw is None or not dw.startswith("q"):
        dur = entry_dur.astype(jnp.uint32)
        return (dur >= lo) & (dur <= hi)
    s = int(dw[1:])
    q = entry_dur.astype(jnp.uint32)
    lo_q = lo >> s
    hi_q = hi >> s
    inside = (q > lo_q) & (q < hi_q)
    boundary = (q == lo_q) | (q == hi_q)
    full = (q << s) | entry_dur_res.astype(jnp.uint32)
    exact = (full >= lo) & (full <= hi)
    return inside | (boundary & exact)


def mask_select(row, ids):
    """Membership lookup on one term's hit-mask row: `row` is [V] bool
    or [W] uint32 bit-words; `ids` indexes the value axis. The packed
    path gathers one word and selects the bit in-register."""
    import jax.numpy as jnp

    if row.dtype == jnp.uint32:
        word = row[ids >> 5]
        return (word >> (ids & 31).astype(jnp.uint32)) & jnp.uint32(1) != 0
    return row[ids]


def mask_select_grouped(vh, g, t, ids):
    """Grouped variant for the multi-block mask table: `vh` is
    [G, T, V] bool or [G, T, W] uint32 words; `g` broadcasts the
    per-page dictionary group over `ids`."""
    import jax.numpy as jnp

    if vh.dtype == jnp.uint32:
        word = vh[g, t, ids >> 5]
        return (word >> (ids & 31).astype(jnp.uint32)) & jnp.uint32(1) != 0
    return vh[g, t, ids]


def is_packed_mask(x) -> bool:
    """True when a probe product's hit mask is in the bit-packed
    format (compile-cache entries from the other gate state must be
    treated as misses so one assembled batch never mixes formats)."""
    return getattr(x, "dtype", None) is not None \
        and str(x.dtype) == "uint32"


@functools.lru_cache(maxsize=1)
def _pack_mask_jit():
    import jax

    @jax.jit
    def _pack(hits):
        import jax.numpy as jnp

        V = hits.shape[-1]
        W = -(-V // 32)
        if W * 32 != V:
            pad = [(0, 0)] * (hits.ndim - 1) + [(0, W * 32 - V)]
            hits = jnp.pad(hits, pad)
        u = hits.reshape(hits.shape[:-1] + (W, 32)).astype(jnp.uint32)
        return (u << jnp.arange(32, dtype=jnp.uint32)).sum(
            axis=-1).astype(jnp.uint32)

    return _pack


def pack_mask_words(hits):
    """bool [..., V] hit mask → uint32 [..., ceil(V/32)] bit-words on
    device (bit i of word w = value id 32*w + i). Already-packed input
    passes through (idempotent across cache/coalesce boundaries)."""
    if is_packed_mask(hits):
        return hits
    return _pack_mask_jit()(hits)


def unpack_mask_words(words, v_pad: int) -> np.ndarray:
    """Host-side expansion of a packed mask row set back to bool — the
    parity bridge for tests/bench (dict_probe.hits_to_ids)."""
    a = np.asarray(words)
    bits = np.unpackbits(a.view(np.uint8), axis=-1, bitorder="little")
    return bits[..., :v_pad].astype(bool)
