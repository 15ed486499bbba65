"""Where `dict_probe.R_MAX` comes from: the scan program
(`multiblock.batch_scan_kernel`) over one full group, testing value
membership by R pairs of compares or by a gather from a hit mask.

    chiprun -- python3 scripts/membership_bench.py [--pages 4096]

One staged group as a high-cardinality tenant's is (1,024 entries a
page, 16 kv slots, int8 keys, int32 value ids under 57,000 a block, 64
blocks), made on the device from a fixed seed. For T = 1 and 2 terms it
times a solo launch with ranges at R = 1 .. 1,024 and with the mask,
and a fused launch of four members at the same R; every launch is
fenced and the median of `--calls` is printed, one JSON line each and a
table at the end. The mask starts to win where its row is the faster;
`R_MAX` is the last R before that. It is also where
`multiblock.ENTRY_RANGES` comes from, the R from which the compares run
once an entry and not once a slot: `--entry-from 1` takes every R that
way and `--entry-from 4096` none, and `--values-per-key` puts the
term's key in that many adjacent slots of every entry, a pass of the
entry form for each. On the CPU it runs at `--pages 8` and proves only
that the shapes trace.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

E, C, BLOCKS, VALS, V_PAD = 1024, 16, 64, 57_000, 65_536


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--pages", type=int, default=4096)
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--ranges", default="1,2,4,8,16,32,64,128,256,512,1024")
    ap.add_argument("--fused", default=None,
                    help="R of the fused launches (default: --ranges)")
    ap.add_argument("--fused-terms", default="1",
                    help="T of the fused launches")
    ap.add_argument("--values-per-key", type=int, default=1,
                    help="slots of every entry that hold the term's key")
    ap.add_argument("--entry-from", type=int, default=None,
                    help="multiblock.ENTRY_RANGES for this run "
                         "(default: the kernel's own)")
    ap.add_argument("--no-mask", action="store_true",
                    help="skip the mask rows (40 s of a call)")
    args = ap.parse_args()
    if not 1 <= args.values_per_key < C:
        ap.error(f"--values-per-key: 1 .. {C - 1}")

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tempo_tpu.search import multiblock
    from tempo_tpu.search.engine import DEFAULT_TOP_K, resolve_top_k
    from tempo_tpu.search.multiblock import (batch_scan_kernel,
                                             mask_scan_kernel)
    from tempo_tpu.utils.jaxenv import enable_compile_cache

    enable_compile_cache()
    if args.entry_from is not None:
        # read when a shape is first traced: set before any is
        multiblock.ENTRY_RANGES = args.entry_from
    dev = jax.devices()[0]
    tag = f"[platform={dev.platform} kind={dev.device_kind}]"
    P = args.pages
    key = jax.random.PRNGKey(33)
    k1, k2, k3 = jax.random.split(key, 3)
    # slot j holds key j, but the term's key (1) fills slots 1 .. k: in
    # sorted-key order, as ColumnarPages.build lays a k-valued key
    slot = np.arange(C)
    kv_key = jnp.broadcast_to(jnp.asarray(np.where(
        (slot >= 1) & (slot <= args.values_per_key), 1, slot).astype(np.int8)),
        (P, E, C))
    kv_val = jax.random.randint(k1, (P, E, C), 0, VALS, dtype=jnp.int32)
    start = jax.random.randint(k2, (P, E), 1_700_000_000, 1_700_086_400,
                               dtype=jnp.int32).astype(jnp.uint32)
    dur = jax.random.randint(k3, (P, E), 1, 60_000,
                             dtype=jnp.int32).astype(jnp.uint32)
    cols = (kv_key + jnp.int8(0), kv_val, start, start + 1, dur,
            jnp.ones((P, E), dtype=bool),
            jnp.repeat(jnp.arange(BLOCKS, dtype=jnp.int32),
                       max(1, P // BLOCKS))[:P])
    jax.block_until_ready(cols)
    top_k = resolve_top_k(DEFAULT_TOP_K, 20)
    rng = np.random.default_rng(33)
    bounds = [jnp.uint32(v) for v in (0, 0xFFFFFFFF, 0, 0xFFFFFFFF)]

    def ranges(T: int, R: int) -> np.ndarray:
        """[BLOCKS, T, R, 2]: R disjoint single-id runs a block and term,
        as a scattered infix's are."""
        lo = np.sort(rng.choice(VALS // 2, (BLOCKS, T, R), replace=True)
                     * 2, axis=-1).astype(np.int32)
        return np.stack([lo, lo], axis=-1)

    def mask(T: int) -> np.ndarray:
        return rng.random((BLOCKS, T, V_PAD)) < 0.01

    def timed(label: str, fn, **facts) -> dict:
        t = time.perf_counter()
        jax.block_until_ready(fn())
        first = time.perf_counter() - t
        ms = []
        for _ in range(args.calls):
            t = time.perf_counter()
            jax.block_until_ready(fn())
            ms.append((time.perf_counter() - t) * 1e3)
        row = dict(facts, label=label, pages=P, launch_ms=statistics.median(ms),
                   min_ms=min(ms), max_ms=max(ms), first_s=first,
                   values_per_key=args.values_per_key,
                   entry_from=multiblock.ENTRY_RANGES)
        print(tag, json.dumps(row), flush=True)
        return row

    def solo(T, vr=None, vh=None):
        tk = jnp.full((BLOCKS, T), 1, dtype=jnp.int32)
        if vh is None:
            vr = jnp.asarray(vr)
            return lambda: batch_scan_kernel(
                *cols, tk, vr, None, *bounds, n_terms=T, top_k=top_k)
        vr = jnp.asarray(np.tile(np.array([1, 0], np.int32), (BLOCKS, T, 1, 1)))
        vh, bg = jnp.asarray(vh), jnp.arange(BLOCKS, dtype=jnp.int32)
        return lambda: mask_scan_kernel(
            *cols, tk, vr, None, *bounds, vh, bg, n_terms=T, top_k=top_k)

    def fused(Q, T, vr=None, vh=None):
        tk = jnp.full((Q, BLOCKS, T), 1, dtype=jnp.int32)
        active = jnp.ones((Q, T), dtype=bool)
        qb = [jnp.full((Q,), v, dtype=jnp.uint32)
              for v in (0, 0xFFFFFFFF, 0, 0xFFFFFFFF)]
        if vh is None:
            vr = jnp.asarray(np.stack([vr] * Q))
            return lambda: batch_scan_kernel(
                *cols, tk, vr, active, *qb, n_terms=T, top_k=top_k)
        vr = jnp.asarray(np.tile(np.array([1, 0], np.int32),
                                 (Q, BLOCKS, T, 1, 1)))
        vh = jnp.asarray(np.stack([vh] * Q))
        bg = jnp.tile(jnp.arange(BLOCKS, dtype=jnp.int32), (Q, 1))
        return lambda: mask_scan_kernel(
            *cols, tk, vr, active, *qb, vh, bg, n_terms=T, top_k=top_k)

    rows = []
    for T in (1, 2):
        for R in (int(r) for r in args.ranges.split(",")):
            rows.append(timed(f"solo T={T} R={R}", solo(T, vr=ranges(T, R)),
                              T=T, R=R, Q=1, membership="range"))
        if not args.no_mask:
            rows.append(timed(f"solo T={T} mask", solo(T, vh=mask(T)),
                              T=T, R=None, Q=1, membership="mask"))
    for T in (int(t) for t in args.fused_terms.split(",")):
        for R in (int(r) for r in (args.fused or args.ranges).split(",")):
            rows.append(timed(f"fused Q=4 T={T} R={R}",
                              fused(4, T, vr=ranges(T, R)),
                              T=T, R=R, Q=4, membership="range"))
    if not args.no_mask:
        rows.append(timed("fused Q=4 T=1 mask", fused(4, 1, vh=mask(1)),
                          T=1, R=None, Q=4, membership="mask"))
    print(tag, "membership  Q  T      R  launch_ms")
    for r in rows:
        print(tag, f"{r['membership']:>10} {r['Q']:>2} {r['T']:>2} "
              f"{str(r['R']):>6} {r['launch_ms']:>10.2f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
