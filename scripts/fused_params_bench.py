"""How a fused launch's seven per-query tables should reach the device,
and how a launch's results should come back: what each way costs the
HOST, one thread, nothing else running.

    chiprun -- python3 scripts/fused_params_bench.py [--pages 512]

One staged group shaped as `share16`'s (1,024 entries a page, 16 kv
slots, int8 keys, int16 value ids, 64 blocks) and the tables
`multiblock.stack_queries` makes for Q members of T terms and R ranges.
Per arm `--calls` launches in a row, each drained before the next (the
device is never the limit); timed per launch are the put (`build`) and
the kernel call's return (`execute`), on the wall clock and on the
thread's CPU clock (the v5e hosts tick it by 10 ms: only the sum over
the calls means anything, so the default is 2,000). The arms:

  seven_puts    `jnp.asarray` of each of the seven tables, then the
                call with seven device arrays: the launch until PR 39
  one_put       `jnp.asarray` of the packed buffer, then the call with
                it (`packed=` the static dims): the launch since PR 39
  device_put    the same with `jax.device_put`
  args_packed   no put: the packed host buffer handed to the jitted
                call, which transfers it on its own argument path
  args_seven    no put: the seven host tables handed to the call

The way out (PR 41), for a solo launch's results and a fused one's
(`--out-shapes`, Q of them; 0 is solo), `--calls` fresh outputs an arm,
each ready on the device before its clock starts; timed are `start`
(the async copies the launch starts) and `fetch` (the drain's blocking
fetch, host values in hand at its end):

  four_fetches  count, inspected, scores [k], idx [k] as four device
                arrays: four `copy_to_host_async`, then `int()` twice
                and `np.asarray` twice: the launch until PR 41
  one_fetch     the one packed int32 array (`engine.pack_out`): one
                copy, one `np.asarray`, `engine.unpack_out`'s views

The page count does not enter a put's, a call's or a fetch's host cost,
so the default group is an eighth of a real one. On the CPU backend
there is no transfer and the numbers are the Python around one.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

E, C, BLOCKS, VALS = 1024, 16, 64, 13_000


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--pages", type=int, default=512)
    ap.add_argument("--calls", type=int, default=2000)
    ap.add_argument("--shapes", default="2x2x1,4x2x1,2x1x512",
                    help="QxTxR of the fused launches")
    ap.add_argument("--out-shapes", default="0,2,4",
                    help="Q of the launches whose way out is timed (0: solo)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from tempo_tpu.search.engine import (DEFAULT_TOP_K, pack_out,
                                         resolve_top_k, start_fetch,
                                         unpack_out)
    from tempo_tpu.search.multiblock import (MultiQuery, batch_scan_kernel,
                                             stack_queries)
    from tempo_tpu.utils.jaxenv import enable_compile_cache

    enable_compile_cache()
    dev = jax.devices()[0]
    tag = f"[platform={dev.platform} kind={dev.device_kind}]"
    P = args.pages
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(39), 3)
    kv_key = jnp.broadcast_to(jnp.arange(C, dtype=jnp.int8), (P, E, C))
    start = jax.random.randint(k2, (P, E), 1_700_000_000, 1_700_086_400,
                               dtype=jnp.int32).astype(jnp.uint32)
    cols = (kv_key + jnp.int8(0),
            jax.random.randint(k1, (P, E, C), 0, VALS, dtype=jnp.int16),
            start, start + 1,
            jax.random.randint(k3, (P, E), 1, 60_000,
                               dtype=jnp.int32).astype(jnp.uint32),
            jnp.ones((P, E), dtype=bool),
            jnp.repeat(jnp.arange(BLOCKS, dtype=jnp.int32),
                       max(1, P // BLOCKS))[:P])
    jax.block_until_ready(cols)
    top_k = resolve_top_k(DEFAULT_TOP_K, 20)
    rng = np.random.default_rng(39)

    def member(T: int, R: int) -> MultiQuery:
        lo = np.sort(rng.choice(VALS // 2, (BLOCKS, T, R)) * 2,
                     axis=-1).astype(np.int32)
        return MultiQuery(
            term_keys=np.ones((BLOCKS, T), dtype=np.int32),
            val_ranges=np.stack([lo, lo], axis=-1), dur_lo=0,
            dur_hi=0xFFFFFFFF, win_start=0, win_end=0xFFFFFFFF, limit=20,
            n_terms=T)

    def timed(names, before, first, second, after):
        """`--calls` times `first` then `second`, each on the wall clock
        and on this thread's CPU clock; `before` and `after` run off
        the clocks. -> {"<name>_wall_ms" | "<name>_cpu_ms": a call's
        mean, "wall_ms" | "cpu_ms": both}."""
        wall = dict.fromkeys(names, 0.0)
        cpu = dict(wall)
        for i in range(args.calls):
            x = before(i)
            w0, c0 = time.perf_counter(), time.thread_time()
            y = first(x)
            w1, c1 = time.perf_counter(), time.thread_time()
            z = second(x, y)
            w2, c2 = time.perf_counter(), time.thread_time()
            after(z)
            wall[names[0]] += w1 - w0
            cpu[names[0]] += c1 - c0
            wall[names[1]] += w2 - w1
            cpu[names[1]] += c2 - c1
        row = {}
        for clock, sums in (("wall", wall), ("cpu", cpu)):
            for st in names:
                row[f"{st}_{clock}_ms"] = sums[st] / args.calls * 1e3
            row[clock + "_ms"] = sum(row[f"{st}_{clock}_ms"] for st in names)
        return row

    rows = []
    for Q, T, R in (map(int, s.split("x")) for s in args.shapes.split(",")):
        cq = stack_queries([member(T, R) for _ in range(Q)])
        seven = (cq.term_keys, cq.val_ranges, cq.term_active, cq.dur_lo,
                 cq.dur_hi, cq.win_start, cq.win_end)
        nones = (None,) * 6

        def by_seven(tables):
            return batch_scan_kernel(*cols, *tables, n_terms=T, top_k=top_k)

        def by_packed(buf):
            return batch_scan_kernel(*cols, buf, *nones, n_terms=T,
                                     top_k=top_k, packed=cq.dims)

        arms = {
            "seven_puts": (lambda: tuple(jnp.asarray(t) for t in seven),
                           by_seven),
            "one_put": (lambda: jnp.asarray(cq.packed), by_packed),
            "device_put": (lambda: jax.device_put(cq.packed), by_packed),
            "args_packed": (lambda: cq.packed, by_packed),
            "args_seven": (lambda: seven, by_seven)}
        want = None
        for name, (put, call) in arms.items():
            out = jax.block_until_ready(call(put()))   # compile, if new
            got = np.asarray(out)
            if want is None:
                want = got
            same = np.array_equal(want, got)
            row = {"arm": name, "Q": Q, "T": T, "R": R, "pages": P,
                   "calls": args.calls, "bytes": int(cq.packed.nbytes),
                   "answers_as_seven_puts": same,
                   **timed(("build", "execute"), lambda i: None,
                           lambda _x, put=put: put(),
                           lambda _x, placed, call=call: call(placed),
                           jax.block_until_ready)}
            print(tag, json.dumps(row), flush=True)
            rows.append(row)
    print(tag, " Q  T    R  arm          build wall|cpu   execute wall|cpu"
          "   both wall|cpu (ms a launch)")
    for r in rows:
        print(tag, f"{r['Q']:>2} {r['T']:>2} {r['R']:>4}  {r['arm']:<12}"
              f" {r['build_wall_ms']:>6.3f}|{r['build_cpu_ms']:<6.3f}"
              f"   {r['execute_wall_ms']:>6.3f}|{r['execute_cpu_ms']:<6.3f}"
              f"   {r['wall_ms']:>6.3f}|{r['cpu_ms']:<6.3f}"
              + ("" if r["answers_as_seven_puts"] else "  ANSWERS DIFFER"))

    # the way out: fresh results of a launch's shapes from a program
    # of two adds (the scan's own time is not the question), as four
    # arrays and as the one packed array
    def results(i, Q):
        lead = (Q,) if Q else ()
        count = jnp.full(lead, 7, jnp.int32) + i
        return (count, jnp.int32(41) + i,
                jnp.broadcast_to(jnp.arange(top_k, dtype=jnp.int32) + i,
                                 (*lead, top_k)),
                jnp.broadcast_to(jnp.arange(top_k, dtype=jnp.int32) - i,
                                 (*lead, top_k)))

    def four_fetch(out):
        count, inspected, scores, idx = out
        return (np.asarray(count) if count.ndim else int(count),
                int(inspected), np.asarray(scores), np.asarray(idx))

    def four_start(out):
        for a in out:
            a.copy_to_host_async()

    out_rows = []
    for Q in (int(q) for q in args.out_shapes.split(",")):
        four = jax.jit(lambda i, Q=Q: results(i, Q))
        one = jax.jit(lambda i, Q=Q: pack_out(*results(i, Q)))
        arms = {"four_fetches": (four, four_start, four_fetch),
                "one_fetch": (one, start_fetch,
                              lambda out: unpack_out(np.asarray(out)))}
        want = None
        for name, (make, start, fetch) in arms.items():
            got = fetch(jax.block_until_ready(make(jnp.int32(3))))
            if want is None:
                want = got
            same = all(np.array_equal(a, b) for a, b in zip(want, got))
            row = {"arm": name, "Q": Q, "k": top_k, "calls": args.calls,
                   "answers_as_four_fetches": same,
                   **timed(("start", "fetch"),
                           lambda i, make=make: jax.block_until_ready(
                               make(jnp.int32(i))),
                           start, lambda out, _y, fetch=fetch: fetch(out),
                           lambda _z: None)}
            print(tag, json.dumps(row), flush=True)
            out_rows.append(row)
    print(tag, " Q  arm           start wall|cpu   fetch wall|cpu"
          "   both wall|cpu (ms a launch)")
    for r in out_rows:
        print(tag, f"{r['Q']:>2}  {r['arm']:<12}"
              f"  {r['start_wall_ms']:>6.3f}|{r['start_cpu_ms']:<6.3f}"
              f"   {r['fetch_wall_ms']:>6.3f}|{r['fetch_cpu_ms']:<6.3f}"
              f"   {r['wall_ms']:>6.3f}|{r['cpu_ms']:<6.3f}"
              + ("" if r["answers_as_four_fetches"] else "  ANSWERS DIFFER"))
    return 0 if all(r["answers_as_seven_puts"] for r in rows) and all(
        r["answers_as_four_fetches"] for r in out_rows) else 1


if __name__ == "__main__":
    sys.exit(main())
