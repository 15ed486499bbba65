"""Find an open-loop cell's knee: `python3 -m chipbench.sweep --workload
<cell> [--rates 5,10,20,...] [--step 10]`.

One set-up (the cell's own: corpus, server, staging, every shape
warmed), then one step of `--step` seconds at each rate, the cell's own
mix and arrival law. A step holds when nothing failed, its backlog (arrivals less
completions) does not grow from the middle of the step to its end, it
drains at once, and its median latency is within four times the first
step's (so start the rates well below the knee); the knee is the highest
rate that holds. Run once per
open-loop cell on the chip; the rate goes into the traffic file as a
number (4/5 of the knee) and the table into PERF.md. The sweep prints
no result line and exits 0 only on a TPU.
"""

from __future__ import annotations

import argparse
import sys

from chipbench import run as harness
from chipbench.lib import percentile


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=20260927)
    ap.add_argument("--rates", default="5,10,15,20,30,40,60,80,120,160")
    ap.add_argument("--step", type=float, default=10.0)
    ap.add_argument("--scale", choices=("full", "tiny"), default="full")
    args = ap.parse_args(argv)
    rates = [float(r) for r in args.rates.split(",")]

    def sweep(stage: str, state: dict) -> None:
        if stage != "warm":
            return
        traffic, ops, client = state["traffic"], state["ops"], state["client"]
        if traffic["loop"] != "open":
            raise SystemExit("the sweep is for open-loop cells")
        harness.say("sweep: rate/s attempted failed p50_ms p95_ms max_ms "
                    "backlog_mid backlog_end drain_s late_max_s holds")
        unloaded = None
        for k, rate in enumerate(rates):
            phase = harness.window_phase(
                traffic, ops, args.step,
                harness.rng_for(args.seed, 100 + k), rate)
            rows = client.run(phase)
            ok = [r for r in rows if r["status"] == 200]
            lat = [(r["done"] - r["due"]) * 1e3 for r in ok]
            by_op: dict = {}
            for r in ok:
                by_op.setdefault(state["requests"][r["i"]]["op"], []).append(
                    (r["done"] - r["due"]) * 1e3)

            def backlog(t):
                return (sum(1 for r in rows if r["due"] <= t)
                        - sum(1 for r in rows if r["done"] <= t))

            mid, end = backlog(args.step / 2), backlog(args.step)
            drain = max((r["done"] for r in rows), default=0) - args.step
            p50 = percentile(lat, 50)
            unloaded = p50 if unloaded is None else unloaded
            holds = (len(ok) == len(rows) and end <= mid + 3
                     and drain < args.step / 10 and p50 <= 4 * unloaded)
            harness.say(
                f"sweep: {rate:g} {len(rows)} {len(rows) - len(ok)} "
                f"{p50:.1f} {percentile(lat, 95):.1f} "
                f"{max(lat, default=0):.1f} {mid} {end} {drain:.2f} "
                f"{client.summaries[-1]['late_max_s']:.3f} "
                f"{'yes' if holds else 'NO'} " + " ".join(
                    f"{op}:p50={percentile(v, 50):.1f}/p95="
                    f"{percentile(v, 95):.1f}" for op, v in sorted(
                        by_op.items())))
            if drain > args.step or len(ok) < len(rows):
                break

    ns = argparse.Namespace(
        workload=args.workload, seed=args.seed, seconds=2.0, trace=0,
        scale=args.scale)
    _, code = harness.run(ns, hook=sweep)
    return code


if __name__ == "__main__":
    sys.exit(main())
