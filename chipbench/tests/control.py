"""The control at the cell's own size, on the chip:

    chiprun -- python3 -m chipbench.tests.control --workload <cell> \
        --seed <n> --breakage block-skipped --seconds 10

One whole run of the cell with the timed path broken underneath
(breakages.py). Prints the run's facts and a last line
`control: breakage=... correct=<bool>`; exits 0 when `correct` came out
false, as it must, and 1 when the broken run passed as correct.
"""

import argparse
import sys

from chipbench import run as harness
from chipbench.tests.breakages import BREAKAGES


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--breakage", choices=sorted(BREAKAGES), required=True)
    args = ap.parse_args(argv)
    seen: dict = {}

    def hook(stage, state):
        seen.setdefault("state", state)
        BREAKAGES[args.breakage](stage, seen["state"])

    ns = argparse.Namespace(workload=args.workload, seed=args.seed,
                            seconds=args.seconds, trace=0, scale="full")
    result, code = harness.run(ns, hook=hook)
    if result is None:
        return code or 1
    print(f"control: workload={args.workload} seed={args.seed} "
          f"breakage={args.breakage} correct={result['correct']}",
          flush=True)
    return 1 if result["correct"] else 0


if __name__ == "__main__":
    sys.exit(main())
