"""Spreads of a cell's runs, as the contract reads them:

    python3 chipbench/tools/spread.py chiprun_out/triA_*.txt -- chiprun_out/triB_*.txt

Each argument is a run's stdout (its last line the result object); `--`
separates the sets. For each metric and set: the values, the median, the
spread (distance between the first and third quartile of Python's
`statistics.quantiles(values, n=4)` over the median), and the spread
with the run farthest from the median left out. Last, five times the
wider spread: what the bound should be about.
"""

import json
import statistics
import sys


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main(argv):
    sets, cur = [], []
    for a in argv:
        if a == "--":
            sets.append(cur)
            cur = []
        else:
            cur.append(a)
    sets.append(cur)
    table: dict = {}
    for k, files in enumerate(sets):
        for f in files:
            with open(f) as fh:
                last = fh.read().strip().splitlines()[-1]
            try:
                doc = json.loads(last)
            except ValueError:
                print(f"{f}: no result line")
                continue
            if not doc.get("correct"):
                print(f"{f}: correct={doc.get('correct')}")
            for name, m in doc["metrics"].items():
                table.setdefault(name, {}).setdefault(k, []).append(m["value"])
    for name, by_set in table.items():
        widest = 0.0
        for k, vals in sorted(by_set.items()):
            if len(vals) < 2:
                print(f"{name} set {k}: {vals}")
                continue
            med = statistics.median(vals)
            far = max(vals, key=lambda v: abs(v - med))
            rest = list(vals)
            rest.remove(far)
            s = spread(vals)
            widest = max(widest, s)
            print(f"{name} set {k}: n={len(vals)} median={med:.6g} "
                  f"spread={100 * s:.2f}% without-farthest="
                  f"{100 * spread(rest) if len(rest) > 1 else 0:.2f}% "
                  f"values={' '.join(f'{v:.6g}' for v in vals)}")
        meds = [statistics.median(v) for _, v in sorted(by_set.items())]
        if len(meds) == 2:
            print(f"{name}: second median / first = {meds[1] / meds[0]:.4f}")
        print(f"{name}: 5 x widest spread = {500 * widest:.1f}%")


if __name__ == "__main__":
    main(sys.argv[1:])
