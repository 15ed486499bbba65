#!/bin/bash
# Runs of one cell, one after the other, in one chip call:
#   chiprun --timeout 3000 -- bash chipbench/tools/sets.sh <workload> <tag> <seconds> <trace> seed...
# Each run's stdout and stderr go to $OUT/<tag>_<seed>.{txt,err} (OUT
# defaults to chiprun_out); the result line and the facts that matter
# come back on stdout.
W=$1; TAG=$2; SEC=$3; TRACE=$4; shift 4
OUT=${OUT:-chiprun_out}
mkdir -p "$OUT"
for S in "$@"; do
  python3 -m chipbench.run --workload "$W" --seed "$S" --seconds "$SEC" --trace "$TRACE" \
    > "$OUT/${TAG}_$S.txt" 2> "$OUT/${TAG}_$S.err"
  echo "rc=$? seed=$S $(tail -1 "$OUT/${TAG}_$S.txt" | cut -c1-1800)"
  grep "jit keys\|groups staged\|jit misses\|late median\|split\|latency from\|MISMATCH\|FATAL" "$OUT/${TAG}_$S.txt" | cut -d']' -f2 | cut -c1-300
done
