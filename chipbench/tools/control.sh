#!/bin/bash
# The control at a cell's own size, three seeds, in one chip call:
#   chiprun --timeout 1500 -- bash chipbench/tools/control.sh <workload> <breakage> seed seed seed
# One whole run a seed (10 s window) with the timed path broken
# underneath (chipbench/tests/breakages.py); each must end `correct=False`.
W=$1; B=$2; shift 2
OUT=${OUT:-chiprun_out}
mkdir -p "$OUT"
for S in "$@"; do
  python3 -m chipbench.tests.control --workload "$W" --seed "$S" --breakage "$B" --seconds 10 \
    > "$OUT/control_${W}_$S.txt" 2> "$OUT/control_${W}_$S.err"
  echo "rc=$? $(tail -1 "$OUT/control_${W}_$S.txt")"
  grep "compared:\|MISMATCH" "$OUT/control_${W}_$S.txt" | cut -d']' -f2 | cut -c1-200
done
