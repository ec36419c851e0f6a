#!/bin/sh
# The one chip command that proves a cell: a cold run (compiles), two
# sets of N runs with the same seeds in both sets, two traced runs.
#   chiprun --timeout 3000 -- sh benchmark/prove.sh <workload> <seconds> [N] [traced]
# Result lines land in chiprun_out/<workload>/runs.jsonl (one per run,
# with the run's tag), each run's stderr beside them.
set -u
W=$1; S=$2; N=${3:-6}; T=${4:-2}
OUT=chiprun_out/$W; mkdir -p "$OUT"
one() { # tag seed trace [extra args]
  tag=$1; seed=$2; trace=$3; shift 3
  t0=$(date +%s)
  line=$(python3 benchmark/run.py --workload "$W" --seed "$seed" --seconds "$S" --trace "$trace" "$@" 2>"$OUT/$tag.err" | tail -n 1)
  rc=$?
  t1=$(date +%s)
  echo "{\"tag\": \"$tag\", \"seed\": $seed, \"rc\": $rc, \"wall_s\": $((t1 - t0)), \"line\": ${line:-null}}" | tee -a "$OUT/runs.jsonl"
  grep "^\[bench" "$OUT/$tag.err" | cut -c1-700 > "$OUT/$tag.bench" || true
  tail -n 40 "$OUT/$tag.err" > "$OUT/$tag.tail"; rm -f "$OUT/$tag.err"
}
one cold 2147483659 0
for set in a b; do
  i=0
  while [ $i -lt "$N" ]; do
    one "$set$i" $((2147483659 + 7919 * (i + 1))) 0
    i=$((i + 1))
  done
done
i=0
while [ $i -lt "$T" ]; do
  one "trace$i" $((2147483659 + 7919 * (i + 1))) 1 --keep-trace "$OUT/trace$i"
  i=$((i + 1))
done
