#!/bin/bash
# How a cell's bounds were measured (PERF.md, section 2): two sets of six
# runs with the same seeds, each run of a set with another seed, all in one
# call on the chip, then one traced run.
#   chiprun --timeout 3000 -- bash benchmarks/tools/sets.sh <cell> <seconds> <seed1> ... <seed6>
# Each run's output goes to chiprun_out/sets/; benchmarks/tools/spread.py reads it.
cell=$1; secs=$2; shift 2
mkdir -p chiprun_out/sets
for set in 1 2; do
  for s in "$@"; do
    log=chiprun_out/sets/${cell}_set${set}_$s.log
    python3 benchmarks/run.py --workload "$cell" --seed "$s" --seconds "$secs" --trace 0 > "$log" 2>&1
    echo "set$set seed $s rc=$? $(tail -1 "$log" | cut -c1-700)"
  done
done
log=chiprun_out/sets/${cell}_trace.log
python3 benchmarks/run.py --workload "$cell" --seed $(( $1 + 7 )) --seconds "$secs" --trace 1 > "$log" 2>&1
echo "trace rc=$? $(tail -1 "$log" | cut -c1-3000)"
