#!/bin/bash
# By hand, on the chip: one cell on several seeds, traced or not.
#   chiprun --chips 1 -- bash chipbench/tests/seeds.sh <cell> <seconds> <trace 0|1> <label> <seed> ...
# Result lines land in chiprun_out/<cell>.<label>.jsonl, for spread.py, and
# each run's progress records in chiprun_out/<cell>.<label>.progress.jsonl.
# The two sets a bound is set from are two calls with the same seeds (labels
# set1, set2) in one chiprun command: sets.sh.
cell=$1; seconds=$2; trace=$3; label=$4; shift 4
mkdir -p chiprun_out
: > chiprun_out/$cell.$label.jsonl
: > chiprun_out/$cell.$label.progress.jsonl
for seed in "$@"; do
  python3 chipbench/run.py --workload $cell --seed $seed --seconds $seconds --trace $trace \
    > chiprun_out/$cell.last.out 2> chiprun_out/$cell.last.err
  echo "$label seed $seed rc=$? $(grep -E '^correct' chiprun_out/$cell.last.err)"
  grep -E '"stage"' chiprun_out/$cell.last.out >> chiprun_out/$cell.$label.progress.jsonl
  grep -E '"set up"|"window"|"trace"|"checked"' chiprun_out/$cell.last.out | cut -c1-1500
  tail -n 1 chiprun_out/$cell.last.out >> chiprun_out/$cell.$label.jsonl
  tail -n 1 chiprun_out/$cell.last.out | cut -c1-900
done
