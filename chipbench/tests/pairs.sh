#!/bin/bash
# By hand, on the chip: one cell on parent and change, a seed a pair, in the
# order parent, change, change, parent, ...
#   chiprun --chips 1 -- bash chipbench/tests/pairs.sh <parent dir> <change dir> <cell> <seconds> <trace 0|1> <seed> ...
# Each side is a checkout of its own under the repo (git archive into a
# directory .gitignore lists), so each has its own compile cache: a side's
# first run compiles. Result lines land in
# chiprun_out/<cell>.pairs<trace>.jsonl as {"side", "seed", "result"}.
parent=$1; change=$2; cell=$3; seconds=$4; trace=$5; shift 5
out=$PWD/chiprun_out; mkdir -p $out
n=0
for seed in "$@"; do
  if [ $((n % 2)) -eq 0 ]; then order="parent change"; else order="change parent"; fi
  for side in $order; do
    dir=$parent; [ $side = change ] && dir=$change
    (cd $dir && python3 chipbench/run.py --workload $cell --seed $seed --seconds $seconds --trace $trace \
      > $out/$cell.$side.last.out 2> $out/$cell.$side.last.err)
    echo "$side seed $seed trace $trace rc=$? $(grep -E '^correct' $out/$cell.$side.last.err)"
    grep -E '"set up"|"window"|"trace"|"counters"' $out/$cell.$side.last.out | cut -c1-1200 \
      | sed "s/^/{\"side\": \"$side\", \"seed\": $seed, \"progress\": /; s/$/}/" >> $out/$cell.pairs$trace.progress.jsonl
    echo "{\"side\": \"$side\", \"seed\": $seed, \"result\": $(tail -n 1 $out/$cell.$side.last.out)}" >> $out/$cell.pairs$trace.jsonl
    tail -n 1 $out/$cell.$side.last.out | cut -c1-700
  done
  n=$((n + 1))
done
