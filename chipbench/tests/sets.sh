#!/bin/bash
# By hand, on the chip: the two sets a bound is set from, for one cell.
#   chiprun --chips 1 --timeout 3000 -- bash chipbench/tests/sets.sh <cell> <seconds> <seed> ...
# Two calls of seeds.sh with the same seeds (labels set1, set2), then the
# quartile spreads of what they wrote.
cell=$1; seconds=$2; shift 2
bash chipbench/tests/seeds.sh $cell $seconds 0 set1 "$@"
bash chipbench/tests/seeds.sh $cell $seconds 0 set2 "$@"
python3 chipbench/tests/spread.py chiprun_out/$cell.set1.jsonl chiprun_out/$cell.set2.jsonl
