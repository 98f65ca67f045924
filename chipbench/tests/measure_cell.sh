#!/bin/bash
# By hand, on the chip: everything a benchmark PR reads of one cell, in one
# call: traced and untraced pairs of parent and change, and the change's two
# sets of six runs with the same seeds.
#   chiprun --chips 1 --timeout 3400 -- bash chipbench/tests/measure_cell.sh <parent dir> <change dir> <cell> <seconds> <6 set seeds> <3 traced seeds>
# The change's runs are made from <change dir> (git archive $(git
# write-tree) unpacked there), which proves that the committed files are
# enough; its chiprun_out is copied to the repo's at the end.
parent=$1; change=$2; cell=$3; seconds=$4; shift 4
s=("$@"); root=$PWD
mkdir -p chiprun_out $change/chiprun_out
# traced pair 1: each side's first run, which compiles
bash chipbench/tests/pairs.sh $parent $change $cell $seconds 1 ${s[6]}
# untraced pairs on the sets' first two seeds
bash chipbench/tests/pairs.sh $parent $change $cell $seconds 0 ${s[0]} ${s[1]}
# the two sets (seeds.sh truncates its files: the pairs' runs are not in them)
(cd $change && bash chipbench/tests/sets.sh $cell $seconds ${s[0]} ${s[1]} ${s[2]} ${s[3]} ${s[4]} ${s[5]}) | cut -c1-1200
# traced pair 2, and a third traced seed on the change alone
bash chipbench/tests/pairs.sh $parent $change $cell $seconds 1 ${s[7]}
(cd $change && bash chipbench/tests/seeds.sh $cell $seconds 1 traced ${s[8]}) | cut -c1-1500
cp $change/chiprun_out/$cell.* chiprun_out/
