#!/bin/bash
# By hand, on the chip: seeds.sh with the host's peak resident memory read
# beside each run (the four-chip cell keeps every closed window's partial
# rows until `check`; VmHWM of the runner, polled once a second).
#   chiprun --chips 4 -- bash chipbench/tests/runs_rss.sh <cell> <seconds> <trace 0|1> <label> <seed> ...
# Result lines land in chiprun_out/<cell>.<label>.jsonl, progress records in
# chiprun_out/<cell>.<label>.progress.jsonl, as seeds.sh leaves them.
cell=$1; seconds=$2; trace=$3; label=$4; shift 4
mkdir -p chiprun_out
: > chiprun_out/$cell.$label.jsonl
: > chiprun_out/$cell.$label.progress.jsonl
for seed in "$@"; do
  start=$(date +%s)
  python3 chipbench/run.py --workload $cell --seed $seed --seconds $seconds --trace $trace \
    > chiprun_out/$cell.last.out 2> chiprun_out/$cell.last.err &
  pid=$!; hwm=0
  while kill -0 $pid 2>/dev/null; do
    # the shell's own read: PR 36's call with awk read nothing on that machine
    while read -r k v _; do [ "$k" = "VmHWM:" ] && hwm=$v; done < /proc/$pid/status 2>/dev/null
    sleep 1
  done
  wait $pid; rc=$?
  echo "$label seed $seed rc=$rc wall_s=$(( $(date +%s) - start )) host_peak_rss_kb=$hwm $(grep -E '^correct' chiprun_out/$cell.last.err)"
  grep -E '"stage"' chiprun_out/$cell.last.out >> chiprun_out/$cell.$label.progress.jsonl
  grep -E '"set up"|"window"|"checked"' chiprun_out/$cell.last.out | cut -c1-1200
  tail -n 1 chiprun_out/$cell.last.out >> chiprun_out/$cell.$label.jsonl
  tail -n 1 chiprun_out/$cell.last.out | cut -c1-600
  [ $rc -ne 0 ] && tail -n 12 chiprun_out/$cell.last.err | cut -c1-600
done
