#!/bin/bash
# By hand, on four chips: the sharded rehearsal (rehearse_x4.py, not a cell)
# twice in one call, so that the second run finds every program in the
# compile cache: cold and warm set-up, compiles in the window, the fullest
# device's peak, and how each run ended (PERF.md section 7 row 1).
#   chiprun --chips 4 --timeout 1320 -- bash chipbench/tests/x4_cold_warm.sh [--stash-rows N]
mkdir -p chiprun_out
for label in cold warm; do
  seed=2147500201; [ $label = warm ] && seed=2147500202
  python3 chipbench/tests/rehearse_x4.py --size l4_1m --seed $seed --seconds 51 "$@" > chiprun_out/x4.$label.out 2> chiprun_out/x4.$label.err
  echo "$label rc=$?"
  grep -E '"stage"' chiprun_out/x4.$label.out | cut -c1-1500
  tail -n 1 chiprun_out/x4.$label.out | cut -c1-1200
  tail -n 12 chiprun_out/x4.$label.err | cut -c1-400
done
