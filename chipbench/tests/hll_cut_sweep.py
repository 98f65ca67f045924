#!/usr/bin/env python3
"""By hand, on the CPU (counts, not times): what `sketch.hll_worst_rel_err`
can read on a cut last second.

    python3 chipbench/tests/hll_cut_sweep.py [--workload l4_1m_sketch.saturate] [--seconds 81]

A run's last event-second is cut wherever the clock stops, at a multiple
of a frame's records, and the sketch check takes its worst relative error
over every window sent, that one too. The flows of a second follow the
configuration's `population.seed`, not `--seed`, so every run that reaches
second k and cuts it at c records reads the same number there. This
sweeps every second 0 ... `--seconds` - 1 and every cut: the registers are
the check's own reference (`checks/sketch_blocks.py`: client fingerprint,
register, rank), the exact count is the distinct client addresses among
the records up to the cut, and the estimate is the program's
(`ops/hll.hll_estimate_np`, what `WindowSketchBlock.distinct()` calls).
Beside it, for the record, the classic estimator the program had before
PR 36 (raw HyperLogLog, linear counting up to 2.5 m). Prints, for each, the worst
cut and how many cuts read over the check's limit (3 sigma: a sound
estimator is over it at about one cut in 400 by chance alone), and exits 1
if any of the program's does.
"""

import argparse
import json
import math
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
CHIPBENCH = os.path.dirname(HERE)
for p in (os.path.dirname(CHIPBENCH), CHIPBENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

import gen  # noqa: E402
import run as chipbench_run  # noqa: E402
import sut  # noqa: E402

from deepflow_tpu.ops.hll import hll_estimate_np  # noqa: E402


def classic_estimate(state: np.ndarray) -> np.ndarray:
    m = state.shape[1]
    alpha = 0.7213 / (1.0 + 1.079 / m)
    raw = alpha * m * m / np.sum(np.exp2(-state.astype(np.float64)), axis=1)
    zeros = np.sum(state == 0, axis=1).astype(np.float64)
    with np.errstate(divide="ignore"):
        linear = m * np.log(m / np.maximum(zeros, 1.0))
    return np.where((raw <= 2.5 * m) & (zeros > 0), linear, raw)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="l4_1m_sketch.saturate")
    ap.add_argument("--seconds", type=int, default=81)
    a = ap.parse_args()
    spec = chipbench_run.load_cell(a.workload)
    check = sut.load_named("check", "sketch_blocks", chipbench_run.CHECK_DIRS)
    schema = gen.load_schema()
    schedule = gen.Schedule(spec["traffic"], schema["wire"]["rows_per_frame"])
    source = gen.FlowSource(schema, spec["config"]["population"], 1, schedule.key_draw)
    s = spec["config"]["pipeline"]["sketch"]
    m = 1 << int(s["hll_precision"])
    limit = float(s["distinct_worst_sigmas"]) * 1.04 / math.sqrt(m)
    frame = schema["wire"]["rows_per_frame"]
    f = schema["flow_record_tag_fields"].index
    worst = {"program": (0.0, None), "classic": (0.0, None)}
    over = {"program": 0, "classic": 0}
    whole = {"program": 0.0, "classic": 0.0}  # the worst of the uncut seconds
    cuts = 0
    for k in range(a.seconds):
        tags, _meters = source.second(k, schedule.records_in_second(k))
        ip0 = [tags[f(f"ip0_w{w}")] for w in range(4)]
        reg = (check.fingerprint(ip0, check.SEED_LO) & np.uint32(m - 1)).astype(np.int64)
        rho = check.leading_zeros(check.fingerprint(ip0, check.SEED_HI)) + 1
        # a record is its client's first where no earlier record has its address
        rows = np.ascontiguousarray(np.stack(ip0).T).view(
            np.dtype((np.void, 16))).ravel()
        _, first = np.unique(rows, return_index=True)
        is_first = np.zeros(rows.size, np.int64)
        is_first[first] = 1
        exact = np.cumsum(is_first)
        regs = np.zeros((1, m), np.int64)
        for c in range(frame, rows.size + frame, frame):
            c = min(c, rows.size)
            lo = c - frame if c % frame == 0 else c - c % frame
            np.maximum.at(regs[0], reg[lo:c], rho[lo:c])
            n = int(exact[c - 1])
            cuts += 1
            for name, est in (("program", hll_estimate_np), ("classic", classic_estimate)):
                err = abs(float(est(regs)[0]) - n) / n
                over[name] += err > limit
                if c == rows.size:
                    whole[name] = max(whole[name], err)
                if err > worst[name][0]:
                    worst[name] = (err, {"second": k, "cut_records": c, "clients": n})
    print(json.dumps({
        "workload": a.workload, "seconds": a.seconds, "cuts": cuts, "limit": limit,
        **{f"{name}_worst_rel_err": w[0] for name, w in worst.items()},
        **{f"{name}_cuts_over_limit": n for name, n in over.items()},
        **{f"{name}_worst_whole_second": e for name, e in whole.items()},
        **{f"{name}_worst_at": w[1] for name, w in worst.items()}}))
    return 0 if worst["program"][0] <= limit else 1


if __name__ == "__main__":
    sys.exit(main())
