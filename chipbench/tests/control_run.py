#!/usr/bin/env python3
"""The low-precision control at a cell's own size, by hand:

    python3 chipbench/tests/control_run.py --workload <cell> --seeds 1,2,3

For each seed it regenerates the cell's event-second 1 (a full window as
the window sends it), computes the documents with the reference held in
bfloat16 - the nearest precision below the float32 the configuration
states - puts them in the program's place, and prints the numbers the
comparison gives beside their limits. Every seed must come out not
correct. It never touches JAX: the control is the NumPy reference itself.
"""

import argparse
import json
import os
import sys

import ml_dtypes
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import gen  # noqa: E402
import reference  # noqa: E402
import run as chipbench_run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    a = ap.parse_args()
    spec = chipbench_run.load_cell(a.workload)
    schema = gen.load_schema()
    schedule = gen.Schedule(spec["traffic"], schema["wire"]["rows_per_frame"])
    ok = True
    for seed in (int(s) for s in a.seeds.split(",")):
        src = gen.FlowSource(schema, spec["config"]["population"], seed,
                             schedule.key_draw)
        tags, meters = src.second(1, schedule.records_in_second(1))
        want = reference.reference_docs(schema, tags, meters)
        ctl = reference.reference_docs(schema, tags, meters,
                                       acc_dtype=ml_dtypes.bfloat16)
        c = reference.compare_docs(schema, ctl[0], ctl[1].astype(np.float32), *want)
        correct = all(c[k] <= lim for k, lim in reference.LIMITS.items())
        ok &= not correct
        print(json.dumps({"workload": a.workload, "seed": seed,
                          "records": int(tags.shape[1]), "control_correct": correct,
                          **{k: {"value": c[k], "limit": lim}
                             for k, lim in reference.LIMITS.items()}}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
