#!/usr/bin/env python3
"""The sketch cell's two controls at the cell's own size, by hand on the chip:

    python3 chipbench/tests/control_sketch.py --control mask --seed <n>
    python3 chipbench/tests/control_sketch.py --control p12 --seed <n>
    python3 chipbench/tests/control_sketch.py --control pending2 --seed <n>

Each is one run of `l4_1m_sketch.saturate` (or `--workload`) through
`run_cell`, as run.py makes it, with one thing wrong, and must come out
NOT correct by the numbers named here (exit 0 then, 1 if the run is
correct or fails by other numbers only):

  mask   one row in a hundred of every batch is masked out of the sketch
         plane, and of nothing else: the exact rollup still takes it. The
         base checks pass; `sketch.rows_missing` and
         `sketch.hll_registers_differ` do not.
  p12    the plane is built at HLL precision 12 and judged by the file's
         limits: registers, counters and bins still equal the reference's
         (built at 12 too), and `sketch.hll_mean_rel_err` is over the
         source's 1%.
  pending2  the plane's pending buffer holds two closed blocks, one fewer
         than the windows the last drain closes at once (ring - 1 = 3): the
         third block is dropped on the device (its rows are counted shed in
         a lane the host would read with the next batch, and there is none
         after the last drain), and `sketch.windows_without_block` is not
         0. This is the run below the configuration's `pending`, for its
         sizing rule.
"""

import argparse
import copy
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run as chipbench_run  # noqa: E402

MUST_FAIL = {
    "mask": {"sketch.rows_missing", "sketch.hll_registers_differ"},
    "p12": {"sketch.hll_mean_rel_err"},
    "pending2": {"sketch.windows_without_block"},
}


def mask_one_row_in_a_hundred() -> None:
    """Wrap the plane's step where the fused step looks it up, before the
    step is built (it is traced on the first batch)."""
    import jax.numpy as jnp

    from deepflow_tpu.aggregator import pipeline

    plane_step = pipeline.sketch_plane_step

    def masked(sk, spec, *, valid, **kw):
        keep = jnp.arange(valid.shape[0]) % 100 != 0
        return plane_step(sk, spec, valid=valid & keep, **kw)

    pipeline.sketch_plane_step = masked


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="l4_1m_sketch.saturate")
    ap.add_argument("--control", choices=sorted(MUST_FAIL), required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    a = ap.parse_args()
    spec = copy.deepcopy(chipbench_run.load_cell(a.workload))
    if a.control == "p12":
        spec["config"]["pipeline"]["sketch"]["hll_precision"] = 12
    elif a.control == "pending2":
        spec["config"]["pipeline"]["sketch"]["pending"] = 2
    else:
        mask_one_row_in_a_hundred()
    workdir = os.path.join(chipbench_run.ROOT, ".chipbench", f"control_{a.control}")
    os.makedirs(workdir, exist_ok=True)

    from deepflow_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    device = chipbench_run.find_chips(int(spec["cell"]["chips"]))
    out = chipbench_run.run_cell(spec, a.seed, a.seconds, False,
                                 workdir=workdir, device=device)
    over = {k for k, c in out["checks"].items()
            if c["limit"] is not None and c["value"] > c["limit"]}
    ok = not out["correct"] and MUST_FAIL[a.control] <= over
    print(json.dumps({
        "control": a.control, "workload": a.workload, "seed": a.seed,
        "control_correct": out["correct"], "over_limit": sorted(over),
        "must_fail": sorted(MUST_FAIL[a.control]), "as_expected": ok,
        "metrics": out["metrics"], "device": out["device"],
        "checks": {k: c for k, c in out["checks"].items()
                   if k.startswith(("sketch.", "pipeline.sketch"))}}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
