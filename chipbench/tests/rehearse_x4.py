#!/usr/bin/env python3
"""By hand: rehearsal deployment (c), the sharded served path, through
run_cell. Not a cell: nothing in BENCHMARK.json names it.

    chiprun --chips 4 -- python3 chipbench/tests/rehearse_x4.py --size l4_1m --seed <n> --seconds 51

runs it at l4_1s_1m's widths (that configuration's file, built by
deployments/rehearsal_sharded.py on four chips, the 60 s tier and four
clients on) and prints run_cell's progress lines and result; what the
next issue sizes `l4_1m_x4.saturate` from (PERF.md section 7 row 1).

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python3 chipbench/tests/rehearse_x4.py --size tiny --seed <n> --seconds 6

is the CPU rehearsal test_seams.py runs (a process of its own, because the
forced device count is read when JAX starts); `--corrupt` alters the tier
rows it hands to the named check, which must then read not correct."""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CHIPBENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(CHIPBENCH)
for p in (ROOT, CHIPBENCH, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

import run as chipbench_run  # noqa: E402
import sut  # noqa: E402

SHARDED = {"chips": 4, "built_by": "rehearsal_sharded",
           "checks": ["tier_rows", "hll_distinct"]}


def spec_for(size: str, workdir: str, stash_rows=None) -> dict:
    """A cell's spec (the metric lists are l4_1m.saturate's) with the
    sharded rehearsal's configuration and a four-client saturate."""
    base = chipbench_run.load_cell("l4_1m.saturate")
    if size == "tiny":
        import tiny

        config = {**tiny.CONFIG, **SHARDED, "pipeline": {
            **tiny.CONFIG["pipeline"], "cascade": {"intervals": [60], "rows": 8192},
            "sketch": {"num_services": 16, "hll_precision": 14,
                       "distinct_rel_err": 0.05}}}
        traffic = {**tiny.SATURATE, "clients": 4, "records_per_event_second": 400}
    else:
        config = {**base["config"], **SHARDED, "pipeline": {
            **base["config"]["pipeline"],
            "cascade": {"intervals": [60], "rows": 1 << 22},
            "sketch": {"num_services": 16, "hll_precision": 14,
                       "distinct_rel_err": 0.01}}}
        traffic = {**base["traffic"], "clients": 4}
    if stash_rows:  # rows of each device's stash and of its tier stash
        config["pipeline"]["stash_rows"] = stash_rows
        config["pipeline"]["cascade"]["rows"] = stash_rows
    os.makedirs(workdir, exist_ok=True)
    paths = {}
    for name, body in (("config", config), ("traffic", traffic)):
        paths[name] = os.path.join(workdir, f"{name}.json")
        with open(paths[name], "w") as f:
            json.dump(body, f)
    return {**base, "config": config, "traffic": traffic,
            "config_path": paths["config"], "traffic_path": paths["traffic"]}


def corrupt_the_tier_rows(served) -> None:
    """One SUM lane of one row of every tier window is off by one part in
    10^4 where the deployment hands its side outputs over."""
    import numpy as np

    side_outputs = served.side_outputs

    def broken():
        out = side_outputs()
        for _interval, db in out["tier_docbatches"]:
            meters = np.array(db.meters)
            meters[0, 2] = meters[0, 2] * np.float32(1.0001) + 1
            db.meters = meters
        return out

    served.side_outputs = broken


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--size", choices=("tiny", "l4_1m"), required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--corrupt", action="store_true")
    ap.add_argument("--stash-rows", type=int, default=None)
    a = ap.parse_args()
    for d, dirs in (("deployments", sut.DEPLOYMENT_DIRS), ("checks", chipbench_run.CHECK_DIRS)):
        dirs.append(os.path.join(HERE, d))
    workdir = os.path.join(ROOT, ".chipbench", f"rehearse_x4.{a.size}")
    spec = spec_for(a.size, workdir, a.stash_rows)
    if a.size == "tiny":
        import jax

        d = jax.devices()[0]
        device = {"platform": d.platform, "kind": d.device_kind,
                  "count": len(jax.devices())}
    else:
        from deepflow_tpu.utils.compile_cache import enable_compile_cache

        chipbench_run.say(stage="start", compile_cache=enable_compile_cache())
        device = chipbench_run.find_chips(4)
    result = chipbench_run.run_cell(
        spec, a.seed, a.seconds, bool(a.trace), workdir=workdir, device=device,
        on_built=corrupt_the_tier_rows if a.corrupt else None)
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']} (limit {c['limit']})", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
