#!/usr/bin/env python3
"""The four-chip cell's controls at the cell's own size, by hand on the chips:

    chiprun --chips 4 -- python3 chipbench/tests/control_x4.py --control device_rows --seed <n>
    ... --control device_block | stash_half

Each is one run of `l4_4m_x4_sketch.saturate` through `run_cell`, as
run.py makes it, with one thing wrong, and must come out NOT correct by
the numbers named here (exit 0 then, 1 if the run is correct or fails by
other numbers only):

  device_rows   the last device's partial rows are left out where the
         reader merges a window's rows by key: the merged documents lack
         what that device folded, and the base check's sum over a window's
         edge documents (`edge_packet_tx_gap`) is not what was sent.
  device_block  the last device's closed sketch blocks are left out of the
         host's merge: the merged block lacks that device's updates
         (`sketch.rows_missing`, `sketch.hll_registers_differ`).
  stash_half    `stash_rows` one power of two under the configuration's:
         the run below its sizing rule; the stashes shed segments
         (`pipeline.stash_evictions`).

`--control none` is the cell as it is. `--chips 1` is for finding faults
at a quarter of the price: a mesh of one device, a quarter of the flows,
records and connections, so that the device's programs, stash and plane
are the cell's (not a measurement: the collective has nobody to talk to). `--size tiny` is the CPU rehearsal
of all of this on four forced host devices, at sizes the CPU folds in
seconds (`stash_half` cannot be shown there):

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python3 chipbench/tests/control_x4.py --size tiny --control none --seed 7 --seconds 6
"""

import argparse
import copy
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
for p in (os.path.dirname(HERE), HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

import run as chipbench_run  # noqa: E402

CELL = "l4_4m_x4_sketch.saturate"
MUST_FAIL = {
    "none": set(),
    "device_rows": {"edge_packet_tx_gap"},
    "device_block": {"sketch.rows_missing", "sketch.hll_registers_differ"},
    "stash_half": {"pipeline.stash_evictions"},
}


def leave_out_the_last_devices_rows(served) -> None:
    """Where the builder merges a window's partial rows by key."""
    import dataclasses

    merged_later = type(served).documents.__globals__["MergedLater"]
    merge = merged_later._merge

    def without_last_device(self):
        keep = self.partial.tags.shape[0] - self.counts[-1]
        self.partial = dataclasses.replace(
            self.partial, tags=self.partial.tags[:keep],
            meters=self.partial.meters[:keep])
        self.counts = list(self.counts[:-1]) + [0]
        return merge(self)

    merged_later._merge = without_last_device


def leave_out_the_last_devices_blocks(served) -> None:
    """Where the manager unpacks each device's drained blocks, device by
    device, ahead of the merge."""
    from deepflow_tpu.parallel import sharded

    unpack, d, calls = sharded.unpack_drained, served.swm.pipe.n_devices, [0]

    def unpack_all_but_the_last(rows, wins, cfg):
        calls[0] += 1
        return [] if calls[0] % d == 0 else unpack(rows, wins, cfg)

    sharded.unpack_drained = unpack_all_but_the_last


def tiny_spec(workdir: str) -> dict:
    import tiny

    base = chipbench_run.load_cell(CELL)
    config = {**tiny.CONFIG, "chips": 4, "built_by": base["config"]["built_by"],
              "checks": base["config"]["checks"],
              "pipeline": {**tiny.CONFIG["pipeline"], "accum_batches": 8, "sketch": {
                  **base["config"]["pipeline"]["sketch"], "num_groups": 16,
                  "cms_width": 1024, "topk_cols": 64}}}
    traffic = {**tiny.SATURATE, "clients": 4, "records_per_event_second": 2400}
    paths = {}
    for name, body in (("config", config), ("traffic", traffic)):
        paths[name] = os.path.join(workdir, f"{name}.json")
        with open(paths[name], "w") as f:
            json.dump(body, f)
    return {**base, "config": config, "traffic": traffic,
            "config_path": paths["config"], "traffic_path": paths["traffic"]}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--control", choices=sorted(MUST_FAIL), required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--size", choices=("cell", "tiny"), default="cell")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=4)
    a = ap.parse_args()
    workdir = os.path.join(chipbench_run.ROOT, ".chipbench", f"control_x4_{a.control}")
    os.makedirs(workdir, exist_ok=True)
    if a.size == "tiny":
        import jax

        spec = tiny_spec(workdir)
        dev = jax.devices()[0]
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": len(jax.devices())}
    else:
        from deepflow_tpu.utils.compile_cache import enable_compile_cache

        spec = copy.deepcopy(chipbench_run.load_cell(CELL))
        if a.chips == 1:
            spec["config"]["chips"] = spec["cell"]["chips"] = 1
            spec["config"]["population"]["tuples"] //= 4
            spec["config"]["pipeline"]["batch_unique_cap"] //= 4
            spec["config"]["pipeline"]["buckets"] = [
                b // 4 for b in spec["config"]["pipeline"]["buckets"]]
            spec["traffic"]["clients"] = 1
            spec["traffic"]["records_per_event_second"] //= 4
            for name in ("config", "traffic"):  # the generator reads files
                spec[f"{name}_path"] = os.path.join(workdir, f"{name}.json")
                with open(spec[f"{name}_path"], "w") as f:
                    json.dump(spec[name], f)
        enable_compile_cache()
        device = chipbench_run.find_chips(int(spec["cell"]["chips"]))
    if a.control == "stash_half":
        spec["config"]["pipeline"]["stash_rows"] //= 2
    on_built = {"device_rows": leave_out_the_last_devices_rows,
                "device_block": leave_out_the_last_devices_blocks}.get(a.control)
    out = chipbench_run.run_cell(spec, a.seed, a.seconds, bool(a.trace),
                                 workdir=workdir, device=device, on_built=on_built)
    over = {k for k, c in out["checks"].items()
            if c["limit"] is not None and c["value"] > c["limit"]}
    ok = (out["correct"] if a.control == "none"
          else not out["correct"] and MUST_FAIL[a.control] <= over)
    print(json.dumps({
        "control": a.control, "size": a.size, "seed": a.seed,
        "control_correct": out["correct"], "over_limit": sorted(over),
        "must_fail": sorted(MUST_FAIL[a.control]), "as_expected": ok,
        "metrics": out["metrics"], "device": out["device"],
        "checks": {k: c for k, c in out["checks"].items()
                   if k in over or k.startswith(("pod.", "sketch.", "pipeline.s"))}}),
        flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
