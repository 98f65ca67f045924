"""A tiny deployment and traffic for the CPU rehearsal: same files, same
code path as a cell, at sizes the CPU folds in seconds."""

import json
import os

import run as chipbench_run

HERE = os.path.dirname(os.path.abspath(__file__))

CONFIG = {
    "name": "tiny", "chips": 1, "agents": 1, "receiver_queues": 4,
    "queue_frames": 4096,
    "population": {"tuples": 300, "keys": "zipf", "zipf_s": 1.1, "seed": 1},
    "pipeline": {"interval": 1, "delay": 2, "stash_rows": 8192,
                 "buckets": [512, 2048], "batch_unique_cap": 2048,
                 "accum_batches": 2, "sketch": False, "cascade": False},
}
SATURATE = {"name": "tiny_saturate", "loop": "closed",
            "in_flight_event_seconds": 1, "records_per_event_second": 6000,
            "key_draw": "each_second", "prefix_records": 256,
            "warm_up_event_seconds": [0],
            "trace_slice": {"start_s": 0.5, "seconds": 1, "window_closes": 1}}
STEADY = {**SATURATE, "name": "tiny_steady", "key_draw": "same_every_second",
          "warm_up_event_seconds": [0, 1]}


def spec(tmp_path, traffic: dict, population_seed: int = 1,
         cell: str = "l4_10k.saturate") -> dict:
    """A cell's spec with the tiny files in place of the cell's own; the
    metric lists are those of `cell` in BENCHMARK.json. Flows of
    a `population_seed` that this process has not run yet close windows
    with document counts it has not compiled for yet."""
    base = chipbench_run.load_cell(cell)
    config = {**CONFIG, "population": {**CONFIG["population"], "seed": population_seed}}
    cfg_path, tr_path = tmp_path / "config.json", tmp_path / "traffic.json"
    cfg_path.write_text(json.dumps(config))
    tr_path.write_text(json.dumps(traffic))
    return {**base, "config": config, "traffic": traffic,
            "config_path": str(cfg_path), "traffic_path": str(tr_path)}
