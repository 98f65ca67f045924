#!/usr/bin/env python
"""Windowed-path microbench (ISSUE 2 acceptance): steady L4Pipeline
ingest with one window close per batch — the end-to-end windowed rate
the product ships through (append + bookkeeping + flush + DocBatch
emission), NOT the raw append kernel rate.

Usage: python bench/winbench_probe.py [repo_root]   (default: parent)
Prints one JSON line {"rec_s", "docs", "batch", "iters"}.
"""

import json
import os
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"

root = sys.argv[1] if len(sys.argv) > 1 else os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))
)
sys.path.insert(0, root)

import numpy as np  # noqa: E402

from deepflow_tpu.aggregator.pipeline import L4Pipeline, PipelineConfig  # noqa: E402
from deepflow_tpu.aggregator.window import WindowConfig  # noqa: E402
from deepflow_tpu.ingest.replay import SyntheticFlowGen  # noqa: E402


def main():
    batch = int(os.environ.get("WINBENCH_BATCH", 1024))
    iters = int(os.environ.get("WINBENCH_ITERS", 60))
    wcfg = {"capacity": 1 << 14}
    if os.environ.get("WINBENCH_ASYNC") == "1":  # double-buffered drain
        wcfg["async_drain"] = True
    try:
        window = WindowConfig(**wcfg)
    except TypeError:  # pre-r7 WindowConfig has no async_drain
        window = WindowConfig(capacity=1 << 14)
    pipe = L4Pipeline(
        PipelineConfig(window=window, batch_size=batch)
    )
    gen = SyntheticFlowGen(num_tuples=2000, seed=0)
    t0 = 1_700_000_000
    # warm every compile path: first batch, steady, advance+flush
    for t in (t0, t0 + 1, t0 + 4, t0 + 5):
        pipe.ingest(gen.flow_batch(batch, t))
    # one window closes per timed batch (interval 1, delay 2)
    batches = [gen.flow_batch(batch, t0 + 10 + i) for i in range(iters)]
    start = time.perf_counter()
    docs = 0
    for fb in batches:
        docs += sum(db.size for db in pipe.ingest(fb))
    docs += sum(db.size for db in pipe.drain())
    elapsed = time.perf_counter() - start
    rec = {
        "rec_s": round(batch * iters / elapsed, 1),
        "docs": docs,
        "batch": batch,
        "iters": iters,
    }
    try:  # stage attribution (ISSUE 3): counter block + span summary
        rec["telemetry"] = pipe.telemetry()
    except Exception as e:  # pre-telemetry pipeline — record why, not crash
        rec["telemetry"] = None
        rec["telemetry_error"] = repr(e)
    from deepflow_tpu.utils.provenance import device_identity

    rec["device"] = device_identity()  # names the platform this ran on
    print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
