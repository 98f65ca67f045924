#!/usr/bin/env python
"""Feeder-runtime microbench (ISSUE 4 acceptance): multi-queue fan-in →
shape-bucketed coalescing → the fused windowed step with a K-batch
counter ring — the full wire-to-window path the product ships through
(frame decode + bucket assembly + double-buffered upload + append +
flush), NOT the raw append kernel rate.

Usage: python bench/feeder_probe.py [repo_root]   (default: parent)
Prints one JSON line with rec_s, host-fetch-per-batch and shed/retrace
accounting. Knobs: FEEDER_ITERS, FEEDER_QUEUES, FEEDER_K,
FEEDER_BUCKETS (comma list). CPU-container numbers demonstrate the
host-overhead half only; on-chip columns are pending per the r6+r7
measurement-debt item (PERF.md §14).
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

from deepflow_tpu.aggregator.pipeline import L4Pipeline, PipelineConfig  # noqa: E402
from deepflow_tpu.aggregator.window import WindowConfig  # noqa: E402
from deepflow_tpu.feeder import (  # noqa: E402
    FeederConfig,
    FeederRuntime,
    PipelineFeedSink,
    encode_flowbatch_frames,
)
from deepflow_tpu.ingest.queues import PyOverwriteQueue  # noqa: E402
from deepflow_tpu.ingest.replay import SyntheticFlowGen  # noqa: E402


def main():
    iters = int(os.environ.get("FEEDER_ITERS", 48))
    n_queues = int(os.environ.get("FEEDER_QUEUES", 4))
    K = int(os.environ.get("FEEDER_K", 4))
    buckets = tuple(
        int(b) for b in os.environ.get("FEEDER_BUCKETS", "256,512,1024").split(",")
    )
    t0 = 1_700_000_000

    pipe = L4Pipeline(PipelineConfig(
        window=WindowConfig(capacity=1 << 14, stats_ring=K),
        batch_size=buckets[-1], bucket_sizes=buckets,
    ))
    queues = [PyOverwriteQueue(1 << 12) for _ in range(n_queues)]
    feeder = FeederRuntime(
        queues, PipelineFeedSink(pipe),
        FeederConfig(frames_per_queue=16),
    )
    gen = SyntheticFlowGen(num_tuples=2000, seed=0)

    # pre-encode every step's frames: the probe times fan-in + decode +
    # coalesce + dispatch, not the synthetic generator
    sizes = [buckets[(i % len(buckets))] - (17 * i) % 64 for i in range(iters)]
    steps = []
    for i, n in enumerate(sizes):
        fb = gen.flow_batch(n, t0 + 10 + i // 4)
        steps.append(encode_flowbatch_frames(fb, agent_id=i, max_rows_per_frame=256))

    # warm every bucket's compile path
    for b in buckets:
        for fr in encode_flowbatch_frames(gen.flow_batch(b, t0), max_rows_per_frame=256):
            queues[0].put(fr)
        feeder.pump()

    c0 = pipe.get_counters()
    f0 = feeder.get_counters()
    docs = 0
    start = time.perf_counter()
    for i, frames in enumerate(steps):
        for j, fr in enumerate(frames):
            queues[j % n_queues].put(fr)
        docs += sum(db.size for db in feeder.pump())
    docs += sum(db.size for db in feeder.flush())
    docs += sum(db.size for db in pipe.drain())
    elapsed = time.perf_counter() - start

    c1 = pipe.get_counters()
    f1 = feeder.get_counters()
    records = f1["records_in"] - f0["records_in"]
    batches = f1["batches_out"] - f0["batches_out"]
    fetches = c1["host_fetches"] - c0["host_fetches"]
    rec = {
        "rec_s": round(records / elapsed, 1),
        "records": records,
        "batches": batches,
        "docs": docs,
        "iters": iters,
        "queues": n_queues,
        "stats_ring": K,
        "buckets": list(buckets),
        "host_fetches": fetches,
        "fetches_per_batch": round(fetches / max(batches, 1), 3),
        "window_advances": c1["window_advances"] - c0["window_advances"],
        "jit_retraces": c1["jit_retraces"],
        "jit_compiles": c1["jit_compiles"],
        "shed_records": f1["shed_records"],
        "pad_rows": f1["pad_rows"] - f0["pad_rows"],
    }
    try:  # stage attribution: counter block + span summaries
        rec["telemetry"] = pipe.telemetry()
        rec["feeder_telemetry"] = {
            "counters": f1,
            "spans": feeder.tracer.summary(),
        }
    except Exception as e:  # absence-tolerant (bench contract)
        rec["telemetry"] = None
        rec["telemetry_error"] = repr(e)
    from deepflow_tpu.utils.provenance import device_identity

    rec["device"] = device_identity()  # names the platform this ran on
    print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
