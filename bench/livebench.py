#!/usr/bin/env python
"""Live read plane A/B (ISSUE 10 acceptance): snapshot overhead on the
§14 feeder-shaped workload + cached vs uncached repeated-query latency.

Two measurements, one JSON line:

  * **ingest**: the §14 feeder workload (multi-queue fan-in → bucketed
    coalescing → fused step, K-batch counter ring) run twice on
    identical streams — without live reads, and with
    `snapshot_interval_pumps` snapshots scheduled between pumps — so
    `overhead_pct` is the end-to-end cost of keeping a live dashboard's
    snapshot warm. The per-ingest fetch budget is asserted unchanged
    (the CI gate owns the hard guarantee; the bench records the rates).
  * **query**: the repeated-dashboard path — one PromQL `query_range`
    over the open-window live overlay evaluated Q times uncached vs
    through the result cache, plus the cache counters. The cached reps
    hit until a new snapshot generation lands, which is exactly the
    production cadence (`min_snapshot_interval`).

Usage: python bench/livebench.py [repo_root]
Knobs: LIVEBENCH_ITERS, LIVEBENCH_SNAP_EVERY, LIVEBENCH_QUERY_REPS,
LIVEBENCH_BUCKETS. CPU-container numbers; on-chip columns pending per
the measurement-debt item (PERF.md §19).
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

T0 = 1_700_000_000


def _run_ingest(iters, buckets, snap_every):
    pipe = L4Pipeline(PipelineConfig(
        window=WindowConfig(capacity=1 << 14, stats_ring=4,
                            min_snapshot_interval=0.0),
        batch_size=buckets[-1], bucket_sizes=buckets,
    ))
    queues = [PyOverwriteQueue(1 << 12) for _ in range(4)]
    feeder = FeederRuntime(
        queues, PipelineFeedSink(pipe),
        FeederConfig(frames_per_queue=16,
                     snapshot_interval_pumps=snap_every),
        name=f"livebench{snap_every}",
    )
    gen = SyntheticFlowGen(num_tuples=2000, seed=0)
    # warmup: compile every bucket + the snapshot read
    for i in range(3):
        fb = gen.flow_batch(buckets[-1], T0 + i)
        for j, fr in enumerate(encode_flowbatch_frames(fb, max_rows_per_frame=256)):
            queues[j % 4].put(fr)
        feeder.pump()
    if snap_every:
        pipe.snapshot_open(force=True)
    rec = 0
    t_start = time.perf_counter()
    for i in range(iters):
        fb = gen.flow_batch(buckets[-1], T0 + 4 + i // 4)
        rec += fb.size
        for j, fr in enumerate(encode_flowbatch_frames(fb, max_rows_per_frame=256)):
            queues[j % 4].put(fr)
        feeder.pump()
    feeder.flush()
    elapsed = time.perf_counter() - t_start
    c = pipe.get_counters()
    fc = feeder.get_counters()
    batches = max(1, fc["batches_out"])
    return {
        "rec_s": round(rec / elapsed, 1),
        "elapsed_s": round(elapsed, 3),
        "records": rec,
        "fetches_per_batch": round(c["host_fetches"] / batches, 3),
        "snapshot_reads": c["snapshot_reads"],
        "snapshot_bytes": c["snapshot_bytes"],
        "snapshots_taken": fc["snapshots_taken"],
        "jit_retraces": c["jit_retraces"],
    }


def _run_query(reps):
    import numpy as np

    from deepflow_tpu.aggregator.window import WindowManager
    from deepflow_tpu.datamodel.schema import FLOW_METER, TAG_SCHEMA
    from deepflow_tpu.integration.dfstats import (
        DEEPFLOW_SYSTEM_DB,
        DEEPFLOW_SYSTEM_TABLE,
        LIVE_METRIC_FLOW_BYTES,
        PipelineLiveSource,
        ensure_system_table,
    )
    from deepflow_tpu.querier.live import LiveRegistry, QueryResultCache
    from deepflow_tpu.querier.promql import query_range
    from deepflow_tpu.storage.store import ColumnarStore

    store = ColumnarStore()
    ensure_system_table(store)
    reg = LiveRegistry()
    # a generously rate-limited snapshot: the cache serves the reps
    wm = WindowManager(WindowConfig(capacity=1 << 12, min_snapshot_interval=60.0))
    reg.register(DEEPFLOW_SYSTEM_DB, DEEPFLOW_SYSTEM_TABLE, PipelineLiveSource(wm))
    n = 512
    meters = np.zeros((FLOW_METER.num_fields, n), np.float32)
    meters[FLOW_METER.index("byte_tx")] = 64.0
    wm.ingest(
        np.full(n, T0, np.uint32),
        np.arange(n, dtype=np.uint32), np.arange(n, dtype=np.uint32),
        np.zeros((TAG_SCHEMA.num_fields, n), np.uint32), meters,
        np.ones(n, bool),
    )
    kw = dict(db=DEEPFLOW_SYSTEM_DB, table=DEEPFLOW_SYSTEM_TABLE, live=reg)

    def run(cache):
        t0 = time.perf_counter()
        for _ in range(reps):
            out = query_range(store, LIVE_METRIC_FLOW_BYTES, T0, T0 + 1, 1,
                              cache=cache, **kw)
        return (time.perf_counter() - t0) / reps * 1e3, len(out)

    uncached_ms, series = run(False)
    cache = QueryResultCache(max_entries=64)
    cached_ms, _ = run(cache)
    cc = cache.get_counters()
    return {
        "series": series,
        "reps": reps,
        "uncached_ms": round(uncached_ms, 3),
        "cached_ms": round(cached_ms, 3),
        "speedup_cached": round(uncached_ms / max(cached_ms, 1e-6), 1),
        "cache": cc,
    }


def main():
    iters = int(os.environ.get("LIVEBENCH_ITERS", 48))
    snap_every = int(os.environ.get("LIVEBENCH_SNAP_EVERY", 4))
    reps = int(os.environ.get("LIVEBENCH_QUERY_REPS", 50))
    buckets = tuple(
        int(b) for b in os.environ.get("LIVEBENCH_BUCKETS", "256,512,1024").split(",")
    )
    try:
        off = _run_ingest(iters, buckets, 0)
        on = _run_ingest(iters, buckets, snap_every)
        query = _run_query(reps)
        rec = {
            "bench": "livebench",
            "iters": iters,
            "snap_every": snap_every,
            "ingest": {
                "off": off,
                "live": on,
                "overhead_pct": round(
                    (off["rec_s"] / max(on["rec_s"], 1e-9) - 1.0) * 100.0, 2
                ),
            },
            "query": query,
        }
    except Exception as e:  # parseable partial record, never a traceback
        rec = {"bench": "livebench", "partial": True, "error": repr(e)}
    from deepflow_tpu.utils.provenance import device_identity

    rec["device"] = device_identity()  # names the platform this ran on
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
