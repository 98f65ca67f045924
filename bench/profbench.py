#!/usr/bin/env python
"""Device profiling plane overhead probe (ISSUE 12 acceptance): the
SAME wire-to-window feeder workload as bench/feeder_probe.py, run with
the profiling plane passive (it is always-on — registration +
span-histogram updates are unavoidable and included in BOTH sides)
versus with an AGGRESSIVE dashboard-rate consumer: every 4th pump (the
§19 livebench snapshot cadence) walks the HBM ledger + the pipeline's
span quantile face AND runs a full collector tick (tpu_hbm_*/span-p99
rows → deepflow_system + ProfileSnapshot publish on a bus). The
A/B isolates what *reading* the always-on plane costs steady-state
ingest; the acceptance bound is <2% with fetch parity (the parity
itself is CI-gated deterministically in
test_perf_gate.py::test_profiling_budget).

Also measured: the profile pull itself — `profile_snapshot()` without
analysis (the hot-path face), the first `analyze=True` pull (pays the
AOT lower+compile per bucket) and the cached repeat — the numbers
`GET /v1/profile/device` serves.

Usage: python bench/profbench.py [repo_root]   (default: parent)
Knobs: PROFBENCH_ITERS, PROFBENCH_BUCKETS (comma list).
Protocol + committed numbers: PERF.md §21, PROFBENCH_r01.json.
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


def run_mode(steps, buckets, profiled: bool):
    from deepflow_tpu.integration.dfstats import system_sink
    from deepflow_tpu.profiling import default_ledger, profile_tick_sink
    from deepflow_tpu.querier.events import QueryEventBus
    from deepflow_tpu.storage.store import ColumnarStore
    from deepflow_tpu.utils.stats import StatsCollector

    pipe = L4Pipeline(PipelineConfig(
        window=WindowConfig(capacity=1 << 14, stats_ring=4),
        batch_size=buckets[-1], bucket_sizes=buckets,
    ))
    queues = [PyOverwriteQueue(1 << 12) for _ in range(4)]
    feeder = FeederRuntime(
        queues, PipelineFeedSink(pipe), FeederConfig(frames_per_queue=16),
    )
    col = bus = None
    if profiled:
        store = ColumnarStore()
        bus = QueryEventBus(name="profbench")
        col = StatsCollector()
        col.register("tpu_hbm", default_ledger)
        col.register("tpu_pipeline_spans", pipe.tracer)
        col.register("tpu_pipeline", pipe)
        col.add_sink(system_sink(store))
        col.add_sink(profile_tick_sink(bus))
    gen = SyntheticFlowGen(num_tuples=2000, seed=0)
    t0 = 1_700_000_000
    for b in buckets:  # warm every bucket's compile path
        for fr in encode_flowbatch_frames(gen.flow_batch(b, t0),
                                          max_rows_per_frame=256):
            queues[0].put(fr)
        feeder.pump()

    f0 = feeder.get_counters()
    start = time.perf_counter()
    for i, frames in enumerate(steps):
        for j, fr in enumerate(frames):
            queues[j % 4].put(fr)
        feeder.pump()
        if profiled and (i + 1) % 4 == 0:
            # the aggressive dashboard cadence (livebench's §19
            # snapshot-every-4-pumps framing): ledger walk + span
            # quantiles + the pipeline profile face + a full dogfood
            # tick (insert + ProfileSnapshot publish) every 4 batches
            default_ledger.get_counters()
            pipe.tracer.get_counters()
            pipe.profile_snapshot()
            col.tick(now=t0 + 10 + i // 4)
    feeder.flush()
    pipe.drain()
    elapsed = time.perf_counter() - start
    f1 = feeder.get_counters()
    records = f1["records_in"] - f0["records_in"]
    out = {
        "rec_s": round(records / elapsed, 1),
        "elapsed_s": round(elapsed, 4),
        "records": records,
        "host_fetches": pipe.get_counters()["host_fetches"],
        "jit_retraces": pipe.get_counters()["jit_retraces"],
    }
    if profiled:
        out["events_published"] = bus.get_counters()["events_published"]
        # the pull-path latencies the REST endpoint serves
        t = time.perf_counter()
        snap = pipe.profile_snapshot()
        out["pull_ms_no_analyze"] = round((time.perf_counter() - t) * 1e3, 3)
        t = time.perf_counter()
        full = pipe.profile_snapshot(analyze=True)
        out["pull_ms_first_analyze"] = round((time.perf_counter() - t) * 1e3, 1)
        t = time.perf_counter()
        pipe.profile_snapshot(analyze=True)
        out["pull_ms_cached_analyze"] = round((time.perf_counter() - t) * 1e3, 3)
        out["hbm_bytes"] = snap["hbm_bytes"]
        out["census"] = full["census"]
        out["span_p99_us"] = {
            k: v for k, v in pipe.tracer.get_counters().items()
            if k.endswith("p99_us")
        }
    return out


def main():
    iters = int(os.environ.get("PROFBENCH_ITERS", 48))
    buckets = tuple(
        int(b) for b in os.environ.get("PROFBENCH_BUCKETS", "256,512,1024").split(",")
    )
    gen = SyntheticFlowGen(num_tuples=2000, seed=0)
    t0 = 1_700_000_000
    sizes = [buckets[(i % len(buckets))] - (17 * i) % 64 for i in range(iters)]
    steps = [
        encode_flowbatch_frames(gen.flow_batch(n, t0 + 10 + i // 4),
                                agent_id=i, max_rows_per_frame=256)
        for i, n in enumerate(sizes)
    ]
    try:
        # throwaway full run (first-pipeline compile/alloc skew), then
        # INTERLEAVED median-of-3 per mode (the §18 cascadebench recipe
        # — this container's CPU is ±30% noisy, and a sequential A/B
        # bakes warmup drift into the sign of a small delta)
        run_mode(steps, buckets, False)
        runs = {False: [], True: []}
        for _ in range(3):
            for mode in (False, True):
                runs[mode].append(run_mode(steps, buckets, mode))

        def median(mode):
            return sorted(runs[mode], key=lambda r: r["rec_s"])[1]

        passive = median(False)
        profiled = median(True)
        rec = {
            "passive": passive,
            "profiled": {k: v for k, v in profiled.items()
                         if k not in ("census", "hbm_bytes", "span_p99_us")},
            "overhead_pct": round(
                (passive["rec_s"] / max(profiled["rec_s"], 1e-9) - 1.0) * 100, 2
            ),
            "fetch_parity": profiled["host_fetches"] == passive["host_fetches"],
            "pull": {
                k: profiled[k] for k in (
                    "pull_ms_no_analyze", "pull_ms_first_analyze",
                    "pull_ms_cached_analyze",
                )
            },
            "hbm_bytes": profiled["hbm_bytes"],
            "census": profiled["census"],
            "span_p99_us": profiled["span_p99_us"],
            "iters": iters,
            "buckets": list(buckets),
        }
    except Exception as e:  # partial-but-parseable (bench contract)
        rec = {"error": repr(e), "partial": True}
    from deepflow_tpu.utils.provenance import device_identity

    rec["device"] = device_identity()  # names the platform this ran on
    print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
