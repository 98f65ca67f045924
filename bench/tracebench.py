#!/usr/bin/env python
"""Window lineage tracing + freshness plane overhead probe (ISSUE 13
acceptance): the SAME wire-to-window feeder workload as
bench/feeder_probe.py, run passive versus with the FULL lineage stack
attached — receiver-admission stamps, feeder pump/journal context,
staged-upload + dispatch binding, advance/flush/store hops, per-tier
freshness lags — plus an aggressive consumer that drains span rows,
reads the lag lanes + exemplars and assembles a live trace tree every
4th pump (the §19/§21 dashboard cadence). The A/B isolates what the
tracing plane costs steady-state ingest; fetch parity itself is
CI-gated deterministically in
test_perf_gate.py::test_lineage_tracing_budget.

Also measured: span-row volume (rows exported per window / per 1k
records — the l7_flow_log lane cost of tracing yourself) and the
pull-path latencies dfctl trace window serves (live assemble, exported
query_trace).

Usage: python bench/tracebench.py [repo_root]   (default: parent)
Knobs: TRACEBENCH_ITERS, TRACEBENCH_BUCKETS (comma list).
Protocol + committed numbers: PERF.md §22, TRACEBENCH_r01.json.
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


def run_mode(steps, buckets, traced: bool):
    from deepflow_tpu.integration.dfstats import docbatch_window_sink
    from deepflow_tpu.storage.store import ColumnarStore
    from deepflow_tpu.tracing.builder import TraceTreeBuilder
    from deepflow_tpu.tracing.lineage import (
        FreshnessTracker,
        LineageTracker,
        query_window_trace,
    )

    pipe = L4Pipeline(PipelineConfig(
        window=WindowConfig(capacity=1 << 14, stats_ring=4),
        batch_size=buckets[-1], bucket_sizes=buckets,
    ))
    lin = fresh = store = wsink = builder = None
    span_rows = 0
    if traced:
        fresh = FreshnessTracker(autoregister=False)
        lin = LineageTracker("tpu.pipeline", 1, freshness=fresh,
                             name="tracebench")
        pipe.attach_lineage(lin)
        store = ColumnarStore()
        wsink = docbatch_window_sink(store, lineage=lin)
        builder = TraceTreeBuilder(
            store, close_after_s=0.0, writer_args={"flush_interval_s": 0.01}
        )
    queues = [PyOverwriteQueue(1 << 12) for _ in range(4)]
    feeder = FeederRuntime(
        queues, PipelineFeedSink(pipe), FeederConfig(frames_per_queue=16),
        lineage=lin,
    )
    gen = SyntheticFlowGen(num_tuples=2000, seed=0)
    t0 = 1_700_000_000
    for b in buckets:  # warm every bucket's compile path
        for fr in encode_flowbatch_frames(gen.flow_batch(b, t0),
                                          max_rows_per_frame=256):
            queues[0].put(fr)
        feeder.pump()

    f0 = feeder.get_counters()
    windows = 0
    start = time.perf_counter()
    for i, frames in enumerate(steps):
        for j, fr in enumerate(frames):
            queues[j % 4].put(fr)
        out = feeder.pump()
        windows += len(out)
        if traced:
            if out:
                wsink(out)
            if (i + 1) % 4 == 0:
                # the dashboard cadence: EXPORT span rows into the
                # store's l7 lane (the real dogfood path — a bare
                # drain would discard the exactly-once rows), read the
                # lag lanes + exemplars, assemble one live tree
                span_rows += lin.export_store(store, builder=builder)
                fresh.get_counters()
                fresh.exemplars()
                lin.assemble(t0 + 10 + i // 4)
    out = feeder.flush()
    out += pipe.drain()
    windows += len(out)
    if traced and out:
        wsink(out)
    elapsed = time.perf_counter() - start
    f1 = feeder.get_counters()
    records = f1["records_in"] - f0["records_in"]
    rec = {
        "rec_s": round(records / elapsed, 1),
        "elapsed_s": round(elapsed, 4),
        "records": records,
        "windows": windows,
        "host_fetches": pipe.get_counters()["host_fetches"],
        "jit_retraces": pipe.get_counters()["jit_retraces"],
    }
    if traced:
        span_rows += lin.export_store(store, builder=builder)
        rec["span_rows"] = span_rows
        rec["span_rows_per_window"] = round(span_rows / max(windows, 1), 2)
        rec["span_rows_per_1k_records"] = round(
            span_rows * 1000.0 / max(records, 1), 2
        )
        rec["freshness"] = {
            k: v for k, v in fresh.get_counters().items()
            if k.endswith(("_lag_ms", "_samples"))
        }
        # pull-path latencies the REST/dfctl surface serves
        t = time.perf_counter()
        lin.assemble(t0 + 10)
        rec["pull_ms_live_assemble"] = round(
            (time.perf_counter() - t) * 1e3, 3
        )
        t = time.perf_counter()
        builder.tick()
        builder.flush()
        rec["assemble_flush_ms"] = round((time.perf_counter() - t) * 1e3, 2)
        # a REAL store-side pull: the l7 rows are in the store (the
        # in-loop exports), so this measures query_trace over them —
        # confirm it did not fall back to the live tracker by probing
        # a store without any live record would serve it too
        t = time.perf_counter()
        got = query_window_trace(store, t0 + 10)
        rec["pull_ms_store_query"] = round((time.perf_counter() - t) * 1e3, 3)
        rec["store_query_nodes"] = 0 if not got else len(got["nodes"])
        lin.close()
    return rec


def main():
    iters = int(os.environ.get("TRACEBENCH_ITERS", 48))
    buckets = tuple(
        int(b)
        for b in os.environ.get("TRACEBENCH_BUCKETS", "256,512,1024").split(",")
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
        # INTERLEAVED median-of-3 per mode (the §18/§21 recipe — this
        # container's CPU is ±30% noisy)
        run_mode(steps, buckets, False)
        runs = {False: [], True: []}
        for _ in range(3):
            for mode in (False, True):
                runs[mode].append(run_mode(steps, buckets, mode))

        def median(mode):
            return sorted(runs[mode], key=lambda r: r["rec_s"])[1]

        passive = median(False)
        traced = median(True)
        rec = {
            "passive": passive,
            "traced": {k: v for k, v in traced.items()
                       if k not in ("freshness",)},
            "overhead_pct": round(
                (passive["rec_s"] / max(traced["rec_s"], 1e-9) - 1.0) * 100, 2
            ),
            "fetch_parity": traced["host_fetches"] == passive["host_fetches"],
            "freshness": traced["freshness"],
            "iters": iters,
            "buckets": list(buckets),
            # on-chip columns reserved (PERF.md §22 protocol): the same
            # A/B re-run on a real TPU fills these
            "on_chip": None,
        }
    except Exception as e:  # partial-but-parseable (bench contract)
        rec = {"error": repr(e), "partial": True}
    from deepflow_tpu.utils.provenance import device_identity

    rec["device"] = device_identity()  # names the platform this ran on
    print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
