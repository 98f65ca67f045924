#!/usr/bin/env python
"""Fleet telemetry plane bench (ISSUE 18 acceptance).

Three measurements, one JSON line at the end (bench contract:
partial-but-parseable on error):

1. **Ingest overhead** — the §14 wire-to-window feeder workload run
   passive vs with the FULL fleet export loop live (pipeline +
   freshness registered on a private collector; every 4th pump — the
   dashboard cadence — ticks a `FleetSink` that builds, encodes, and
   ships one frame over real TCP to a local `FleetAggregator`).
   Acceptance: overhead within noise; fetch parity itself is CI-gated
   deterministically in test_perf_gate::test_fleet_export_budget.

2. **Aggregator cost is O(hosts)** — merged-read latency
   (merged_counters + merged_hists + skew) swept over host count with
   fixed per-host lane content. The merge walks per-host SUMMARIES, so
   cost grows with hosts, and the sweep's per-host-normalized latency
   should stay ~flat.

3. **…not O(samples)** — one host's frame built from a span face that
   observed S samples, S swept ×64. Frame bytes and merge latency are
   bounded by the log-hist BIN count, not S: the ratio rows pin both
   near 1×.

Usage: python bench/fleetbench.py [repo_root]
Knobs: FLEETBENCH_ITERS (feeder pumps; default 64),
       FLEETBENCH_HOSTS (comma list; default 2,4,8,16).
Protocol + committed numbers: PERF.md §26.
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
from deepflow_tpu.fleet import (  # noqa: E402
    FleetAggregator,
    FleetExporter,
    FleetFrame,
    FleetSink,
    encode_fleet_frame,
)
from deepflow_tpu.ingest.queues import PyOverwriteQueue  # noqa: E402
from deepflow_tpu.ingest.replay import SyntheticFlowGen  # noqa: E402
from deepflow_tpu.utils.provenance import bench_provenance  # noqa: E402

ITERS = int(os.environ.get("FLEETBENCH_ITERS", "64"))
HOSTS = tuple(
    int(x) for x in os.environ.get("FLEETBENCH_HOSTS", "2,4,8,16").split(",")
)
BUCKETS = (64, 128, 256)
T0 = 1_700_000_000


def run_mode(fleet: bool) -> dict:
    from deepflow_tpu.tracing.lineage import FreshnessTracker
    from deepflow_tpu.utils.stats import StatsCollector

    pipe = L4Pipeline(PipelineConfig(
        window=WindowConfig(capacity=1 << 14, stats_ring=4),
        batch_size=BUCKETS[-1], bucket_sizes=BUCKETS,
    ))
    q = PyOverwriteQueue(1 << 10)
    feeder = FeederRuntime(
        [q], PipelineFeedSink(pipe), FeederConfig(frames_per_queue=8),
        name="fleetbench",
    )
    agg = sink = col = None
    if fleet:
        agg = FleetAggregator(expiry_s=3600.0, autoregister=False).start()
        col = StatsCollector()
        fresh = FreshnessTracker(autoregister=False)
        col.register("tpu_pipeline", pipe, group="0")
        exporter = FleetExporter(
            "bench-host", group="0", collector=col,
            hist_faces={"fresh": fresh},
        )
        sink = FleetSink(agg.endpoint(), exporter)
        col.add_sink(sink)

    gen = SyntheticFlowGen(num_tuples=200, seed=47)

    def pump(t):
        fb = gen.flow_batch(128, t)
        for fr in encode_flowbatch_frames(fb, max_rows_per_frame=64):
            q.put(fr)
        return feeder.pump()

    rows = 0
    for t in (T0, T0 + 1):  # warmup: bucket compiles
        rows += sum(int(d.size) for d in pump(t))
    rows = 0
    t_start = time.perf_counter()
    for i in range(ITERS):
        t = T0 + 2 + i // 4
        rows += sum(int(d.size) for d in pump(t))
        if fleet and i % 4 == 3:  # dashboard cadence, profbench's §21
            col.tick(float(t))
    rows += sum(int(d.size) for d in feeder.flush())
    wall = time.perf_counter() - t_start
    out = {"rec_s": round(rows / wall, 1), "rows": rows,
           "wall_s": round(wall, 4)}
    if fleet:
        assert sink.flush(30)
        sc = sink.get_counters()
        deadline = time.time() + 30
        while (agg.counters["frames_rx"] < sc["frames_sent"]
               and time.time() < deadline):
            time.sleep(0.01)
        out["frames_sent"] = sc["frames_sent"]
        out["frame_bytes_avg"] = round(
            sc["bytes_sent"] / max(sc["frames_sent"], 1), 1
        )
        out["frames_rx"] = agg.counters["frames_rx"]
        out["send_errors"] = sc["send_errors"]
        sink.close()
        agg.stop()
    return out


def synth_frame(host: str, n_lanes: int = 4, bins: int = 64,
                n_fields: int = 16) -> FleetFrame:
    """Fixed-size per-host summary: the merge-cost sweeps hold lane
    content constant so the only variable is what each sweep varies."""
    return FleetFrame(
        host=host, group="0", epoch=0, seq=0, timestamp=float(T0),
        points=((float(T0), "tpu_mesh_swm", {"group": "0"},
                 {f"f{i}": i * 3 + 1 for i in range(n_fields)}),),
        hists={"g0": {
            f"lane{j}": [[b, b + 1] for b in range(bins)]
            for j in range(n_lanes)
        }},
    )


def merge_read_ms(agg, reps: int = 50) -> float:
    t0 = time.perf_counter()
    for _ in range(reps):
        agg.merged_counters()
        agg.merged_hists()
        agg.skew()
    return (time.perf_counter() - t0) / reps * 1e3


def host_scaling() -> list[dict]:
    rows = []
    for n in HOSTS:
        agg = FleetAggregator(expiry_s=3600.0, autoregister=False)
        for h in range(n):
            agg.ingest(synth_frame(f"host{h}"))
        ms = merge_read_ms(agg)
        rows.append({"hosts": n, "merge_read_ms": round(ms, 4),
                     "ms_per_host": round(ms / n, 5)})
    return rows


def sample_independence() -> list[dict]:
    """Same host, the span face fed S vs 64·S samples: frame bytes and
    merge cost must track the BIN count, not S."""
    from deepflow_tpu.utils.spans import SpanTracer

    rows = []
    for s in (2_000, 128_000):
        tr = SpanTracer()
        for i in range(s):
            tr.record("stage", 10 + (i % 500))
        exp = FleetExporter("hostS", group="0",
                            hist_faces={"spans": tr},
                            clock=lambda: float(T0))
        frame = exp.build(points=[])
        nbytes = len(encode_fleet_frame(frame))
        agg = FleetAggregator(expiry_s=3600.0, autoregister=False)
        agg.ingest(frame)
        rows.append({
            "samples": s, "frame_bytes": nbytes,
            "hist_bins_nonzero": sum(
                len(v) for v in frame.hists["spans"].values()
            ),
            "merge_read_ms": round(merge_read_ms(agg), 4),
        })
    return rows


def main() -> dict:
    run_mode(fleet=False)  # throwaway: heat the process-wide jit cache
    passive = run_mode(fleet=False)
    fleet = run_mode(fleet=True)
    overhead = (passive["rec_s"] / max(fleet["rec_s"], 1e-9) - 1.0) * 100
    hosts_rows = host_scaling()
    samples_rows = sample_independence()
    lo, hi = hosts_rows[0], hosts_rows[-1]
    srow_lo, srow_hi = samples_rows[0], samples_rows[-1]
    return {
        "iters": ITERS,
        "passive": passive,
        "fleet": fleet,
        "overhead_pct": round(overhead, 2),
        "hosts_rows": hosts_rows,
        # O(hosts) statement: read latency normalized per host is flat
        "per_host_ms_ratio": round(
            hi["ms_per_host"] / max(lo["ms_per_host"], 1e-9), 3
        ),
        "samples_rows": samples_rows,
        # O(samples) independence: 64× the samples, ~1× the cost/bytes
        "samples_ratio": srow_hi["samples"] / srow_lo["samples"],
        "frame_bytes_ratio": round(
            srow_hi["frame_bytes"] / max(srow_lo["frame_bytes"], 1), 3
        ),
        "merge_ms_ratio": round(
            srow_hi["merge_read_ms"] / max(srow_lo["merge_read_ms"], 1e-9), 3
        ),
        "provenance": bench_provenance(),
    }


if __name__ == "__main__":
    try:
        rec = main()
    except Exception as e:  # partial-but-parseable (bench contract)
        rec = {"error": repr(e), "partial": True}
    from deepflow_tpu.utils.provenance import device_identity

    rec["device"] = device_identity()  # names the platform this ran on
    print(json.dumps(rec), flush=True)
