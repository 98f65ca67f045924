#!/usr/bin/env python
"""Frame-journal overhead probe (ISSUE 6 acceptance): the SAME
wire-to-window feeder workload as bench/feeder_probe.py, run journal-off
then journal-on (and journal-on + fsync-per-mark), so the A/B isolates
exactly what crash-safe ingest costs on the steady-state path — the
per-frame append (one buffered write + crc32) and the per-pump
mark+flush.

Usage: python bench/journal_probe.py [repo_root]   (default: parent)
Prints one JSON line with rec_s per mode, overhead %, and journal byte
accounting. Knobs: JOURNAL_ITERS, JOURNAL_BUCKETS (comma list),
JOURNAL_DIR (default: a tempdir; point at the real target volume for
honest fsync numbers). Protocol + committed numbers: PERF.md §16.
"""

import json
import os
import sys
import tempfile
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
    FrameJournal,
    PipelineFeedSink,
    encode_flowbatch_frames,
)
from deepflow_tpu.ingest.queues import PyOverwriteQueue  # noqa: E402
from deepflow_tpu.ingest.replay import SyntheticFlowGen  # noqa: E402


def run_mode(steps, buckets, journal_path, fsync):
    pipe = L4Pipeline(PipelineConfig(
        window=WindowConfig(capacity=1 << 14, stats_ring=4),
        batch_size=buckets[-1], bucket_sizes=buckets,
    ))
    journal = (
        FrameJournal(journal_path, fsync=fsync)
        if journal_path is not None else None
    )
    queues = [PyOverwriteQueue(1 << 12) for _ in range(4)]
    feeder = FeederRuntime(
        queues, PipelineFeedSink(pipe), FeederConfig(frames_per_queue=16),
        journal=journal,
    )
    gen = SyntheticFlowGen(num_tuples=2000, seed=0)
    t0 = 1_700_000_000
    for b in buckets:  # warm every bucket's compile path
        for fr in encode_flowbatch_frames(gen.flow_batch(b, t0), max_rows_per_frame=256):
            queues[0].put(fr)
        feeder.pump()
    if journal is not None:
        journal.rotate()  # time only the steady-state appends

    f0 = feeder.get_counters()
    start = time.perf_counter()
    for frames in steps:
        for j, fr in enumerate(frames):
            queues[j % 4].put(fr)
        feeder.pump()
    feeder.flush()
    pipe.drain()
    elapsed = time.perf_counter() - start
    f1 = feeder.get_counters()
    records = f1["records_in"] - f0["records_in"]
    out = {
        "rec_s": round(records / elapsed, 1),
        "elapsed_s": round(elapsed, 4),
        "records": records,
    }
    if journal is not None:
        jc = journal.get_counters()
        out["journal_frames"] = jc["frames"]
        out["journal_bytes"] = jc["bytes"]
        out["journal_marks"] = jc["marks"]
        out["bytes_per_record"] = round(jc["bytes"] / max(records, 1), 1)
        journal.close()
    return out


def main():
    iters = int(os.environ.get("JOURNAL_ITERS", 48))
    buckets = tuple(
        int(b) for b in os.environ.get("JOURNAL_BUCKETS", "256,512,1024").split(",")
    )
    gen = SyntheticFlowGen(num_tuples=2000, seed=0)
    t0 = 1_700_000_000
    sizes = [buckets[(i % len(buckets))] - (17 * i) % 64 for i in range(iters)]
    steps = [
        encode_flowbatch_frames(gen.flow_batch(n, t0 + 10 + i // 4),
                                agent_id=i, max_rows_per_frame=256)
        for i, n in enumerate(sizes)
    ]

    jdir = os.environ.get("JOURNAL_DIR")
    tmp = None
    if jdir is None:
        tmp = tempfile.TemporaryDirectory(prefix="dfj_")
        jdir = tmp.name
    try:
        # throwaway full run: the first pipeline in the process pays
        # one-time compile/alloc costs that would skew the A/B, then
        # best-of-2 per mode to shed host-jitter outliers
        run_mode(steps, buckets, None, False)

        def best(path, fsync):
            runs = [run_mode(steps, buckets, path, fsync) for _ in range(2)]
            return max(runs, key=lambda r: r["rec_s"])

        off = best(None, False)
        on = best(os.path.join(jdir, "probe.journal"), False)
        on_fsync = best(os.path.join(jdir, "probe_fsync.journal"), True)
        rec = {
            "journal_off": off,
            "journal_on": on,
            "journal_on_fsync": on_fsync,
            "overhead_pct": round(
                (off["rec_s"] / max(on["rec_s"], 1e-9) - 1.0) * 100, 2
            ),
            "overhead_fsync_pct": round(
                (off["rec_s"] / max(on_fsync["rec_s"], 1e-9) - 1.0) * 100, 2
            ),
            "iters": iters,
            "buckets": list(buckets),
        }
    except Exception as e:  # partial-but-parseable (bench contract)
        rec = {"error": repr(e), "partial": True}
    finally:
        if tmp is not None:
            tmp.cleanup()
    from deepflow_tpu.utils.provenance import device_identity

    rec["device"] = device_identity()  # names the platform this ran on
    print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
