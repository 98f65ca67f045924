#!/usr/bin/env python
"""Receiver→decode→enrich e2e throughput (docs/s) — the server ingest
path around the kernel bench (VERDICT r3 #7: e2e must stay within ~3x
of the kernel-only number). Run from repo root:

    python bench/e2e_ingest.py [--cpu] [--docs N]

Pumps pre-encoded METRICS frames through a real TCP socket into the
batched unmarshaller (decode → device enrich → writer), then reports
documents/second end to end.
"""

from __future__ import annotations

import argparse
import sys
import threading
import time

if "--cpu" in sys.argv:
    import os

    os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, ".")

import numpy as np


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--cpu", action="store_true")
    p.add_argument("--docs", type=int, default=200_000)
    p.add_argument("--frame-docs", type=int, default=256)
    p.add_argument("--workers", type=int, default=4)
    # frames spread across N agent ids — the receiver hash-fans by
    # agent, so one lone agent would serialize onto one decode queue
    p.add_argument("--agents", type=int, default=8)
    args = p.parse_args()

    from deepflow_tpu.aggregator.pipeline import L4Pipeline, PipelineConfig
    from deepflow_tpu.aggregator.window import WindowConfig
    from deepflow_tpu.controller.resources import ResourceDB
    from deepflow_tpu.datamodel.batch import FlowBatch
    from deepflow_tpu.ingest.codec import encode_docbatch
    from deepflow_tpu.ingest.framing import FlowHeader, MessageType, encode_frame
    from deepflow_tpu.ingest.receiver import Receiver
    from deepflow_tpu.ingest.replay import SyntheticFlowGen
    from deepflow_tpu.server.flow_metrics import FlowMetricsIngester

    # 1. produce realistic doc frames once (agent-side pipeline output)
    pipe = L4Pipeline(PipelineConfig(window=WindowConfig(capacity=1 << 15), batch_size=4096))
    gen = SyntheticFlowGen(num_tuples=5_000, seed=0)
    t0 = 1_700_000_000
    docs = []
    t = t0
    while sum(d.size for d in docs) < args.docs:
        docs += pipe.ingest(FlowBatch.from_records(gen.records(4096, t)))
        t += 1
    docs += pipe.drain()
    msgs = []
    for db in docs:
        msgs += encode_docbatch(db, flags=1)
    msgs = msgs[: args.docs]
    frames = []
    for i in range(0, len(msgs), args.frame_docs):
        h = FlowHeader(
            msg_type=int(MessageType.METRICS),
            agent_id=1 + (i // args.frame_docs) % args.agents,
            organization_id=1,
        )
        frames.append(encode_frame(h, msgs[i : i + args.frame_docs]))
    payload = b"".join(frames)
    print(f"prepared {len(msgs)} docs in {len(frames)} frames "
          f"({len(payload) / 1e6:.1f} MB)", flush=True)

    # 2. server side: receiver → batched unmarshaller → counting writer
    class CountWriter:
        def __init__(self):
            self.docs = 0
            self.lock = threading.Lock()

        def put(self, batch):
            with self.lock:
                self.docs += int(batch.keep.sum())

    recv = Receiver()
    recv.start()
    writer = CountWriter()
    platform = ResourceDB().build_platform_table(1).build()
    ing = FlowMetricsIngester(
        recv, writer, platform_state=platform, n_workers=args.workers,
        queue_capacity=1 << 15, prefer_native=not args.cpu,
    )

    # warm the enrich kernel compile out of the timed region
    import socket

    s = socket.create_connection(("127.0.0.1", recv.tcp_port))
    s.sendall(frames[0])
    deadline = time.time() + 120
    while writer.docs == 0 and time.time() < deadline:
        time.sleep(0.01)
    base = writer.docs

    t_start = time.perf_counter()
    s.sendall(payload)
    want = base + len(msgs)
    deadline = time.time() + 300
    while writer.docs < want and time.time() < deadline:
        time.sleep(0.005)
    dt = time.perf_counter() - t_start
    s.close()

    done = writer.docs - base
    print(f"e2e: {done} docs in {dt:.2f}s = {done / dt / 1e6:.3f} M docs/s "
          f"(counters: {ing.get_counters()})")
    ing.stop()
    recv.stop()


if __name__ == "__main__":
    main()
