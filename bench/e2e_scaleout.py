#!/usr/bin/env python
"""Host-ingest scale-out: N shared-nothing ingester worker PROCESSES
(one receiver + decoder pool + writer each — the reference's
multi-analyzer deployment, flow_metrics.go:55-61 + per-analyzer
processes), fed disjoint agent shards of one workload.

    python bench/e2e_scaleout.py [--procs 1 2 4] [--docs N]

Each worker is its own OS process with its own TCP receiver port; the
parent generates the doc frames once, shards them by agent id (the same
hash fanout the receiver applies internally), feeds every worker its
shard concurrently, and reports per-worker and aggregate docs/s.

HONESTY NOTE: this build container exposes ONE CPU core
(sched_getaffinity = 1), so aggregate throughput here measures
timesharing, not parallel speedup — the harness demonstrates the
shared-nothing property (no cross-process contention point: aggregate ≈
N × single ÷ N on one core, i.e. per-worker rate stays flat as N grows)
and records the per-core rate; on an M-core host the same harness is
the ≥Mx deployment shape. PERF.md carries the measured table.
"""

from __future__ import annotations

import argparse
import json
import multiprocessing as mp
import os
import socket
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _prepare(docs_target: int, frame_docs: int, agents: int) -> list[tuple[int, bytes]]:
    """(agent_id, frame bytes) pairs — built once in the parent."""
    from deepflow_tpu.aggregator.pipeline import L4Pipeline, PipelineConfig
    from deepflow_tpu.aggregator.window import WindowConfig
    from deepflow_tpu.datamodel.batch import FlowBatch
    from deepflow_tpu.ingest.codec import encode_docbatch
    from deepflow_tpu.ingest.framing import FlowHeader, MessageType, encode_frame
    from deepflow_tpu.ingest.replay import SyntheticFlowGen

    pipe = L4Pipeline(PipelineConfig(window=WindowConfig(capacity=1 << 15), batch_size=4096))
    gen = SyntheticFlowGen(num_tuples=5_000, seed=0)
    t = 1_700_000_000
    docs = []
    while sum(d.size for d in docs) < docs_target:
        docs += pipe.ingest(FlowBatch.from_records(gen.records(4096, t)))
        t += 1
    docs += pipe.drain()
    msgs = []
    for db in docs:
        msgs += encode_docbatch(db, flags=1)
    msgs = msgs[:docs_target]
    frames = []
    for i in range(0, len(msgs), frame_docs):
        agent = 1 + (i // frame_docs) % agents
        h = FlowHeader(msg_type=int(MessageType.METRICS), agent_id=agent,
                       organization_id=1)
        frames.append((agent, encode_frame(h, msgs[i : i + frame_docs]),
                       len(msgs[i : i + frame_docs])))
    return frames


def _worker(port_q, result_q, warm_docs: int, n_docs_expected: int,
            n_decoders: int):
    """One shared-nothing ingester process. The parent first sends a
    warm shard (JAX import + enrich-kernel compile happen there); the
    timed region covers only the steady frames after `ready`."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    import threading

    from deepflow_tpu.controller.resources import ResourceDB
    from deepflow_tpu.ingest.receiver import Receiver
    from deepflow_tpu.server.flow_metrics import FlowMetricsIngester

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
        recv, writer, platform_state=platform, n_workers=n_decoders,
        queue_capacity=1 << 15, prefer_native=True,
    )
    port_q.put(recv.tcp_port)
    deadline = time.time() + 600
    while writer.docs < warm_docs and time.time() < deadline:
        time.sleep(0.01)
    warm_seen = writer.docs  # may exceed warm_docs if the frame was resent
    result_q.put({"ready": True})
    # steady clock starts at the FIRST steady doc, not at `ready` —
    # the parent still has to drain every worker's handshake before it
    # feeds, and that idle gap must not deflate the rate
    while writer.docs <= warm_seen and time.time() < deadline:
        time.sleep(0.002)
    t0 = time.perf_counter()
    base = warm_seen
    while writer.docs < warm_seen + n_docs_expected and time.time() < deadline:
        time.sleep(0.005)
    dt = time.perf_counter() - t0
    result_q.put({"docs": writer.docs - base, "seconds": round(dt, 3)})
    ing.stop()
    recv.stop()


def run(n_procs: int, frames, total_docs: int) -> dict:
    ctx = mp.get_context("spawn")
    port_q = ctx.Queue()
    result_qs = [ctx.Queue() for _ in range(n_procs)]
    # shard frames by agent — the receiver-level hash fanout, applied
    # across processes (flow_metrics.go:55-61 at deployment scale)
    shards: list[list[bytes]] = [[] for _ in range(n_procs)]
    shard_docs = [0] * n_procs
    warm: list[tuple[bytes, int] | None] = [None] * n_procs
    for agent, frame, ndocs in frames:
        i = agent % n_procs
        if warm[i] is None:
            warm[i] = (frame, ndocs)
        else:
            shards[i].append(frame)
            shard_docs[i] += ndocs

    procs = []
    for i in range(n_procs):
        p = ctx.Process(
            target=_worker,
            args=(port_q, result_qs[i],
                  warm[i][1] if warm[i] is not None else 0, shard_docs[i], 2),
        )
        p.start()
        procs.append(p)
    ports = [port_q.get(timeout=300) for _ in procs]

    socks = [socket.create_connection(("127.0.0.1", port)) for port in ports]
    # warm phase: compiles + imports happen outside the timed region.
    # The warm frame is resent on timeout — worker startup on an
    # oversubscribed host can race the first delivery. A proc whose
    # shard is empty (more procs than agent ids) gets no warm frame and
    # reports 0 docs immediately.
    for s, w in zip(socks, warm):
        if w is not None:
            s.sendall(w[0])
    # NOTE: a ready timeout means the worker is still starting (TCP
    # already delivered the frame) — the resend is a last-resort nudge
    # whose duplicate docs are absorbed by the worker's warm_seen
    # baseline, not counted into the steady region
    for q, s, w in zip(result_qs, socks, warm):
        if w is None:
            continue
        for attempt in range(6):
            try:
                assert q.get(timeout=120).get("ready")
                break
            except Exception:
                if attempt == 5:
                    raise
                s.sendall(w[0])

    t0 = time.perf_counter()
    import threading

    def feed(sock, shard):
        sock.sendall(b"".join(shard))

    feeders = [threading.Thread(target=feed, args=(s, sh))
               for s, sh in zip(socks, shards)]
    for f in feeders:
        f.start()
    results = [q.get(timeout=600) for q in result_qs]
    dt = time.perf_counter() - t0
    for f in feeders:
        f.join()
    for s in socks:
        s.close()
    for p in procs:
        p.join(timeout=30)
    done = sum(r["docs"] for r in results)
    return {
        "n_procs": n_procs,
        "docs": done,
        "wall_s": round(dt, 3),
        "agg_docs_s": round(done / dt, 1),
        "per_proc_docs_s": [round(r["docs"] / max(r["seconds"], 1e-9), 1)
                            for r in results],
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--procs", type=int, nargs="+", default=[1, 2, 4])
    ap.add_argument("--docs", type=int, default=200_000)
    ap.add_argument("--frame-docs", type=int, default=256)
    args = ap.parse_args()
    frames = _prepare(args.docs, args.frame_docs, agents=16)
    total = sum(n for _, _, n in frames)
    print(f"prepared {total} docs in {len(frames)} frames", flush=True)
    rows = [run(n, frames, total) for n in args.procs]
    print(json.dumps({"cores": len(os.sched_getaffinity(0)), "rows": rows}),
          flush=True)


if __name__ == "__main__":
    main()
