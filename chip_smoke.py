#!/usr/bin/env python
"""chip_smoke.py — the served path once, on one TPU chip, checked.

One process, one chip, the entry points a user calls. It FAILS (non-zero
exit, no result line) when JAX finds no TPU: it never sets a platform
and never falls back to the CPU. Phases (each checked against a plain
NumPy reference written here, independent of the code under test):

  served   flow frames (SyntheticFlowGen, --seed) → Receiver over a
           local socket → receiver queues → FeederRuntime +
           PipelineFeedSink → L4Pipeline/WindowManager fused step →
           window close and flush → one window's documents as METRICS
           frames into the composed Server (decode → device enrich_docs
           → store) → one SQL and one PromQL query.
  kernel   append×2 + fold of make_ingest_step at bench.py's shape; the
           compiled HLO must contain the Pallas kernel.
  sketch   two windows with WindowConfig.sketch on (HLL + count-min +
           top-K, default XLA path); distinct count within 1%.

With `--chips 4` it runs ONLY the four-chip path (ShardedFeedSink /
ShardedWindowManager on a 4-device mesh, cascade on) and the one-chip
WindowManager on the same records for comparison.

Every line but the last is a JSON progress record; the last line is
    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}
"""

from __future__ import annotations

import argparse
import concurrent.futures
import dataclasses
import json
import os
import socket
import sys
import tempfile
import threading
import time

import numpy as np

T0 = 1_700_000_000
SUM_RTOL = 1e-6  # f32 tree-order sums vs the f64 reference
SQL_SUM_RTOL = 2e-5  # the querier adds an f32 column in f32 (pairwise)


def say(**rec) -> None:
    print(json.dumps(rec, default=str), flush=True)


class SmokeFailure(AssertionError):
    pass


def check(cond, what: str, **detail) -> None:
    if not cond:
        raise SmokeFailure(f"{what}: {json.dumps(detail, default=str)}")


@dataclasses.dataclass(frozen=True)
class Sizes:
    """One deployment's scale. `full()` is what a one-chip ingester
    holds; `tiny()` is the CPU rehearsal (tests/test_chip_smoke.py)."""

    tuples: int  # distinct 5-tuples
    records_per_window: int
    windows: int  # full-size windows that must close BEFORE the drain
    prefix: int  # records of the oracle-checked first window
    buckets: tuple[int, ...]
    unique_cap: int
    capacity: int  # stash rows
    accum_batches: int
    server_windows: int  # closed windows whose documents go through Server
    kernel_batch: int
    kernel_capacity: int
    kernel_unique_cap: int
    kernel_tuples: int
    sketch_records: int
    hll_precision: int

    @classmethod
    def full(cls) -> "Sizes":
        return cls(
            tuples=100_000, records_per_window=1_100_000, windows=5,
            prefix=4096, buckets=(32768, 131072), unique_cap=131072,
            capacity=1 << 21, accum_batches=2, server_windows=1,
            # bench.py's shape
            kernel_batch=1 << 21, kernel_capacity=1 << 16,
            kernel_unique_cap=1 << 15, kernel_tuples=10_000,
            sketch_records=1_100_000, hll_precision=14,
        )

    @classmethod
    def tiny(cls) -> "Sizes":
        return cls(
            tuples=300, records_per_window=3000, windows=5, prefix=256,
            buckets=(512, 2048), unique_cap=2048, capacity=1 << 13,
            accum_batches=2, server_windows=1,
            kernel_batch=4096, kernel_capacity=1 << 11,
            kernel_unique_cap=1 << 10, kernel_tuples=200,
            sketch_records=3000, hll_precision=14,
        )


# ---------------------------------------------------------------------------
# phase timing: wall split into compile and steady from JAX's own events


class CompileClock:
    """Seconds in XLA's backend compile (persistent-cache reads
    included). Tracing and lowering nest, so they are left in "steady":
    seconds against the minutes a whole program compiles for."""

    _EVENTS = ("/jax/core/compile/backend_compile_duration",)

    def __init__(self):
        import jax.monitoring

        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_kw) -> None:
        if event in self._EVENTS:
            self.compile_s += secs


class CacheCounter:
    """This process's persistent-cache hits and misses, from JAX's own
    monitoring events."""

    def __init__(self):
        import jax.monitoring

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on)

    def _on(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


class Phase:
    """Wall time of one phase, split into compile (CompileClock) and
    steady (the rest), with the device's peak bytes at the end."""

    def __init__(self, name: str, clock: CompileClock, cache):
        self.name, self.clock, self.cache = name, clock, cache

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0 = self.clock.compile_s
        self.h0, self.m0 = self.cache.hits, self.cache.misses
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            return
        import jax

        wall = time.perf_counter() - self.t0
        comp = self.clock.compile_s - self.c0
        stats = jax.devices()[0].memory_stats() or {}
        say(phase=self.name, wall_s=wall, compile_s=comp,
            steady_s=wall - comp,
            cache_hits=self.cache.hits - self.h0,
            cache_misses=self.cache.misses - self.m0,
            peak_bytes_in_use=stats.get("peak_bytes_in_use"))


# ---------------------------------------------------------------------------
# the plain reference: NumPy group-by over the same records


def _group_rows(cols: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Sort n rows given COLUMN-major (`cols` [k, n] u32) and find the
    groups of equal rows: (order [n], starts [g]). Plain and exact, no
    hashing: the columns that vary are bit-packed, by their own widths,
    into as few u64 words as hold them, and np.lexsort orders the
    words."""
    n = cols.shape[1]
    if n == 0:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    top = cols.max(axis=1)
    words, cur, used = [], np.zeros(n, np.uint64), 0
    for c in np.flatnonzero(top != cols.min(axis=1)):
        bits = int(top[c]).bit_length()
        if used + bits > 64:
            words.append(cur)
            cur, used = np.zeros(n, np.uint64), 0
        cur = (cur << np.uint64(bits)) | cols[c].astype(np.uint64)
        used += bits
    words.append(cur)
    order = np.lexsort(words[::-1])
    differs = np.zeros(n, bool)
    differs[0] = True
    for w in words:
        ws = w[order]
        differs[1:] |= ws[1:] != ws[:-1]
    return order, np.flatnonzero(differs)


def _group_reduce(cols: np.ndarray, meters: np.ndarray, sum_mask: np.ndarray):
    """Group `meters` [n, M] by the rows `cols` [k, n] holds column-major
    and reduce each lane in f64 with np.add/np.maximum.reduceat. Returns
    (index of each group's first row [g], reduced [g, M] f64). Lanes
    that are zero everywhere (most of FLOW_METER on this traffic) skip
    the reduce."""
    order, starts = _group_rows(cols)
    out = np.zeros((starts.size, meters.shape[1]), np.float64)
    for c in np.flatnonzero(meters.any(axis=0)):
        col = meters[:, c][order].astype(np.float64)
        fn = np.add if sum_mask[c] else np.maximum
        out[:, c] = fn.reduceat(col, starts)
    return order[starts], out


def reference_docs(fb) -> tuple[np.ndarray, np.ndarray]:
    """Expected documents of ONE window's flow records: (tags [g, T]
    u32 in TAG_SCHEMA order, meters [g, M] f64).

    Records group by their whole tag row first (exact: equal records
    fan out to equal documents), then fan out to the ≤4 documents the
    reference collector emits per flow (single-side ×2, edge ×2), then
    group again by document key. The fanout below covers the traffic
    SyntheticFlowGen makes — packet signal, default FanoutConfig,
    active hosts and service, TCP/UDP, no VIP/MAC — and says so if a
    record is outside it; the general rules are oracle_l4_rollup's, and
    the first window is compared with that oracle too."""
    from deepflow_tpu.aggregator.fanout import FanoutConfig
    from deepflow_tpu.datamodel.batch import FLOW_RECORD_TAG_FIELDS
    from deepflow_tpu.datamodel.code import CodeId, MeterId, SignalSource
    from deepflow_tpu.datamodel.schema import FLOW_METER, TAG_SCHEMA, MergeOp

    sum_mask = np.array([f.op is MergeOp.SUM for f in FLOW_METER.fields])
    raw = np.stack([np.asarray(fb.tags[f], np.uint32)[fb.valid]
                    for f in FLOW_RECORD_TAG_FIELDS])  # [fields, n]
    first, m_u = _group_reduce(raw, fb.meters[fb.valid], sum_mask)
    r = {f: raw[i][first] for i, f in enumerate(FLOW_RECORD_TAG_FIELDS)}

    in_domain = (
        (r["signal_source"] == int(SignalSource.PACKET))
        & (r["is_active_host0"] == 1) & (r["is_active_host1"] == 1)
        & (r["is_active_service"] == 1)
        & ((r["protocol"] == 6) | (r["protocol"] == 17))
        & (r["is_vip0"] == 0) & (r["is_vip1"] == 0)
        & (r["l3_epc_id"] != 0xFFFE) & (r["l3_epc_id"] < 0x8000)
        & (r["l3_epc_id1"] < 0x8000)
        & ((r["direction0"] & 0xF8) == 0) & ((r["direction1"] & 0xF8) == 0)
        & (r["direction0"] != 3) & (r["direction1"] != 3)  # LOCAL_TO_LOCAL
    )
    check(bool(in_domain.all()), "records outside the reference fanout's domain",
          n=int((~in_domain).sum()))

    # meter as seen from side 1: tx/rx lanes swap, zero_on_reverse zero
    rev = np.arange(FLOW_METER.num_fields)
    zero = np.zeros(FLOW_METER.num_fields, bool)
    for i, f in enumerate(FLOW_METER.fields):
        if f.reverse_with:
            rev[i] = FLOW_METER.index(f.reverse_with)
        zero[i] = f.zero_on_reverse
    m_rev = np.where(zero[None, :], 0.0, m_u[:, rev])

    cfg = FanoutConfig()
    n = first.size
    ix = TAG_SCHEMA.index

    def doc(rows, **cols):  # column-major [T, docs]
        t = np.zeros((TAG_SCHEMA.num_fields, int(rows.sum())), np.uint32)
        shared = dict(
            meter_id=int(MeterId.FLOW), global_thread_id=cfg.global_thread_id,
            agent_id=cfg.agent_id, is_ipv6=r["is_ipv6"], protocol=r["protocol"],
            tap_type=r["tap_type"], signal_source=r["signal_source"],
            pod_id=r["pod_id"],
        )
        for k, v in {**shared, **cols}.items():
            t[ix(k)] = v[rows] if isinstance(v, np.ndarray) else v
        return t

    d0, d1 = r["direction0"], r["direction1"]
    ip0 = {f"ip0_w{w}": r[f"ip0_w{w}"] for w in range(4)}
    ip1_as_0 = {f"ip0_w{w}": r[f"ip1_w{w}"] for w in range(4)}
    ip1 = {f"ip1_w{w}": r[f"ip1_w{w}"] for w in range(4)}
    port = r["server_port"]
    tags, meters = [], []
    # single-side documents
    s0 = d0 != 0
    tags.append(doc(s0, code_id=int(CodeId.SINGLE_IP_PORT), **ip0,
                    l3_epc_id=r["l3_epc_id"], direction=d0, tap_side=d0,
                    server_port=0, gpid0=r["gpid0"]))
    meters.append(m_u[s0])
    s1 = d1 != 0
    tags.append(doc(s1, code_id=int(CodeId.SINGLE_IP_PORT), **ip1_as_0,
                    l3_epc_id=r["l3_epc_id1"], direction=d1, tap_side=d1,
                    server_port=port, gpid0=r["gpid1"]))
    meters.append(m_rev[s1])
    # edge documents: one per known direction; a flow with neither gets
    # one with direction NONE (0)
    edge = dict(code_id=int(CodeId.EDGE_IP_PORT), **ip0, **ip1,
                l3_epc_id=r["l3_epc_id"], l3_epc_id1=r["l3_epc_id1"],
                server_port=port, tap_port=r["tap_port"],
                gpid0=r["gpid0"], gpid1=r["gpid1"])
    for rows, d in ((s0, d0), (s1, d1), (~s0 & ~s1, np.zeros(n, np.uint32))):
        tags.append(doc(rows, direction=d, tap_side=d, **edge))
        meters.append(m_u[rows])
    tags = np.concatenate(tags, axis=1)
    meters = np.concatenate(meters)

    # group by KEY columns; a group's tags are its first row's
    first, red = _group_reduce(tags[TAG_SCHEMA.key_mask], meters, sum_mask)
    return np.ascontiguousarray(tags[:, first].T), red


def compare_docs(got_tags, got_meters, want_tags, want_meters, what: str) -> dict:
    """Keys, counts and MAX lanes exactly; SUM lanes within SUM_RTOL of
    the f64 reference (f32 meters, tree-order sums)."""
    from deepflow_tpu.datamodel.schema import FLOW_METER, TAG_SCHEMA, MergeOp

    key_cols = np.flatnonzero(TAG_SCHEMA.key_mask)
    check(got_tags.shape[0] == want_tags.shape[0], f"{what}: document count",
          got=got_tags.shape[0], want=want_tags.shape[0])
    both = np.ascontiguousarray(
        np.concatenate([got_tags[:, key_cols], want_tags[:, key_cols]]).T)
    order, starts = _group_rows(both)
    n = got_tags.shape[0]
    check(starts.size == n and bool((starts == 2 * np.arange(n)).all()),
          f"{what}: key sets differ", groups=int(starts.size), docs=n)
    pair = np.sort(order.reshape(n, 2), axis=1)  # one of `got`, one of `want`
    check(bool(((pair[:, 0] < n) & (pair[:, 1] >= n)).all()),
          f"{what}: duplicate keys")
    go, wo = pair[:, 0], pair[:, 1] - n
    check(np.array_equal(got_tags[go], want_tags[wo]), f"{what}: tag rows differ")
    g = got_meters[go].astype(np.float64)
    w = want_meters[wo]
    is_sum = np.array([f.op is MergeOp.SUM for f in FLOW_METER.fields])
    check(np.array_equal(g[:, ~is_sum], w[:, ~is_sum]), f"{what}: MAX lanes differ")
    err = np.abs(g[:, is_sum] - w[:, is_sum]) / np.maximum(np.abs(w[:, is_sum]), 1.0)
    check(float(err.max(initial=0.0)) <= SUM_RTOL, f"{what}: SUM lanes differ",
          max_rel_err=float(err.max(initial=0.0)), rtol=SUM_RTOL)
    return {"docs": int(got_tags.shape[0]),
            "sum_max_rel_err": float(err.max(initial=0.0))}


def flowbatch_records(fb) -> list[dict]:
    """FlowBatch rows → the dict records oracle_l4_rollup reads."""
    from deepflow_tpu.datamodel.schema import FLOW_METER

    names = FLOW_METER.field_names()
    out = []
    for i in np.flatnonzero(fb.valid):
        rec = {k: int(v[i]) for k, v in fb.tags.items()}
        rec["meter"] = {n: int(fb.meters[i, j]) for j, n in enumerate(names)
                        if fb.meters[i, j]}
        out.append(rec)
    return out


def oracle_arrays(oracle: dict) -> tuple[np.ndarray, np.ndarray]:
    from deepflow_tpu.datamodel.schema import FLOW_METER, TAG_SCHEMA

    tn, mn = TAG_SCHEMA.field_names(), FLOW_METER.field_names()
    tags = np.array([[d.tag[k] for k in tn] for d in oracle.values()], np.uint32)
    meters = np.array([[d.meter[k] for k in mn] for d in oracle.values()],
                      np.float64)
    return tags.reshape(-1, len(tn)), meters.reshape(-1, len(mn))


# ---------------------------------------------------------------------------
# served path


class FrameSender:
    """A sender thread writing raw frames to a Receiver's TCP port."""

    def __init__(self, port: int):
        self.sock = socket.create_connection(("127.0.0.1", port), timeout=30)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self._thread: threading.Thread | None = None
        self.error: BaseException | None = None

    def send(self, frames: list[bytes]) -> None:
        self.join()

        def run():
            try:
                for fr in frames:
                    self.sock.sendall(fr)
            except BaseException as e:  # surfaced by join()
                self.error = e

        self._thread = threading.Thread(target=run, daemon=True)
        self._thread.start()

    def join(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self.error is not None:
            raise self.error

    def close(self) -> None:
        try:
            self.join()
        finally:
            self.sock.close()


def _pump_until(feeder, records_in: int, out: list, timeout_s: float = 600.0):
    """Pump the feeder until it has taken `records_in` records in."""
    deadline = time.monotonic() + timeout_s
    seen = feeder.get_counters()["records_in"]
    while seen < records_in:
        out.extend(feeder.pump())
        c = feeder.get_counters()
        # a failed dispatch is swallowed and shed by the feeder: fail here
        check(c["emit_failures"] == 0 and c["degraded_entries"] == 0,
              "the feeder's dispatch into the fused step failed",
              emit_failures=c["emit_failures"], lost_records=c["lost_records"],
              degraded_entries=c["degraded_entries"])
        now = c["records_in"]
        if now == seen:  # nothing arrived yet: the frames are in flight
            check(time.monotonic() < deadline, "feeder starved",
                  want=records_in, counters=feeder.get_counters())
            time.sleep(0.0005)
        seen = now


def _feeder_health(feeder, receiver) -> dict:
    f = feeder.get_counters()
    return {
        "emit_failures": f["emit_failures"], "lost_records": f["lost_records"],
        "degraded_entries": f["degraded_entries"],
        "feeder_shed_records": f["shed_records"],
        "feeder_bad_frames": f["bad_frames"],
        "queue_overwritten": f["queue_overwritten"],
        "receiver_bad_frames": receiver.get_counters()["bad_frames"],
    }


def _pipeline_health(pipe) -> dict:
    p = pipe.get_counters()
    return {k: p[k] for k in (
        "stash_evictions", "prereduce_shed", "drop_before_window",
        "fetch_retries", "dispatch_retries", "jit_retraces")}


def make_pipeline(sz: Sizes, *, sketch=None, buckets=None):
    from deepflow_tpu.aggregator.pipeline import L4Pipeline, PipelineConfig
    from deepflow_tpu.aggregator.window import WindowConfig

    buckets = buckets or sz.buckets
    return L4Pipeline(PipelineConfig(
        window=WindowConfig(interval=1, delay=2, capacity=sz.capacity,
                            accum_batches=sz.accum_batches, sketch=sketch),
        batch_size=buckets[-1], bucket_sizes=buckets,
        batch_unique_cap=sz.unique_cap,
    ))


def drive_feeder(sink, drain, seconds: list, *, seed: int, tuples: int,
                 on_window=None):
    """Flow frames → Receiver (local socket) → queues → FeederRuntime →
    `sink` (a PipelineFeedSink or a ShardedFeedSink). `seconds` is
    [(timestamp, n_records)]. Calls `on_window(fb)` with each second's
    FlowBatch (the reference's input); `drain()` flushes the open
    windows at the end. Returns (flushed DocBatches before the drain,
    after the drain, feeder health counters, feed stats)."""
    from deepflow_tpu.feeder import (
        FeederConfig, FeederRuntime, encode_flowbatch_frames,
    )
    from deepflow_tpu.ingest.framing import MessageType
    from deepflow_tpu.ingest.queues import new_queue
    from deepflow_tpu.ingest.receiver import Receiver
    from deepflow_tpu.ingest.replay import SyntheticFlowGen

    gen = SyntheticFlowGen(num_tuples=tuples, seed=seed, start_time=T0)
    receiver = Receiver(tcp_port=0, udp_port=0)
    queues = [new_queue(1 << 12) for _ in range(4)]
    receiver.register_handler(MessageType.TAGGEDFLOW, queues)
    receiver.start()
    sender = FrameSender(receiver.tcp_port)
    feeder = FeederRuntime(queues, sink, FeederConfig(), name="chip_smoke")
    before, sent = [], 0
    gen_s = feed_s = 0.0
    try:
        for t, n in seconds:
            g0 = time.perf_counter()
            fb = gen.flow_batch(n, t)
            frames = encode_flowbatch_frames(fb, agent_id=1 + t % 7)
            gen_s += time.perf_counter() - g0
            f0 = time.perf_counter()
            sender.send(frames)
            sent += n
            if on_window is not None:
                on_window(fb)  # the reference runs while the frames fly
            _pump_until(feeder, sent, before)
            feed_s += time.perf_counter() - f0
        sender.join()
        before.extend(feeder.flush())
        closed_before = len({int(db.timestamp[0]) for db in before})
        after = drain()
        health = _feeder_health(feeder, receiver)
    finally:
        sender.close()
        receiver.stop()
    stats = {"records": sent, "gen_encode_s": gen_s, "feed_s": feed_s,
             "closed_before_drain": closed_before,
             "batches": feeder.get_counters()["batches_out"],
             "pad_rows": feeder.get_counters()["pad_rows"]}
    return before, after, health, stats


def drive_pipeline(pipe, seconds, **kw):
    """drive_feeder into a one-chip L4Pipeline; health gains the
    pipeline's own lanes."""
    from deepflow_tpu.feeder import PipelineFeedSink

    before, after, health, stats = drive_feeder(
        PipelineFeedSink(pipe), pipe.drain, seconds, **kw)
    return before, after, {**health, **_pipeline_health(pipe)}, stats


def _by_window(docbatches: list) -> dict:
    out: dict = {}
    for db in docbatches:
        check(bool(db.valid.all()), "flushed batch with invalid rows")
        w = int(db.timestamp[0])
        check(bool((db.timestamp == w).all()), "flushed batch spans windows")
        t, m = out.get(w, (None, None))
        out[w] = (db.tags if t is None else np.concatenate([t, db.tags]),
                  db.meters if m is None else np.concatenate([m, db.meters]))
    return out


def through_server(docs_by_window: dict, windows: list[int], tmpdir: str,
                   pipe) -> dict:
    """The chosen windows' documents as METRICS frames into the
    composed Server, then one SQL and one PromQL query, each compared
    with the reference's numbers for the same documents."""
    from deepflow_tpu.datamodel.batch import DocBatch
    from deepflow_tpu.datamodel.code import CodeId, DocumentFlag
    from deepflow_tpu.datamodel.schema import FLOW_METER, TAG_SCHEMA
    from deepflow_tpu.ingest.codec import encode_docbatch
    from deepflow_tpu.ingest.framing import FlowHeader, MessageType, encode_frame
    from deepflow_tpu.integration.dfstats import (
        DEEPFLOW_SYSTEM_DB, DEEPFLOW_SYSTEM_TABLE, system_metric_name, system_sink,
    )
    from deepflow_tpu.querier.promql import query_instant
    from deepflow_tpu.server.main import Server
    from deepflow_tpu.utils.config import load_config
    from deepflow_tpu.utils.stats import default_collector

    cfg, _ = load_config({
        "receiver": {"tcp_port": 0, "udp_port": 0},
        "ingester": {"n_decoders": 2},
        "storage": {"root": os.path.join(tmpdir, "store"), "writer_flush_s": 0.2},
    })
    srv = Server(cfg).start()
    sink = system_sink(srv.store)
    default_collector.add_sink(sink)
    try:
        t0 = time.perf_counter()
        frames, n_docs = [], 0
        for w in windows:
            tags, meters = docs_by_window[w]
            db = DocBatch(tags=tags, meters=meters,
                          timestamp=np.full(tags.shape[0], w, np.uint32),
                          valid=np.ones(tags.shape[0], bool))
            msgs = encode_docbatch(db, flags=int(DocumentFlag.PER_SECOND_METRICS))
            n_docs += len(msgs)
            for off in range(0, len(msgs), 1024):
                header = FlowHeader(msg_type=int(MessageType.METRICS), agent_id=1)
                frames.append(encode_frame(header, msgs[off:off + 1024]))
        encode_s = time.perf_counter() - t0
        sender = FrameSender(srv.receiver.tcp_port)
        try:
            sender.send(frames)
            deadline = time.monotonic() + 600
            while srv.flow_metrics.get_counters()["docs_written"] < n_docs:
                check(time.monotonic() < deadline, "server did not write the documents",
                      want=n_docs, counters=srv.flow_metrics.get_counters())
                time.sleep(0.05)
        finally:
            sender.close()
        srv.doc_writer.flush()
        ingest_s = time.perf_counter() - t0 - encode_s
        fm = srv.flow_metrics.get_counters()
        check(fm["decode_errors"] == 0 and fm["drop_other_region"] == 0
              and fm["docs_written"] == n_docs, "server ingest counters", **fm)

        # SQL: per-window totals of the single-side and the edge tables
        tags = np.concatenate([docs_by_window[w][0] for w in windows])
        meters = np.concatenate([docs_by_window[w][1] for w in windows])
        code = tags[:, TAG_SCHEMA.index("code_id")]
        is_edge = code == int(CodeId.EDGE_IP_PORT)
        sql = {}
        for table, rows in (("network.1s", ~is_edge), ("network_map.1s", is_edge)):
            res = srv.query.execute(
                f"SELECT Count() AS c, Sum(byte_tx) AS b, Max(rtt_max) AS r "
                f"FROM {table}"
            )
            got = {k: float(res.values[k][0]) for k in ("c", "b", "r")}
            m64 = meters[rows].astype(np.float64)
            want = {
                "c": float(rows.sum()),
                "b": float(m64[:, FLOW_METER.index("byte_tx")].sum()),
                "r": float(m64[:, FLOW_METER.index("rtt_max")].max(initial=0.0)),
            }
            check(got["c"] == want["c"] and got["r"] == want["r"]
                  and abs(got["b"] - want["b"]) <= SQL_SUM_RTOL * max(want["b"], 1.0),
                  f"SQL over {table}", got=got, want=want)
            sql[table] = got

        # PromQL over the server's own telemetry: what the device flushed
        # (the pipeline's counter) and what the store took in
        srv.tick()
        now = int(time.time()) + 1
        prom = {}
        for metric, want in (
            (system_metric_name("tpu_pipeline", "flushed_doc"),
             float(pipe.get_counters()["flushed_doc"])),
            (system_metric_name("flow_metrics_ingester", "docs_written"),
             float(n_docs)),
        ):
            out = query_instant(srv.store, f"sum({metric})", now,
                                db=DEEPFLOW_SYSTEM_DB, table=DEEPFLOW_SYSTEM_TABLE)
            check(len(out) == 1 and out[0]["value"] == want,
                  f"PromQL {metric}", got=out, want=want)
            prom[metric] = out[0]["value"]
        return {"server_docs": n_docs, "server_windows": len(windows),
                "doc_encode_s": encode_s, "server_ingest_s": ingest_s,
                "sql": sql, "promql": prom}
    finally:
        default_collector.remove_sink(sink)
        srv.stop()


def phase_served(sz: Sizes, seed: int, tmpdir: str) -> dict:
    from deepflow_tpu.aggregator.fanout import FanoutConfig
    from deepflow_tpu.oracle.numpy_oracle import oracle_l4_rollup

    pipe = make_pipeline(sz)
    # second 0 carries ONLY the oracle-checked prefix; `windows`
    # full-size windows must close before the drain (3 stay open)
    n_full = sz.windows + 3
    seconds = [(T0, sz.prefix)] + [
        (T0 + 1 + i, sz.records_per_window) for i in range(n_full)
    ]
    # the reference runs on its own threads, beside the feed
    pool = concurrent.futures.ThreadPoolExecutor(max_workers=4)
    futures: dict = {}
    prefix_fb = []

    def on_window(fb):
        if not prefix_fb:
            prefix_fb.append(fb)
        futures[int(fb.tags["timestamp"][0])] = pool.submit(reference_docs, fb)

    before, after, health, stats = drive_pipeline(
        pipe, seconds, seed=seed, tuples=sz.tuples, on_window=on_window
    )
    want = {w: f.result() for w, f in futures.items()}
    check(all(v == 0 for v in health.values()), "served path shed, failed or retraced",
          **health)
    check(stats["closed_before_drain"] >= sz.windows + 1,
          "too few windows closed before the drain", **stats)
    got = _by_window(before + after)
    check(sorted(got) == sorted(want), "flushed windows", got=sorted(got),
          want=sorted(want))
    cmp = dict(zip(sorted(want), pool.map(
        lambda w: compare_docs(*got[w], *want[w], what=f"window {w - T0}"),
        sorted(want))))
    pool.shutdown()
    distinct = _group_rows(np.ascontiguousarray(np.concatenate(
        [want[w][0] for w in want if w != T0]).T))[1].size
    # the prefix window against the repo's scalar oracle
    oracle = oracle_l4_rollup(flowbatch_records(prefix_fb[0]), FanoutConfig())
    oc = compare_docs(*got[T0], *oracle_arrays(oracle), what="oracle prefix")
    full = [w for w in sorted(got) if w != T0]
    served = through_server(got, full[:sz.server_windows], tmpdir, pipe)
    c = pipe.get_counters()
    pipe.close()
    return {
        "records": stats["records"], "windows": len(got),
        "closed_before_drain": stats["closed_before_drain"],
        "docs": sum(v["docs"] for v in cmp.values()),
        "docs_per_full_window": cmp[full[0]]["docs"],
        "distinct_doc_keys": int(distinct),
        "sum_max_rel_err": max(v["sum_max_rel_err"] for v in cmp.values()),
        "oracle_prefix_docs": oc["docs"], "health": health,
        "gen_encode_s": stats["gen_encode_s"], "feed_s": stats["feed_s"],
        "feed_records_per_s": stats["records"] / max(stats["feed_s"], 1e-9),
        "batches": stats["batches"], "pad_rows": stats["pad_rows"],
        "jit_compiles": c["jit_compiles"], "host_fetches": c["host_fetches"],
        "stash_occupancy_last": c["stash_occupancy"],
        "server_docs_cut": f"{sz.server_windows} of {len(full)} full windows",
        **served,
    }


# ---------------------------------------------------------------------------
# kernel cycle: bench.py's append×2 + fold


def phase_kernel(sz: Sizes, seed: int) -> dict:
    import jax
    import jax.numpy as jnp

    from deepflow_tpu.aggregator.fanout import FANOUT_LANES, FanoutConfig
    from deepflow_tpu.aggregator.pipeline import make_ingest_step
    from deepflow_tpu.aggregator.stash import accum_init, stash_init
    from deepflow_tpu.datamodel.schema import FLOW_METER, TAG_SCHEMA
    from deepflow_tpu.ingest.replay import SyntheticFlowGen
    from deepflow_tpu.ops.segment import _use_pallas_reduce

    gen = SyntheticFlowGen(num_tuples=sz.kernel_tuples, seed=seed, start_time=T0)
    fbs = [gen.flow_batch(sz.kernel_batch, T0), gen.flow_batch(sz.kernel_batch, T0)]
    append_fn, fold_fn = make_ingest_step(
        FanoutConfig(), interval=1, batch_unique_cap=sz.kernel_unique_cap
    )
    append = jax.jit(append_fn, donate_argnums=(0, 1))
    fold = jax.jit(fold_fn, donate_argnums=(0, 1))
    stride = FANOUT_LANES * sz.kernel_unique_cap
    state = stash_init(sz.kernel_capacity, TAG_SCHEMA, FLOW_METER)
    acc = accum_init(2 * stride, TAG_SCHEMA, FLOW_METER)

    def args(fb):
        return ({k: jnp.asarray(v) for k, v in fb.tags.items()},
                jnp.asarray(fb.meters), jnp.asarray(fb.valid))

    a0 = args(fbs[0])
    pallas = _use_pallas_reduce()
    # compile once, ahead of time, and run those executables: their HLO
    # is the proof that the Pallas kernel (not a reference) ran
    append = append.lower(state, acc, jnp.int32(0), *a0).compile()
    fold = fold.lower(state, acc).compile()
    hlo_has_kernel = {"append": "tpu_custom_call" in append.as_text(),
                      "fold": "tpu_custom_call" in fold.as_text()}
    if pallas:
        check(all(hlo_has_kernel.values()),
              "the compiled kernel cycle holds no Pallas call", **hlo_has_kernel)

    t0 = time.perf_counter()
    for k, fb in enumerate(fbs):
        state, acc = append(state, acc, jnp.int32(k * stride), *args(fb))
    state, acc = fold(state, acc)
    jax.block_until_ready(state)
    first_s = time.perf_counter() - t0

    # steady: the same cycle again on device-resident inputs (a second
    # identical cycle folds the same keys; SUM lanes double)
    a1 = args(fbs[1])
    jax.block_until_ready((a0, a1))
    np.asarray(state.slot[:1])  # compiles the tiny slice timed below
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        for k, a in enumerate((a0, a1)):
            state, acc = append(state, acc, jnp.int32(k * stride), *a)
        state, acc = fold(state, acc)
        jax.block_until_ready(state)
        times.append(time.perf_counter() - t0)
    cycles = 4
    # does block_until_ready wait for the device? If it returned early,
    # this tiny (already compiled) fetch would take the rest of a cycle
    t0 = time.perf_counter()
    np.asarray(state.slot[:1])
    fetch_after_block_s = time.perf_counter() - t0

    valid = np.asarray(state.valid)
    check(int(np.asarray(state.dropped_overflow)) == 0, "kernel cycle shed rows")
    got_tags = np.asarray(state.tags).T[valid]
    got_meters = np.asarray(state.meters).T[valid]
    from deepflow_tpu.datamodel.batch import FlowBatch

    want_tags, want_meters = reference_docs(FlowBatch.concat(fbs))
    from deepflow_tpu.datamodel.schema import MergeOp

    is_sum = np.array([f.op is MergeOp.SUM for f in FLOW_METER.fields])
    want_meters = np.where(is_sum[None, :], want_meters * cycles, want_meters)
    cmp = compare_docs(got_tags, got_meters, want_tags, want_meters,
                       what="kernel cycle")
    return {
        "batch": sz.kernel_batch, "capacity": sz.kernel_capacity,
        "unique_cap": sz.kernel_unique_cap, "pallas": pallas,
        "hlo_has_kernel": hlo_has_kernel, "first_cycle_s": first_s,
        "steady_cycle_s": times, "steady_cycle_median_s": float(np.median(times)),
        "fetch_after_block_s": fetch_after_block_s,
        "records_per_cycle": 2 * sz.kernel_batch, **cmp,
    }


# ---------------------------------------------------------------------------
# sketch plane


def phase_sketch(sz: Sizes, seed: int) -> dict:
    from deepflow_tpu.aggregator.sketchplane import SketchConfig

    # one bucket: a third fused-step shape, and no more, to compile
    pipe = make_pipeline(sz, sketch=SketchConfig(hll_precision=sz.hll_precision),
                         buckets=sz.buckets[-1:])
    clients: dict = {}

    def on_window(fb):
        ip0 = np.stack([fb.tags[f"ip0_w{w}"] for w in range(4)])
        clients[int(fb.tags["timestamp"][0])] = _group_rows(ip0)[1].size

    seconds = [(T0 + i, sz.sketch_records) for i in range(2)]
    before, after, health, stats = drive_pipeline(
        pipe, seconds, seed=seed + 1, tuples=sz.tuples, on_window=on_window
    )
    check(all(v == 0 for v in health.values()), "sketch run shed, failed or retraced",
          **health)
    blocks = {b.window: b for b in pipe.pop_closed_sketches()}
    check(sorted(blocks) == sorted(clients), "closed sketch blocks",
          got=sorted(blocks), want=sorted(clients))
    errs = {}
    for w, want in clients.items():
        est = blocks[w].distinct()
        errs[w - T0] = {"estimate": est, "exact": want,
                        "rel_err": abs(est - want) / want}
        check(abs(est - want) <= 0.01 * want, "HLL distinct count off by >1%",
              **errs[w - T0])
        check(blocks[w].n_updates > 0 and int(blocks[w].cms.sum()) > 0
              and blocks[w].tk_votes.size > 0, "count-min / top-K lanes empty")
    c = pipe.get_counters()
    check(c["sketch_rows"] > 0 and c["sketch_shed"] == 0, "sketch plane lanes",
          sketch_rows=c["sketch_rows"], sketch_shed=c["sketch_shed"])
    docs = sum(db.size for db in before + after)
    pipe.close()
    return {"records": stats["records"], "windows": len(blocks), "docs": docs,
            "distinct": errs, "sketch_rows": c["sketch_rows"], "health": health}


# ---------------------------------------------------------------------------
# four chips: the sharded served path against the one-chip manager


def _spread(tree, n_devices: int, what: str) -> int:
    """Every leaf of `tree` must hold one shard on each of the mesh's
    devices — state spread over the chips, not all on the first.
    Returns the bytes device 0 holds."""
    import jax

    on_first = 0
    for leaf in jax.tree.leaves(tree):
        if leaf.size == 0:  # lanes a mode leaves empty (pool off)
            continue
        shards = leaf.addressable_shards
        devs = {sh.device.id for sh in shards}
        check(len(devs) == n_devices, f"{what} is not spread over the mesh",
              devices=sorted(devs), shape=leaf.shape)
        check(all(sh.data.shape[0] == leaf.shape[0] // n_devices for sh in shards),
              f"{what} shards are not even", shape=leaf.shape)
        on_first += sum(sh.data.nbytes for sh in shards
                        if sh.device.id == min(devs))
    return on_first


def phase_sharded(sz: Sizes, seed: int, n_devices: int = 4) -> dict:
    """ShardedFeedSink / ShardedWindowManager on an n-device mesh with
    the cascade on, and the one-chip WindowManager (L4Pipeline) on the
    same records: the rows must be equal. The sharded manager keeps one
    exact stash per device and concatenates their rows at the flush, so
    its documents are merged by key here before the comparison."""
    from deepflow_tpu.datamodel.schema import FLOW_METER, TAG_SCHEMA, MergeOp
    from deepflow_tpu.feeder import ShardedFeedSink
    from deepflow_tpu.parallel.mesh import make_mesh
    from deepflow_tpu.parallel.sharded import (
        ShardedConfig, ShardedPipeline, ShardedWindowManager,
    )

    bucket = sz.buckets[-1]
    seconds = [(T0 + i, sz.records_per_window) for i in range(sz.windows + 3)]
    mesh = make_mesh(n_devices, n_hosts=1)
    cfg = ShardedConfig(
        # records spread by batch position, so every device sees almost
        # every key: each holds half the one-chip stash
        capacity_per_device=sz.capacity // 2, accum_batches=sz.accum_batches,
        batch_unique_cap=bucket // n_devices, num_services=16,
        hll_precision=sz.hll_precision, cascade=(60,),
        cascade_capacity=sz.capacity // 2,
    )
    swm = ShardedWindowManager(ShardedPipeline(mesh, cfg), delay=2)
    t0 = time.perf_counter()
    before, after, health, stats = drive_feeder(
        ShardedFeedSink(swm, (bucket,)), swm.drain, seconds,
        seed=seed, tuples=sz.tuples,
    )
    sharded_s = time.perf_counter() - t0
    c = swm.get_counters()
    health.update(
        stash_overflow=int(np.asarray(swm.stash.dropped_overflow).sum()),
        drop_before_window=c["drop_before_window"],
        fetch_retries=c["fetch_retries"], dispatch_retries=c["dispatch_retries"],
        cascade_shed=c["cascade_shed"], sketch_blocks_dropped=c["sketch_blocks_dropped"],
    )
    check(all(v == 0 for v in health.values()), "sharded path shed or failed",
          **health)
    check(c["cascade_rows"] > 0, "the cascade folded no rows", **c)
    spread = {
        "stash_bytes_on_first": _spread(swm.stash, n_devices, "stash"),
        "sketch_bytes_on_first": _spread(swm.sketches, n_devices, "sketch state"),
        "cascade_bytes_on_first": _spread(swm.tier_stashes, n_devices,
                                          "cascade tier stash"),
    }
    sharded = _by_window(before + after)
    n_blocks = len(swm.pop_closed_sketches())
    swm.close()

    pipe = make_pipeline(sz, buckets=(bucket,))
    t0 = time.perf_counter()
    b1, a1, h1, _ = drive_pipeline(pipe, seconds, seed=seed, tuples=sz.tuples)
    one_chip_s = time.perf_counter() - t0
    check(all(v == 0 for v in h1.values()), "one-chip comparison shed or failed",
          **h1)
    one = _by_window(b1 + a1)
    pipe.close()

    check(sorted(sharded) == sorted(one), "flushed windows differ",
          sharded=sorted(sharded), one_chip=sorted(one))
    sum_mask = np.array([f.op is MergeOp.SUM for f in FLOW_METER.fields])
    docs = 0
    partial_rows = 0
    for w in sorted(one):
        tags, meters = sharded[w]
        partial_rows += tags.shape[0]
        first, merged = _group_reduce(
            np.ascontiguousarray(tags[:, TAG_SCHEMA.key_mask].T), meters, sum_mask)
        docs += compare_docs(tags[first], merged.astype(np.float32),
                             one[w][0], one[w][1].astype(np.float64),
                             what=f"sharded vs one chip, window {w - T0}")["docs"]
    return {
        "devices": n_devices, "records": stats["records"], "windows": len(one),
        "closed_before_drain": stats["closed_before_drain"], "docs": docs,
        "sharded_partial_rows": partial_rows, "sketch_blocks": n_blocks,
        "cascade_rows": c["cascade_rows"], "sharded_s": sharded_s,
        "one_chip_s": one_chip_s, "health": health, **spread,
    }


# ---------------------------------------------------------------------------


def run_phases(sz: Sizes, seed: int, clock=None, cache=None) -> dict:
    """The three one-chip phases in order; raises SmokeFailure on the
    first that fails. Tests call this on the CPU at `Sizes.tiny()`."""

    clock = clock or CompileClock()
    cache = cache or CacheCounter()
    out = {}
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmpdir:
        with Phase("served", clock, cache):
            out["served"] = phase_served(sz, seed, tmpdir)
            say(phase="served", **out["served"])
    with Phase("kernel", clock, cache):
        out["kernel"] = phase_kernel(sz, seed)
        say(phase="kernel", **out["kernel"])
    with Phase("sketch", clock, cache):
        out["sketch"] = phase_sketch(sz, seed)
        say(phase="sketch", **out["sketch"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--records-per-window", type=int, default=None,
                    help="cut records per window (never widths or keys)")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {device}", file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found {device}",
              file=sys.stderr)
        return 2

    from deepflow_tpu import native
    from deepflow_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    cache = CacheCounter()
    clock = CompileClock()
    decoder = "native (built from native/src)" if native.rebuild() else (
        f"python codec (no native build: {native.build_error()})")
    import jaxlib

    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:
        libtpu = "unknown"
    sz = Sizes.full()
    if args.records_per_window:
        say(cut=f"records per window {sz.records_per_window} -> "
            f"{args.records_per_window}")
        sz = dataclasses.replace(sz, records_per_window=args.records_per_window,
                                 sketch_records=args.records_per_window)
    say(device=device, jax=jax.__version__, jaxlib=jaxlib.__version__,
        libtpu=libtpu, python=sys.version.split()[0], decoder=decoder,
        cache_dir=cache_dir,
        cache_entries_at_start=len(os.listdir(cache_dir))
        if os.path.isdir(cache_dir) else 0,
        sizes=dataclasses.asdict(sz), seed=args.seed)

    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            with Phase("sharded", clock, cache):
                say(phase="sharded", **phase_sharded(sz, args.seed, 4))
        else:
            run_phases(sz, args.seed, clock, cache)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    say(total_s=time.perf_counter() - t0, compile_s=clock.compile_s,
        cache_hits=cache.hits, cache_misses=cache.misses,
        peak_bytes_in_use=(dev.memory_stats() or {}).get("peak_bytes_in_use"))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
