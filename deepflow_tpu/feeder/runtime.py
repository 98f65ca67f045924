"""Feeder runtime — multi-queue fan-in for the fused windowed step.

The fused per-batch jit step (aggregator/pipeline.py) runs at device
rate, but nothing upstream could feed it at rate: the receiver fans
frames into bare OverwriteQueues and every caller hand-rolled its own
batch assembly, so the device idled between host-side decode bursts.
The FPGA sketch-acceleration literature hits the same wall — the sketch
core only reaches line rate once a dedicated feed stage owns
coalescing, padding and result drain-out (arXiv:2504.16896,
arXiv:2503.13515). This module is that stage:

  * **fan-in**: drain N overwrite queues round-robin (optionally
    weighted), rotating the start queue each pump so no queue starves;
  * **shape-bucketed coalescing**: decoded records accumulate in a
    pending buffer and emit as fixed-shape batches from a small set of
    buckets (pad-to-bucket) — the fused step compiles once per bucket
    and NEVER retraces across mixed traffic (JitCacheMonitor's
    expected_compiles budget covers the bucket set);
  * **backpressure + deterministic shedding**: per-queue high/low
    watermarks with hysteresis; a queue above its high watermark gets a
    doubled drain budget but only the NEWEST half is admitted — the
    oldest frames are shed WHOLE (never partial batches), counted
    per-frame via a header peek (no decode), and accounted both in the
    feeder's Countable counters (→ deepflow_system via the stats
    sinks) and in the device counter block's CB_FEEDER_SHED lane on
    the next dispatched batch;
  * **double-buffered upload**: the pipeline sink stages batch i+1's
    packed tag matrix (async device put) before dispatching batch i,
    mirroring `async_drain` on the output side;
  * **one write a record**: a decoded frame stays views of its bytes
    (FlowChunk) until the sink writes it into a reused staging buffer
    that already has the upload's shape (datamodel/batch.StagingRing) —
    nothing is concatenated, padded or stacked on the way.

Fault tolerance (ISSUE 6) — every failure class on the
feeder→device→flush path is either retried, contained, or counted:

  * **poisoned-frame quarantine**: sink codecs catch ALL decode
    failures at the `decode_frame` boundary (FrameCodecBase), count
    them, and park the head bytes in a bounded quarantine ring —
    corrupt wire data never raises into `pump()`;
  * **graceful degradation**: when a sink dispatch fails even after
    the window manager's transient-retry policy, the runtime flips to
    DEGRADED: drain budgets halve, admitted frames are shed WHOLE and
    counted (`lost_records`/`degraded_shed_records` — no uncounted
    loss), and every `probe_interval` pumps one probe batch flows
    through the full dispatch path; a success flips back to healthy.
    The state machine is (healthy) --emit fail--> (degraded, probe
    countdown) --probe ok--> (healthy);
  * **crash-loop guard**: `serve()` wraps every pump in a containment
    try — a pump exception restarts the loop with capped exponential
    backoff and a counted health state (`pump_errors`,
    `pump_failstreak`) instead of silently killing the daemon thread;
  * **frame journal**: with `journal=` set, every admitted frame is
    appended (pump boundaries marked) BEFORE decode, so recovery =
    restore the window checkpoint + `replay_journal` through the
    normal decode path — bit-exact against an uninterrupted run
    (journal.py has the barrier protocol; `checkpoint()` is the
    flush→snapshot→rotate barrier).

Sinks adapt the record plane to each window controller:
`PipelineFeedSink` (flow records → RollupPipeline's fused step),
`WindowManagerFeedSink` (pb Documents via ingest/codec.py → the
doc-level WindowManager append), `ShardedFeedSink` (flow records → one
ShardedWindowManager per shard group; run one FeederRuntime per group).
"""

from __future__ import annotations

import dataclasses
import logging
import threading
import time
from collections import deque
from pathlib import Path

import numpy as np

from .. import chaos
from ..datamodel.batch import StagingBuffer, StagingRing
from ..datamodel.schema import FLOW_METER
from ..ingest.framing import HEADER_LEN, FlowHeader, MessageType, split_message_spans
from ..utils.spans import (
    SPAN_FEEDER_ASSEMBLE,
    SPAN_FEEDER_COALESCE,
    SPAN_FEEDER_DECODE,
    SPAN_FEEDER_DISPATCH,
    SPAN_FEEDER_DRAIN,
    SPAN_FEEDER_PUMP,
    SPAN_FEEDER_STAGING_WAIT,
    SpanTracer,
)
from ..utils.retry import RetryPolicy, decorrelated_rng
from ..utils.stats import register_countable
from .flowframe import decode_flowframe_matrices, peek_rows

_log = logging.getLogger(__name__)

# span name → the counter FeederRuntime.get_counters() republishes its CPU
# lane under (`feeder.pump` → `pump_cpu_us`): the six that are read through
# the harness's counters plane (`feeder.staging_wait`'s lane stays on the
# tracer's own face)
_CPU_LANE_COUNTERS = {
    name: f"{name.removeprefix('feeder.')}_cpu_us"
    for name in (SPAN_FEEDER_PUMP, SPAN_FEEDER_DRAIN, SPAN_FEEDER_COALESCE,
                 SPAN_FEEDER_DECODE, SPAN_FEEDER_DISPATCH, SPAN_FEEDER_ASSEMBLE)
}

# ---------------------------------------------------------------------------
# record chunks — what decoded frames become inside the pending buffer


@dataclasses.dataclass
class FlowChunk:
    """Flow records (pre-fanout) as their frame held them: `tags` the
    [T, n] u32 matrix in FLOW_RECORD_TAG_FIELDS order, `meters` [n, M]
    f32 — views of the frame's bytes; splitting slices them."""

    tags: np.ndarray
    meters: np.ndarray

    @property
    def rows(self) -> int:
        return int(self.meters.shape[0])

    def split(self, n: int) -> tuple["FlowChunk", "FlowChunk"]:
        return (FlowChunk(self.tags[:, :n], self.meters[:n]),
                FlowChunk(self.tags[:, n:], self.meters[n:]))


@dataclasses.dataclass
class DocChunk:
    """Decoded Documents (post-fanout) for the doc-level append path."""

    timestamp: np.ndarray  # [n] u32
    tags: np.ndarray  # [n, T] u32 (TAG_SCHEMA order)
    meters: np.ndarray  # [n, M] f32

    @property
    def rows(self) -> int:
        return int(self.timestamp.shape[0])

    def split(self, n: int) -> tuple["DocChunk", "DocChunk"]:
        a = DocChunk(self.timestamp[:n], self.tags[:n], self.meters[:n])
        b = DocChunk(self.timestamp[n:], self.tags[n:], self.meters[n:])
        return a, b


# ---------------------------------------------------------------------------
# sinks

QUARANTINE_KEEP = 8  # poisoned frames retained for diagnosis (head bytes)


class FrameCodecBase:
    """The poisoned-frame quarantine boundary every sink codec shares.

    `decode_frame` NEVER raises: any failure — magic/version/field
    drift, truncation, a decoder bug, an injected chaos fault — is
    counted (`decode_errors`), the frame's head bytes parked in a
    bounded `quarantine` ring, and None returned, so a hostile frame
    is isolated without touching the pump loop (ISSUE 6). Subclasses
    implement `_decode_frame` with the untrusted-edge raise-on-drift
    stance decoders already take."""

    def __init__(self):
        self.decode_errors = 0
        self.quarantine: deque = deque(maxlen=QUARANTINE_KEEP)
        # where the sink's feeder-side spans go (feeder.assemble): its
        # own tracer until a FeederRuntime adopts the sink and hands it
        # the runtime's, so a feeder.* name lives on one tracer
        self.tracer = SpanTracer(service="deepflow_tpu.feeder")

    def _decode_frame(self, raw: bytes):
        raise NotImplementedError

    def decode_frame(self, raw: bytes):
        try:
            chaos.maybe_fail(chaos.SITE_DECODE)
            return self._decode_frame(raw)
        except Exception as exc:
            self.decode_errors += 1
            self.quarantine.append(
                (type(exc).__name__, str(exc)[:160], bytes(raw[:64]))
            )
            return None


class _FlowFrameCodec(FrameCodecBase):
    """Shared decode face for sinks that eat flowframe (TAGGEDFLOW)
    frames, and the one place their batches are assembled. `staging`
    is the ring the batches are written into (the consumer's own where
    it has one, else one made here); `host_copy_bytes` counts every byte
    the feed writes to host memory between a decoded frame and the
    upload, zero fill included, `staging_waits` the times a writer
    found the ring's next buffer still in flight and `staging_wait_us`
    how long it then waited for the device to have read it (all three
    reach the feeder's get_counters())."""

    def __init__(self, staging: StagingRing | None = None):
        super().__init__()
        self.staging = staging or StagingRing(FLOW_METER.num_fields)
        self.host_copy_bytes = 0
        self.staging_wait_us = 0

    @property
    def staging_waits(self) -> int:
        return self.staging.waits

    def count_records(self, raw: bytes) -> int:
        body = raw[HEADER_LEN:]
        return sum(peek_rows(body[o : o + ln]) for o, ln in split_message_spans(body))

    def _decode_frame(self, raw: bytes) -> FlowChunk | None:
        header = FlowHeader.parse(raw[:HEADER_LEN])
        if header.msg_type != int(MessageType.TAGGEDFLOW):
            raise ValueError(f"flow sink got msg_type {header.msg_type}")
        body = raw[HEADER_LEN:]
        parts = [
            decode_flowframe_matrices(body[o : o + ln])
            for o, ln in split_message_spans(body)
        ]
        if not parts:
            return None
        if len(parts) == 1:
            return FlowChunk(*parts[0])
        # a frame of several messages (no sender of this repo makes one)
        # is joined here, and the join counted with the feed's copies
        chunk = FlowChunk(np.concatenate([t for t, _ in parts], axis=1),
                          np.concatenate([m for _, m in parts]))
        self.host_copy_bytes += chunk.tags.nbytes + chunk.meters.nbytes
        return chunk

    def _assemble(self, chunks: list[FlowChunk], rows: int, bucket: int) -> StagingBuffer:
        """One batch's chunks → one staging buffer of `bucket` rows in
        the upload's layout (datamodel/batch.StagingBuffer): each chunk
        written once where the upload reads it, the stale tail zeroed.
        The wait for a buffer the device is still reading is a span of
        its own, kept only when it blocked."""
        with self.tracer.span(SPAN_FEEDER_ASSEMBLE):
            buf = self.staging.offer(bucket)
            with self.tracer.span(SPAN_FEEDER_STAGING_WAIT) as wait:
                blocked = self.staging.settle(buf)
                if not blocked:
                    wait.discard()
            if blocked:
                self.staging_wait_us += wait.duration_us
            copied = sum(buf.write(c.tags, c.meters) for c in chunks)
            assert buf.rows == rows
            self.host_copy_bytes += copied + buf.finish()
            return buf


class PipelineFeedSink(_FlowFrameCodec):
    """Flow records → RollupPipeline (the fused windowed step), with the
    double-buffered upload: `emit` STAGES the new batch (async device
    put) and dispatches the PREVIOUSLY staged one, so the tag-matrix
    transfer of batch i+1 overlaps batch i's in-flight compute. Outputs
    therefore trail by one emitted batch until flush().

    Dispatch-failure contract: when the held batch's dispatch raises,
    its rows are counted into `lost_records` and the FRESHLY staged
    batch survives in the double buffer — the runtime's next (probe)
    emit dispatches it, so one device hiccup costs exactly one batch."""

    def __init__(self, pipeline, *, double_buffer: bool = True):
        super().__init__(pipeline.staging)
        if not pipeline.config.bucket_sizes:
            raise ValueError(
                "PipelineFeedSink needs PipelineConfig.bucket_sizes — the "
                "feeder's pad-to-bucket contract is what keeps the fused "
                "step from retracing"
            )
        self.pipeline = pipeline
        self.double_buffer = double_buffer
        self.bucket_sizes = tuple(pipeline.config.bucket_sizes)
        self._held = None  # (StagedBatch, shed, rows) awaiting dispatch
        self._shed_carry = 0  # shed count whose batch had no valid rows
        self.lost_records = 0  # rows lost to failed dispatches
        # device profiling plane (ISSUE 12): the double-buffered staged
        # upload (tag matrix + meters + valid, device handles awaiting
        # dispatch) is HBM this sink owns — weakly registered so the
        # ledger's tpu_hbm_staged_bytes lane shows the feeder's upload
        # footprint next to the manager's planes
        from ..profiling.ledger import register_profilable

        self._ledger_src = register_profilable("feeder_sink", self)

    def device_planes(self) -> dict:
        held = self._held
        staged = held[0] if held is not None else None
        return {
            "staged": None if staged is None else [
                staged.tag_mat, staged.meters, staged.valid
            ],
        }

    def emit(self, chunks: list[FlowChunk], rows: int, bucket: int, shed: int) -> list:
        buf = self._assemble(chunks, rows, bucket)
        carried = self._shed_carry
        shed += carried
        self._shed_carry = 0
        try:
            staged = self.pipeline.stage(buf)  # starts the three uploads
        except Exception:
            # admission itself failed (e.g. device OOM on the async
            # put): this batch's rows are gone and must be counted, or
            # delivered = records_out − lost_records over-reports. The
            # runtime re-arms only the shed IT passed in, so the carry
            # must go back into the buffer or it undercounts the
            # device-plane feeder_shed lane. The staging buffer went
            # back to its ring: no staged batch holds it.
            self.lost_records += rows
            self._shed_carry += carried
            raise
        try:
            out = self.flush()  # dispatch the previously staged batch
        except Exception:
            # the HELD batch failed (flush counted its rows lost); keep
            # the new batch staged for the probe emit. The runtime
            # re-owns `shed` (it re-arms _shed_pending on failure); the
            # carry goes back into the buffer.
            # += not =: flush() may have just deposited the failed
            # batch's own held_shed into the carry
            if staged is not None:
                self._held = (staged, 0, rows)
            self._shed_carry += carried
            raise
        if staged is None:  # all-padding emit — carry its shed forward
            self._shed_carry = shed
        elif self.double_buffer:
            self._held = (staged, shed, rows)
        else:
            try:
                out += self.pipeline.ingest_staged(staged, feeder_shed=shed)
            except Exception:
                # same contract as the stage()/flush() failure paths:
                # the runtime re-arms only the shed IT passed in, so the
                # carried share must go back into the buffer or the
                # device-plane feeder_shed lane permanently undercounts
                self.lost_records += rows
                self._shed_carry += carried
                raise
        return out

    def flush(self) -> list:
        """Dispatch the held double-buffered batch, if any."""
        if self._held is None:
            return []
        held, held_shed, held_rows = self._held
        self._held = None
        try:
            return self.pipeline.ingest_staged(held, feeder_shed=held_shed)
        except Exception:
            # the batch's rows are lost (counted), but its attached shed
            # count must survive into the carry or the device-plane
            # feeder_shed lane permanently undercounts
            self.lost_records += held_rows
            self._shed_carry += held_shed
            raise

    def snapshot(self):
        """Live read plane (ISSUE 10): refresh the pipeline's open
        window snapshot (rate-limited) — the feeder's between-pump
        scheduling hook."""
        return self.pipeline.snapshot_open()


class ShardedFeedSink(_FlowFrameCodec):
    """Flow records → ShardedWindowManager (one feeder per shard
    group). Buckets must be divisible by the mesh's device count — the
    sharded step splits the leading dim evenly across devices."""

    def __init__(self, swm, bucket_sizes: tuple[int, ...]):
        super().__init__()
        d = swm.pipe.n_devices
        bad = [b for b in bucket_sizes if b % d]
        if bad:
            raise ValueError(
                f"bucket sizes {bad} not divisible by device count {d}"
            )
        self.swm = swm
        self.bucket_sizes = tuple(bucket_sizes)
        self.feeder_shed = 0  # sharded path has no device counter block
        # one compile of the sharded step a bucket; more is a retrace
        swm.expect_batch_shapes(len(self.bucket_sizes))

    def emit(self, chunks: list[FlowChunk], rows: int, bucket: int, shed: int) -> list:
        buf = self._assemble(chunks, rows, bucket)
        out = self.swm.ingest(buf.tag_columns(), buf.meters, buf.valid)
        # the sharded step has no per-batch sync and its outputs are
        # donated to the next one, so the buffer waits on the manager's
        # handle for the step it just dispatched
        if self.swm.step_done is not None:
            buf.dispatched(self.swm.step_done)
        # only account the shed once the batch actually landed — on a
        # failed dispatch the runtime re-owns it
        self.feeder_shed += shed
        return out

    def flush(self) -> list:
        return []

    def snapshot(self):
        """Refresh the sharded manager's open-window snapshot (the
        feeder's between-pump live-read hook, ISSUE 10)."""
        return self.swm.snapshot_open()


class WindowManagerFeedSink(FrameCodecBase):
    """pb Documents (METRICS lane, ingest/codec.py) → the doc-level
    WindowManager append. Keys are the packed-word fingerprints
    computed host-side with the SAME plan the device uses
    (DOC_KEY_PACK + fingerprint64_words), so feeder-fed rows merge with
    device-fingerprinted rows for the same logical key."""

    def __init__(self, wm, bucket_sizes: tuple[int, ...], *, meter_id=None, decoder=None):
        from ..datamodel.code import MeterId
        from ..ingest.codec import DocumentDecoder

        super().__init__()
        self.wm = wm
        self.bucket_sizes = tuple(bucket_sizes)
        self.meter_id = int(MeterId.FLOW if meter_id is None else meter_id)
        self.decoder = decoder if decoder is not None else DocumentDecoder()
        self.other_meter_rows = 0  # decoded docs of non-target meter types

    def count_records(self, raw: bytes) -> int:
        return len(split_message_spans(raw[HEADER_LEN:]))

    def _decode_frame(self, raw: bytes) -> DocChunk | None:
        body = raw[HEADER_LEN:]
        spans = split_message_spans(body)
        batches = self.decoder.decode_parts([(body, spans)])
        chunk = None
        for meter_id, db in batches.items():
            if meter_id != self.meter_id:
                self.other_meter_rows += db.tags.shape[0]
                continue
            chunk = DocChunk(db.timestamp, db.tags, db.meters)
        return chunk

    def emit(self, chunks: list[DocChunk], rows: int, bucket: int, shed: int) -> list:
        from ..datamodel.code import DOC_KEY_PACK, pack_tag_words
        from ..datamodel.schema import TAG_SCHEMA
        from ..ops.hashing import fingerprint64_words

        ts = np.zeros(bucket, dtype=np.uint32)
        tags = np.zeros((bucket, TAG_SCHEMA.num_fields), dtype=np.uint32)
        meters = np.zeros((bucket, self.wm.meter_schema.num_fields), dtype=np.float32)
        valid = np.zeros(bucket, dtype=bool)
        off = 0
        for c in chunks:
            n = c.rows
            ts[off : off + n] = c.timestamp
            tags[off : off + n] = c.tags
            meters[off : off + n] = c.meters
            valid[off : off + n] = True
            off += n
        assert off == rows
        cols = {
            f: tags[:, TAG_SCHEMA.index(f)] for f in DOC_KEY_PACK.field_names()
        }
        hi, lo = fingerprint64_words(pack_tag_words(cols, DOC_KEY_PACK, np), xp=np)
        return self.wm.ingest(
            ts, hi.astype(np.uint32), lo.astype(np.uint32),
            np.ascontiguousarray(tags.T), np.ascontiguousarray(meters.T),
            valid, feeder_shed=shed,
        )

    def flush(self) -> list:
        return []


# ---------------------------------------------------------------------------
# the runtime


@dataclasses.dataclass(frozen=True)
class FeederConfig:
    # frames a queue may contribute per visit (scaled by its weight)
    frames_per_queue: int = 16
    # queue visits per pump() = rounds × len(queues)
    rounds_per_pump: int = 4
    # per-queue depth watermarks, as a fraction of queue capacity, with
    # hysteresis: ≥ high enters pressure (doubled drain budget, oldest
    # half shed), ≤ low leaves it
    high_watermark: float = 0.75
    low_watermark: float = 0.25
    # relative drain weights per queue (None = equal); a weight-2 queue
    # contributes 2× frames_per_queue per visit
    weights: tuple[int, ...] | None = None
    # emit the sub-bucket tail at the end of each pump (freshness) —
    # off, records wait for a full max-size bucket (efficiency)
    emit_partial: bool = True
    # pumps between probe dispatches while DEGRADED (ISSUE 6): every
    # probe_interval-th pump lets one batch through the full dispatch
    # path; a success flips the runtime back to healthy
    probe_interval: int = 8
    # serve(): max flushed-output batches held for on_flush redelivery
    # while the callback keeps failing; beyond it the OLDEST are shed
    # and counted (held_outputs_shed lanes) — a broken downstream must
    # not grow the hold list until the process OOMs. 0 = unbounded.
    max_held_outputs: int = 256
    # live read plane (ISSUE 10): refresh the sink's open-window
    # snapshot every N pumps, BETWEEN dispatches — the snapshot read
    # never interleaves into a pump's emit sequence, so the feeder's
    # steady-state ingest fetch budget is untouched (CI-gated,
    # test_perf_gate::test_live_read_budget). The sink must expose
    # `snapshot()` (PipelineFeedSink/ShardedFeedSink → snapshot_open);
    # the refresh keeps the rate-limited snapshot warm so dashboard
    # pulls between pumps return the cached read. 0 = off (pull-only).
    snapshot_interval_pumps: int = 0
    # push query plane (ISSUE 11): the (db, table) the feeder's flushed
    # outputs are attributed to when an event_bus is attached — the
    # WindowClosed/TierClosed events the pump publishes after its last
    # emit carry these, so standing queries over the dogfood table
    # re-evaluate exactly when their data moved
    event_db: str = "deepflow_system"
    event_table: str = "deepflow_system"


class FeederRuntime:
    """Drains N overwrite queues into shape-bucketed batches for one
    windowed sink. Drive it explicitly with `pump()` (bench/tests) or
    via the `serve()` polling thread."""

    def __init__(
        self,
        queues: list,
        sink,
        config: FeederConfig = FeederConfig(),
        *,
        name: str = "feeder",
        tracer: SpanTracer | None = None,
        journal=None,
        event_bus=None,
        lineage=None,
    ):
        if not queues:
            raise ValueError("need at least one queue")
        if config.weights is not None and len(config.weights) != len(queues):
            raise ValueError(
                f"{len(config.weights)} weights for {len(queues)} queues"
            )
        if not getattr(sink, "bucket_sizes", None):
            raise ValueError("sink must declare bucket_sizes")
        self.queues = list(queues)
        self.sink = sink
        self.config = config
        self.buckets = tuple(sorted(sink.bucket_sizes))
        self.name = name
        self.tracer = tracer if tracer is not None else SpanTracer(
            service="deepflow_tpu.feeder"
        )
        if hasattr(sink, "tracer"):
            sink.tracer = self.tracer  # feeder.assemble joins the runtime's spans
        self._journal = journal
        # push query plane (ISSUE 11): flushed outputs become
        # WindowClosed/TierClosed events AFTER the pump's last emit —
        # the drain-side hook that turns a window close into an eager
        # cache invalidation + one shared subscription evaluation
        self._event_bus = event_bus
        # window lineage plane (ISSUE 13): the feeder owns the
        # pre-window hops — pump start, receiver-admission pairing and
        # journal appends park in the tracker's pending context and
        # bind to window ids at the sink's dispatch. Every admitted
        # frame must consume exactly one admission stamp; frames the
        # OverwriteQueue silently overwrote never reach the feeder, so
        # each pump drops stamps by the queues' overwritten-counter
        # delta (baseline taken here — pre-attach drops don't count).
        self._lineage = lineage
        self._overwritten_base = sum(
            int(getattr(q, "overwritten", 0)) for q in queues
        )
        self._weights = config.weights or (1,) * len(queues)
        self._pressure = [False] * len(queues)
        self._chunks: deque = deque()
        self._rows = 0
        self._shed_pending = 0  # records shed since the last emit
        self._rr = 0  # rotating first-queue index (starvation-proof)
        self._lock = threading.Lock()
        # serializes pump/flush/checkpoint/replay against each other:
        # a checkpoint racing the serve() thread could otherwise admit
        # (and journal) frames between the barrier flush and
        # sync_offset — below the barrier offset but absent from the
        # snapshot, so replay would skip them (silent loss). RLock:
        # checkpoint() calls flush() re-entrantly.
        self._pump_mutex = threading.RLock()
        self._thread: threading.Thread | None = None
        self._stop = threading.Event()
        # degraded-mode state machine (ISSUE 6)
        self.degraded = False
        self._probe_now = True
        self._probe_countdown = 0
        self._pump_failstreak = 0  # consecutive serve()-loop pump failures
        self.counters = {
            "frames_in": 0,
            "records_in": 0,
            "bad_frames": 0,
            "batches_out": 0,
            "records_out": 0,
            "pad_rows": 0,
            "shed_frames": 0,
            "shed_records": 0,
            "pressure_events": 0,
            # fault-tolerance lanes
            "emit_failures": 0,
            "lost_records": 0,
            "degraded_entries": 0,
            "degraded_exits": 0,
            "degraded_shed_records": 0,
            "probe_attempts": 0,
            "pump_errors": 0,
            "flush_callback_errors": 0,
            "held_outputs_shed": 0,
            "held_output_shed_records": 0,
            "checkpoint_aborts": 0,
            "replayed_frames": 0,
            # live read plane (ISSUE 10)
            "snapshots_taken": 0,
            "snapshot_errors": 0,
            # push query plane (ISSUE 11)
            "events_published": 0,
            # pumps that drained nothing and emitted nothing: they
            # record no span (a starved feeder pumps ~2,000 times a
            # second and would turn the span ring over in one); their
            # wall is kept: in a closed loop it is the round trip
            # sender → Receiver → queue that the feeder sat out
            "idle_pumps": 0,
            "idle_pump_us": 0,
            # Σ len(q) over the visits and the visits: their ratio is the
            # mean number of frames waiting when the feeder comes for them
            "queue_depth_sum": 0,
            "queue_visits": 0,
        }
        # decode_frame time of the round under way, wall and CPU
        # (feeder.decode is ONE record a round, not one a frame: see
        # _record_decode)
        self._decode_us = 0
        self._decode_cpu_ns = 0
        self._decode_frames = 0
        self._pump_count = 0
        self.last_snapshot = None  # most recent scheduled OpenSnapshot
        self._snapshot_err_logged = False
        # False after a checkpoint() that aborted (barrier flush or
        # snapshot save failed) — callers that prune old checkpoints or
        # journals MUST check it before treating the call as durable.
        self.last_checkpoint_ok = True
        self._held_shed_logged = False
        register_countable("tpu_feeder", self, name=name)
        register_countable("tpu_feeder_spans", self.tracer, name=name)

    # -- countable face --------------------------------------------------
    def get_counters(self) -> dict:
        with self._lock:
            out = dict(self.counters)
        out["pending_rows"] = self._rows
        out["queue_overwritten"] = sum(
            int(getattr(q, "overwritten", 0)) for q in self.queues
        )
        out["queues_in_pressure"] = sum(self._pressure)
        # health lanes: the deepflow_system rows dashboards alert on
        out["degraded"] = int(self.degraded)
        out["pump_failstreak"] = self._pump_failstreak
        out["healthy"] = int(not self.degraded and self._pump_failstreak == 0)
        out["last_checkpoint_ok"] = int(self.last_checkpoint_ok)
        out["decode_errors"] = int(getattr(self.sink, "decode_errors", 0))
        out["host_copy_bytes"] = int(getattr(self.sink, "host_copy_bytes", 0))
        out["staging_waits"] = int(getattr(self.sink, "staging_waits", 0))
        out["staging_wait_us"] = int(getattr(self.sink, "staging_wait_us", 0))
        # the feeder spans' CPU lanes as `<stage>_cpu_us`. They repeat
        # `<stage>.cpu_us` of the tracer's own Countable face on purpose:
        # chipbench's `spans` plane passes count / total_us only, and a
        # benchmark PR that widens it retires these (ROADMAP)
        for name, cpu_us in self.tracer.cpu_us(tuple(_CPU_LANE_COUNTERS)).items():
            out[_CPU_LANE_COUNTERS[name]] = cpu_us
        if self._journal is not None:
            for k, v in self._journal.get_counters().items():
                out[f"journal_{k}"] = v
        return out

    def _count(self, key: str, n: int = 1) -> None:
        with self._lock:
            self.counters[key] += n

    # -- degraded-mode state machine -------------------------------------
    def _count_records_safe(self, raw: bytes) -> int:
        """Header-peek record count that survives corrupt frames — the
        shed accounting must never be the thing that raises."""
        try:
            return self.sink.count_records(raw)
        except Exception:
            return 0

    def _enter_degraded(self) -> None:
        self._probe_countdown = self.config.probe_interval
        self._probe_now = False
        if not self.degraded:
            self.degraded = True
            self._count("degraded_entries")
            _log.warning(
                "feeder %s: sink dispatch failed after retries — entering "
                "degraded mode (shedding, probing every %d pumps)",
                self.name, self.config.probe_interval,
            )

    def _note_emit_ok(self) -> None:
        if self.degraded:
            self.degraded = False
            self._count("degraded_exits")
            _log.warning(
                "feeder %s: probe dispatch succeeded — leaving degraded mode",
                self.name,
            )

    def _probe_tick(self) -> None:
        """Per-pump probe schedule: healthy pumps always dispatch;
        degraded pumps shed until the countdown elapses, then let one
        pump's batches through as the probe."""
        if not self.degraded:
            self._probe_now = True
            return
        self._probe_countdown -= 1
        if self._probe_countdown <= 0:
            self._probe_now = True
            self._probe_countdown = self.config.probe_interval
        else:
            self._probe_now = False

    def _drop_admit_stamp(self) -> None:
        """One admitted frame contributed no rows (bad/empty/shed):
        consume its receiver admission stamp WITHOUT folding it into
        the lineage context, or the FIFO pairing drifts stale
        (ISSUE 13 — every admitted frame must pop exactly one stamp)."""
        if self._lineage is not None:
            self._lineage.drop_stamps(1)

    def _shed_frame(self, raw: bytes) -> None:
        """Degraded-mode shed: whole frames, counted via header peek —
        the same stance as watermark shedding, plus the degraded lane."""
        self._count("shed_frames")
        self._drop_admit_stamp()
        n = self._count_records_safe(raw)
        self._count("shed_records", n)
        self._count("degraded_shed_records", n)
        with self._lock:
            self._shed_pending += n

    # -- drain + shed ----------------------------------------------------
    def _visit(self, i: int, admit: list) -> int:
        """Drain queue i once; append admitted frames, shed the rest.
        Returns frames drained. Deterministic: the decision depends
        only on queue depth at visit time and the configured
        watermarks (the shed-policy test pins this)."""
        q = self.queues[i]
        budget = self._weights[i] * self.config.frames_per_queue
        if self.degraded:
            # shrunk drain budget: a degraded pipeline stops pretending
            # it can keep up — the watermark shed upstream does the rest
            budget = max(1, budget // 2)
        cap = int(getattr(q, "capacity", 0) or 0)
        depth = len(q)
        with self._lock:
            self.counters["queue_depth_sum"] += depth
            self.counters["queue_visits"] += 1
        if cap:
            if not self._pressure[i] and depth >= self.config.high_watermark * cap:
                self._pressure[i] = True
                self._count("pressure_events")
            elif self._pressure[i] and depth <= self.config.low_watermark * cap:
                self._pressure[i] = False
        if self._pressure[i]:
            # pressure: drain twice the budget to burn the backlog down,
            # admit only the NEWEST `budget` frames, shed the oldest
            # WHOLE (the OverwriteQueue stance — freshest data wins) and
            # account every dropped record via the header peek
            drained = q.gets(2 * budget, timeout_ms=0)
            cut = max(len(drained) - budget, 0)
            for raw in drained[:cut]:
                self._count("shed_frames")
                self._drop_admit_stamp()
                n = self._count_records_safe(raw)
                self._count("shed_records", n)
                with self._lock:
                    self._shed_pending += n
            admit.extend(drained[cut:])
            return len(drained)
        drained = q.gets(budget, timeout_ms=0)
        admit.extend(drained)
        return len(drained)

    # -- coalescing ------------------------------------------------------
    def _take(self, n: int) -> list:
        """Pop exactly n rows of chunks from the pending buffer."""
        out = []
        need = n
        while need > 0:
            c = self._chunks.popleft()
            if c.rows <= need:
                out.append(c)
                need -= c.rows
            else:
                head, tail = c.split(need)
                out.append(head)
                self._chunks.appendleft(tail)
                need = 0
        self._rows -= n
        return out

    def _emit(self, rows: int, bucket: int) -> list:
        chunks = self._take(rows)
        if self.degraded:
            # a dispatch attempted while degraded IS the probe — count
            # it here, not in _probe_tick, so idle pumps (which test
            # nothing) never inflate the probe_attempts lane
            self._count("probe_attempts")
        with self._lock:
            shed, self._shed_pending = self._shed_pending, 0
        lost0 = getattr(self.sink, "lost_records", None)
        try:
            with self.tracer.span(SPAN_FEEDER_DISPATCH):
                out = self.sink.emit(chunks, rows, bucket, shed)
        except Exception:
            # containment: the dispatch failed even after the window
            # manager's transient retries. Count what was actually lost
            # (sinks with a double buffer keep the staged batch), re-arm
            # the un-delivered shed so the device lane still sees it on
            # the next successful batch, and flip to degraded.
            lost = rows if lost0 is None else self.sink.lost_records - lost0
            self._count("emit_failures")
            self._count("lost_records", lost)
            # records_out counts rows that LEFT the coalescing buffer in
            # both outcomes (conservation: records_in = records_out +
            # pending_rows always holds); delivered = records_out −
            # lost_records
            self._count("records_out", rows)
            with self._lock:
                self._shed_pending += lost + shed
            self._enter_degraded()
            return []
        self._note_emit_ok()
        self._count("batches_out")
        self._count("records_out", rows)
        self._count("pad_rows", bucket - rows)
        return out

    def _admit(self, chunk, out: list) -> None:
        self._chunks.append(chunk)
        self._rows += chunk.rows
        max_b = self.buckets[-1]
        while self._rows >= max_b:
            out.extend(self._emit(max_b, max_b))
            if self.degraded:
                # the emit just failed — stop hammering the device; the
                # remaining pending rows wait for the probe
                break

    def _bucket_for(self, rows: int) -> int:
        for b in self.buckets:
            if rows <= b:
                return b
        return self.buckets[-1]

    def _process_frame(self, raw: bytes, out: list) -> None:
        """Decode one admitted frame through the sink codec and coalesce
        it — the single path pump() and replay_journal() share, so
        recovery exercises no special-case decode code."""
        errs0 = int(getattr(self.sink, "decode_errors", 0))
        t0, cpu0 = time.perf_counter(), time.thread_time_ns()
        try:
            chunk = self.sink.decode_frame(raw)
        except Exception:
            # sinks quarantine internally (FrameCodecBase); this guard
            # covers foreign sink implementations only
            self._count("bad_frames")
            self._drop_admit_stamp()
            return
        finally:
            self._decode_cpu_ns += time.thread_time_ns() - cpu0
            self._decode_us += int((time.perf_counter() - t0) * 1e6)
            self._decode_frames += 1
        if int(getattr(self.sink, "decode_errors", 0)) > errs0:
            self._count("bad_frames")  # quarantined by the codec
            self._drop_admit_stamp()
            return
        self._count("frames_in")
        if chunk is None or chunk.rows == 0:
            self._drop_admit_stamp()
            return
        if self._lineage is not None:
            # pair this admitted frame with its receiver admission
            # stamp (FIFO) — opens the receiver.admit hop in the
            # pending context
            self._lineage.note_frames(1)
        self._count("records_in", chunk.rows)
        self._admit(chunk, out)

    def _record_decode(self, start_s: float) -> None:
        """The decode_frame calls since the last record, as ONE
        feeder.decode span: they interleave with the dispatches that a
        full bucket triggers, and a record a frame (107–128 a second)
        would push the spans a profile needs out of the ring. `start_s`
        is where the first of them began."""
        if self._decode_frames:
            self.tracer.record(SPAN_FEEDER_DECODE, self._decode_us, start_s=start_s,
                               cpu_us=self._decode_cpu_ns // 1000)
            self._decode_us = self._decode_cpu_ns = self._decode_frames = 0

    # -- the pump --------------------------------------------------------
    def pump(self) -> list:
        """One fan-in cycle: drain every queue (rounds_per_pump visits
        each, rotating the start index), decode + coalesce into bucket
        batches, emit them into the sink, and — with emit_partial —
        flush the sub-bucket tail padded to its smallest bucket.
        Returns whatever the sink's window controller flushed."""
        with self._pump_mutex:
            return self._pump_locked()

    def _pump_locked(self) -> list:
        with self.tracer.span(SPAN_FEEDER_PUMP) as pump_span:
            out, busy = self._pump_spanned()
            if not busy:
                pump_span.discard()
        if not busy:
            with self._lock:
                self.counters["idle_pumps"] += 1
                self.counters["idle_pump_us"] += pump_span.duration_us
        return out

    def _pump_spanned(self) -> tuple[list, bool]:
        """The pump under its feeder.pump span → (outputs, whether it
        took a frame off a queue or dispatched a batch)."""
        out: list = []
        drained_total = 0
        if self._lineage is not None:
            self._lineage.begin_pump()
            # frames lost to queue OVERWRITE never reach _process_frame
            # — consume their admission stamps here or the FIFO pairing
            # drifts stale under sustained backpressure
            ow = sum(int(getattr(q, "overwritten", 0)) for q in self.queues)
            if ow > self._overwritten_base:
                self._lineage.drop_stamps(ow - self._overwritten_base)
            self._overwritten_base = ow
        self._probe_tick()
        dispatch0 = self.counters["batches_out"] + self.counters["emit_failures"]
        nq = len(self.queues)
        for _ in range(self.config.rounds_per_pump):
            admit: list = []
            with self.tracer.span(SPAN_FEEDER_DRAIN) as drain_span:
                drained = 0
                for j in range(nq):
                    drained += self._visit((self._rr + j) % nq, admit)
                if not drained:
                    drain_span.discard()  # empty queues: nothing to account
            self._rr = (self._rr + 1) % nq
            if not admit and not drained:
                break
            drained_total += drained
            with self.tracer.span(SPAN_FEEDER_COALESCE) as coalesce_span:
                # one shed decision per round: frames the live run
                # sheds-and-counts are NOT journaled — replay would
                # resurrect rows the counters already declared shed,
                # double-accounting them across the shed and delivered
                # lanes
                shedding = self.degraded and not self._probe_now
                # journal the WHOLE admitted round before touching the
                # device: a kill anywhere downstream (dispatch, fetch,
                # flush) then loses nothing the journal can't replay
                if self._journal is not None and not shedding:
                    j0 = (self._lineage.clock()
                          if self._lineage is not None else 0.0)
                    for raw in admit:
                        self._journal.append(raw)
                    if self._lineage is not None and admit:
                        self._lineage.note_journal(j0)
                for raw in admit:
                    if shedding:
                        self._shed_frame(raw)
                        continue
                    self._process_frame(raw, out)
                self._record_decode(coalesce_span.wall)
        if (
            self.config.emit_partial
            and self._rows > 0
            and (self._probe_now or not self.degraded)
        ):
            out.extend(self._emit(self._rows, self._bucket_for(self._rows)))
        if self._journal is not None:
            self._journal.mark()
        dispatched = (
            self.counters["batches_out"] + self.counters["emit_failures"]
            != dispatch0
        )
        if self.degraded and self._probe_now and not dispatched:
            # the probe pump had no data to send, so nothing was tested:
            # keep the probe armed instead of re-arming the countdown —
            # otherwise a feeder that goes idle while degraded sheds the
            # first frames that arrive after the device already recovered
            self._probe_countdown = 0
        # live snapshot scheduling (ISSUE 10): AFTER the pump's last
        # emit, BEFORE the next pump's first dispatch — the read-only
        # snapshot never stalls the feed path, and snapshot_open's rate
        # limit makes an over-eager schedule harmless. Guarded: a broken
        # snapshot path degrades the live view, never the pump.
        if self.config.snapshot_interval_pumps > 0:
            self._pump_count += 1
            if (
                self._pump_count % self.config.snapshot_interval_pumps == 0
                and hasattr(self.sink, "snapshot")
            ):
                try:
                    self.last_snapshot = self.sink.snapshot()
                    self._count("snapshots_taken")
                except Exception:
                    self._count("snapshot_errors")
                    if not self._snapshot_err_logged:
                        self._snapshot_err_logged = True
                        _log.exception(
                            "feeder %s: open-window snapshot failed — live "
                            "reads degrade to flushed-only", self.name,
                        )
                else:
                    self._publish_snapshot_event()
        self._publish_events(out)
        return out, bool(drained_total) or dispatched

    # -- push events (ISSUE 11) ------------------------------------------
    def _publish_events(self, out: list) -> None:
        """Flushed outputs → one WindowClosed/TierClosed batch on the
        attached bus. One publish per pump, so K windows closed by one
        drain reach every standing query as ONE delivery (the
        coalescing contract subscriptions/alerts pin). Guarded: the
        event plane must never stall or fail the drain."""
        if self._event_bus is None or not out:
            return
        try:
            from ..querier.events import docbatch_events

            events = docbatch_events(
                out, db=self.config.event_db, table=self.config.event_table
            )
            if events:
                n = self._event_bus.publish(events)
                self._count("events_published", n)
        except Exception:
            _log.debug("feeder %s: event publish failed (contained)",
                       self.name, exc_info=True)

    def _publish_snapshot_event(self) -> None:
        if self._event_bus is None or self.last_snapshot is None:
            return
        try:
            from ..querier.events import SnapshotAdvanced

            n = self._event_bus.publish(SnapshotAdvanced(
                self.config.event_db, self.config.event_table,
                int(getattr(self.last_snapshot, "seq", 0)),
            ))
            self._count("events_published", n)
        except Exception:
            _log.debug("feeder %s: snapshot event publish failed (contained)",
                       self.name, exc_info=True)

    def flush(self) -> list:
        """Emit every pending record (tail bucket) and push anything the
        sink holds (the double-buffered staged batch); does NOT drain
        the sink's open windows — that stays the owner's shutdown call."""
        with self._pump_mutex:
            out: list = []
            if self._rows > 0:
                out.extend(self._emit(self._rows, self._bucket_for(self._rows)))
            lost0 = getattr(self.sink, "lost_records", None)
            try:
                with self.tracer.span(SPAN_FEEDER_DISPATCH):
                    out.extend(self.sink.flush())
            except Exception:
                lost = 0 if lost0 is None else self.sink.lost_records - lost0
                self._count("emit_failures")
                self._count("lost_records", lost)
                with self._lock:
                    self._shed_pending += lost
                self._enter_degraded()
            self._publish_events(out)
            return out

    # -- journal recovery ------------------------------------------------
    def checkpoint(self, save) -> list:
        """The flush→snapshot→rotate checkpoint barrier.

        Flushes every pending row and the sink's staged batch (so the
        window state covers all admitted frames), calls `save(barrier)`
        — a closure around e.g. checkpoint.save_window_state, with
        `barrier` = {"journal_epoch", "journal_offset"} to embed in the
        snapshot meta — then rotates the journal. Returns every output
        the barrier flushed (including whatever `save` returns, e.g.
        save_window_state's in-flight windows); callers must emit them
        BEFORE treating the checkpoint as durable.

        If the barrier flush itself fails to deliver (a sink dispatch
        error), the checkpoint ABORTS — counted (`checkpoint_aborts`)
        and logged, snapshot not written, journal not rotated. The
        failed rows' journal records are the only replayable copy left;
        snapshotting without them and rotating would convert a
        transient failure into permanent loss. The previous checkpoint
        plus the intact journal still recover everything. The returned
        outputs look identical either way, so `last_checkpoint_ok`
        (also a get_counters lane) records per-call success — callers
        that prune older checkpoints/journals after this call MUST
        check it, or an abort turns their pruning into permanent loss.

        Safe to call from any thread while serve() runs: the pump
        mutex holds the barrier (flush → sync_offset → save → rotate)
        closed against concurrent admits — a frame journaled between
        the flush and the barrier offset would be skipped by replay
        yet missing from the snapshot."""
        with self._pump_mutex:
            ef0 = self.counters["emit_failures"]
            out = self.flush()
            if self.counters["emit_failures"] > ef0:
                self.last_checkpoint_ok = False
                self._count("checkpoint_aborts")
                _log.warning(
                    "feeder %s: checkpoint aborted — the barrier flush failed "
                    "to deliver; journal kept (not rotated), snapshot not "
                    "written", self.name,
                )
                return out
            # a snapshot failure must not take the barrier flush's
            # outputs down with it: those windows already left the
            # manager state and the caller is their only route out.
            # Abort (counted), deliver `out`, keep the journal — the
            # old checkpoint + un-rotated journal still recover
            # everything. KillPoint is a BaseException and still
            # pierces (process death must not be absorbed).
            try:
                barrier = None
                if self._journal is not None:
                    epoch, off = self._journal.sync_offset()
                    barrier = {"journal_epoch": epoch, "journal_offset": off}
                res = save(barrier)
            except Exception:
                self.last_checkpoint_ok = False
                self._count("checkpoint_aborts")
                _log.exception(
                    "feeder %s: checkpoint aborted — snapshot save failed; "
                    "journal kept (not rotated), flushed outputs delivered",
                    self.name,
                )
                return out
            if res:
                out.extend(res)
                self._publish_events(res)  # barrier-flushed windows push too
            if self._journal is not None:
                self._journal.rotate()
            self.last_checkpoint_ok = True
            return out

    def quiesce(self, save, *, max_pumps: int = 64) -> list:
        """Drain-to-barrier for an ownership handover (ISSUE 15): pump
        until every queue is empty and no rows are pending, then run
        the flush→snapshot→rotate checkpoint barrier. The resulting
        snapshot + rotated journal are the complete transferable state
        of this feeder's sink — the old owner of a rebalancing shard
        group calls this, the new owner restores from what it wrote.

        Loud by contract: a queue whose backlog stops SHRINKING across
        a full pump (a producer still feeding it — the caller must
        fence admission FIRST, e.g. by flipping the receiver's route
        epoch) or an aborted barrier checkpoint raises
        RebalanceAbortError — a handover must never publish state it
        is not sure is complete. `max_pumps` is slack on top of the
        backlog-sized budget (each pump drains a bounded frame budget,
        so a large FENCED backlog legitimately needs many pumps — the
        abort keys on progress, not an iteration count). Returns every
        output the drain and barrier flushed; the caller emits them
        before treating the handover as durable (the checkpoint()
        contract)."""
        from ..chaos import RebalanceAbortError

        with self._pump_mutex:
            out: list = []
            qlen = sum(len(q) for q in self.queues)
            # fenced admission ⇒ every pump strictly shrinks the
            # backlog ⇒ at most one pump per queued frame (+ slack for
            # pending-row tail emits); unfenced admission trips the
            # no-progress check long before this budget
            for _ in range(qlen + max_pumps):
                out.extend(self.pump())
                if self._rows == 0 and all(
                    len(q) == 0 for q in self.queues
                ):
                    break
                now_qlen = sum(len(q) for q in self.queues)
                if now_qlen >= qlen and now_qlen > 0:
                    err = RebalanceAbortError(
                        f"feeder {self.name}: queue backlog did not "
                        f"shrink across a quiesce pump ({qlen} → "
                        f"{now_qlen} frames) — admission was not "
                        "fenced before the handover (flip the route "
                        "epoch first)"
                    )
                    err.outputs = out  # already-flushed windows must
                    # still reach the caller: the abort cancels the
                    # MOVE, not the drain's deliveries
                    raise err
                qlen = now_qlen
            else:
                err = RebalanceAbortError(
                    f"feeder {self.name}: rows still pending after the "
                    "quiesce pump budget — the sink is not draining"
                )
                err.outputs = out
                raise err
            out.extend(self.checkpoint(save))
            if not self.last_checkpoint_ok:
                err = RebalanceAbortError(
                    f"feeder {self.name}: handover barrier checkpoint "
                    "aborted — state not transferable; the previous "
                    "checkpoint and the un-rotated journal still "
                    "recover everything on THIS host"
                )
                err.outputs = out
                raise err
            return out

    def replay_journal(self, path, *, barrier: dict | None = None) -> list:
        """Recovery: replay a (crashed) feeder's journal through the
        NORMAL decode path. FRAME records flow through _process_frame
        (same coalescing, same bucket emits), MARK records re-create
        the pump-boundary tail emits — so batch boundaries, and
        therefore f32 meter fold order and flushed rows, are bit-exact
        vs the uninterrupted run. `barrier` (from the checkpoint meta)
        skips records the snapshot already covers when the crash landed
        between save and rotate; a rotated journal (epoch advanced)
        replays in full. Frames are re-journaled into THIS runtime's
        journal, so recovery itself is crash-safe. After the replay,
        call pump(): it completes the interrupted pump's tail emit.

        Replaying from THIS runtime's own journal path (the natural
        fixed-path restart) is safe: the entries are read up front and
        the live journal is rotated first, so replayed frames are
        re-appended exactly once into the fresh epoch instead of
        duplicated behind their originals — a second crash would
        otherwise double-apply every one of them."""
        from .journal import REC_FRAME, REC_MARK, read_journal

        with self._pump_mutex:
            out: list = []
            t_replay = time.time()
            epoch, entries, truncated = read_journal(path)
            if self._journal is not None:
                try:
                    aliased = Path(path).resolve() == self._journal.path.resolve()
                except OSError:
                    aliased = False
                if aliased:
                    self._journal.rotate()
            skip_off = -1
            if barrier and barrier.get("journal_epoch") == epoch:
                skip_off = int(barrier.get("journal_offset", 0))
            if truncated:
                _log.warning(
                    "feeder %s: journal %s has a torn tail (crash mid-write) — "
                    "replaying the clean prefix", self.name, path,
                )
            for kind, payload, off in entries:
                if off < skip_off:
                    continue
                if kind == REC_FRAME:
                    if self._journal is not None:
                        self._journal.append(payload)
                    self._count("replayed_frames")
                    self._process_frame(payload, out)
                elif kind == REC_MARK:
                    if self.config.emit_partial and self._rows > 0:
                        out.extend(self._emit(self._rows, self._bucket_for(self._rows)))
                    if self._journal is not None:
                        self._journal.mark()
            self._record_decode(t_replay)
            return out

    # -- thread ----------------------------------------------------------
    def _hold_for_redelivery(self, held: list, new: list) -> list:
        """Extend the serve() redelivery buffer, bounded by
        config.max_held_outputs: while on_flush keeps failing the pump
        keeps producing, and an unbounded hold list turns a broken
        downstream into an OOM. Beyond the cap the OLDEST outputs are
        shed and counted (held_outputs_shed / held_output_shed_records)
        — the same counted-shedding contract as every other overflow
        lane, logged once per overflow episode."""
        held.extend(new)
        cap = self.config.max_held_outputs
        if cap and len(held) > cap:
            drop = len(held) - cap
            shed, held = held[:drop], held[drop:]
            rows = sum(
                int(getattr(o, "size", 0) or getattr(o, "count", 0) or 0)
                for o in shed
            )
            self._count("held_outputs_shed", drop)
            self._count("held_output_shed_records", rows)
            if not self._held_shed_logged:
                self._held_shed_logged = True
                _log.error(
                    "feeder %s: on_flush redelivery buffer overflowed — shed "
                    "%d oldest output batches (%d records); downstream has "
                    "been failing past max_held_outputs=%d",
                    self.name, drop, rows, cap,
                )
        return held

    def serve(self, poll_ms: int = 20, on_flush=None) -> None:
        """Background pump loop; `on_flush(outputs)` receives every
        non-empty result (flushed windows must not be dropped on the
        floor by a fire-and-forget loop). Crash-loop guard (ISSUE 6):
        a pump exception is counted (`pump_errors`) and the loop
        restarts with capped exponential backoff — the daemon thread
        never dies silently; `pump_failstreak`/`healthy` expose the
        state. An `on_flush` exception is counted separately
        (`flush_callback_errors`) and its outputs are HELD and
        re-delivered on the next loop — at-least-once up to
        config.max_held_outputs, beyond which the oldest are shed and
        counted (never silently dropped)."""
        if self._thread is not None:
            return
        self._stop.clear()
        idle = poll_ms / 1000.0
        # shared backoff policy, decorrelated per instance: N feeder
        # daemons recovering from the same device fault must not retry
        # in lockstep (the herd the jitter exists to break)
        policy = RetryPolicy(
            base_delay_s=idle, max_delay_s=5.0, multiplier=2.0, jitter=0.5
        )
        rng = decorrelated_rng(hash(self.name) & 0xFFFF)

        def run():
            cb_failstreak = 0
            undelivered: list = []
            while not self._stop.is_set():
                try:
                    got = self.pump()
                except Exception:
                    self._count("pump_errors")
                    self._pump_failstreak += 1
                    if self._pump_failstreak == 1:
                        _log.exception(
                            "feeder %s: pump failed — restarting loop with "
                            "backoff", self.name,
                        )
                    self._stop.wait(policy.delay(self._pump_failstreak, rng))
                    continue
                if self._pump_failstreak:
                    _log.warning(
                        "feeder %s: pump loop recovered after %d failures",
                        self.name, self._pump_failstreak,
                    )
                    self._pump_failstreak = 0
                # flushed windows are held and re-delivered until
                # on_flush accepts them (a callback that raises mid-way
                # may see a window twice); the hold is BOUNDED — see
                # _hold_for_redelivery
                if on_flush is not None:
                    undelivered = self._hold_for_redelivery(undelivered, got)
                if undelivered and on_flush is not None:
                    batch, undelivered = undelivered, []
                    try:
                        on_flush(batch)
                    except Exception:
                        undelivered = batch
                        cb_failstreak += 1
                        self._count("flush_callback_errors")
                        _log.exception(
                            "feeder %s: on_flush failed — holding %d "
                            "outputs for redelivery", self.name, len(batch),
                        )
                        self._stop.wait(policy.delay(cb_failstreak, rng))
                        continue
                    cb_failstreak = 0
                    self._held_shed_logged = False
                if not got:
                    self._stop.wait(idle)

        self._thread = threading.Thread(target=run, daemon=True)
        self._thread.start()

    def stop(self, timeout: float = 5.0) -> None:
        self._stop.set()
        t, self._thread = self._thread, None
        if t is not None:
            t.join(timeout=timeout)
