"""Feeder runtime (ISSUE 4): K-batch counter ring bit-exactness,
multi-queue fan-in + shape-bucketed coalescing, deterministic shedding,
queue/receiver satellites, checkpoint v1 removal."""

from __future__ import annotations

import numpy as np
import pytest

from deepflow_tpu.aggregator.pipeline import L4Pipeline, PipelineConfig
from deepflow_tpu.aggregator.window import WindowConfig, WindowManager
from deepflow_tpu.datamodel.batch import (
    FLOW_RECORD_TAG_FIELDS,
    STAGING_RING_LEN,
    FlowBatch,
    StagingRing,
)
from deepflow_tpu.datamodel.schema import FLOW_METER, TAG_SCHEMA
from deepflow_tpu.feeder import (
    FeederConfig,
    FeederRuntime,
    PipelineFeedSink,
    WindowManagerFeedSink,
    decode_flowframe_body,
    encode_flowbatch_body,
    encode_flowbatch_frames,
    peek_rows,
)
from deepflow_tpu.feeder.runtime import _FlowFrameCodec
from deepflow_tpu.ingest.queues import PyOverwriteQueue, register_queue_stats
from deepflow_tpu.ingest.replay import SyntheticFlowGen

T0 = 1_700_000_000


def _doc_key(db):
    return (db.size, float(db.meters.sum()), int(db.tags.sum()),
            int(db.timestamp.sum()))


def _run_pipeline(K, sizes, *, buckets=None, seed=3, async_drain=False):
    cfg = PipelineConfig(
        window=WindowConfig(capacity=1 << 12, stats_ring=K,
                            async_drain=async_drain),
        batch_size=256,
        bucket_sizes=buckets,
    )
    gen = SyntheticFlowGen(num_tuples=200, seed=seed)
    pipe = L4Pipeline(cfg)
    docs = []
    for i, n in enumerate(sizes):
        docs += pipe.ingest(FlowBatch.from_records(gen.records(n, T0 + i)))
    docs += pipe.drain()
    return sorted(_doc_key(db) for db in docs), pipe.get_counters()


# ---------------------------------------------------------------------------
# K-batch counter ring


@pytest.mark.parametrize("K", [4, 7])
def test_stats_ring_bit_exact_vs_per_batch_oracle(K):
    """K ∈ {4, 7} with one window advance per batch — every advance
    lands mid-ring (12 batches is not a multiple of 7, and the drain
    points never align with the closes). Flushed windows must be
    bit-exact vs the per-batch fetch oracle (K=1)."""
    sizes = [64] * 12
    oracle, c1 = _run_pipeline(1, sizes)
    ringed, cK = _run_pipeline(K, sizes)
    assert ringed == oracle
    # same funnel accounting once settled
    for key in ("doc_in", "flushed_doc", "drop_before_window",
                "window_advances"):
        assert cK[key] == c1[key], key
    # and strictly fewer stats fetches: 1 per K batches instead of 1/batch
    assert cK["host_fetches"] < c1["host_fetches"]


def test_stats_ring_late_rows_gated_identically():
    """Out-of-order traffic where the deferred gate matters: batches
    jump forward (closing windows mid-ring) then fall back inside and
    beyond the delay. The device-resident start_window must drop
    exactly what per-batch fetching would have dropped."""
    def run(K):
        cfg = PipelineConfig(
            window=WindowConfig(capacity=1 << 12, stats_ring=K),
            batch_size=64,
        )
        gen = SyntheticFlowGen(num_tuples=50, seed=9)
        pipe = L4Pipeline(cfg)
        docs = []
        # t pattern: advance to T0+10 closes windows; T0+1 is then LATE
        # (before start_window), T0+9 is within delay
        for t in (T0, T0 + 1, T0 + 2, T0 + 10, T0 + 1, T0 + 9, T0 + 11,
                  T0 + 3, T0 + 12, T0 + 30, T0 + 5, T0 + 31):
            docs += pipe.ingest(FlowBatch.from_records(gen.records(32, t)))
        docs += pipe.drain()
        return sorted(_doc_key(db) for db in docs), pipe.get_counters()

    oracle, c1 = run(1)
    assert c1["drop_before_window"] > 0  # the scenario exercises the gate
    for K in (4, 7):
        ringed, cK = run(K)
        assert ringed == oracle, K
        assert cK["drop_before_window"] == c1["drop_before_window"]


def test_stats_ring_settle_on_partial_ring():
    """drain-on-checkpoint: settle() fetches a partially-filled ring so
    host counters catch up without waiting for K dispatches."""
    cfg = PipelineConfig(
        window=WindowConfig(capacity=1 << 12, stats_ring=8), batch_size=64
    )
    gen = SyntheticFlowGen(num_tuples=50, seed=4)
    pipe = L4Pipeline(cfg)
    for i in range(3):  # 3 < K=8: nothing fetched yet
        pipe.ingest(FlowBatch.from_records(gen.records(40, T0 + i)))
    c = pipe.get_counters()
    assert c["doc_in"] == 0 and c["stats_ring_pending"] == 3
    pipe.wm.settle()
    c = pipe.get_counters()
    assert c["stats_ring_pending"] == 0
    assert c["doc_in"] > 0  # blocks replayed into host counters


def test_stats_ring_checkpoint_roundtrip(tmp_path):
    """Mid-stream save/restore with a filled ring: nothing lost or
    duplicated (save settles the ring first)."""
    from deepflow_tpu.aggregator.checkpoint import (
        load_window_state,
        save_window_state,
    )

    cfg = PipelineConfig(
        window=WindowConfig(capacity=1 << 12, stats_ring=4), batch_size=64
    )
    stream = [(T0, 40), (T0 + 1, 40), (T0 + 10, 40), (T0 + 11, 30)]

    def run(save_after):
        gen = SyntheticFlowGen(num_tuples=40, seed=7)
        pipe = L4Pipeline(cfg)
        docs = []
        for i, (t, n) in enumerate(stream):
            docs += pipe.ingest(FlowBatch.from_records(gen.records(n, t)))
            if save_after == i:
                in_flight = save_window_state(pipe.wm, tmp_path / "wm.ckpt")
                docs += [pipe._to_docbatch(f) for f in in_flight]
                pipe = L4Pipeline(cfg)
                pipe.wm = load_window_state(
                    tmp_path / "wm.ckpt", TAG_SCHEMA, FLOW_METER
                )
        docs += pipe.drain()
        c = FLOW_METER.index("packet_tx")
        return (sum(float(db.meters[:, c].sum()) for db in docs),
                sum(db.size for db in docs))

    assert run(save_after=1) == run(save_after=None)


def test_stats_ring_opening_batch_spanning_delay():
    """Regression (r9 review): when the FIRST non-empty batch spans
    more than `delay` seconds, the host opens the span AND advances it
    within the same block — the device gate must land on the advanced
    value, or ring mode admits rows per-batch mode late-drops."""
    def run(K):
        cfg = PipelineConfig(
            window=WindowConfig(interval=1, delay=0, capacity=1 << 10,
                                stats_ring=K),
            batch_size=64,
        )
        gen = SyntheticFlowGen(num_tuples=20, seed=13)
        pipe = L4Pipeline(cfg)
        docs = []
        # batch 1 spans [T0, T0+5] (> delay=0); batch 2's T0+2 rows are
        # late in per-batch mode and must be late in ring mode too
        r1 = gen.records(8, T0)
        r1 += gen.records(8, T0 + 5)
        docs += pipe.ingest(FlowBatch.from_records(r1))
        docs += pipe.ingest(FlowBatch.from_records(gen.records(8, T0 + 2)))
        docs += pipe.drain()
        return sorted(_doc_key(db) for db in docs), pipe.get_counters()

    oracle, c1 = run(1)
    assert c1["drop_before_window"] > 0  # the scenario exercises the race
    ringed, c4 = run(4)
    assert ringed == oracle
    assert c4["drop_before_window"] == c1["drop_before_window"]


def test_stats_ring_flush_all_resyncs_device_gate():
    """Regression (r9 review): flush_all() jumps the host span past
    every drained window; the device gate must follow, or a straggler
    ingest re-opens an already-emitted window and it flushes TWICE."""
    def run(K):
        cfg = PipelineConfig(
            window=WindowConfig(capacity=1 << 10, stats_ring=K),
            batch_size=64,
        )
        gen = SyntheticFlowGen(num_tuples=20, seed=17)
        pipe = L4Pipeline(cfg)
        docs = []
        docs += pipe.ingest(FlowBatch.from_records(gen.records(16, T0)))
        docs += pipe.drain()  # emits window T0; span moves past it
        # straggler at T0 again: must be late-dropped on BOTH paths
        docs += pipe.ingest(FlowBatch.from_records(gen.records(16, T0)))
        docs += pipe.drain()
        return sorted(_doc_key(db) for db in docs), pipe.get_counters()

    oracle, c1 = run(1)
    ringed, c4 = run(4)
    assert ringed == oracle
    assert c4["drop_before_window"] == c1["drop_before_window"] > 0
    assert c4["flushed_doc"] == c1["flushed_doc"]


def test_stats_ring_rejects_async_drain_combo():
    with pytest.raises(ValueError, match="stats_ring"):
        WindowManager(WindowConfig(stats_ring=4, async_drain=True))


# ---------------------------------------------------------------------------
# shape buckets


def test_bucketed_ingest_zero_retraces_and_bit_exact():
    sizes = [30, 64, 100, 256, 17, 200, 64, 90, 256, 11]
    oracle, _ = _run_pipeline(1, sizes, buckets=(64, 128, 256))
    got, c = _run_pipeline(4, sizes, buckets=(64, 128, 256))
    assert got == oracle
    assert c["jit_retraces"] == 0
    assert 1 <= c["jit_compiles"] <= 3  # ≤ one compile per bucket
    over, _ = _run_pipeline(1, [10], buckets=(64, 128, 256))  # fits fine
    with pytest.raises(ValueError, match="bucket"):
        _run_pipeline(1, [300], buckets=(64, 128, 256))


def test_bucket_sizes_validated():
    with pytest.raises(ValueError, match="bucket_sizes"):
        PipelineConfig(bucket_sizes=(128, 64))
    with pytest.raises(ValueError, match="bucket_sizes"):
        PipelineConfig(bucket_sizes=())


def test_jit_cache_monitor_expected_compiles():
    from deepflow_tpu.utils.spans import JitCacheMonitor

    class FakeFn:
        size = 0

        def _cache_size(self):
            return self.size

    fn = FakeFn()
    mon = JitCacheMonitor(fn, expected_compiles=3)
    fn.size = 2
    mon.poll()
    assert (mon.compiles, mon.retraces) == (2, 0)
    fn.size = 3
    mon.poll()
    assert (mon.compiles, mon.retraces) == (3, 0)
    fn.size = 5  # beyond the bucket budget → real retraces
    mon.poll()
    assert (mon.compiles, mon.retraces) == (3, 2)


# ---------------------------------------------------------------------------
# flowframe codec


def test_flowframe_roundtrip_and_peek():
    gen = SyntheticFlowGen(num_tuples=30, seed=1)
    fb = gen.flow_batch(50, T0)
    fb.valid[40:] = False  # only valid rows travel
    body = encode_flowbatch_body(fb)
    assert peek_rows(body) == 40
    out = decode_flowframe_body(body)
    assert out.size == 40 and bool(out.valid.all())
    for k in fb.tags:
        np.testing.assert_array_equal(out.tags[k], fb.tags[k][:40])
    np.testing.assert_array_equal(out.meters, fb.meters[:40])


def test_flowframe_rejects_garbage():
    with pytest.raises(ValueError, match="magic"):
        decode_flowframe_body(b"\x00" * 64)
    gen = SyntheticFlowGen(num_tuples=10, seed=1)
    body = encode_flowbatch_body(gen.flow_batch(8, T0))
    with pytest.raises(ValueError, match="truncated"):
        decode_flowframe_body(body[:-8])
    assert peek_rows(b"\x00" * 3) == 0  # short peek is a 0, not a crash


# ---------------------------------------------------------------------------
# the staging buffer (ISSUE 31): a batch written once, in the upload's layout


class _CaptureSink(_FlowFrameCodec):
    """The flow sinks' decode and assembly with no device behind them:
    every emitted batch's staged arrays are copied out."""

    def __init__(self, bucket_sizes):
        super().__init__(StagingRing(FLOW_METER.num_fields))
        self.bucket_sizes = bucket_sizes
        self.batches = []  # (tag_mat, meters, valid, rows, bucket)

    def emit(self, chunks, rows, bucket, shed):
        buf = self._assemble(chunks, rows, bucket)
        self.batches.append((buf.tag_mat.copy(), buf.meters.copy(),
                             buf.valid.copy(), rows, bucket))
        return []

    def flush(self):
        return []


def _old_staged_arrays(parts, bucket):
    """What the upload read before ISSUE 31, kept as the reference:
    FlowBatch.concat of the chunks, pad_to the bucket, np.stack of the
    tag columns in sorted-name order."""
    fb = FlowBatch.concat(parts).pad_to(bucket)
    tag_mat = np.stack([np.asarray(fb.tags[k], dtype=np.uint32)
                        for k in sorted(fb.tags)])
    return tag_mat, fb.meters, fb.valid


def _poison(ring, buckets):
    """Every buffer of every bucket filled to its last row with
    non-zero garbage, as an earlier, longer batch would leave it."""
    for b in buckets:
        tags = np.full((len(FLOW_RECORD_TAG_FIELDS), b), 0xDEADBEEF, np.uint32)
        meters = np.full((b, FLOW_METER.num_fields), 7.5, np.float32)
        for _ in range(STAGING_RING_LEN):
            buf = ring.acquire(b)
            buf.write(tags, meters)
            buf.finish()


STAGING_CASES = {
    # name: (buckets, pumps: each a list of frames, a frame a list of
    # message row counts)
    "one_chunk": ((64, 256), [[[100]]]),
    "52_x_2048": ((32768, 131072), [[[2048]] * 52]),
    "chunk_split_by_take": ((64, 128), [[[50]] * 7]),
    "rows_equal_bucket": ((64, 128), [[[64], [64]]]),
    "under_smallest_bucket": ((64, 128), [[[5]]]),
    "frame_of_several_messages": ((64, 128), [[[10, 20, 30], [7]]]),
    "shorter_after_longer": ((64, 128), [[[120]], [[90]], [[3]], [[128]], [[1]]]),
}


@pytest.mark.parametrize("case", sorted(STAGING_CASES))
def test_staged_arrays_bit_for_bit_vs_concat_pad_stack(case):
    """The writer's tag_mat / meters / valid equal the old formulation
    on the same rows, batch by batch, and nothing a buffer held before
    shows in the tail."""
    from deepflow_tpu.ingest.framing import FlowHeader, MessageType, encode_frame

    buckets, pumps = STAGING_CASES[case]
    gen = SyntheticFlowGen(num_tuples=500, seed=31)
    sink = _CaptureSink(buckets)
    q = PyOverwriteQueue(1 << 10)
    feeder = FeederRuntime([q], sink, FeederConfig(frames_per_queue=64))
    _poison(sink.staging, buckets)
    sent, t = [], 0
    for frames in pumps:
        for messages in frames:
            fbs = [gen.flow_batch(n, T0 + (t := t + 1)) for n in messages]
            q.put(encode_frame(
                FlowHeader(msg_type=int(MessageType.TAGGEDFLOW), agent_id=1),
                [encode_flowbatch_body(fb) for fb in fbs]))
            sent += fbs
        feeder.pump()
    assert sink.decode_errors == 0 and sink.batches
    # cut the sent rows where the feeder cut its batches, as _take did
    pending = list(sent)
    for tag_mat, meters, valid, rows, bucket in sink.batches:
        parts, need = [], rows
        while need:
            fb = pending.pop(0)
            if fb.size > need:
                pending.insert(0, fb.slice(need, fb.size))
                fb = fb.slice(0, need)
            parts.append(fb)
            need -= fb.size
        want_tags, want_meters, want_valid = _old_staged_arrays(parts, bucket)
        assert tag_mat.dtype == np.uint32 and meters.dtype == np.float32
        np.testing.assert_array_equal(tag_mat, want_tags)
        assert meters.tobytes() == np.ascontiguousarray(want_meters).tobytes()
        np.testing.assert_array_equal(valid, want_valid)
    assert not pending
    assert feeder.get_counters()["records_out"] == sum(fb.size for fb in sent)


def test_host_copy_bytes_is_rows_written_plus_the_zeroed_tail():
    """[count] A record is 37 u32 tags + 62 f32 meters + its mask byte =
    397 B, written once; the tail costs only what the buffer's previous
    fill left past the new rows."""
    record = 4 * len(FLOW_RECORD_TAG_FIELDS) + 4 * FLOW_METER.num_fields + 1
    assert record == 397
    gen = SyntheticFlowGen(num_tuples=100, seed=32)
    sink = _CaptureSink((128,))
    q = PyOverwriteQueue(64)
    feeder = FeederRuntime([q], sink, FeederConfig())
    want = 0
    # one bucket, a ring of three: batch i reuses the buffer of batch i-3
    sizes = [100, 128, 40, 60, 128, 90, 10, 5, 90]
    for i, n in enumerate(sizes):
        for fr in encode_flowbatch_frames(gen.flow_batch(n, T0 + i), max_rows_per_frame=50):
            q.put(fr)
        feeder.pump()
        before = sizes[i - STAGING_RING_LEN] if i >= STAGING_RING_LEN else 0
        want += record * (n + max(0, before - n))
        assert feeder.get_counters()["host_copy_bytes"] == want, i
    assert feeder.get_counters()["staging_waits"] == 0
    assert sink.staging.allocated == STAGING_RING_LEN
    # a frame of several messages is joined at decode: counted too
    from deepflow_tpu.ingest.framing import FlowHeader, MessageType, encode_frame

    q.put(encode_frame(
        FlowHeader(msg_type=int(MessageType.TAGGEDFLOW), agent_id=1),
        [encode_flowbatch_body(gen.flow_batch(n, T0 + 20)) for n in (4, 6)]))
    feeder.pump()
    want += (record - 1) * 10 + record * (10 + max(0, sizes[-3] - 10))
    assert feeder.get_counters()["host_copy_bytes"] == want


def test_ring_allocates_its_length_per_bucket_used():
    """[count] 50 batches over two of three buckets make 2 x the ring's
    length of buffers and no more; the bucket never used makes none."""
    gen = SyntheticFlowGen(num_tuples=100, seed=33)
    sink = _CaptureSink((32, 64, 128))
    q = PyOverwriteQueue(64)
    feeder = FeederRuntime([q], sink, FeederConfig())
    for i in range(50):
        n = 20 if i % 2 else 100
        for fr in encode_flowbatch_frames(gen.flow_batch(n, T0 + i)):
            q.put(fr)
        feeder.pump()
    assert len(sink.batches) == 50
    assert sink.staging.allocated == 2 * STAGING_RING_LEN
    assert sorted(b for b, _ in sink.staging._rings) == [32, 128]


# ---------------------------------------------------------------------------
# fan-in + coalescing end-to-end


def _feed_queues(queues, gen, sizes, max_rows=50):
    """Deterministic drain schedule: per timestep, frames round-robin
    over the queues."""
    for t, n in enumerate(sizes):
        fb = gen.flow_batch(n, T0 + t)
        for i, fr in enumerate(
            encode_flowbatch_frames(fb, agent_id=t, max_rows_per_frame=max_rows)
        ):
            queues[(t + i) % len(queues)].put(fr)
        yield t


def test_feeder_fanin_matches_direct_ingest():
    """3-queue fan-in through the feeder produces bit-exact flushed
    windows vs direct pipeline ingest of the same per-timestep batches
    (pump-per-timestep keeps batch boundaries aligned)."""
    sizes = [150, 90, 256, 64, 200, 150, 30, 256, 110, 70]
    buckets = (64, 128, 256)

    gen = SyntheticFlowGen(num_tuples=200, seed=3)
    direct = L4Pipeline(PipelineConfig(
        window=WindowConfig(capacity=1 << 12, stats_ring=4),
        batch_size=256, bucket_sizes=buckets,
    ))
    docs_direct = []
    for t, n in enumerate(sizes):
        docs_direct += direct.ingest(gen.flow_batch(n, T0 + t))
    docs_direct += direct.drain()

    gen2 = SyntheticFlowGen(num_tuples=200, seed=3)
    pipe = L4Pipeline(PipelineConfig(
        window=WindowConfig(capacity=1 << 12, stats_ring=4),
        batch_size=256, bucket_sizes=buckets,
    ))
    queues = [PyOverwriteQueue(1 << 10) for _ in range(3)]
    feeder = FeederRuntime(
        queues, PipelineFeedSink(pipe), FeederConfig(frames_per_queue=8)
    )
    docs = []
    for _ in _feed_queues(queues, gen2, sizes):
        docs += feeder.pump()
    docs += feeder.flush()
    docs += pipe.drain()

    def rows(dbs):
        out = []
        for db in dbs:
            for i in range(db.size):
                out.append((int(db.timestamp[i]), tuple(db.tags[i].tolist()),
                            tuple(db.meters[i].tolist())))
        return sorted(out)

    assert rows(docs) == rows(docs_direct)
    fc = feeder.get_counters()
    assert fc["records_in"] == sum(sizes) == fc["records_out"]
    assert fc["shed_records"] == 0 and fc["bad_frames"] == 0
    pc = pipe.get_counters()
    assert pc["jit_retraces"] == 0
    assert pc["doc_in"] == direct.get_counters()["doc_in"]


def test_feeder_double_buffer_holds_one_batch():
    gen = SyntheticFlowGen(num_tuples=50, seed=5)
    pipe = L4Pipeline(PipelineConfig(
        window=WindowConfig(capacity=1 << 10), batch_size=64,
        bucket_sizes=(64,),
    ))
    q = PyOverwriteQueue(64)
    sink = PipelineFeedSink(pipe)  # double_buffer=True
    feeder = FeederRuntime([q], sink, FeederConfig())
    for fr in encode_flowbatch_frames(gen.flow_batch(40, T0), max_rows_per_frame=40):
        q.put(fr)
    feeder.pump()
    # staged but not dispatched: the device hasn't seen the batch
    assert sink._held is not None
    assert pipe.get_counters()["doc_in"] == 0
    feeder.flush()
    pipe.wm.settle()
    assert sink._held is None
    assert pipe.get_counters()["doc_in"] > 0


def test_feeder_shed_deterministic_and_accounted():
    """Fixed drain schedule → identical shed decisions, counts and
    emitted batches across runs; every dropped record shows up in the
    feeder counters AND the pipeline's CB_FEEDER_SHED lane."""
    def run():
        gen = SyntheticFlowGen(num_tuples=20, seed=2)
        q = [PyOverwriteQueue(8)]
        pipe = L4Pipeline(PipelineConfig(
            window=WindowConfig(capacity=1 << 10), batch_size=64,
            bucket_sizes=(64,),
        ))
        feeder = FeederRuntime(
            q, PipelineFeedSink(pipe, double_buffer=False),
            FeederConfig(frames_per_queue=2, rounds_per_pump=1),
        )
        # overfill: 8 frames into a capacity-8 queue → depth ≥ high
        # watermark at the first visit
        for t in range(8):
            for fr in encode_flowbatch_frames(
                gen.flow_batch(10, T0 + t), max_rows_per_frame=10
            ):
                q[0].put(fr)
        feeder.pump()
        feeder.pump()
        feeder.flush()
        pipe.wm.settle()
        return feeder.get_counters(), pipe.get_counters()

    fc1, pc1 = run()
    fc2, pc2 = run()
    # counts, not clocks: the `*_us` lanes (CPU lanes, waits) are times
    counts = lambda c: {k: v for k, v in c.items() if not k.endswith("_us")}
    assert counts(fc1) == counts(fc2) and len(counts(fc1)) < len(fc1)
    assert fc1["shed_frames"] > 0 and fc1["pressure_events"] > 0
    # whole frames only: shed records are a multiple of the frame size
    assert fc1["shed_records"] % 10 == 0
    # conservation: every record either ingested or accounted as shed
    assert fc1["records_in"] + fc1["shed_records"] == 80
    # the device counter block saw every shed record
    assert pc1["feeder_shed"] == fc1["shed_records"] == pc2["feeder_shed"]


def test_feeder_doc_sink_merges_like_device_path():
    """METRICS pb frames → WindowManagerFeedSink: host-side packed-word
    fingerprints must merge identical doc keys exactly like the device
    path (5 ports × 2 windows → 10 rows)."""
    from deepflow_tpu.datamodel.batch import DocBatch
    from deepflow_tpu.ingest.codec import encode_docbatch
    from deepflow_tpu.ingest.framing import FlowHeader, MessageType, encode_frame

    n = 40
    tags = np.zeros((n, TAG_SCHEMA.num_fields), np.uint32)
    tags[:, TAG_SCHEMA.index("meter_id")] = 1  # FLOW
    tags[:, TAG_SCHEMA.index("code_id")] = 1
    tags[:, TAG_SCHEMA.index("server_port")] = np.arange(n) % 5 + 80
    meters = np.zeros((n, FLOW_METER.num_fields), np.float32)
    meters[:, FLOW_METER.index("packet_tx")] = 1
    ts = np.full(n, T0, np.uint32)
    ts[n // 2:] = T0 + 5
    db = DocBatch(tags=tags, meters=meters, timestamp=ts,
                  valid=np.ones(n, bool))
    frame = encode_frame(
        FlowHeader(msg_type=int(MessageType.METRICS), agent_id=1),
        encode_docbatch(db),
    )

    wm = WindowManager(WindowConfig(capacity=1 << 10, stats_ring=4))
    q = PyOverwriteQueue(64)
    q.put(frame)
    feeder = FeederRuntime([q], WindowManagerFeedSink(wm, (32, 64)))
    flushed = feeder.pump()
    flushed += wm.flush_all()
    assert sum(f.count for f in flushed) == 10
    assert wm.get_counters()["doc_in"] == n
    # packet_tx mass conserved through the merge
    col = FLOW_METER.index("packet_tx")
    assert sum(float(f.meters[:, col].sum()) for f in flushed) == n


def test_feeder_sharded_sink_and_bucket_validation():
    from deepflow_tpu.feeder import ShardedFeedSink
    from deepflow_tpu.ops.histogram import LogHistSpec
    from deepflow_tpu.parallel.mesh import make_mesh
    from deepflow_tpu.parallel.sharded import (
        ShardedConfig,
        ShardedPipeline,
        ShardedWindowManager,
    )

    mesh = make_mesh(2)
    cfg = ShardedConfig(
        capacity_per_device=1 << 10, num_services=16, hll_precision=6,
        hist=LogHistSpec(bins=64, vmin=1.0, gamma=1.3),
    )
    swm = ShardedWindowManager(ShardedPipeline(mesh, cfg))
    with pytest.raises(ValueError, match="divisible"):
        ShardedFeedSink(swm, (63, 128))

    sizes = [100, 64, 120, 90, 100, 30]

    # direct oracle: same per-timestep batches, padded to the same
    # buckets, straight into a fresh manager
    gen0 = SyntheticFlowGen(num_tuples=100, seed=6)
    swm0 = ShardedWindowManager(ShardedPipeline(mesh, cfg))
    direct = []
    for t, n in enumerate(sizes):
        fb = gen0.flow_batch(n, T0 + t).pad_to(64 if n <= 64 else 128)
        direct += swm0.ingest(fb.tags, fb.meters, fb.valid)
    direct += swm0.drain()

    def rows(dbs):
        acc = []
        for db in dbs:
            for i in range(db.size):
                acc.append((int(db.timestamp[i]), tuple(db.tags[i].tolist()),
                            tuple(db.meters[i].tolist())))
        return sorted(acc)

    # (a) order-preserving fan-in (single queue): flushed rows BIT-EXACT
    # vs direct ingest — row order decides per-device stash assignment,
    # so this is the apples-to-apples sharded oracle
    gen = SyntheticFlowGen(num_tuples=100, seed=6)
    q = PyOverwriteQueue(256)
    feeder = FeederRuntime(
        [q], ShardedFeedSink(swm, (64, 128)), FeederConfig(frames_per_queue=8)
    )
    out = []
    for t in _feed_queues([q], gen, sizes, max_rows=40):
        out += feeder.pump()
    out += swm.drain()
    assert swm.get_counters()["flow_in"] == sum(sizes)
    assert rows(out) == rows(direct)

    # (b) true multi-queue fan-in permutes rows across devices (exact
    # stashes never merge cross-device — reference per-pipeline
    # isolation), so assert conservation: same row count and same total
    # per-window mass on a sum-merged meter column
    from deepflow_tpu.datamodel.schema import FLOW_METER as _M

    gen2 = SyntheticFlowGen(num_tuples=100, seed=6)
    swm2 = ShardedWindowManager(ShardedPipeline(mesh, cfg))
    queues = [PyOverwriteQueue(256) for _ in range(2)]
    feeder2 = FeederRuntime(
        queues, ShardedFeedSink(swm2, (64, 128)), FeederConfig(frames_per_queue=8)
    )
    out2 = []
    for t in _feed_queues(queues, gen2, sizes, max_rows=40):
        out2 += feeder2.pump()
    out2 += swm2.drain()
    col = _M.index("packet_tx")

    def mass(dbs):
        """Per-window (key set, sum-meter mass): both are invariant to
        the row permutation (a key split across devices flushes as two
        rows, but its identity and its summed meters are conserved)."""
        per_w = {}
        for db in dbs:
            w = int(db.timestamp[0])
            keys, tx = per_w.setdefault(w, (set(), 0.0))
            keys.update(tuple(db.tags[i].tolist()) for i in range(db.size))
            per_w[w] = (keys, tx + float(db.meters[:, col].sum()))
        return per_w

    assert mass(out2) == mass(direct)


def test_feeder_serve_thread_drains_queue():
    import time as _time

    gen = SyntheticFlowGen(num_tuples=30, seed=8)
    pipe = L4Pipeline(PipelineConfig(
        window=WindowConfig(capacity=1 << 10), batch_size=64,
        bucket_sizes=(64,),
    ))
    q = PyOverwriteQueue(256)
    got = []
    feeder = FeederRuntime(
        [q], PipelineFeedSink(pipe, double_buffer=False), FeederConfig()
    )
    feeder.serve(poll_ms=5, on_flush=got.extend)
    try:
        for t in range(4):
            for fr in encode_flowbatch_frames(gen.flow_batch(50, T0 + t)):
                q.put(fr)
        deadline = _time.time() + 10
        while feeder.get_counters()["records_in"] < 200 and _time.time() < deadline:
            _time.sleep(0.02)
    finally:
        feeder.stop()
    assert feeder.get_counters()["records_in"] == 200


# ---------------------------------------------------------------------------
# satellites: queue counters, receiver closed-queue skip, checkpoint v1


def test_queue_counters_reach_stats_collector():
    from deepflow_tpu.utils.stats import StatsCollector

    col = StatsCollector()
    q = PyOverwriteQueue(2)
    # register on a private collector (not the process default)
    src = col.register("ingest_queue", q, msg_type="3", queue="0")
    q.put(b"a")
    q.put(b"b")
    q.put(b"c")  # overwrites oldest
    pts = col.tick()
    pt = [p for p in pts if p.module == "ingest_queue"][0]
    assert pt.fields["overwritten"] == 1
    assert pt.fields["depth"] == 2
    assert pt.fields["capacity"] == 2
    assert pt.fields["closed"] == 0
    q.close()
    assert col.tick()[0].fields["closed"] == 1
    col.deregister(src)


def test_receiver_registers_queue_stats_and_skips_closed(monkeypatch):
    from deepflow_tpu.ingest.framing import FlowHeader, MessageType, encode_frame
    from deepflow_tpu.ingest.receiver import Receiver
    from deepflow_tpu.utils import stats as stats_mod

    col = stats_mod.StatsCollector()
    monkeypatch.setattr(stats_mod, "default_collector", col)

    rx = Receiver()
    q_open, q_closed = PyOverwriteQueue(16), PyOverwriteQueue(16)
    rx.register_handler(MessageType.METRICS, [q_open, q_closed])
    q_closed.close()

    def frame(agent_id):
        return encode_frame(
            FlowHeader(msg_type=int(MessageType.METRICS), agent_id=agent_id),
            [b"\x08\x01"],
        )

    # agent 0 → queue 0 (open), agent 1 → queue 1 (closed)
    raw0, raw1 = frame(0), frame(1)
    from deepflow_tpu.ingest.framing import HEADER_LEN

    rx._dispatch(FlowHeader.parse(raw0[:HEADER_LEN]), raw0, ("t", 0))
    rx._dispatch(FlowHeader.parse(raw1[:HEADER_LEN]), raw1, ("t", 0))  # must NOT raise
    assert len(q_open) == 1
    assert rx.counters["queue_closed"] == 1
    assert rx.counters["rx_frames"] == 2
    # the registration satellite: both queues are live sources
    pts = [p for p in col.tick() if p.module == "ingest_queue"]
    assert len(pts) == 2
    assert {dict(p.tags)["queue"] for p in pts} == {"0", "1"}


def test_checkpoint_v1_load_is_a_clear_error(tmp_path):
    import io
    import json

    from deepflow_tpu.aggregator.checkpoint import load_window_state

    # a v1-shaped file (per-leaf arrays; the removed branch's input)
    meta = {"version": 1, "num_tags": TAG_SCHEMA.num_fields, "fill": 0,
            "start_window": None, "drop_before_window": 0,
            "total_docs_in": 0, "total_flushed": 0, "interval": 1,
            "delay": 2, "capacity": 64, "accum_batches": 8}
    buf = io.BytesIO()
    np.savez_compressed(
        buf, meta=np.frombuffer(json.dumps(meta).encode(), np.uint8),
        stash_slot=np.zeros(64, np.uint32),
    )
    p = tmp_path / "v1.ckpt"
    p.write_bytes(buf.getvalue())
    with pytest.raises(ValueError, match="v1.*unsupported|unsupported.*v1"):
        load_window_state(p, TAG_SCHEMA, FLOW_METER)
