"""ISSUE 31: the staging ring under reuse. A batch is written once into a
reused host buffer that already has the upload's shape; on the CPU backend
the device array made from a 64-byte aligned buffer may alias it for its
whole life, so these tests reuse the buffers under the same hazard the
chip's asynchronous transfer makes.

(a) 3 x the ring's length of batches with distinct contents through
FeederRuntime -> PipelineFeedSink -> L4Pipeline at chipbench/tests/tiny.py's
shapes: every flushed window equals the NumPy group-by; (b) a buffer held
in flight makes the writer wait, and the wait is counted and (ISSUE 38)
timed under a span of its own; staged batches held undispatched are never
written over; (c) the failure paths of
tests/test_chaos.py keep their counts and leave the ring as it was; (d)
[count] `bytes_uploaded` a batch and the one span each a batch.
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np
import pytest

from deepflow_tpu import chaos
from deepflow_tpu.aggregator.pipeline import L4Pipeline, PipelineConfig
from deepflow_tpu.aggregator.window import WindowConfig
from deepflow_tpu.datamodel.batch import (
    FLOW_RECORD_TAG_FIELDS,
    STAGED_TAG_ORDER,
    STAGING_RING_LEN,
    FlowBatch,
    StagingRing,
)
from deepflow_tpu.datamodel.schema import FLOW_METER
from deepflow_tpu.feeder import (
    FeederConfig,
    FeederRuntime,
    PipelineFeedSink,
    encode_flowbatch_frames,
)
from deepflow_tpu.ingest.queues import PyOverwriteQueue
from deepflow_tpu.ingest.replay import SyntheticFlowGen
from deepflow_tpu.utils.spans import (
    SPAN_FEEDER_ASSEMBLE,
    SPAN_FEEDER_STAGING_WAIT,
    SPAN_INGEST_STAGE,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIPBENCH = os.path.join(ROOT, "chipbench")
T0 = 1_700_000_000
RECORD = 4 * len(FLOW_RECORD_TAG_FIELDS) + 4 * FLOW_METER.num_fields + 1


@pytest.fixture(scope="module")
def chipbench_modules():
    added = [p for p in (CHIPBENCH, os.path.join(CHIPBENCH, "tests"))
             if p not in sys.path]
    sys.path[:0] = added
    import gen
    import reference
    import tiny
    import wire

    yield {"gen": gen, "reference": reference, "tiny": tiny, "wire": wire}
    for p in added:
        sys.path.remove(p)


def _tiny_pipe(tiny) -> L4Pipeline:
    p = tiny.CONFIG["pipeline"]
    buckets = tuple(p["buckets"])
    return L4Pipeline(PipelineConfig(
        window=WindowConfig(interval=p["interval"], delay=p["delay"],
                            capacity=p["stash_rows"],
                            accum_batches=p["accum_batches"]),
        batch_size=buckets[-1], bucket_sizes=buckets,
        batch_unique_cap=p["batch_unique_cap"]))


def _small_pipe(buckets=(64,)) -> L4Pipeline:
    return L4Pipeline(PipelineConfig(
        window=WindowConfig(capacity=1 << 10), batch_size=buckets[-1],
        bucket_sizes=buckets))


# ---------------------------------------------------------------------------
# (a) reuse, end to end, against the NumPy group-by


def test_reused_buffers_flush_what_the_numpy_group_by_gives(chipbench_modules):
    m = chipbench_modules
    schema = m["gen"].load_schema()
    source = m["gen"].FlowSource(schema, m["tiny"].CONFIG["population"], seed=31)
    pipe = _tiny_pipe(m["tiny"])
    q = PyOverwriteQueue(1 << 10)
    sink = PipelineFeedSink(pipe)
    feeder = FeederRuntime([q], sink, FeederConfig(frames_per_queue=64))
    # one batch an event-second, the two buckets in turn: 3 x the ring's
    # length of batches a bucket, every one with records of its own
    seconds = 6 * STAGING_RING_LEN
    sizes = {k: 1500 if k % 2 else 300 for k in range(seconds)}
    flushed, sent = [], {}
    try:
        for k, n in sizes.items():
            tags, meters = source.second(k, n)
            sent[m["gen"].T0 + k] = (tags, meters)
            for frame in m["wire"].encode_frames(tags, meters, schema["wire"]):
                q.put(frame)
            flushed += feeder.pump()
        flushed += feeder.flush()
        flushed += pipe.drain()
        fc = feeder.get_counters()
        assert fc["batches_out"] == seconds and fc["lost_records"] == 0
        assert fc["records_out"] == sum(sizes.values())
        # both rings went round three times and made no buffer beyond their length
        assert pipe.staging.allocated == 2 * STAGING_RING_LEN
        for bufs in pipe.staging._rings.values():
            assert all(b.tag_mat.ctypes.data % 64 == 0 for b in bufs)
        # written once: the records, and the tails shorter batches found
        assert RECORD * fc["records_out"] <= fc["host_copy_bytes"] \
            < (RECORD + 1) * fc["records_out"]
        got = {int(db.timestamp[0]): db for db in flushed}
        assert sorted(got) == sorted(sent) and len(flushed) == seconds
        for w, (tags, meters) in sent.items():
            want_tags, want_meters = m["reference"].reference_docs(schema, tags, meters)
            r = m["reference"].compare_docs(
                schema, got[w].tags, got[w].meters, want_tags, want_meters)
            assert r["docs"] == r["docs_got"] > 0
            for name, limit in m["reference"].LIMITS.items():
                assert r[name] <= limit, (w, name, r)
    finally:
        pipe.close()


def test_stage_flowbatch_goes_through_the_same_writer():
    """pipe.ingest(FlowBatch) — warm-up, chip_smoke, the agent — fills a
    buffer of the pipeline's own ring as one chunk, the batch's mask
    kept; direct and feeder-fed batches share the ring."""
    pipe = _small_pipe((64, 128))
    gen = SyntheticFlowGen(num_tuples=40, seed=5)
    try:
        fb = gen.flow_batch(50, T0)
        fb.valid[::3] = False
        staged = pipe.stage(fb)
        buf = staged.source
        assert buf.names == STAGED_TAG_ORDER and buf.bucket == 64
        assert (buf.rows, buf.n_valid) == (50, int(fb.valid.sum()))
        for j, name in enumerate(buf.names):
            np.testing.assert_array_equal(buf.tag_mat[j, :50], fb.tags[name])
            assert not buf.tag_mat[j, 50:].any()
        np.testing.assert_array_equal(buf.meters[:50], fb.meters)
        np.testing.assert_array_equal(buf.valid[:50], fb.valid)
        assert not buf.valid[50:].any() and not buf.meters[50:].any()
        np.testing.assert_array_equal(np.asarray(staged.tag_mat), buf.tag_mat)
        assert staged.padded_rows == 64
        # all-padding: nothing staged, the buffer is free at once
        empty = gen.flow_batch(8, T0)
        empty.valid[:] = False
        assert pipe.stage(empty) is None
        # over the largest bucket: the caller's error, as pad_to's was
        with pytest.raises(ValueError, match="exceeds the largest shape bucket"):
            pipe.stage(gen.flow_batch(200, T0))
        assert PipelineFeedSink(pipe).staging is pipe.staging
    finally:
        pipe.close()


# ---------------------------------------------------------------------------
# (b) the ring's discipline


class _Handle:
    """A device array still in flight."""

    def __init__(self):
        self.waited = 0

    def is_ready(self):
        return bool(self.waited)

    def block_until_ready(self):
        self.waited += 1


def test_writer_waits_for_a_buffer_in_flight_and_counts_it():
    pipe = _small_pipe()
    q = PyOverwriteQueue(64)
    sink = PipelineFeedSink(pipe)
    feeder = FeederRuntime([q], sink, FeederConfig())
    gen = SyntheticFlowGen(num_tuples=40, seed=7)

    def batch(i):
        for fr in encode_flowbatch_frames(gen.flow_batch(40, T0 + i)):
            q.put(fr)
        return feeder.pump()

    try:
        for i in range(2 * STAGING_RING_LEN):
            batch(i)
        # the per-batch stats fetch returns before a buffer comes round again
        assert feeder.get_counters()["staging_waits"] == 0
        bufs, = pipe.staging._rings.values()
        assert len(bufs) == STAGING_RING_LEN
        nxt, h = bufs[0], _Handle()  # least recently used: the next one out
        nxt.dispatched(h)  # its step has not run yet
        batch(10)
        assert h.waited == 1 and bufs[-1] is nxt
        assert feeder.get_counters()["staging_waits"] == 1 == pipe.staging.waits
        batch(11)
        assert feeder.get_counters()["staging_waits"] == 1
        assert pipe.staging.allocated == STAGING_RING_LEN
    finally:
        pipe.close()


class _SlowHandle(_Handle):
    """In flight for `delay_s` more once somebody waits for it; it keeps
    how long that wait took on the clock the spans use."""

    def __init__(self, delay_s: float):
        super().__init__()
        self.delay_s = delay_s
        self.blocked_us = 0

    def block_until_ready(self):
        t0 = time.perf_counter()
        time.sleep(self.delay_s)
        super().block_until_ready()
        self.blocked_us = int((time.perf_counter() - t0) * 1e6)


@pytest.mark.parametrize("ready", [False, True])
def test_staging_wait_is_a_span_of_its_own_only_when_it_blocked(ready):
    pipe = _small_pipe()
    q = PyOverwriteQueue(64)
    feeder = FeederRuntime([q], PipelineFeedSink(pipe), FeederConfig())
    gen = SyntheticFlowGen(num_tuples=40, seed=7)
    try:
        for i in range(2 * STAGING_RING_LEN + 1):
            if i == 2 * STAGING_RING_LEN:
                # the next buffer out: the step that read it has, or has not, run
                bufs, = pipe.staging._rings.values()
                h = _SlowHandle(0.02)
                h.waited = int(ready)
                bufs[0].dispatched(h)
            for fr in encode_flowbatch_frames(gen.flow_batch(40, T0 + i)):
                q.put(fr)
            feeder.pump()
        c, tr = feeder.get_counters(), feeder.tracer
        waits = tr.recent(SPAN_FEEDER_STAGING_WAIT)
        if ready:
            assert waits == [] and SPAN_FEEDER_STAGING_WAIT not in tr.summary()
            assert (c["staging_waits"], c["staging_wait_us"], h.blocked_us) == (0, 0, 0)
            return
        wait, = waits
        assemble = {r.span_id: r for r in tr.recent(SPAN_FEEDER_ASSEMBLE)}
        # a child of the batch's feeder.assemble, whose time holds it
        assert assemble[wait.parent_span_id].duration_us >= wait.duration_us
        assert c["staging_waits"] == 1 == tr.summary()[SPAN_FEEDER_STAGING_WAIT]["count"]
        assert c["staging_wait_us"] == wait.duration_us >= h.blocked_us >= 20_000
        # the thread slept through it: wall, not CPU (the lane is the span's
        # own: the feeder republishes six lanes as counters, not this one)
        assert wait.cpu_us < wait.duration_us // 4 and "staging_wait_cpu_us" not in c
        assert tr.get_counters()[f"{SPAN_FEEDER_STAGING_WAIT}.cpu_us"] == wait.cpu_us
        assert c["assemble_cpu_us"] < tr.summary()[SPAN_FEEDER_ASSEMBLE]["total_us"]
    finally:
        pipe.close()


def test_staged_batches_held_undispatched_are_never_written_over():
    """A caller may hold more staged batches than the ring is long: the
    ring grows before it hands out memory a live staged batch reads."""
    pipe = _small_pipe()
    gen = SyntheticFlowGen(num_tuples=40, seed=9)
    try:
        fbs = [gen.flow_batch(30 + i, T0) for i in range(STAGING_RING_LEN + 2)]
        held = [pipe.stage(fb) for fb in fbs]
        assert len({id(s.source) for s in held}) == len(held)
        assert pipe.staging.allocated == len(held)
        for s, fb in zip(held, fbs):
            np.testing.assert_array_equal(
                np.asarray(s.meters)[: fb.size], fb.meters)
        out = []
        for s in held:
            out += pipe.ingest_staged(s)
            assert not s.source.held()
        del held, s
        # dispatched and run: the same buffers serve from here on
        for i in range(2 * STAGING_RING_LEN):
            pipe.ingest(gen.flow_batch(20, T0 + 1))
        assert pipe.staging.allocated == STAGING_RING_LEN + 2
        assert pipe.staging.waits == 0
    finally:
        pipe.close()


def test_ring_is_keyed_by_bucket_and_tag_names():
    ring = StagingRing(FLOW_METER.num_fields)
    a = ring.acquire(64)
    b = ring.acquire(64, ("timestamp", "agent_id"))
    c = ring.acquire(128)
    assert a.tag_mat.shape == (len(FLOW_RECORD_TAG_FIELDS), 64)
    assert b.tag_mat.shape == (2, 64) and c.valid.shape == (128,)
    assert ring.allocated == 3
    # a name set that is not the wire's takes columns by name only
    with pytest.raises(ValueError, match="wire-order"):
        b.write(np.zeros((len(FLOW_RECORD_TAG_FIELDS), 4), np.uint32),
                np.zeros((4, FLOW_METER.num_fields), np.float32))
    b.write({"timestamp": np.arange(4), "agent_id": np.ones(4)},
            np.zeros((4, FLOW_METER.num_fields), np.float32))
    assert b.tag_mat[:, :4].tolist() == [[0, 1, 2, 3], [1, 1, 1, 1]]


# ---------------------------------------------------------------------------
# (c) the failure paths keep their counts and give their buffer back


def _deliver(q, fb):
    for fr in encode_flowbatch_frames(fb):
        q.put(fr)


def test_failed_stage_counts_its_rows_and_returns_its_buffer():
    pipe = _small_pipe()
    q = PyOverwriteQueue(64)
    sink = PipelineFeedSink(pipe)
    feeder = FeederRuntime([q], sink, FeederConfig(probe_interval=1))
    gen = SyntheticFlowGen(num_tuples=40, seed=11)
    real_stage, fail = pipe.stage, {"n": 1}

    def flaky_stage(buf):
        if fail["n"]:
            fail["n"] -= 1
            raise RuntimeError("RESOURCE_EXHAUSTED: device put failed")
        return real_stage(buf)

    pipe.stage = flaky_stage
    try:
        _deliver(q, gen.flow_batch(40, T0))
        feeder.pump()
        c = feeder.get_counters()
        assert c["lost_records"] == 40 == sink.lost_records
        assert c["emit_failures"] == 1 and sink._held is None
        # the buffer it had filled is held by no staged batch
        bufs, = pipe.staging._rings.values()
        assert len(bufs) == 1 and not bufs[0].held()
        for i in range(1, 2 * STAGING_RING_LEN):
            _deliver(q, gen.flow_batch(40, T0 + i))
            feeder.pump()
        c = feeder.get_counters()
        assert c["lost_records"] == 40 and c["staging_waits"] == 0
        assert c["records_out"] == 40 * 2 * STAGING_RING_LEN
        assert pipe.staging.allocated == STAGING_RING_LEN
    finally:
        pipe.close()


def test_failed_held_dispatch_keeps_the_fresh_batch_staged():
    pipe = _small_pipe()
    q = PyOverwriteQueue(64)
    sink = PipelineFeedSink(pipe)
    feeder = FeederRuntime([q], sink, FeederConfig(probe_interval=1))
    gen = SyntheticFlowGen(num_tuples=40, seed=13)
    try:
        _deliver(q, gen.flow_batch(40, T0))
        feeder.pump()  # staged and held
        first = sink._held[0].source
        chaos.install(chaos.FaultPlan().add(chaos.FaultRule(
            chaos.SITE_DISPATCH, count=10**9, error=chaos.DeviceLost)))
        try:
            fresh = gen.flow_batch(30, T0 + 1)
            _deliver(q, fresh)
            feeder.pump()  # stages the fresh batch, the held one's dispatch fails
        finally:
            chaos.uninstall()
        c = feeder.get_counters()
        assert c["lost_records"] == 40 == sink.lost_records
        staged, _shed, rows = sink._held
        assert rows == 30 and staged.source is not first and staged.source.held()
        assert not first.held()  # its staged batch is gone: free, nothing to wait for
        np.testing.assert_array_equal(np.asarray(staged.meters)[:30], fresh.meters)
        for i in range(2, 2 + 2 * STAGING_RING_LEN):
            _deliver(q, gen.flow_batch(40, T0 + i))
            feeder.pump()
        feeder.flush()
        c = feeder.get_counters()
        assert c["lost_records"] == 40 and c["degraded"] == 0
        assert pipe.staging.allocated == STAGING_RING_LEN
        assert pipe.get_counters()["doc_in"] > 0
    finally:
        pipe.close()


# ---------------------------------------------------------------------------
# (d) [count] what a batch uploads, and its two spans


def test_bytes_uploaded_a_batch_is_the_bucket_in_the_uploads_layout():
    pipe = _small_pipe((64, 128))
    q = PyOverwriteQueue(64)
    feeder = FeederRuntime([q], PipelineFeedSink(pipe), FeederConfig())
    gen = SyntheticFlowGen(num_tuples=40, seed=15)
    try:
        want = 0
        for i, n in enumerate((40, 100, 64, 128, 5)):
            _deliver(q, gen.flow_batch(n, T0 + i))
            feeder.pump()
            want += RECORD * (64 if n <= 64 else 128)
            assert pipe.get_counters()["bytes_uploaded"] == want
        pipe.ingest(gen.flow_batch(70, T0 + 9))  # the direct path counts the same
        assert pipe.get_counters()["bytes_uploaded"] == want + RECORD * 128
        f, p = feeder.tracer.summary(), pipe.tracer.summary()
        assert f[SPAN_FEEDER_ASSEMBLE]["count"] == 5
        assert p[SPAN_INGEST_STAGE]["count"] == 6
    finally:
        pipe.close()
