"""Fault-injection containment (ISSUE 6): every injected fault class —
device dispatch, host fetch, frame decode, queue overrun, sink/storage
write, checkpoint I/O — must either retry to success or degrade with
counted shedding. No silent thread death, no uncounted data loss.
Every scenario is seeded/indexed so it replays identically."""

from __future__ import annotations

import random
import time

import numpy as np
import pytest

from deepflow_tpu import chaos
from deepflow_tpu.aggregator.pipeline import L4Pipeline, PipelineConfig
from deepflow_tpu.aggregator.window import WindowConfig
from deepflow_tpu.datamodel.batch import FlowBatch
from deepflow_tpu.feeder import (
    FeederConfig,
    FeederRuntime,
    PipelineFeedSink,
    encode_flowbatch_frames,
)
from deepflow_tpu.ingest.queues import PyOverwriteQueue
from deepflow_tpu.ingest.replay import SyntheticFlowGen
from deepflow_tpu.utils.retry import RetryPolicy, is_transient, retry_call

T0 = 1_700_000_000
FAST_RETRY = RetryPolicy(attempts=4, base_delay_s=0.0, max_delay_s=0.0)


@pytest.fixture(autouse=True)
def _no_leftover_plan():
    yield
    chaos.uninstall()


def _mk_pipe(**wkw):
    cfg = PipelineConfig(
        window=WindowConfig(capacity=1 << 12, **wkw),
        batch_size=256,
        bucket_sizes=(64, 128, 256),
    )
    pipe = L4Pipeline(cfg)
    pipe.wm.retry_policy = FAST_RETRY
    return pipe


def _mk_feeder(pipe, nq=1, **fkw):
    queues = [PyOverwriteQueue(1 << 10) for _ in range(nq)]
    feeder = FeederRuntime(
        queues, PipelineFeedSink(pipe),
        FeederConfig(frames_per_queue=64, **fkw),
    )
    return queues, feeder


def _deliver(queues, fb, max_rows=64):
    for j, fr in enumerate(encode_flowbatch_frames(fb, max_rows_per_frame=max_rows)):
        queues[j % len(queues)].put(fr)


def _mass(dbs):
    from deepflow_tpu.datamodel.schema import FLOW_METER

    c = FLOW_METER.index("packet_tx")
    return (sum(float(db.meters[:, c].sum()) for db in dbs),
            sum(db.size for db in dbs))


# ---------------------------------------------------------------------------
# plan determinism


def test_fault_plan_is_deterministic():
    def run():
        plan = chaos.FaultPlan(seed=7).add(
            chaos.FaultRule("s", p=0.3, count=100, error=chaos.TransientDeviceError),
        )
        fired = []
        for i in range(50):
            try:
                plan.fire("s")
            except chaos.TransientDeviceError:
                fired.append(i)
        return fired

    a, b = run(), run()
    assert a == b and a  # same seed → identical schedule, and it fires


def test_fault_plan_indexed_rules():
    plan = chaos.FaultPlan().add(
        chaos.FaultRule("s", at=(2, 5), error=chaos.FetchTimeout),
    )
    hits = []
    for i in range(8):
        try:
            plan.fire("s")
        except chaos.FetchTimeout:
            hits.append(i)
    assert hits == [2, 5]
    assert plan.calls["s"] == 8 and plan.injected["s"] == 2


def test_retry_policy_classification_and_backoff():
    assert is_transient(chaos.TransientDeviceError("x"))
    assert is_transient(RuntimeError("RESOURCE_EXHAUSTED: out of memory"))
    assert not is_transient(chaos.DeviceLost("gone"))
    assert not is_transient(ValueError("nope"))

    # jittered delays stay within [base*(1-j), cap]
    pol = RetryPolicy(attempts=5, base_delay_s=0.1, max_delay_s=0.3, jitter=0.5)
    rng = random.Random(3)
    for k in (1, 2, 3, 4):
        d = pol.delay(k, rng)
        assert 0.0 < d <= 0.3
    # retry_call: transient → retried; non-transient → immediate raise
    state = {"n": 0}

    def flaky():
        state["n"] += 1
        if state["n"] < 3:
            raise chaos.TransientDeviceError("try again")
        return "ok"

    assert retry_call(flaky, FAST_RETRY) == "ok"
    assert state["n"] == 3
    with pytest.raises(chaos.DeviceLost):
        retry_call(lambda: (_ for _ in ()).throw(chaos.DeviceLost("x")), FAST_RETRY)


def test_dispatch_retry_is_admission_time_only():
    """UNAVAILABLE/ABORTED can be a MID-FLIGHT device loss — the
    dispatch paths donate their accumulators, so retrying one would
    hit a consumed buffer and mask the real error. The dispatch
    classifier accepts only admission-time codes; the fetch path (no
    donation) keeps the broad set."""
    from deepflow_tpu.utils.retry import is_dispatch_transient

    assert is_dispatch_transient(chaos.TransientDeviceError("x"))
    assert is_dispatch_transient(RuntimeError("RESOURCE_EXHAUSTED: oom"))
    assert not is_dispatch_transient(RuntimeError("UNAVAILABLE: device lost"))
    assert not is_dispatch_transient(RuntimeError("ABORTED: replica failure"))
    assert is_transient(RuntimeError("UNAVAILABLE: device hiccup"))

    calls = {"n": 0}

    def boom():
        calls["n"] += 1
        raise RuntimeError("UNAVAILABLE: device lost mid-flight")

    with pytest.raises(RuntimeError):
        retry_call(boom, FAST_RETRY, classify=is_dispatch_transient)
    assert calls["n"] == 1  # no retry against a consumed buffer


def test_retry_delay_survives_unbounded_failstreaks():
    """serve()'s crash-loop guard feeds the uncapped pump failstreak
    into policy.delay — without the exponent clamp, 2.0**1024 raises
    OverflowError and kills the guard thread after ~17 hours of
    continuous failure (the exact silent death it exists to prevent)."""
    pol = RetryPolicy(base_delay_s=0.005, max_delay_s=0.5, multiplier=2.0,
                      jitter=0.0)
    rng = random.Random(1)
    assert pol.delay(100_000, rng) == 0.5
    # the zero-delay test policy shape stays safe too
    assert FAST_RETRY.delay(100_000, rng) == 0.0


# ---------------------------------------------------------------------------
# dispatch + fetch faults: retry to success, bit-exact output


def test_transient_dispatch_and_fetch_faults_retry_to_identical_output():
    gen_args = dict(num_tuples=120, seed=21)

    def run(plan):
        gen = SyntheticFlowGen(**gen_args)
        pipe = _mk_pipe()
        out = []
        if plan is not None:
            chaos.install(plan)
        try:
            for i, t in enumerate((T0, T0 + 1, T0 + 5, T0 + 6)):
                out += pipe.ingest(FlowBatch.from_records(gen.records(200, t)))
            out += pipe.drain()
        finally:
            chaos.uninstall()
        return out, pipe.get_counters()

    oracle, oc = run(None)
    plan = chaos.FaultPlan().add(
        chaos.FaultRule(chaos.SITE_DISPATCH, at=(1, 2), error=chaos.TransientDeviceError),
        chaos.FaultRule(chaos.SITE_FETCH, at=(3,), error=chaos.FetchTimeout),
    )
    faulted, fc = run(plan)
    assert plan.injected == {chaos.SITE_DISPATCH: 2, chaos.SITE_FETCH: 1}
    assert fc["dispatch_retries"] == 2 and fc["fetch_retries"] == 1
    assert oc["dispatch_retries"] == 0 and oc["fetch_retries"] == 0
    # bit-exact: same windows, same rows, same meter bits
    assert len(faulted) == len(oracle)
    for a, b in zip(faulted, oracle):
        np.testing.assert_array_equal(a.timestamp, b.timestamp)
        np.testing.assert_array_equal(a.tags, b.tags)
        assert a.meters.tobytes() == b.meters.tobytes()


# ---------------------------------------------------------------------------
# sustained dispatch failure: degraded mode + probe recovery


def test_sustained_dispatch_failure_degrades_and_probe_recovers():
    pipe = _mk_pipe()
    queues, feeder = _mk_feeder(pipe, probe_interval=3)
    gen = SyntheticFlowGen(num_tuples=100, seed=5)

    # healthy warmup
    _deliver(queues, gen.flow_batch(100, T0))
    feeder.pump()
    assert feeder.get_counters()["healthy"] == 1

    # device goes away hard: every dispatch fails, non-transient
    chaos.install(chaos.FaultPlan().add(
        chaos.FaultRule(chaos.SITE_DISPATCH, count=10**9, error=chaos.DeviceLost)
    ))
    _deliver(queues, gen.flow_batch(100, T0 + 1))
    feeder.pump()
    c = feeder.get_counters()
    assert c["degraded"] == 1 and c["healthy"] == 0
    assert c["emit_failures"] >= 1
    assert c["degraded_entries"] == 1

    # while degraded: frames are shed WHOLE and counted, no exceptions
    shed0 = c["shed_records"]
    for i in range(2):  # probe_interval=3 → these pumps shed
        _deliver(queues, gen.flow_batch(50, T0 + 2 + i))
        feeder.pump()
    c = feeder.get_counters()
    assert c["degraded"] == 1
    assert c["shed_records"] > shed0
    assert c["degraded_shed_records"] > 0

    # device comes back; the next probe pump flows through and recovers
    chaos.uninstall()
    recovered = False
    for i in range(4):
        _deliver(queues, gen.flow_batch(50, T0 + 5 + i))
        feeder.pump()
        if feeder.get_counters()["degraded"] == 0:
            recovered = True
            break
    assert recovered
    c = feeder.get_counters()
    assert c["probe_attempts"] >= 1
    assert c["degraded_exits"] == 1 and c["healthy"] == 1

    # no uncounted loss: conservation across the lanes — every decoded
    # record either left the buffer (counted out, with losses counted
    # separately) or is still pending; every un-decoded record was shed
    # with a count
    feeder.flush()
    c = feeder.get_counters()
    assert c["records_in"] == c["records_out"] + c["pending_rows"], c
    assert c["lost_records"] > 0
    assert c["shed_records"] >= c["degraded_shed_records"] > 0


def test_idle_probe_pumps_keep_the_probe_armed():
    """A probe pump with no data tests nothing — the probe must stay
    armed so the FIRST data-bearing pump after an idle stretch goes
    through dispatch instead of being shed. Without the re-arm, an
    idle degraded feeder burns its probe on empty pumps and sheds
    fresh traffic even though the device already recovered."""
    pipe = _mk_pipe()
    queues, feeder = _mk_feeder(pipe, probe_interval=4)
    gen = SyntheticFlowGen(num_tuples=100, seed=5)

    # healthy warmup: the double-buffered sink stages one batch behind,
    # so the first dispatch (and the fault) lands on the second pump
    _deliver(queues, gen.flow_batch(100, T0))
    feeder.pump()
    chaos.install(chaos.FaultPlan().add(
        chaos.FaultRule(chaos.SITE_DISPATCH, count=10**9, error=chaos.DeviceLost)
    ))
    _deliver(queues, gen.flow_batch(100, T0 + 1))
    feeder.pump()
    assert feeder.get_counters()["degraded"] == 1

    # device recovers while the feeder sits idle; the countdown elapses
    # across empty pumps with nothing to probe with
    chaos.uninstall()
    for _ in range(6):
        feeder.pump()
    c = feeder.get_counters()
    assert c["degraded"] == 1  # nothing was dispatched, so still degraded
    # idle pumps dispatch nothing, so they are NOT probe attempts — the
    # lane must stay meaningful for dashboards during the outage
    assert c["probe_attempts"] == 0
    shed0 = c["shed_records"]

    # first data after the idle stretch IS the probe — it must dispatch
    # (and recover), not shed
    _deliver(queues, gen.flow_batch(50, T0 + 1))
    feeder.pump()
    c = feeder.get_counters()
    assert c["degraded"] == 0 and c["degraded_exits"] == 1
    assert c["probe_attempts"] >= 1  # the real dispatch counted
    assert c["shed_records"] == shed0
    feeder.flush()
    c = feeder.get_counters()
    assert c["records_in"] == c["records_out"] + c["pending_rows"]


def test_degraded_mode_is_visible_in_deepflow_system():
    """The health lanes dogfood into the deepflow_system table like
    every other counter (graceful-degradation acceptance: health rows
    via dfstats)."""
    from deepflow_tpu.integration.dfstats import (
        DEEPFLOW_SYSTEM_DB,
        DEEPFLOW_SYSTEM_TABLE,
        system_sink,
    )
    from deepflow_tpu.storage.store import ColumnarStore
    from deepflow_tpu.utils.stats import StatsCollector

    pipe = _mk_pipe()
    queues, feeder = _mk_feeder(pipe, probe_interval=100)
    col = StatsCollector(interval_s=999)
    col.register("tpu_feeder", feeder, name="chaos-test")
    store = ColumnarStore()
    col.add_sink(system_sink(store))

    gen = SyntheticFlowGen(num_tuples=60, seed=9)
    # warmup pump stages the first batch (the double buffer dispatches
    # one batch behind) — the SECOND pump's dispatch hits the fault
    _deliver(queues, gen.flow_batch(80, T0))
    feeder.pump()
    chaos.install(chaos.FaultPlan().add(
        chaos.FaultRule(chaos.SITE_DISPATCH, count=10**9, error=chaos.DeviceLost)
    ))
    _deliver(queues, gen.flow_batch(80, T0 + 1))
    feeder.pump()
    chaos.uninstall()
    col.tick(now=float(T0))

    rows = store.scan(DEEPFLOW_SYSTEM_DB, DEEPFLOW_SYSTEM_TABLE)
    by_metric = dict(zip(rows["metric"], rows["value"]))
    assert by_metric["tpu_feeder_degraded"] == 1.0
    assert by_metric["tpu_feeder_healthy"] == 0.0
    assert by_metric["tpu_feeder_lost_records"] > 0


# ---------------------------------------------------------------------------
# decode faults: quarantine, never the pump loop


def test_corrupt_frames_quarantine_and_count():
    pipe = _mk_pipe()
    queues, feeder = _mk_feeder(pipe)
    gen = SyntheticFlowGen(num_tuples=60, seed=13)
    rng = random.Random(0xBAD)

    frames = encode_flowbatch_frames(gen.flow_batch(120, T0), max_rows_per_frame=32)
    good, bad = 0, 0
    for i, fr in enumerate(frames):
        if i % 3 == 1:
            queues[0].put(chaos.bitflip_frame(fr, rng, flips=8))
            bad += 1
        elif i % 3 == 2:
            queues[0].put(chaos.truncate_frame(fr, rng))
            bad += 1
        else:
            queues[0].put(fr)
            good += 1
    feeder.pump()
    c = feeder.get_counters()
    sink = feeder.sink
    # every hostile frame is isolated + counted and the pump never
    # raised. NOTE: a bit-flip can land in meter/tag payload bytes and
    # still decode (the flowframe body has no crc) — decode_errors ≤
    # bad — but magic/length/field-count checks catch the rest.
    assert sink.decode_errors > 0
    assert c["bad_frames"] == sink.decode_errors <= bad
    assert len(sink.quarantine) == min(sink.decode_errors, 8)
    assert c["frames_in"] >= good
    # the good frames' records flowed through normally
    assert c["records_in"] > 0 and c["healthy"] == 1


def test_decode_site_fault_is_quarantined():
    """An injected decoder exception (a decoder BUG, not just bad
    bytes) is contained at the same boundary."""
    pipe = _mk_pipe()
    queues, feeder = _mk_feeder(pipe)
    gen = SyntheticFlowGen(num_tuples=40, seed=3)
    _deliver(queues, gen.flow_batch(64, T0), max_rows=16)
    chaos.install(chaos.FaultPlan().add(
        chaos.FaultRule(chaos.SITE_DECODE, at=(0,), error=RuntimeError("decoder bug"))
    ))
    feeder.pump()  # must not raise
    chaos.uninstall()
    c = feeder.get_counters()
    assert c["bad_frames"] == 1
    assert feeder.sink.quarantine[0][0] == "RuntimeError"
    assert c["frames_in"] > 0  # the rest of the frames decoded fine


# ---------------------------------------------------------------------------
# queue overruns: burst in, overwrites + shed counted, pump survives


def test_queue_overrun_burst_is_counted_and_contained():
    pipe = _mk_pipe()
    q = PyOverwriteQueue(32)  # tiny queue
    feeder = FeederRuntime(
        [q], PipelineFeedSink(pipe), FeederConfig(frames_per_queue=8)
    )
    gen = SyntheticFlowGen(num_tuples=60, seed=17)
    # burst way past capacity: the queue overwrites oldest (counted),
    # the feeder's watermark machinery sheds deterministically
    for t in range(6):
        _deliver([q], gen.flow_batch(200, T0 + t), max_rows=16)
    for _ in range(4):
        feeder.pump()
    c = feeder.get_counters()
    assert c["queue_overwritten"] > 0
    assert c["pressure_events"] >= 1
    assert c["shed_records"] > 0  # watermark shed engaged, counted
    assert c["records_in"] > 0  # and the pipeline kept flowing
    assert c["healthy"] == 1


# ---------------------------------------------------------------------------
# sink/storage write faults


def test_table_writer_retries_transient_and_counts_persistent_failures():
    from deepflow_tpu.storage.store import ColumnarStore, ColumnSpec, TableSchema
    from deepflow_tpu.storage.writer import TableWriter

    schema = TableSchema("t", (ColumnSpec("time", "u4"), ColumnSpec("v", "f8")))
    store = ColumnarStore()
    w = TableWriter(store, "db", schema, flush_interval_s=0.02, retries=3)
    try:
        # one transient write fault → the retry loop absorbs it
        chaos.install(chaos.FaultPlan().add(
            chaos.FaultRule(chaos.SITE_SINK_WRITE, at=(0,), error=chaos.SinkWriteError)
        ))
        w.put({"time": np.asarray([T0], np.uint32), "v": np.asarray([1.0])})
        deadline = time.time() + 5
        while time.time() < deadline and w.get_counters()["write_ok"] < 1:
            time.sleep(0.02)
        c = w.get_counters()
        assert c["write_ok"] == 1 and c["retry"] >= 1 and c["write_fail"] == 0

        # persistent storage failure → counted as failed, thread alive
        chaos.install(chaos.FaultPlan().add(
            chaos.FaultRule(chaos.SITE_SINK_WRITE, count=10**9,
                            error=chaos.SinkWriteError)
        ))
        w.put({"time": np.asarray([T0 + 1], np.uint32), "v": np.asarray([2.0])})
        deadline = time.time() + 5
        while time.time() < deadline and w.get_counters()["write_fail"] < 1:
            time.sleep(0.02)
        assert w.get_counters()["write_fail"] == 1
        chaos.uninstall()
        # storage back → the writer keeps working (no dead thread)
        w.put({"time": np.asarray([T0 + 2], np.uint32), "v": np.asarray([3.0])})
        deadline = time.time() + 5
        while time.time() < deadline and w.get_counters()["write_ok"] < 2:
            time.sleep(0.02)
        assert w.get_counters()["write_ok"] == 2
    finally:
        chaos.uninstall()
        w.stop()


# ---------------------------------------------------------------------------
# checkpoint I/O faults


def test_checkpoint_io_fault_leaves_previous_checkpoint_intact(tmp_path):
    from deepflow_tpu.aggregator.checkpoint import (
        load_window_state,
        save_window_state,
    )
    from deepflow_tpu.datamodel.schema import FLOW_METER, TAG_SCHEMA

    gen = SyntheticFlowGen(num_tuples=40, seed=7)
    pipe = _mk_pipe()
    pipe.ingest(FlowBatch.from_records(gen.records(100, T0)))
    path = tmp_path / "wm.ckpt"
    save_window_state(pipe.wm, path)
    good = path.read_bytes()

    pipe.ingest(FlowBatch.from_records(gen.records(100, T0 + 1)))
    chaos.install(chaos.FaultPlan().add(
        chaos.FaultRule(chaos.SITE_CHECKPOINT_IO, at=(0,),
                        error=chaos.CheckpointIOError)
    ))
    with pytest.raises(OSError):
        save_window_state(pipe.wm, path)
    chaos.uninstall()
    # the fault hit mid-save — the previous checkpoint must be intact
    assert path.read_bytes() == good
    wm = load_window_state(path, TAG_SCHEMA, FLOW_METER)
    assert wm.total_docs_in > 0
    # and the manager is still usable after the failed save
    pipe.ingest(FlowBatch.from_records(gen.records(50, T0 + 2)))


# ---------------------------------------------------------------------------
# serve() crash-loop guard


def test_serve_survives_pump_exceptions():
    pipe = _mk_pipe()

    class BrokenQueue(PyOverwriteQueue):
        def __init__(self, cap):
            super().__init__(cap)
            self.explode = False

        def gets(self, n, timeout_ms=-1):
            if self.explode:
                self.explode = False
                raise RuntimeError("queue backend wedged")
            return super().gets(n, timeout_ms)

    q = BrokenQueue(1 << 10)
    feeder = FeederRuntime([q], PipelineFeedSink(pipe), FeederConfig())
    got = []
    feeder.serve(poll_ms=5, on_flush=got.extend)
    try:
        gen = SyntheticFlowGen(num_tuples=40, seed=23)
        _deliver([q], gen.flow_batch(60, T0))
        deadline = time.time() + 10
        while time.time() < deadline and feeder.get_counters()["records_in"] < 60:
            time.sleep(0.02)
        assert feeder.get_counters()["records_in"] >= 60

        q.explode = True  # one pump blows up
        deadline = time.time() + 10
        while time.time() < deadline and feeder.get_counters()["pump_errors"] < 1:
            time.sleep(0.02)
        assert feeder.get_counters()["pump_errors"] == 1

        # the loop restarted: later traffic still flows and the health
        # state recovers (failstreak resets after the next clean pump)
        _deliver([q], gen.flow_batch(60, T0 + 1))
        deadline = time.time() + 10
        while time.time() < deadline:
            c = feeder.get_counters()
            if c["records_in"] >= 120 and c["pump_failstreak"] == 0:
                break
            time.sleep(0.02)
        c = feeder.get_counters()
        assert c["records_in"] >= 120
        assert c["pump_failstreak"] == 0 and c["healthy"] == 1
    finally:
        feeder.stop()


def test_serve_holds_outputs_when_on_flush_fails():
    """A raising on_flush must not drop flushed windows on the floor:
    they are held and re-delivered (at-least-once) once the callback
    recovers, with the failure counted."""
    pipe = _mk_pipe(delay=1)
    q = PyOverwriteQueue(1 << 10)
    feeder = FeederRuntime([q], PipelineFeedSink(pipe), FeederConfig())
    delivered = []
    state = {"fail": True}

    def on_flush(outs):
        if state["fail"]:
            raise RuntimeError("downstream writer wedged")
        delivered.extend(outs)

    feeder.serve(poll_ms=5, on_flush=on_flush)
    try:
        gen = SyntheticFlowGen(num_tuples=50, seed=29)
        # two windows' worth, then traffic past delay so they flush
        # (one batch per pump: the double-buffered sink trails by one)
        for i, t in enumerate((T0, T0 + 1, T0 + 4, T0 + 5)):
            _deliver([q], gen.flow_batch(80, t))
            deadline = time.time() + 10
            while (time.time() < deadline
                   and feeder.get_counters()["records_in"] < 80 * (i + 1)):
                time.sleep(0.01)
        deadline = time.time() + 10
        while (time.time() < deadline
               and feeder.get_counters()["flush_callback_errors"] < 1):
            time.sleep(0.02)
        c = feeder.get_counters()
        assert c["flush_callback_errors"] >= 1
        assert not delivered  # nothing leaked through while broken

        state["fail"] = False  # downstream recovers
        deadline = time.time() + 10
        while time.time() < deadline and not delivered:
            time.sleep(0.02)
        assert delivered  # the HELD outputs arrived — not dropped
        assert sum(db.size for db in delivered) > 0
    finally:
        feeder.stop()


def test_checkpoint_aborts_when_barrier_flush_fails(tmp_path):
    """checkpoint() during a device failure must NOT snapshot+rotate:
    the journal holds the only replayable copy of the rows the flush
    failed to deliver — rotating would convert a transient failure
    into permanent loss."""
    from deepflow_tpu.feeder import FrameJournal

    pipe = _mk_pipe()
    q = PyOverwriteQueue(1 << 10)
    feeder = FeederRuntime(
        [q], PipelineFeedSink(pipe), FeederConfig(frames_per_queue=64),
        journal=FrameJournal(tmp_path / "j.bin"),
    )
    gen = SyntheticFlowGen(num_tuples=60, seed=37)
    _deliver([q], gen.flow_batch(80, T0))
    feeder.pump()  # stages batch 1 (double buffer)

    chaos.install(chaos.FaultPlan().add(
        chaos.FaultRule(chaos.SITE_DISPATCH, count=10**9, error=chaos.DeviceLost)
    ))
    saves = []
    feeder.checkpoint(lambda barrier: saves.append(barrier) or [])
    chaos.uninstall()

    c = feeder.get_counters()
    assert c["checkpoint_aborts"] == 1
    assert not saves  # the snapshot was never written
    assert feeder._journal.epoch == 0  # and the journal was NOT rotated
    assert feeder._journal.get_counters()["rotations"] == 0

    # device back: a later checkpoint goes through normally
    _deliver([q], gen.flow_batch(40, T0 + 1))
    feeder.pump()
    feeder.checkpoint(lambda barrier: saves.append(barrier) or [])
    assert saves and feeder._journal.epoch == 1


def test_degraded_shed_frames_are_not_journaled(tmp_path):
    """Frames the live run sheds-and-counts in degraded mode must not
    be journaled: replay would resurrect rows the counters already
    declared shed, double-accounting them across lanes."""
    from deepflow_tpu.feeder import FrameJournal

    pipe = _mk_pipe()
    q = PyOverwriteQueue(1 << 10)
    feeder = FeederRuntime(
        [q], PipelineFeedSink(pipe),
        FeederConfig(frames_per_queue=64, probe_interval=100),
        journal=FrameJournal(tmp_path / "j.bin"),
    )
    gen = SyntheticFlowGen(num_tuples=60, seed=41)
    _deliver([q], gen.flow_batch(80, T0))
    feeder.pump()
    chaos.install(chaos.FaultPlan().add(
        chaos.FaultRule(chaos.SITE_DISPATCH, count=10**9, error=chaos.DeviceLost)
    ))
    _deliver([q], gen.flow_batch(80, T0 + 1))
    feeder.pump()  # fails → degraded (this round WAS journaled pre-fault)
    chaos.uninstall()
    assert feeder.get_counters()["degraded"] == 1
    frames0 = feeder._journal.get_counters()["frames"]

    _deliver([q], gen.flow_batch(80, T0 + 2))
    feeder.pump()  # degraded, non-probe → shed WHOLE
    c = feeder.get_counters()
    assert c["degraded_shed_records"] >= 80
    assert feeder._journal.get_counters()["frames"] == frames0


def test_sync_offset_survives_flush_failure(tmp_path):
    """A flush hiccup during the checkpoint barrier must NOT yield
    offset 0 — that direction makes replay double-apply every record
    the snapshot already covers."""
    from deepflow_tpu.feeder import FrameJournal

    j = FrameJournal(tmp_path / "j.bin")
    j.append(b"covered-by-snapshot")
    j.mark()
    good_epoch, good_off = j.sync_offset()
    assert good_off > 0

    real_flush = j._f.flush
    j._f.flush = lambda: (_ for _ in ()).throw(OSError("disk hiccup"))
    epoch, off = j.sync_offset()
    assert (epoch, off) == (good_epoch, good_off)  # NOT (epoch, 0)
    assert j.get_counters()["io_errors"] == 1
    j._f.flush = real_flush
    j.close()


def test_failed_flush_preserves_held_shed_in_carry():
    """The held batch's attached shed count must survive a failed
    dispatch into _shed_carry — dropping it permanently undercounts
    the device-plane feeder_shed lane."""
    pipe = _mk_pipe()
    sink = PipelineFeedSink(pipe)
    gen = SyntheticFlowGen(num_tuples=40, seed=43)
    fb = gen.flow_batch(64, T0)
    staged = pipe.stage(fb)
    sink._held = (staged, 5, 64)  # a staged batch carrying shed=5
    chaos.install(chaos.FaultPlan().add(
        chaos.FaultRule(chaos.SITE_DISPATCH, count=10**9, error=chaos.DeviceLost)
    ))
    with pytest.raises(chaos.DeviceLost):
        sink.flush()
    chaos.uninstall()
    assert sink.lost_records == 64
    assert sink._shed_carry == 5  # not dropped with the batch


def test_checkpoint_save_failure_still_delivers_flush_outputs(tmp_path):
    """A snapshot I/O failure inside checkpoint() must not take the
    barrier flush's outputs down with it: those windows already left
    the manager state and the checkpoint caller is their only route
    out. Abort (counted), deliver the outputs, keep the journal — the
    previous checkpoint plus the un-rotated journal still recover
    everything."""
    from deepflow_tpu.feeder import FrameJournal

    pipe = _mk_pipe()
    q = PyOverwriteQueue(1 << 10)
    feeder = FeederRuntime(
        [q], PipelineFeedSink(pipe), FeederConfig(frames_per_queue=64),
        journal=FrameJournal(tmp_path / "j.bin"),
    )
    gen = SyntheticFlowGen(num_tuples=60, seed=47)
    _deliver([q], gen.flow_batch(80, T0))
    feeder.pump()
    # a batch far past window T0's close: the barrier flush's dispatch
    # of the held batch is what advances the watermark and drains it
    _deliver([q], gen.flow_batch(80, T0 + 10))
    feeder.pump()

    def bad_save(barrier):
        raise chaos.CheckpointIOError("disk full")

    out = feeder.checkpoint(bad_save)  # must NOT raise
    _, rows = _mass(out)
    assert rows > 0  # the closed windows' rows delivered, not dropped
    c = feeder.get_counters()
    assert c["checkpoint_aborts"] == 1
    assert feeder._journal.epoch == 0  # and the journal was NOT rotated
    assert feeder._journal.get_counters()["rotations"] == 0

    # snapshot path healthy again: the next checkpoint completes
    saves = []
    feeder.checkpoint(lambda barrier: saves.append(barrier) or [])
    assert saves and feeder._journal.epoch == 1


def test_single_buffer_dispatch_failure_restores_shed_carry():
    """double_buffer=False: the carried shed from a prior all-padding
    emit must go back into _shed_carry when the dispatch fails — the
    runtime re-arms only the shed IT passed in, so dropping the carry
    permanently undercounts the device-plane feeder_shed lane."""
    from deepflow_tpu.datamodel.batch import FLOW_RECORD_TAG_FIELDS
    from deepflow_tpu.feeder.runtime import FlowChunk

    pipe = _mk_pipe()
    sink = PipelineFeedSink(pipe, double_buffer=False)
    gen = SyntheticFlowGen(num_tuples=40, seed=59)
    fb = gen.flow_batch(64, T0)
    sink._shed_carry = 5  # left by a prior all-padding emit
    chaos.install(chaos.FaultPlan().add(
        chaos.FaultRule(chaos.SITE_DISPATCH, count=10**9, error=chaos.DeviceLost)
    ))
    with pytest.raises(chaos.DeviceLost):
        # a chunk is what a frame holds: the [T, n] matrix in wire order
        chunk = FlowChunk(
            np.stack([fb.tags[f] for f in FLOW_RECORD_TAG_FIELDS]), fb.meters)
        sink.emit([chunk], fb.size, 64, shed=2)
    chaos.uninstall()
    assert sink.lost_records == 64
    assert sink._shed_carry == 5  # carried share restored, not dropped


def test_stage_admission_failure_counts_lost_records():
    """A failure in the sink's own admission step (pipeline.stage — the
    async device put, before any dispatch) must count the batch into
    lost_records: delivered = records_out − lost_records must not
    over-report."""
    pipe = _mk_pipe()
    queues, feeder = _mk_feeder(pipe)
    gen = SyntheticFlowGen(num_tuples=60, seed=31)

    real_stage = pipe.stage
    state = {"fail": 1}

    def flaky_stage(fb):
        if state["fail"]:
            state["fail"] -= 1
            raise RuntimeError("RESOURCE_EXHAUSTED: device put failed")
        return real_stage(fb)

    pipe.stage = flaky_stage
    _deliver(queues, gen.flow_batch(100, T0))
    feeder.pump()  # must not raise (containment) — but must count
    c = feeder.get_counters()
    assert c["lost_records"] == 100
    assert c["emit_failures"] == 1
    assert c["records_in"] == c["records_out"] + c["pending_rows"], c


# ---------------------------------------------------------------------------
# sender reconnect accounting


def test_sender_reconnect_counters_are_queryable():
    import socket as socket_mod

    from deepflow_tpu.ingest.framing import MessageType
    from deepflow_tpu.ingest.sender import UniformSender

    # grab a port nothing listens on
    s = socket_mod.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()

    snd = UniformSender(
        [("127.0.0.1", port)], MessageType.METRICS,
        prefer_native_queue=False, flush_interval=0.02,
    )
    try:
        snd.send([b"hello"])
        deadline = time.time() + 5
        while time.time() < deadline and snd.get_counters()["send_errors"] < 2:
            time.sleep(0.02)
        c = snd.get_counters()
        # capped backoff keeps attempting; every field is Countable-visible
        assert c["send_errors"] >= 2
        assert c["connected"] == 0
        for k in ("reconnects", "reconnect_success", "queue_depth", "dropped"):
            assert k in c
    finally:
        snd.close(drain_timeout=0.2)
    # shutdown with every server unreachable sheds the pending buffer —
    # COUNTED (close() joins with a timeout, so wait for the thread to
    # reach the shed-and-exit path before asserting)
    deadline = time.time() + 5
    while time.time() < deadline and snd.get_counters()["shutdown_shed_msgs"] == 0:
        time.sleep(0.02)
    assert snd.get_counters()["shutdown_shed_msgs"] >= 1


def test_serve_redelivery_buffer_is_bounded_and_counted():
    """While on_flush keeps failing the pump keeps producing; the hold
    list must not grow without limit (OOM is not containment). Beyond
    max_held_outputs the OLDEST outputs are shed and counted — same
    counted-shedding contract as every other overflow lane."""

    class _Out:
        def __init__(self, size):
            self.size = size

    pipe = _mk_pipe()
    queues, feeder = _mk_feeder(pipe, max_held_outputs=4)

    held: list = []
    for i in range(10):
        held = feeder._hold_for_redelivery(held, [_Out(size=10 + i)])
    assert len(held) == 4  # bounded
    assert [o.size for o in held] == [16, 17, 18, 19]  # oldest shed first
    c = feeder.get_counters()
    assert c["held_outputs_shed"] == 6
    assert c["held_output_shed_records"] == sum(10 + i for i in range(6))

    # 0 = unbounded (opt-out keeps the old contract)
    _, unbounded = _mk_feeder(_mk_pipe(), max_held_outputs=0)
    held = []
    for i in range(10):
        held = unbounded._hold_for_redelivery(held, [_Out(size=1)])
    assert len(held) == 10
    assert unbounded.get_counters()["held_outputs_shed"] == 0


def test_checkpoint_abort_is_visible_per_call(tmp_path):
    """An aborted checkpoint returns a normal-looking outputs list; a
    caller pruning old checkpoints after a 'successful' call would
    destroy the only recovery source. last_checkpoint_ok must record
    per-call success — False after an abort, True again only after a
    checkpoint that actually snapshotted+rotated."""
    from deepflow_tpu.feeder import FrameJournal

    pipe = _mk_pipe()
    q = PyOverwriteQueue(1 << 10)
    feeder = FeederRuntime(
        [q], PipelineFeedSink(pipe), FeederConfig(frames_per_queue=64),
        journal=FrameJournal(tmp_path / "j.bin"),
    )
    assert feeder.last_checkpoint_ok  # no aborted checkpoint yet
    gen = SyntheticFlowGen(num_tuples=60, seed=41)
    _deliver([q], gen.flow_batch(80, T0))
    feeder.pump()

    chaos.install(chaos.FaultPlan().add(
        chaos.FaultRule(chaos.SITE_DISPATCH, count=10**9, error=chaos.DeviceLost)
    ))
    feeder.checkpoint(lambda barrier: [])
    chaos.uninstall()
    assert feeder.last_checkpoint_ok is False
    assert feeder.get_counters()["last_checkpoint_ok"] == 0

    # snapshot-save failure is an abort too (outputs still delivered)
    _deliver([q], gen.flow_batch(40, T0 + 1))
    feeder.pump()

    def broken_save(barrier):
        raise OSError("disk full")

    feeder.checkpoint(broken_save)
    assert feeder.last_checkpoint_ok is False

    # a clean checkpoint flips it back
    feeder.checkpoint(lambda barrier: [])
    assert feeder.last_checkpoint_ok is True
    assert feeder.get_counters()["last_checkpoint_ok"] == 1
