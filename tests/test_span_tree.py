"""ISSUE 26: the span tree (nesting and self time on one per-thread stack
shared by every tracer), the served path's leaf spans, the idle pump, the
compile lanes, the stage names on the device programs, and the per-layer
metric files that read the new spans and counters. ISSUE 38: the CPU lane
of every span, the feeder's two waits and queue depth, the Receiver's
clocks, and the nine layer files that read them."""

import glob
import json
import os
import socket
import sys
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepflow_tpu.utils import spans
from deepflow_tpu.utils.spans import (
    FEEDER_SPAN_NAMES,
    PIPELINE_SPAN_NAMES,
    SPAN_FEEDER_ASSEMBLE,
    SPAN_FEEDER_COALESCE,
    SPAN_FEEDER_DECODE,
    SPAN_FEEDER_DISPATCH,
    SPAN_FEEDER_DRAIN,
    SPAN_FEEDER_PUMP,
    SPAN_FEEDER_STAGING_WAIT,
    SPAN_FLUSH_DRAIN,
    SPAN_FLUSH_FETCH,
    SPAN_FLUSH_JOIN,
    SPAN_FLUSH_RESERVE,
    SPAN_FLUSH_ROWS,
    SPAN_FLUSH_SPLIT,
    SPAN_FLUSH_WAIT,
    SPAN_INGEST_DISPATCH,
    SPAN_INGEST_STAGE,
    SPAN_XLA_COMPILE,
    SpanHistSpec,
    SpanTracer,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIPBENCH = os.path.join(ROOT, "chipbench")
NEW_LAYERS = (
    "feeder.pump_ms_per_mrec", "feeder.decode_ms_per_mrec",
    "feeder.assemble_ms_per_mrec", "step.stage_ms_per_mrec",
    "flush.wait_ms_per_window", "flush.rows_ms_per_window",
    "flush.split_ms_per_window", "flush.compile_ms_per_window",
    "flush.fetched_rows_per_live_row",  # PR 27
    "stash.live_share", "step.doc_rows_per_record",  # PR 28
    "flush.docs_per_window", "flush.fetch_ms_per_window",
    "fold.live_block_share",  # PR 29
    "feeder.host_copy_bytes_per_record",  # PR 31
    # PR 33, the sketch cell's own (they carry a `workloads` list)
    "sketch.flush_ms_per_window", "sketch.fetched_bytes_per_block_byte",
    "sketch.rows_per_record",
    # PR 34, the close's host half
    "flush.join_ms_per_window", "flush.host_write_bytes_per_doc",
    "flush.reserved_row_share",
)
# read from a run whose closes are several pages (`tiny_paged_run`): a
# stash under one page is handed on as a view and reserves nothing
PAGED_LAYERS = ("flush.host_write_bytes_per_doc", "flush.reserved_row_share")
# PR 36, the four-chip cell's own (they list it alone): what only the
# sharded close does
SHARDED_CELL = "l4_4m_x4_sketch.saturate"
SHARDED_LAYERS = (
    "sharded_step_roofline", "close.collective_share_of_busy",
    "close.collective_ms_per_window", "close.sketch_merge_ms_per_window",
    "close.partial_rows_per_record",
)
# PR 37, the close's two host passes over the pool's threads (every cell's)
POOLED_LAYERS = ("flush.pooled_byte_share", "flush.reserve_ms_per_window")
# PR 38, the host's time by owner: CPU lanes, the feeder's two waits, queue
# depth, the Receiver's clocks (every cell's)
HOST_TIME_LAYERS = (
    "feeder.pump_cpu_share", "feeder.starved_ms_per_mrec",
    "feeder.staging_wait_ms_per_mrec", "feeder.assemble_cpu_ms_per_mrec",
    "feeder.decode_cpu_ms_per_mrec", "feeder.drain_cpu_ms_per_mrec",
    "feeder.queue_depth_frames", "receiver.busy_ms_per_mrec",
    "receiver.cpu_ms_per_mrec",
)
# PR 39, the four-chip cell's again: the share of closed blocks that came
# off the devices merged
DEVICE_MERGE_LAYERS = ("close.device_merged_block_share",)
# 0.0 is a reading: no acquire of a tiny run need block (one chip: the
# per-batch stats.fetch syncs first; the CPU's sharded step may have run by
# the time its buffer comes round), and its pumps need not find the queues
# empty (the socket may always be ahead of the feeder)
MAY_READ_ZERO = ("feeder.staging_wait_ms_per_mrec", "feeder.starved_ms_per_mrec")


# ---------------------------------------------------------------------------
# (1) nesting


def test_spans_nest_across_two_tracers_with_self_time():
    feeder, pipe = SpanTracer(service="f"), SpanTracer(service="p")
    with feeder.span("outer"):
        time.sleep(0.002)
        with pipe.span("inner", window="7@1s"):
            time.sleep(0.004)
            pipe.record("leaf", 1500)  # pre-measured work joins the tree too
        feeder.record("sibling", 300)
    outer, = feeder.recent("outer")
    inner, = pipe.recent("inner")
    leaf, = pipe.recent("leaf")
    sibling, = feeder.recent("sibling")
    assert outer.parent_span_id == "" and len(outer.span_id) == 16
    assert inner.parent_span_id == outer.span_id and inner.window == "7@1s"
    assert leaf.parent_span_id == inner.span_id
    assert sibling.parent_span_id == outer.span_id
    assert {r.trace_id for r in (outer, inner, leaf, sibling)} == {outer.trace_id}
    assert len({r.span_id for r in (outer, inner, leaf, sibling)}) == 4
    f, p = feeder.summary(), pipe.summary()
    assert f["outer"]["self_us"] == (
        f["outer"]["total_us"] - p["inner"]["total_us"] - 300)
    assert p["inner"]["self_us"] == p["inner"]["total_us"] - 1500
    assert p["leaf"]["self_us"] == p["leaf"]["total_us"] == 1500
    assert f["outer"]["self_us"] >= 1500  # its own 2 ms sleep
    assert feeder.get_counters()["outer.self_us"] == f["outer"]["self_us"]


def test_span_stacks_of_two_threads_are_independent():
    tr = SpanTracer()
    inside, release = threading.Event(), threading.Event()

    def other():
        with tr.span("other.root"):
            inside.set()
            assert release.wait(10)

    t = threading.Thread(target=other)
    with tr.span("main.root"):
        t.start()
        assert inside.wait(10)
        with tr.span("main.child"):  # opened while other.root is open over there
            pass
        release.set()
        t.join(10)
    assert not t.is_alive()
    main_root, = tr.recent("main.root")
    main_child, = tr.recent("main.child")
    other_root, = tr.recent("other.root")
    assert main_child.parent_span_id == main_root.span_id
    assert other_root.parent_span_id == "" and main_root.parent_span_id == ""
    assert other_root.trace_id != main_root.trace_id
    s = tr.summary()
    # the other thread's span took no self time from this thread's root
    assert s["main.root"]["self_us"] == (
        s["main.root"]["total_us"] - s["main.child"]["total_us"])
    assert s["other.root"]["self_us"] == s["other.root"]["total_us"]


def test_discarded_span_leaves_nothing_and_explicit_ids_pass_through():
    tr = SpanTracer()
    with tr.span("parent"):
        with tr.span("idle") as s:
            s.discard()
        tr.record("hop", 40, trace_id="t" * 32, span_id="s" * 16,
                  parent_span_id="p" * 16)  # lineage context: not re-parented
    assert tr.recent("idle") == [] and "idle" not in tr.summary()
    hop, = tr.recent("hop")
    assert (hop.trace_id, hop.span_id, hop.parent_span_id) == (
        "t" * 32, "s" * 16, "p" * 16)
    parent = tr.summary()["parent"]
    assert parent["self_us"] == parent["total_us"]


def test_hist_bin_matches_the_closed_form():
    import math

    spec = SpanHistSpec(bins=512, vmin=1.0, gamma=1.02)
    for v in (0, 0.5, 1, 2, 17, 999, 123456, 10**9, 10**30):
        want = 0 if v <= 1 else min(
            int(math.floor(math.log(v / 1.0) / math.log(1.02))), 511)
        assert spec.bin(v) == want, v
    assert SpanHistSpec() == SpanHistSpec()  # the cached log is no part of it


# ---------------------------------------------------------------------------
# (1b) the CPU lane (ISSUE 38). Lanes of one run are compared with each
# other; no wall clock is held to a bound.


def _spin_cpu(ns: int) -> None:
    """Burn `ns` of this thread's own CPU time."""
    end = time.thread_time_ns() + ns
    while time.thread_time_ns() < end:
        pass


def test_cpu_lane_tells_a_sleeping_span_from_a_spinning_one():
    tr = SpanTracer()
    with tr.span("sleeps"):
        time.sleep(0.05)
    with tr.span("spins"):
        _spin_cpu(20_000_000)
    sleeps, spins = tr.summary()["sleeps"], tr.summary()["spins"]
    assert 0 <= sleeps["cpu_us"] < sleeps["total_us"] // 5
    # the lane holds what the thread burned and cannot pass the wall. How
    # near the wall it comes is the machine's to say (on a loaded one a
    # spinning thread is descheduled: that is wall, not CPU), so the two
    # spans' shares are held against each other and not against a bound
    assert 20_000 <= spins["cpu_us"] <= spins["total_us"] + 1
    assert spins["cpu_us"] * sleeps["total_us"] > 10 * sleeps["cpu_us"] * spins["total_us"]
    rec, = tr.recent("sleeps")
    assert rec.cpu_us == sleeps["cpu_us"] and rec.duration_us == sleeps["total_us"]


def test_a_parents_cpu_lane_holds_its_childs_and_every_face_carries_it():
    feeder, pipe = SpanTracer(service="f"), SpanTracer(service="p")
    with feeder.span("outer"):
        time.sleep(0.01)
        with pipe.span("inner"):
            _spin_cpu(10_000_000)
    outer, inner = feeder.summary()["outer"], pipe.summary()["inner"]
    # children included, as total_us is; the sleep adds wall, not CPU
    assert outer["cpu_us"] >= inner["cpu_us"] >= 10_000
    assert outer["total_us"] - outer["cpu_us"] >= inner["total_us"] - inner["cpu_us"]
    assert feeder.get_counters()["outer.cpu_us"] == outer["cpu_us"]
    assert feeder.recent("outer")[0].cpu_us == outer["cpu_us"]
    assert pipe.cpu_us(("inner", "never.ran")) == {"inner": inner["cpu_us"], "never.ran": 0}
    # a discarded span leaves no lane, and says how long it took
    with pipe.span("idle") as idle:
        idle.discard()
        time.sleep(0.002)
    assert idle.duration_us >= 2_000 and "idle" not in pipe.summary()


def test_record_takes_its_cpu_lane_from_the_caller():
    tr = SpanTracer()
    with tr.span("round"):
        tr.record("decode", 900, cpu_us=700)
        tr.record("decode", 300, cpu_us=250)
        tr.record("hop", 40)  # a caller that measured no CPU
    s = tr.summary()
    assert (s["decode"]["count"], s["decode"]["total_us"], s["decode"]["cpu_us"]) == (2, 1200, 950)
    assert s["hop"]["cpu_us"] == 0
    assert [r.cpu_us for r in tr.recent("decode")] == [700, 250]
    assert tr.get_counters()["decode.cpu_us"] == 950
    assert tr.cpu_us(("decode",)) == {"decode": 950}


def test_a_spinning_neighbour_adds_nothing_to_a_sleeping_spans_cpu():
    """The lane is ONE thread's clock: while this thread sleeps under its
    span another spins through the same wall, and waits for the GIL it
    holds are wall too, not CPU."""
    tr = SpanTracer()
    started, stop = threading.Event(), threading.Event()

    def neighbour():
        with tr.span("spins"):
            started.set()
            while not stop.is_set():
                _spin_cpu(1_000_000)

    t = threading.Thread(target=neighbour)
    t.start()
    try:
        assert started.wait(10)
        with tr.span("sleeps"):
            time.sleep(0.08)
    finally:
        stop.set()
        t.join(10)
    assert not t.is_alive()
    s = tr.summary()
    assert s["spins"]["total_us"] >= s["sleeps"]["total_us"] >= 80_000
    assert s["sleeps"]["cpu_us"] < s["sleeps"]["total_us"] // 5
    assert s["sleeps"]["cpu_us"] < s["spins"]["cpu_us"] // 4


# ---------------------------------------------------------------------------
# (2) the served path, as chipbench/tests/tiny.py builds it


@pytest.fixture(scope="module")
def chipbench_modules():
    """chipbench's own modules (it is no package: its files import each
    other by bare name)."""
    added = [p for p in (CHIPBENCH, os.path.join(CHIPBENCH, "tests"))
             if p not in sys.path]
    sys.path[:0] = added
    import gen
    import layers
    import sut
    import tiny
    import wire

    yield {"gen": gen, "layers": layers, "sut": sut, "tiny": tiny, "wire": wire}
    for p in added:
        sys.path.remove(p)


def _pump_until_taken(feeder, want: int, base_in: int, flushed: list) -> None:
    deadline = time.monotonic() + 120
    while feeder.get_counters()["records_in"] - base_in < want:
        assert time.monotonic() < deadline, "the feeder took too few records"
        out = feeder.pump()
        flushed.extend(out)
        if not out:
            time.sleep(0.001)


def _tiny_run(m, config):
    """Receiver -> queues -> FeederRuntime -> PipelineFeedSink ->
    L4Pipeline at tiny.py's sizes: five event-seconds over TCP, pumped
    until taken, then planes in the harness's shape (run.py)."""
    schema = m["gen"].load_schema()
    served = m["sut"].build(config)
    try:
        source = m["gen"].FlowSource(schema, m["tiny"].CONFIG["population"], 5)
        c0, s0 = served.counters(), served.spans()
        ring0 = {id(r) for tr in served.tracers() for r in tr.recent()}
        flushed, sent = [], 0
        with socket.create_connection(("127.0.0.1", served.port), timeout=30) as sock:
            for k in range(1, 6):
                tags, meters = source.second(k, 6000)
                for frame in m["wire"].encode_frames(tags, meters, schema["wire"]):
                    sock.sendall(frame)
                sent += 6000
                # a second at a time, so that rounds and closes interleave
                _pump_until_taken(served.feeder, sent, c0["feeder.records_in"], flushed)
        flushed += served.feeder.flush()
        served.block()
        c1, s1 = served.counters(), served.spans()
        records = [r for tr in served.tracers()
                   for r in tr.recent() if id(r) not in ring0]
        spans_plane = {n: {f: s1[n][f] - s0.get(n, {}).get(f, 0) for f in s1[n]}
                       for n in s1}
        counters = {k: c1[k] - c0.get(k, 0) for k in c1}
        yield {
            "records": records, "sent": sent,
            "feeder": served.feeder.tracer.summary(),
            "pipe": served.tracers()[1].summary(),
            "planes": {"spans": spans_plane, "counters": counters,
                       "run": {"windows_closed": len(
                           {int(db.timestamp[0]) for db in flushed})}},
        }
    finally:
        served.close()


@pytest.fixture(scope="module")
def tiny_run(chipbench_modules):
    yield from _tiny_run(chipbench_modules, chipbench_modules["tiny"].CONFIG)


@pytest.fixture(scope="module")
def tiny_paged_run(chipbench_modules):
    """The same run with pages of 64 rows, so that a close fetches several
    and, from its third on, joins them into a reserve (PR 34)."""
    import deepflow_tpu.aggregator.window as window_mod

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(window_mod, "PAGE_ROWS", 64)
        yield from _tiny_run(chipbench_modules, chipbench_modules["tiny"].CONFIG)


@pytest.fixture(scope="module")
def tiny_pooled_run(chipbench_modules):
    """`tiny_paged_run` with every pass of the close's host half divided
    over four threads (PR 37: `utils/hostpool.py`; as its constants stand
    a tiny run's passes are far under the threshold and stay inline)."""
    import deepflow_tpu.aggregator.window as window_mod
    from deepflow_tpu.utils import hostpool

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(window_mod, "PAGE_ROWS", 64)
        mp.setattr(hostpool, "WORKERS", 4)
        mp.setattr(hostpool, "POOL_MIN_BYTES", 0)
        mp.setattr(hostpool, "_cores", lambda: 64)
        yield from _tiny_run(chipbench_modules, chipbench_modules["tiny"].CONFIG)


@pytest.fixture(scope="module")
def tiny_sketch_run(chipbench_modules):
    """The same run with the sketch plane on, built as the sketch cell's
    configuration names it (`built_by`: chipbench/deployments/l4_sketch.py)."""
    tiny = chipbench_modules["tiny"].CONFIG
    yield from _tiny_run(chipbench_modules, {
        **tiny, "built_by": "l4_sketch", "pipeline": {**tiny["pipeline"], "sketch": {
            "num_groups": 16, "hll_precision": 12, "cms_depth": 4, "cms_width": 4096,
            "hist_bins": 256, "hist_vmin": 1.0, "hist_gamma": 1.04,
            "topk_rows": 2, "topk_cols": 512, "pool": None, "pending": 4}}})


@pytest.fixture(scope="module")
def tiny_sharded_run(chipbench_modules):
    """The same run through the sharded deployment the four-chip cell's
    configuration names (`built_by`: chipbench/deployments/l4_sharded.py)
    on four forced host devices, with pages of 64 rows and every pass of
    the close's host half divided over four threads (as `tiny_pooled_run`:
    the accepted `flush.pooled_byte_share` reads above 0 only so). The
    patches are on while the run is built and driven and off before a test
    reads it, whichever fixture was built before or comes after."""
    import deepflow_tpu.aggregator.window as window_mod
    from deepflow_tpu.utils import hostpool

    if len(jax.devices()) < 4:
        pytest.skip("needs four (forced host) devices")
    tiny = chipbench_modules["tiny"].CONFIG
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(window_mod, "PAGE_ROWS", 64)
        mp.setattr(hostpool, "WORKERS", 4)
        mp.setattr(hostpool, "POOL_MIN_BYTES", 0)
        mp.setattr(hostpool, "_cores", lambda: 64)
        driven = _tiny_run(chipbench_modules, {
            **tiny, "chips": 4, "built_by": "l4_sharded",
            "pipeline": {**tiny["pipeline"], "accum_batches": 8, "sketch": {
                "num_groups": 16, "hll_precision": 12, "cms_depth": 4,
                "cms_width": 4096, "hist_bins": 256, "hist_vmin": 1.0,
                "hist_gamma": 1.04, "topk_rows": 2, "topk_cols": 512,
                "pool": None, "pending": 3}}})
        run = next(driven)
    yield run
    driven.close()  # the run's `finally`: the served path closes


def test_served_path_emits_every_leaf_span_with_its_count(tiny_run):
    f, p, c = tiny_run["feeder"], tiny_run["pipe"], tiny_run["planes"]["counters"]
    assert c["feeder.records_in"] == tiny_run["sent"]
    assert tiny_run["planes"]["run"]["windows_closed"] >= 2
    # a name lives on one tracer: feeder.* on the feeder's, the rest on the pipeline's
    # (but for the staging wait, which is recorded only when it blocked)
    assert set(FEEDER_SPAN_NAMES) - {SPAN_FEEDER_STAGING_WAIT} <= set(f)
    assert not set(FEEDER_SPAN_NAMES) & set(p)
    assert f.get(SPAN_FEEDER_STAGING_WAIT, {"count": 0})["count"] == c["feeder.staging_waits"]
    for name in (SPAN_INGEST_STAGE, SPAN_INGEST_DISPATCH, SPAN_FLUSH_DRAIN,
                 SPAN_FLUSH_WAIT, SPAN_FLUSH_ROWS, SPAN_FLUSH_SPLIT):
        assert name in p and name not in f and name in PIPELINE_SPAN_NAMES
    # one feeder.decode a round that decoded a frame = one a coalesce here
    # (nothing is shed); one ingest.stage and one feeder.assemble a batch;
    # the three flush phases once a drained entry
    assert f[SPAN_FEEDER_DECODE]["count"] == f[SPAN_FEEDER_COALESCE]["count"] > 0
    assert f[SPAN_FEEDER_DRAIN]["count"] >= f[SPAN_FEEDER_COALESCE]["count"]
    assert f[SPAN_FEEDER_ASSEMBLE]["count"] == c["feeder.batches_out"] > 0
    assert p[SPAN_INGEST_STAGE]["count"] == c["feeder.batches_out"]
    # the double buffer holds the last staged batch back until flush()
    assert p[SPAN_INGEST_DISPATCH]["count"] == c["feeder.batches_out"]
    assert (p[SPAN_FLUSH_WAIT]["count"] == p[SPAN_FLUSH_ROWS]["count"]
            == p[SPAN_FLUSH_SPLIT]["count"] == c["pipeline.window_advances"] > 0)
    assert p[SPAN_FLUSH_DRAIN]["count"] <= p[SPAN_FLUSH_WAIT]["count"]


def test_children_fit_inside_their_parents(tiny_run):
    f, p = tiny_run["feeder"], tiny_run["pipe"]
    total = lambda s, *names: sum(s[n]["total_us"] for n in names)
    assert total(p, SPAN_FLUSH_WAIT, SPAN_FLUSH_ROWS, SPAN_FLUSH_SPLIT) \
        <= p[SPAN_FLUSH_DRAIN]["total_us"]
    assert f[SPAN_FEEDER_ASSEMBLE]["total_us"] + p[SPAN_INGEST_STAGE]["total_us"] \
        <= f[SPAN_FEEDER_DISPATCH]["total_us"]
    assert f[SPAN_FEEDER_DECODE]["total_us"] <= f[SPAN_FEEDER_COALESCE]["total_us"]
    assert total(f, SPAN_FEEDER_DRAIN, SPAN_FEEDER_COALESCE) \
        <= f[SPAN_FEEDER_PUMP]["total_us"]
    for s in (f, p):
        for name, agg in s.items():
            assert 0 <= agg["self_us"] <= agg["total_us"], name


@pytest.mark.parametrize("run", ["tiny_run", "tiny_sharded_run"])
def test_cpu_lanes_fit_inside_their_walls_on_the_served_path(run, request):
    run = request.getfixturevalue(run)
    f, p, c = run["feeder"], run["pipe"], run["planes"]["counters"]
    for s in (f, p):
        for name, agg in s.items():
            # the CPU clock is read inside the wall's reads; a microsecond a
            # span for the two truncations
            assert 0 <= agg["cpu_us"] <= agg["total_us"] + agg["count"], name
    # a parent's lane holds its children's, across the two tracers
    assert f[SPAN_FEEDER_ASSEMBLE]["cpu_us"] + p[SPAN_INGEST_STAGE]["cpu_us"] \
        <= f[SPAN_FEEDER_DISPATCH]["cpu_us"] + 2 * f[SPAN_FEEDER_DISPATCH]["count"]
    assert f[SPAN_FEEDER_DECODE]["cpu_us"] <= f[SPAN_FEEDER_COALESCE]["cpu_us"] \
        + f[SPAN_FEEDER_COALESCE]["count"]
    assert f[SPAN_FEEDER_PUMP]["cpu_us"] > 0 and f[SPAN_FEEDER_DECODE]["cpu_us"] > 0
    # the feeder's counters are those lanes (the window's share of them)
    for name in set(FEEDER_SPAN_NAMES) - {SPAN_FEEDER_STAGING_WAIT}:
        assert 0 <= c[f"{name}_cpu_us"] <= f[name]["cpu_us"], name
    assert f"{SPAN_FEEDER_STAGING_WAIT}_cpu_us" not in c  # that lane stays on the tracer
    # the Receiver's threads: both clocks advanced (the CPU is the threads'
    # whole, the recv calls' too: neither bounds the other)
    assert c["receiver.cpu_us"] > 0 and c["receiver.busy_us"] > 0
    # the feeder looked at its queues and found frames waiting there
    assert 0 < c["feeder.queue_depth_sum"] and c["feeder.queue_visits"] >= 4
    assert c["feeder.idle_pump_us"] >= 0 and c["feeder.staging_wait_us"] >= 0
    assert (c["feeder.staging_wait_us"] > 0) == (c["feeder.staging_waits"] > 0)


def test_the_reserve_is_host_work_inside_the_wait(tiny_run, tiny_paged_run):
    """`flush.reserve` is a child of `flush.wait`: the three phases still
    add up to `flush.drain`, and a manager that reserves nothing (every
    close under one page) has no such span."""
    assert SPAN_FLUSH_RESERVE not in tiny_run["pipe"]
    p, c = tiny_paged_run["pipe"], tiny_paged_run["planes"]["counters"]
    total = lambda *names: sum(p[n]["total_us"] for n in names)
    assert p[SPAN_FLUSH_RESERVE]["total_us"] <= p[SPAN_FLUSH_WAIT]["total_us"]
    assert total(SPAN_FLUSH_FETCH, SPAN_FLUSH_JOIN) <= p[SPAN_FLUSH_ROWS]["total_us"]
    drain = p[SPAN_FLUSH_DRAIN]
    phases = total(SPAN_FLUSH_WAIT, SPAN_FLUSH_ROWS, SPAN_FLUSH_SPLIT)
    assert phases <= drain["total_us"]
    # the three phases are all of the drain's children: the reserve's
    # time is inside the wait's, not a fourth phase beside them
    assert drain["total_us"] - phases == drain["self_us"]
    assert 0 < p[SPAN_FLUSH_RESERVE]["count"] < p[SPAN_FLUSH_WAIT]["count"]
    assert p[SPAN_FLUSH_RESERVE]["compiles"] == 0
    assert SPAN_FLUSH_RESERVE not in spans.FLUSH_SPAN_NAMES
    by_id = {r.span_id: r for r in tiny_paged_run["records"]}
    kids = [r for r in tiny_paged_run["records"] if r.name == SPAN_FLUSH_RESERVE]
    assert kids and all(by_id[r.parent_span_id].name == SPAN_FLUSH_WAIT for r in kids)
    assert 0 < c["pipeline.flush_rows_reserved"] <= c["pipeline.flush_rows_live"]
    assert c["pipeline.flush_host_write_bytes"] >= 396 * c["pipeline.flush_rows_reserved"]
    # every reserve and every copied row is a host pass; none of this size divides
    assert c["pipeline.flush_host_pass_bytes"] >= c["pipeline.flush_host_write_bytes"]
    assert c["pipeline.flush_pooled_bytes"] == 0


def test_pool_threads_open_no_span_and_the_tree_keeps_its_shape(
        tiny_paged_run, tiny_pooled_run):
    """The same run with the reserve's touch and the join's copy divided
    over threads records the same spans under the same parents: the feed
    thread opens and closes `flush.reserve` and `flush.join`, a worker
    opens nothing (a span opened on another thread would be a root)."""
    import threading

    from deepflow_tpu.utils import hostpool

    one, many = tiny_paged_run, tiny_pooled_run
    c = many["planes"]["counters"]
    assert 0 < c["pipeline.flush_pooled_bytes"] <= c["pipeline.flush_host_pass_bytes"]
    assert len(hostpool._threads) >= 3
    assert all(t.daemon for t in hostpool._threads)
    assert {t.name for t in threading.enumerate()} >= {t.name for t in hostpool._threads}
    assert set(many["pipe"]) == set(one["pipe"]) and set(many["feeder"]) == set(one["feeder"])
    for name in (SPAN_FLUSH_RESERVE, SPAN_FLUSH_JOIN, SPAN_FLUSH_FETCH,
                 SPAN_FLUSH_WAIT, SPAN_FLUSH_ROWS, SPAN_FLUSH_SPLIT):
        assert many["pipe"][name]["count"] == one["pipe"][name]["count"], name
    parents = {SPAN_FLUSH_RESERVE: SPAN_FLUSH_WAIT, SPAN_FLUSH_JOIN: SPAN_FLUSH_ROWS,
               SPAN_FLUSH_FETCH: SPAN_FLUSH_ROWS, SPAN_FLUSH_WAIT: SPAN_FLUSH_DRAIN,
               SPAN_FLUSH_ROWS: SPAN_FLUSH_DRAIN, SPAN_FLUSH_SPLIT: SPAN_FLUSH_DRAIN}
    by_id = {r.span_id: r for r in many["records"]}
    seen = set()
    for r in many["records"]:
        if r.name in parents and r.parent_span_id in by_id:
            assert by_id[r.parent_span_id].name == parents[r.name], r
            seen.add(r.name)
    assert seen == set(parents)
    # the counters that say what a close wrote do not move with the pool
    for k in ("pipeline.flush_rows_reserved", "pipeline.flush_rows_live",
              "pipeline.flush_host_write_bytes", "pipeline.flush_host_pass_bytes",
              "pipeline.flush_pages", "pipeline.host_fetches"):
        assert c[k] == one["planes"]["counters"][k], k


def test_ring_records_name_their_parents(tiny_run):
    by_id = {r.span_id: r for r in tiny_run["records"]}
    assert len(by_id) == len(tiny_run["records"])  # ids are unique across tracers
    parents = {
        SPAN_FEEDER_PUMP: {""},
        SPAN_FEEDER_DRAIN: {SPAN_FEEDER_PUMP},
        SPAN_FEEDER_COALESCE: {SPAN_FEEDER_PUMP},
        SPAN_FEEDER_DECODE: {SPAN_FEEDER_COALESCE},
        # a full bucket dispatches inside the round; the tail after it
        SPAN_FEEDER_DISPATCH: {SPAN_FEEDER_COALESCE, SPAN_FEEDER_PUMP},
        SPAN_FEEDER_ASSEMBLE: {SPAN_FEEDER_DISPATCH},
        SPAN_INGEST_STAGE: {SPAN_FEEDER_DISPATCH},
        SPAN_INGEST_DISPATCH: {SPAN_FEEDER_DISPATCH},
        SPAN_FLUSH_DRAIN: {SPAN_FEEDER_DISPATCH},
        SPAN_FLUSH_WAIT: {SPAN_FLUSH_DRAIN},
        SPAN_FLUSH_ROWS: {SPAN_FLUSH_DRAIN},
        SPAN_FLUSH_SPLIT: {SPAN_FLUSH_DRAIN},
    }
    seen = set()
    for r in tiny_run["records"]:
        if r.name not in parents:
            continue
        # feeder.flush() at the end dispatches outside any pump
        if r.name == SPAN_FEEDER_DISPATCH and r.parent_span_id == "":
            continue
        parent = by_id.get(r.parent_span_id)
        assert (parent.name if parent else "") in parents[r.name], r
        seen.add(r.name)
    assert seen == set(parents)
    roots = {by_id[r.trace_id[-16:]].name for r in tiny_run["records"]
             if r.trace_id[-16:] in by_id}
    assert SPAN_FEEDER_PUMP in roots


@pytest.mark.parametrize("name", NEW_LAYERS)
def test_new_layer_file_reads_a_number_from_a_tiny_run(name, request, chipbench_modules):
    layers = chipbench_modules["layers"]
    spec = layers.load_layer(name)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry, = [m for m in json.load(f)["per_layer"] if m["name"] == name]
    assert {k: spec[k] for k in entry} == entry  # the file and its entry agree
    sketch = name.startswith("sketch.")
    assert ("workloads" in entry) == sketch  # only the sketch cell reports its own
    run = request.getfixturevalue(
        "tiny_sketch_run" if sketch else
        "tiny_paged_run" if name in PAGED_LAYERS else "tiny_run")
    value = layers.read_metric(spec, run["planes"])
    if sketch:
        # a run without the plane reads nothing (its `sketch_rows` lane
        # rides every counter block and reads 0: no record passed a plane)
        plain = request.getfixturevalue("tiny_run")["planes"]
        assert not layers.read_metric(spec, plain)
        if name != "sketch.flush_ms_per_window":  # one block a page, every record
            assert value == 1.0
    assert isinstance(value, float) and value >= 0.0
    if name != "flush.compile_ms_per_window":  # 0.0 where no close compiled
        assert value > 0.0
    # a program without the span or counter (the parent commit) reads nothing
    assert layers.read_metric(spec, {"spans": {}, "counters": {
        "feeder.records_in": 1}, "run": {"windows_closed": 1}}) is None


@pytest.mark.parametrize("name", POOLED_LAYERS)
def test_pooled_layer_file_reads_a_number_where_the_close_reserves(
        name, tiny_run, tiny_paged_run, tiny_pooled_run, chipbench_modules):
    layers = chipbench_modules["layers"]
    spec = layers.load_layer(name)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry, = [m for m in json.load(f)["per_layer"] if m["name"] == name]
    assert {k: spec[k] for k in entry} == entry and "workloads" not in entry
    assert (entry["layer"], entry["moves"]) == ("window close / flush", "records_per_s")
    pooled = layers.read_metric(spec, tiny_pooled_run["planes"])
    inline = layers.read_metric(spec, tiny_paged_run["planes"])
    assert isinstance(pooled, float) and isinstance(inline, float)
    if name == "flush.pooled_byte_share":
        # passes under the threshold read 0, not nothing: the 10k cells' line
        assert 0.0 == inline < pooled <= 100.0
    else:
        assert inline > 0.0 and pooled > 0.0
        # a run whose closes fit one page reserves nothing: no span, no number
        assert layers.read_metric(spec, tiny_run["planes"]) is None
    # a program without the span or counter (the parent commit) reads nothing
    assert layers.read_metric(spec, {"spans": {}, "counters": {
        "feeder.records_in": 1}, "run": {"windows_closed": 1}}) is None


@pytest.mark.parametrize("name", HOST_TIME_LAYERS)
def test_host_time_layer_file_reads_a_number_from_both_tiny_runs(
        name, tiny_run, tiny_sharded_run, chipbench_modules):
    layers = chipbench_modules["layers"]
    spec = layers.load_layer(name)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry, = [m for m in json.load(f)["per_layer"] if m["name"] == name]
    assert {k: spec[k] for k in entry} == entry and "workloads" not in entry
    assert (entry["layer"], entry["moves"]) == ("wire in + feeder", "records_per_s")
    assert entry["source"] == (
        "program_span" if name == "feeder.pump_cpu_share" else "program_counter")
    for run in (tiny_run, tiny_sharded_run):
        value = layers.read_metric(spec, run["planes"])
        assert isinstance(value, float) and value >= 0.0
        if name not in MAY_READ_ZERO:
            assert value > 0.0
        if name == "feeder.pump_cpu_share":
            assert value <= 100.0 + 1e-3
    # a program without the counters (the parent commit) reads nothing, not
    # 0: its spans and its other counters are there
    parent = {"spans": {"feeder.pump": {"count": 3, "total_us": 900}},
              "counters": {"feeder.records_in": 1, "feeder.staging_waits": 0,
                           "feeder.idle_pumps": 7, "receiver.rx_frames": 2},
              "run": {"windows_closed": 1}}
    assert layers.read_metric(spec, parent) is None


# a trace plane by hand: the device's share of a tiny run is not the CPU's to give
_TRACE = {"trace": {"slice_records": 30_000, "busy_s": 0.5,
                    "module_s": {"fused_step": 0.01, "sharded_window_close": 0.001}},
          "schema": {"record_bytes": 396},
          "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}}


@pytest.mark.parametrize("name", SHARDED_LAYERS + DEVICE_MERGE_LAYERS)
def test_sharded_layer_file_reads_a_number_from_a_tiny_sharded_run(
        name, tiny_run, tiny_sharded_run, chipbench_modules):
    layers = chipbench_modules["layers"]
    spec = layers.load_layer(name)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry, = [m for m in json.load(f)["per_layer"] if m["name"] == name]
    assert {k: spec[k] for k in entry} == entry  # the file and its entry agree
    assert entry["workloads"] == [SHARDED_CELL] and entry["moves"] == "records_per_s"
    value = layers.read_metric(spec, {**tiny_sharded_run["planes"], **_TRACE})
    assert isinstance(value, float) and value > 0.0
    if name.endswith("roofline"):
        assert value < 100.0
    if name in DEVICE_MERGE_LAYERS:
        assert value == 100.0  # the pool is off: every block merges on the devices
    # the one-chip program has no such span, counter or module
    plain = {**tiny_run["planes"], **_TRACE,
             "trace": {**_TRACE["trace"], "module_s": {}}}
    assert layers.read_metric(spec, plain) is None
    assert layers.read_metric(spec, {"spans": {}, "counters": {
        "feeder.records_in": 1}, "run": {"windows_closed": 1}}) is None


def test_the_sharded_manager_has_every_span_and_counter_the_accepted_metrics_read(
        tiny_sharded_run, chipbench_modules):
    """A metric without a `workloads` list is every cell's, the four-chip
    cell's too: whatever reads the program's spans and counters finds
    them under the one-chip manager's names."""
    layers = chipbench_modules["layers"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        everyones = [m for m in json.load(f)["per_layer"] if "workloads" not in m
                     and m["source"] in ("program_span", "program_counter")]
    # 23 until PR 37 added the pooled share and the reserve, 25 until PR 38
    # added the host's time by owner
    assert len(everyones) == 34
    for m in everyones:
        value = layers.read_metric(layers.load_layer(m["name"]), tiny_sharded_run["planes"])
        assert isinstance(value, float), m["name"]
        # 0.0 where no close compiled; where none of this run's three
        # closes fitted its reserve (tests/test_sharded_deployment.py has
        # a run in which they do); and MAY_READ_ZERO's two
        if m["name"] not in ("flush.compile_ms_per_window", "flush.reserved_row_share",
                             *MAY_READ_ZERO):
            assert value > 0.0, m["name"]
    p = tiny_sharded_run["pipe"]
    assert p["flush.sketch_merge"]["total_us"] <= p["flush.sketch"]["total_us"] \
        <= p[SPAN_FLUSH_SPLIT]["total_us"]
    assert p["stats.fetch"]["total_us"] + p[SPAN_FLUSH_RESERVE]["total_us"] \
        <= p[SPAN_FLUSH_WAIT]["total_us"]
    assert p["window.close_collective"]["count"] == p["window.advance"]["count"] > 0


def test_layer_files_and_benchmark_entries_pair_up():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        names = [m["name"] for m in json.load(f)["per_layer"]]
    files = {os.path.basename(p)[:-5]
             for p in glob.glob(os.path.join(CHIPBENCH, "layers", "*.json"))}
    new = (list(NEW_LAYERS) + list(SHARDED_LAYERS) + list(POOLED_LAYERS)
           + list(HOST_TIME_LAYERS) + list(DEVICE_MERGE_LAYERS))
    assert set(names) == files and names[-len(new):] == new


# ---------------------------------------------------------------------------
# (3) the idle pump


def test_idle_pump_records_no_span_and_counts_itself():
    from deepflow_tpu.feeder import FeederConfig, FeederRuntime
    from deepflow_tpu.ingest.queues import new_queue

    class Sink:
        bucket_sizes = (8,)

    feeder = FeederRuntime([new_queue(16), new_queue(16)], Sink(), FeederConfig(),
                           name="idle")
    assert feeder.get_counters()["idle_pump_us"] == 0
    for _ in range(3):
        assert feeder.pump() == []
    assert feeder.tracer.recent() == [] and feeder.tracer.summary() == {}
    c = feeder.get_counters()
    assert c["idle_pumps"] == 3
    # their wall is kept (three pumps of two queue visits each cannot take
    # under a microsecond), and still no span, no aggregate, no CPU lane
    assert c["idle_pump_us"] > 0
    assert all(v == 0 for k, v in c.items() if k.endswith("_cpu_us"))
    assert (c["queue_visits"], c["queue_depth_sum"]) == (6, 0)


def test_queue_depth_is_read_where_the_feeder_looks():
    """`queue_depth_sum` adds up `len(q)` as each visit finds it, before
    the visit drains the queue; `queue_visits` counts the visits."""
    from deepflow_tpu.feeder import FeederConfig, FeederRuntime
    from deepflow_tpu.ingest.queues import new_queue

    class Sink:
        bucket_sizes = (8,)

        def decode_frame(self, raw):
            return None  # a frame of no rows: taken off the queue, nothing to emit

        def count_records(self, raw):
            return 0

    queues = [new_queue(16), new_queue(16)]
    feeder = FeederRuntime(queues, Sink(), FeederConfig(frames_per_queue=2, rounds_per_pump=1),
                           name="depth")
    for _ in range(3):
        queues[0].put(b"frame")
    queues[1].put(b"frame")
    feeder.pump()  # finds 3 and 1, takes 2 and 1
    c = feeder.get_counters()
    assert (c["queue_visits"], c["queue_depth_sum"], c["frames_in"]) == (2, 4, 3)
    feeder.pump()  # finds 1 and 0
    feeder.pump()  # finds 0 and 0: idle
    c = feeder.get_counters()
    assert (c["queue_visits"], c["queue_depth_sum"], c["frames_in"]) == (6, 5, 4)
    assert c["idle_pumps"] == 1 and c["idle_pump_us"] > 0
    # the CPU lanes the feeder republishes are its tracer's own
    lanes = feeder.tracer.summary()
    assert c["pump_cpu_us"] == lanes[SPAN_FEEDER_PUMP]["cpu_us"]
    assert c["drain_cpu_us"] == lanes[SPAN_FEEDER_DRAIN]["cpu_us"]
    assert c["decode_cpu_us"] == lanes[SPAN_FEEDER_DECODE]["cpu_us"]
    assert c["staging_wait_us"] == 0
    # six lanes, the ones a layer file or the traced run's deltas read
    assert sorted(k for k in c if k.endswith("_cpu_us")) == [
        "assemble_cpu_us", "coalesce_cpu_us", "decode_cpu_us", "dispatch_cpu_us",
        "drain_cpu_us", "pump_cpu_us"]


# ---------------------------------------------------------------------------
# (3b) the Receiver's clocks (ISSUE 38)


def _receiver_with_queue():
    from deepflow_tpu.ingest.framing import MessageType
    from deepflow_tpu.ingest.queues import new_queue
    from deepflow_tpu.ingest.receiver import Receiver

    rx, q = Receiver(), new_queue(64)
    rx.register_handler(MessageType.METRICS, [q])
    rx.start()
    return rx, q


def _metrics_frame(agent_id: int, size: int) -> bytes:
    from deepflow_tpu.ingest.framing import FlowHeader, MessageType, encode_frame

    return encode_frame(FlowHeader(msg_type=int(MessageType.METRICS), agent_id=agent_id),
                        [bytes(size)])


def _wait_for(cond, what: str) -> None:
    deadline = time.monotonic() + 30
    while not cond():
        assert time.monotonic() < deadline, what
        time.sleep(0.002)


def test_receiver_threads_clock_their_own_work_through_a_real_socket():
    rx, q = _receiver_with_queue()
    try:
        with socket.create_connection(("127.0.0.1", rx.tcp_port), timeout=30) as sock:
            _wait_for(lambda: rx.get_counters()["tcp_conns"] == 1, "no connection")
            c = rx.get_counters()
            assert (c["busy_us"], c["cpu_us"], c["rx_frames"]) == (0, 0, 0)
            # frames of several recvs each: most stretches only reassemble
            for i in range(6):
                sock.sendall(_metrics_frame(i, 200_000))
            _wait_for(lambda: rx.get_counters()["rx_frames"] == 6, "frames not routed")
            c = rx.get_counters()
            # the CPU is the thread's whole since it started, the recv calls'
            # kernel copy too; busy the stretches after a recv: neither
            # bounds the other
            assert len(q) == 6 and c["busy_us"] > 0 and c["cpu_us"] > 0
        # a connection that closes folds its last stretch in and stops the clocks
        _wait_for(lambda: not rx._conns, "connection not closed")
        after = rx.get_counters()
        assert after["busy_us"] >= c["busy_us"] and after["cpu_us"] >= c["cpu_us"]
        time.sleep(0.02)
        again = rx.get_counters()
        assert (again["busy_us"], again["cpu_us"]) == (after["busy_us"], after["cpu_us"])
    finally:
        rx.stop()


def test_receiver_reads_the_cpu_clock_once_a_frame_not_once_a_recv(monkeypatch):
    """The thread's CPU clock is a system call (5.5 us on the chip's host):
    a connection thread reads it when it starts, once a frame routed and
    when it ends, and folds in what it used since the read before; the wall
    clock twice a recv. The lane is the thread's own: a neighbour that
    spins through the same wall adds nothing to it."""
    reads = {"cpu": 0, "wall": 0}
    cpu_clock, wall_clock = time.thread_time_ns, time.perf_counter_ns

    def counted(kind, clock):
        def read():
            if threading.current_thread().name == "rx-under-test":
                reads[kind] += 1
            return clock()
        return read

    monkeypatch.setattr(time, "thread_time_ns", counted("cpu", cpu_clock))
    monkeypatch.setattr(time, "perf_counter_ns", counted("wall", wall_clock))
    rx, q = _receiver_with_queue()
    stop = threading.Event()
    spun = []

    def neighbour():
        t0 = cpu_clock()
        while not stop.is_set():
            _spin_cpu(1_000_000)
        spun.append(cpu_clock() - t0)

    spinner = threading.Thread(target=neighbour, daemon=True)
    try:
        server, client = socket.socketpair()
        t = threading.Thread(target=rx._conn_loop, args=(server, ("pair", 0)),
                             name="rx-under-test", daemon=True)
        server.settimeout(0.5)
        t.start()
        spinner.start()
        for i in range(5):
            raw = _metrics_frame(i, 150_000)
            for off in range(0, len(raw), 10_000):  # fifteen and a bit recvs a frame at least
                client.sendall(raw[off:off + 10_000])
                time.sleep(0.0005)
        _wait_for(lambda: rx.get_counters()["rx_frames"] == 5, "frames not routed")
        client.close()
        t.join(10)
        stop.set()
        spinner.join(10)
        assert not t.is_alive() and not spinner.is_alive() and len(q) == 5
        # start, a frame (five), end; the wall twice a recv
        assert reads["cpu"] == 1 + 5 + 1 and reads["wall"] >= 2 * 20
        c = rx.get_counters()
        # the connection thread slept between the sender's pieces while the
        # neighbour spun: lanes of one run against each other, no wall bound
        assert 0 < c["cpu_us"] * 1000 < spun[0] and c["busy_us"] > 0
    finally:
        stop.set()
        rx.stop()


def test_receiver_udp_thread_clocks_its_datagrams():
    rx, q = _receiver_with_queue()
    try:
        assert rx.get_counters()["busy_us"] == 0
        with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as sock:
            for i in range(4):
                sock.sendto(_metrics_frame(i, 1_000), ("127.0.0.1", rx.udp_port))
            sock.sendto(b"short", ("127.0.0.1", rx.udp_port))  # counted, clocked too
        _wait_for(lambda: rx.get_counters()["udp_frames"] == 5, "datagrams not taken")
        c = rx.get_counters()
        assert (c["rx_frames"], c["bad_frames"], len(q)) == (4, 1, 4)
        assert c["busy_us"] > 0 and c["cpu_us"] >= 0
    finally:
        rx.stop()


# ---------------------------------------------------------------------------
# (4) compiles, seen by the program


def test_compile_inside_a_span_is_charged_to_it_once():
    tr = SpanTracer()
    fn = jax.jit(lambda x: x * 3 + 1)
    x = jnp.arange(7)  # made out here: building it compiles too
    with tr.span("outer"):
        with tr.span("compiles.here"):
            fn(x).block_until_ready()
        with tr.span("compiles.here"):
            fn(x).block_until_ready()  # cached: no event
    first, second = tr.recent("compiles.here")
    compile_rec, = tr.recent(SPAN_XLA_COMPILE)
    assert compile_rec.parent_span_id == first.span_id != second.span_id
    assert compile_rec.trace_id == first.trace_id
    assert first.start_s <= compile_rec.start_s
    assert compile_rec.duration_us <= first.duration_us
    s = tr.summary()
    assert SPAN_XLA_COMPILE not in s  # a ring record, no aggregate
    here = s["compiles.here"]
    assert (here["compiles"], here["compile_us"]) == (1, compile_rec.duration_us)
    assert here["self_us"] <= here["total_us"] - compile_rec.duration_us
    assert s["outer"]["compiles"] == 0  # the innermost span takes it
    assert tr.compile_lanes() == (1, compile_rec.duration_us)
    assert tr.compile_lanes(("outer",)) == (0, 0)
    assert tr.get_counters()["compiles.here.compiles"] == 1


def test_compile_outside_any_span_goes_to_the_unspanned_lanes():
    tr = SpanTracer()
    with tr.span("registers.the.listener"):
        pass
    x = jnp.arange(11)
    before = spans.unspanned_compiles()
    jax.jit(lambda x: x - 5)(x).block_until_ready()
    after = spans.unspanned_compiles()
    assert after["compiles"] == before["compiles"] + 1
    assert after["compile_us"] > before["compile_us"]
    assert tr.recent(SPAN_XLA_COMPILE) == [] and tr.compile_lanes() == (0, 0)


def test_window_manager_counts_compiles_under_its_spans(monkeypatch):
    from deepflow_tpu.aggregator import window as window_mod
    from deepflow_tpu.aggregator.pipeline import L4Pipeline, PipelineConfig
    from deepflow_tpu.aggregator.window import WindowConfig
    from deepflow_tpu.datamodel.batch import FlowBatch
    from deepflow_tpu.ingest.replay import SyntheticFlowGen

    # a page size of this test's own, under the stash's rows: the first
    # close compiles the page program, whatever ran in the process before
    monkeypatch.setattr(window_mod, "PAGE_ROWS", 257)
    pipe = L4Pipeline(PipelineConfig(window=WindowConfig(capacity=1 << 10),
                                     batch_size=64))
    try:
        c = pipe.get_counters()
        assert (c["xla_compiles"], c["xla_compile_us"],
                c["flush_compiles"], c["flush_compile_us"]) == (0, 0, 0, 0)
        gen = SyntheticFlowGen(num_tuples=23, seed=3)
        for t in (5000, 5001, 5004, 5008):
            pipe.ingest(FlowBatch.from_records(gen.records(57, t)))
        c = pipe.get_counters()
        assert c["xla_compiles"] >= c["flush_compiles"] >= 1
        assert c["xla_compile_us"] >= c["flush_compile_us"] > 0
        rows = pipe.tracer.summary()[SPAN_FLUSH_ROWS]
        assert rows["compiles"] == 1  # the page program, once
        assert c["jit_compiles"] == 1  # the fused step's own monitor stays
        # closes with other document counts compile nothing more
        for i, t in enumerate((5012, 5016, 5020)):
            pipe.ingest(FlowBatch.from_records(gen.records(11 + 17 * i, t)))
        assert pipe.get_counters()["flush_compiles"] == c["flush_compiles"]
        assert pipe.get_counters()["flush_rows_live"] > c["flush_rows_live"] > 0
    finally:
        pipe.close()


# ---------------------------------------------------------------------------
# (5) stage names on the device programs


def _lowered_texts() -> dict:
    from deepflow_tpu.aggregator import stash
    from deepflow_tpu.aggregator.pipeline import L4Pipeline, PipelineConfig
    from deepflow_tpu.aggregator.sketchplane import SketchConfig
    from deepflow_tpu.aggregator.window import WindowConfig
    from deepflow_tpu.datamodel.batch import FlowBatch
    from deepflow_tpu.datamodel.schema import FLOW_METER, TAG_SCHEMA
    from deepflow_tpu.ingest.replay import SyntheticFlowGen

    text = lambda lowered: lowered.as_text(debug_info=True)
    st = stash.stash_init(256, TAG_SCHEMA, FLOW_METER)
    acc = stash.accum_init(128, TAG_SCHEMA, FLOW_METER)
    cols = lambda mask: tuple(int(i) for i in np.nonzero(mask)[0])
    sums, maxs = cols(FLOW_METER.sum_mask), cols(FLOW_METER.max_mask)
    out = {
        "fold": text(stash.collector_fold_counted.lower(st, acc, sums, maxs)),
        "merge_fold": text(stash.collector_merge_fold.lower(
            st, acc, jnp.uint32(9), sums, maxs)),
        "flush_range": text(stash.stash_flush_range.lower(
            st, np.uint32(0), np.uint32(9), compact=True)),
    }
    # the fused step with the pre-reduce and the sketch plane on: the
    # arguments of its first dispatch, recorded by the step census
    pipe = L4Pipeline(PipelineConfig(
        window=WindowConfig(capacity=1 << 10, sketch=SketchConfig()),
        batch_size=64, batch_unique_cap=64))
    try:
        gen = SyntheticFlowGen(num_tuples=9, seed=1)
        captured = {}
        observe = pipe._census.observe

        def capture(service, kind, rows, fn, args):
            captured["text"] = text(fn.lower(*args))
            return observe(service, kind, rows, fn, args)

        pipe._census.observe = capture
        try:
            pipe.ingest(FlowBatch.from_records(gen.records(40, 7000)))
        finally:
            pipe._census.observe = observe
        out["step"] = captured["text"]
    finally:
        pipe.close()
    return out


@pytest.fixture(scope="module")
def lowered_texts():
    return _lowered_texts()


@pytest.mark.parametrize("program,scope", [
    ("step", "step.prereduce"), ("step", "step.sketch"), ("step", "step.fanout"),
    ("step", "step.fingerprint"), ("step", "step.counter_block"),
    ("step", "step.append"),
    # the pre-reduce is the fold's group-by under the step's own scope
    ("step", "step.prereduce/fold.sort"),
    ("fold", "fold.concat"), ("fold", "fold.sort"), ("fold", "fold.segments"),
    ("fold", "fold.reduce"), ("fold", "fold.compact"),
    ("merge_fold", "fold.sort"), ("merge_fold", "fold.merge_ranks"),
    ("merge_fold", "fold.merge_order"), ("merge_fold", "fold.concat"),
    ("merge_fold", "fold.reduce"),
    ("flush_range", "flush.order"), ("flush_range", "flush.pack"),
    ("flush_range", "flush.compact"),
])
def test_lowered_program_names_its_stages(program, scope, lowered_texts):
    assert scope in lowered_texts[program]


def test_jitted_programs_keep_the_names_the_trace_groups_match(lowered_texts):
    with open(os.path.join(CHIPBENCH, "trace_groups.json")) as f:
        groups = json.load(f)["modules"]
    for program, group in (("step", "fused_step"), ("fold", "fold"),
                           ("flush_range", "flush_range")):
        module = lowered_texts[program].split("module @", 1)[1].split()[0]
        assert any(module.startswith(p) for p in groups[group]), (module, groups[group])


def test_pallas_kernels_carry_names():
    from deepflow_tpu.ops.segreduce_pallas import sorted_segment_sum_max

    n, cap = 256, 16
    seg = jnp.sort(jnp.arange(n, dtype=jnp.int32) % cap)
    rows = jnp.ones((n, 8), jnp.float32)
    first = jnp.searchsorted(seg, jnp.arange(cap, dtype=jnp.int32))
    jaxpr = str(jax.make_jaxpr(
        lambda r, s, f: sorted_segment_sum_max(r, s, cap, f))(rows, seg, first))
    assert "segreduce_suffix_scan" in jaxpr
