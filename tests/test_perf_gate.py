"""Perf regression gate — what a kernel regression changes and a loaded
CPU does not.

Round 3 shipped a 7x kernel regression behind 164 green correctness
tests because nothing in the suite watched the programs. The first two
gates compile the append+fold pair of the production cadence
(append × accum_batches + fold, aggregator/pipeline.py) at a small shape
and count, in the compiled programs' HLO text:

  * the `sort` instructions, exactly: a second keyed sort is the cost
    the one-sort designs exist to avoid, and
  * all instructions, under about three times today's count: a
    log-depth scan in place of a linear one, or a program whose size
    grows with its shapes — the round-3 failure modes — multiplies it.

The cycles still run, so the programs still execute; no clock is read.
Times, rates and compile seconds come from the chip (chipbench/).
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepflow_tpu.aggregator.fanout import FANOUT_LANES, FanoutConfig
from deepflow_tpu.aggregator.pipeline import make_ingest_step
from deepflow_tpu.aggregator.stash import accum_init, stash_init
from deepflow_tpu.datamodel.schema import FLOW_METER, TAG_SCHEMA
from deepflow_tpu.ingest.replay import SyntheticFlowGen

BATCH = 1024
CAPACITY = 1 << 12
ACCUM_BATCHES = 4

_HLO_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.\-]+ = ", re.M)


def _program_counts(compiled) -> tuple[int, int]:
    """(sort instructions, all instructions) in a compiled program."""
    text = compiled.as_text()
    return len(re.findall(r" sort\(", text)), len(_HLO_INSTRUCTION.findall(text))


def _compile_count_and_cycle(batch_unique_cap):
    """Compile the append and the fold at the gate's shape, run three
    cycles of the production cadence on them, and return their counts
    as ((append sorts, append instructions), (fold sorts, fold
    instructions))."""
    gen = SyntheticFlowGen(num_tuples=500, seed=0)
    fb = gen.flow_batch(BATCH, 1_700_000_000)
    tags = {k: jnp.asarray(v) for k, v in fb.tags.items()}
    meters, valid = jnp.asarray(fb.meters), jnp.asarray(fb.valid)

    append_fn, fold_fn = make_ingest_step(
        FanoutConfig(), interval=1, batch_unique_cap=batch_unique_cap
    )
    stride = FANOUT_LANES * (batch_unique_cap or BATCH)
    state = stash_init(CAPACITY, TAG_SCHEMA, FLOW_METER)
    acc = accum_init(ACCUM_BATCHES * stride, TAG_SCHEMA, FLOW_METER)
    append = jax.jit(append_fn, donate_argnums=(0, 1)).lower(
        state, acc, jnp.int32(0), tags, meters, valid).compile()
    fold = jax.jit(fold_fn, donate_argnums=(0, 1)).lower(state, acc).compile()

    live = []
    for _ in range(3):
        for k in range(ACCUM_BATCHES):
            state, acc = append(
                state, acc, jnp.int32(k * stride), tags, meters, valid
            )
        state, acc = fold(state, acc)
        live.append(int(jnp.sum(state.valid)))
    # the same batch every time: the stash holds its keys after the first
    # fold and nothing more after the third
    assert live[0] > 0 and live == [live[0]] * 3, live
    assert int(state.dropped_overflow) == 0
    return _program_counts(append), _program_counts(fold)


def test_hot_path_compile_and_steady_state_bounds():
    (append_sorts, append_instr), (fold_sorts, fold_instr) = (
        _compile_count_and_cycle(None))
    # no pre-reduce: the append sorts nothing; the fold sorts its keys
    # once and the positions of its segment heads once
    assert (append_sorts, fold_sorts) == (0, 2)
    assert append_instr < 5000, append_instr  # 1626 when written
    assert fold_instr < 1700, fold_instr  # 571 when written


def test_prereduce_hot_path_bounds():
    """The same gate for the cadence bench.py and the served path run:
    the batch-local pre-reduce (batch_unique_cap) before fanout is the
    fold's group-by over the batch, so the append gains the fold's two
    sorts and the fold stays as it was."""
    (append_sorts, append_instr), (fold_sorts, fold_instr) = (
        _compile_count_and_cycle(512))
    assert (append_sorts, fold_sorts) == (2, 2)
    assert append_instr < 10000, append_instr  # 3309 when written
    assert fold_instr < 1700, fold_instr  # 571 when written


# ---------------------------------------------------------------------------
# Host-sync budget (ISSUE 2): every device→host fetch stalls the host
# on the device. All WindowManager transfers route through
# window.host_fetch; this gate shims that seam and asserts the
# per-ingest fetch count is a small constant — independent of batch
# rows AND of how many windows a single advance closes — so a
# reintroduced np.asarray-per-batch (or per-window flush loop)
# regression trips in CPU CI.

SYNC_BUDGET = 3  # stats vector + flush row count + packed flush rows


@pytest.mark.parametrize("page_rows,pooled", [(None, False), (64, False), (64, True)],
                         ids=["whole", "paged_reserve", "paged_reserve_pooled"])
def test_window_ingest_host_sync_budget(monkeypatch, page_rows, pooled):
    import deepflow_tpu.aggregator.window as window_mod
    from deepflow_tpu.utils import hostpool
    from deepflow_tpu.aggregator.pipeline import L4Pipeline, PipelineConfig
    from deepflow_tpu.aggregator.window import WindowConfig
    from deepflow_tpu.datamodel.batch import FlowBatch

    counts = {"n": 0}
    real_fetch = window_mod.host_fetch

    def counting_fetch(x):
        counts["n"] += 1
        return real_fetch(x)

    monkeypatch.setattr(window_mod, "host_fetch", counting_fetch)
    if page_rows:
        # closes of several pages, so the drains join into a destination
        # reserved under flush.wait (PR 34): a host array, no fetch
        monkeypatch.setattr(window_mod, "PAGE_ROWS", page_rows)
    if pooled:
        # the reserve's touch and the join's copy over four threads
        # (PR 37): host memory only, no fetch
        monkeypatch.setattr(hostpool, "WORKERS", 4)
        monkeypatch.setattr(hostpool, "POOL_MIN_BYTES", 0)
        monkeypatch.setattr(hostpool, "_cores", lambda: 64)

    pipe = L4Pipeline(
        PipelineConfig(window=WindowConfig(capacity=1 << 12), batch_size=256)
    )
    gen = SyntheticFlowGen(num_tuples=200, seed=3)

    def fetches(n_rows: int, t: int) -> int:
        before = counts["n"]
        pipe.ingest(FlowBatch.from_records(gen.records(n_rows, t)))
        return counts["n"] - before

    t0 = 1_700_000_000
    no_advance = fetches(64, t0)  # first batch, nothing closes
    assert no_advance <= SYNC_BUDGET
    one_close = fetches(256, t0 + 4)  # advance: one occupied window closes
    assert one_close <= SYNC_BUDGET
    # a 100-window jump: ~97 empty + occupied windows close in ONE advance
    many_close = fetches(256, t0 + 104)
    assert many_close <= SYNC_BUDGET
    assert many_close <= one_close  # budget must not scale with windows closed
    # batch size must not change the budget either
    assert fetches(16, t0 + 105) <= SYNC_BUDGET
    if page_rows:
        # second after second of one size: from the third close on the
        # rows land in a reserve, inside the same budget
        for k in range(6):
            assert fetches(256, t0 + 106 + k) <= SYNC_BUDGET
        c = pipe.get_counters()
        assert c["flush_pages"] > c["window_advances"]
        assert 0 < c["flush_rows_reserved"] <= c["flush_rows_live"]
        assert pipe.tracer.summary()["flush.reserve"]["count"] >= 2
        assert c["flush_host_pass_bytes"] >= c["flush_host_write_bytes"] > 0
        assert (c["flush_pooled_bytes"] > 0) == pooled
    # counters read scalar reductions, never the full valid plane — and
    # stay O(1) fetches
    before = counts["n"]
    _ = pipe.counters
    assert counts["n"] - before <= 2
    # the Countable face must be FETCH-FREE (a ticking collector thread
    # samples it mid-ingest) while still carrying the device counter
    # block's lanes and the transfer accounting
    before = counts["n"]
    c = pipe.get_counters()
    assert counts["n"] - before == 0
    for key in ("stash_occupancy", "stash_evictions", "excess_word_hits",
                "host_fetches", "bytes_fetched", "bytes_uploaded",
                "flush_host_pass_bytes", "flush_pooled_bytes"):
        assert key in c
    assert c["host_fetches"] > 0 and c["bytes_fetched"] > 0
    assert c["bytes_uploaded"] > 0


def test_sharded_window_ingest_host_sync_budget(monkeypatch):
    """The sharded twin of the budget gate: ShardedWindowManager
    ingest/drain under the same host_fetch shim — the per-ingest fetch
    count must stay ≤ SYNC_BUDGET regardless of device count (the
    batched drain fetches ONE bundled scalar vector + ONE list of the
    devices' page shards, a single `device_get`), and the
    transfer-byte counter must account every fetched byte."""
    import deepflow_tpu.aggregator.window as window_mod
    from deepflow_tpu.ops.histogram import LogHistSpec
    from deepflow_tpu.parallel.mesh import make_mesh
    from deepflow_tpu.parallel.sharded import (
        ShardedConfig,
        ShardedPipeline,
        ShardedWindowManager,
    )

    counts = {"n": 0, "bytes": 0}
    real_fetch = window_mod.host_fetch

    def counting_fetch(x):
        counts["n"] += 1
        arr = real_fetch(x)
        counts["bytes"] += (
            sum(a.nbytes for a in arr) if isinstance(arr, list) else arr.nbytes
        )
        return arr

    monkeypatch.setattr(window_mod, "host_fetch", counting_fetch)

    gen = SyntheticFlowGen(num_tuples=200, seed=5)
    t0 = 1_700_000_000
    per_ingest: dict[int, list[int]] = {}
    for n_dev in (1, 4):
        mesh = make_mesh(n_dev)
        cfg = ShardedConfig(
            capacity_per_device=1 << 10, num_services=16, hll_precision=6,
            hist=LogHistSpec(bins=64, vmin=1.0, gamma=1.3),
        )
        wm = ShardedWindowManager(ShardedPipeline(mesh, cfg))
        n0, b0 = counts["n"], counts["bytes"]
        fetches = []
        for t in (t0, t0 + 1, t0 + 4, t0 + 104, t0 + 105):
            fb = gen.flow_batch(64 * n_dev, t)
            before = counts["n"]
            wm.ingest(fb.tags, fb.meters, fb.valid)
            fetches.append(counts["n"] - before)
        per_ingest[n_dev] = fetches
        assert max(fetches) <= SYNC_BUDGET, (n_dev, fetches)
        before = counts["n"]
        wm.drain()
        assert counts["n"] - before <= SYNC_BUDGET
        # transfer accounting: the manager's counters mirror exactly what
        # the shim saw for this manager (count AND bytes)
        before = counts["n"]
        c = wm.get_counters()
        assert counts["n"] == before  # the Countable face fetches nothing
        assert c["host_fetches"] == counts["n"] - n0
        assert c["bytes_fetched"] == counts["bytes"] - b0
        assert c["bytes_uploaded"] > 0
        # the close's host passes (PR 37), under the one-chip names
        assert c["flush_pooled_bytes"] <= c["flush_host_pass_bytes"]
    # the budget must not scale with shard count
    assert max(per_ingest[4]) <= max(per_ingest[1]) + 0


def test_jit_retrace_gate():
    """Steady-state windowed ingest over K same-shape batches must
    trigger ZERO recompiles of the fused step (the silent
    compile-per-batch failure mode a shape/weak-type leak reintroduces).
    Asserted via the pipeline's JitCacheMonitor retrace counter."""
    from deepflow_tpu.aggregator.pipeline import L4Pipeline, PipelineConfig
    from deepflow_tpu.aggregator.window import WindowConfig
    from deepflow_tpu.datamodel.batch import FlowBatch

    pipe = L4Pipeline(
        PipelineConfig(window=WindowConfig(capacity=1 << 12), batch_size=256)
    )
    gen = SyntheticFlowGen(num_tuples=200, seed=7)
    t0 = 1_700_000_000
    # warmup: first batch compiles the fused step (counted as a compile)
    pipe.ingest(FlowBatch.from_records(gen.records(128, t0)))
    c = pipe.get_counters()
    assert c["jit_compiles"] == 1, c
    base_retraces = c["jit_retraces"]
    # steady state: same shape, advancing timestamps (window closes ride
    # along) — K batches, zero retraces allowed
    for i in range(6):
        pipe.ingest(FlowBatch.from_records(gen.records(128, t0 + 1 + i)))
    c = pipe.get_counters()
    assert c["jit_retraces"] == base_retraces == 0, (
        f"fused step recompiled during steady-state same-shape ingest "
        f"(retraces={c['jit_retraces']}) — shape leak"
    )


def test_feeder_host_fetch_budget(monkeypatch):
    """Feeder-runtime budget (ISSUE 4): with a K-batch counter ring the
    steady-state fetch count over B ingested batches must be
    ≤ ceil(B/K) + 2 per window span (stats ring drains + the two
    advance fetches) — strictly < 1 fetch per batch — and mixed bucket
    sizes must trigger ZERO retraces of the fused step (one compile per
    bucket is the budget, anything more is a shape leak)."""
    import deepflow_tpu.aggregator.window as window_mod
    from deepflow_tpu.aggregator.pipeline import L4Pipeline, PipelineConfig
    from deepflow_tpu.aggregator.window import WindowConfig
    from deepflow_tpu.feeder import (
        FeederConfig,
        FeederRuntime,
        PipelineFeedSink,
        encode_flowbatch_frames,
    )
    from deepflow_tpu.ingest.queues import PyOverwriteQueue

    counts = {"n": 0}
    real_fetch = window_mod.host_fetch

    def counting_fetch(x):
        counts["n"] += 1
        return real_fetch(x)

    monkeypatch.setattr(window_mod, "host_fetch", counting_fetch)

    K = 4
    buckets = (64, 128, 256)
    pipe = L4Pipeline(PipelineConfig(
        window=WindowConfig(capacity=1 << 12, stats_ring=K),
        batch_size=256, bucket_sizes=buckets,
    ))
    queues = [PyOverwriteQueue(1 << 10) for _ in range(3)]
    feeder = FeederRuntime(
        queues, PipelineFeedSink(pipe), FeederConfig(frames_per_queue=8)
    )
    gen = SyntheticFlowGen(num_tuples=300, seed=11)

    t0 = 1_700_000_000
    sizes = [60, 120, 250, 40, 200, 64, 90, 256, 30, 180, 128, 70,
             250, 55, 140, 33]
    before = counts["n"]
    for i, n in enumerate(sizes):
        fb = gen.flow_batch(n, t0 + i // 4)  # one window advance per 4 batches
        for j, fr in enumerate(encode_flowbatch_frames(fb, max_rows_per_frame=64)):
            queues[j % 3].put(fr)
        feeder.pump()
    fetches = counts["n"] - before
    B = len(sizes)
    advances = pipe.get_counters()["window_advances"]
    assert advances >= 2  # the span actually advanced mid-run
    # the acceptance bound: ring drains + 2 fetches per advance, and
    # strictly below one fetch per ingested batch
    assert fetches <= -(-B // K) + 2 * advances, (fetches, advances)
    assert fetches < B, f"{fetches} fetches for {B} batches — ring not engaged"
    # mixed buckets: one compile per bucket max, zero retraces
    c = pipe.get_counters()
    assert c["jit_retraces"] == 0, c
    assert c["jit_compiles"] <= len(buckets)
    assert feeder.get_counters()["shed_records"] == 0


def test_merge_fold_budget_and_fold_work_gate(monkeypatch):
    """ISSUE 5 fold-work gate: fold_mode="merge" steady advancing ingest
    must (a) stay inside the same ≤3-fetch budget — the merge-fold adds
    ZERO steady-state host fetches (fold_rows rides the counter block) —
    and (b) demonstrate the span-bounded advance via the CB_FOLD_ROWS
    lane: merge-mode fold row counts strictly below both the full-sort
    mode's fold rows and the live stash occupancy. Flushed output must
    stay identical between modes, with zero fused-step retraces."""
    import deepflow_tpu.aggregator.window as window_mod
    from deepflow_tpu.aggregator.pipeline import L4Pipeline, PipelineConfig
    from deepflow_tpu.aggregator.window import WindowConfig

    counts = {"n": 0}
    real_fetch = window_mod.host_fetch

    def counting_fetch(x):
        counts["n"] += 1
        return real_fetch(x)

    monkeypatch.setattr(window_mod, "host_fetch", counting_fetch)

    from deepflow_tpu.datamodel.batch import FlowBatch

    pipes = {
        mode: L4Pipeline(
            PipelineConfig(
                window=WindowConfig(capacity=1 << 13, delay=3, fold_mode=mode),
                batch_size=256,
            )
        )
        for mode in ("full", "merge")
    }
    gen = SyntheticFlowGen(num_tuples=500, seed=17)
    t0 = 1_700_000_000
    # 3 batches build up open windows (big stash), then steady +1s
    # advances close one window span per batch
    times = [t0, t0 + 1, t0 + 2, t0 + 6, t0 + 7, t0 + 8]
    flushed = {m: [] for m in pipes}
    fold_rows = {m: [] for m in pipes}
    for t in times:
        fb = FlowBatch.from_records(gen.records(256, t))
        for mode, pipe in pipes.items():
            before = counts["n"]
            flushed[mode].extend(db.size for db in pipe.ingest(fb))
            assert counts["n"] - before <= SYNC_BUDGET, (mode, t)
            fold_rows[mode].append(pipe.get_counters()["fold_rows"])
    assert flushed["merge"] == flushed["full"]

    full_c = pipes["full"].get_counters()
    merge_c = pipes["merge"].get_counters()
    assert merge_c["window_advances"] >= 2
    # the lane shows the row savings: a span-bounded advance fold sorts
    # only the closing windows' acc rows (often ZERO on advances whose
    # closing windows already folded — that is the point), while the
    # full-sort fold re-sorts the whole live stash + ring every time.
    # Compare the PEAK lane values over the identical stream.
    assert max(fold_rows["merge"]) > 0
    assert max(fold_rows["merge"]) < max(fold_rows["full"]), fold_rows
    # ...and every merge-mode fold stayed below the full mode's peak
    assert all(r < max(fold_rows["full"]) for r in fold_rows["merge"])
    for c in (full_c, merge_c):
        assert c["jit_retraces"] == 0, c


def test_sketch_plane_host_sync_budget(monkeypatch):
    """ISSUE 8 gate: the per-window sketch plane adds ZERO fetches —
    closed blocks ride the advance drain's existing transfers, so the
    ≤3-fetch budget holds with sketches ON; with a K=4 counter ring the
    steady-state stays strictly below one fetch per batch; the fused
    step never retraces; and the CB v4 sketch lane proves updates ran
    inside the fused dispatch."""
    import deepflow_tpu.aggregator.window as window_mod
    from deepflow_tpu.aggregator.pipeline import L4Pipeline, PipelineConfig
    from deepflow_tpu.aggregator.sketchplane import SketchConfig
    from deepflow_tpu.aggregator.window import WindowConfig
    from deepflow_tpu.datamodel.batch import FlowBatch
    from deepflow_tpu.ops.histogram import LogHistSpec

    counts = {"n": 0}
    real_fetch = window_mod.host_fetch

    def counting_fetch(x):
        counts["n"] += 1
        return real_fetch(x)

    monkeypatch.setattr(window_mod, "host_fetch", counting_fetch)

    sk = SketchConfig(
        num_groups=4, hll_precision=7, cms_depth=2, cms_width=256,
        hist=LogHistSpec(bins=32, vmin=1.0, gamma=1.3),
        topk_rows=2, topk_cols=64, pending=8,
    )
    gen = SyntheticFlowGen(num_tuples=200, seed=23)
    t0 = 1_700_000_000

    # (a) per-batch mode: every ingest — including multi-window
    # advances — stays inside the same ≤3-fetch budget as exact-only
    pipe = L4Pipeline(PipelineConfig(
        window=WindowConfig(capacity=1 << 12, sketch=sk), batch_size=256,
    ))
    for t in (t0, t0 + 1, t0 + 4, t0 + 104, t0 + 105):
        before = counts["n"]
        pipe.ingest(FlowBatch.from_records(gen.records(128, t)))
        assert counts["n"] - before <= SYNC_BUDGET, t - t0
    c = pipe.get_counters()
    assert c["sketch_rows"] > 0, "sketch lane never moved — plane not fused"
    assert c["jit_retraces"] == 0, c
    blocks = pipe.pop_closed_sketches()
    assert blocks, "advances closed windows but no sketch blocks drained"

    # (b) K=4 counter ring: <1 stats fetch per batch with the plane on
    K = 4
    pipe_k = L4Pipeline(PipelineConfig(
        window=WindowConfig(capacity=1 << 12, stats_ring=K, sketch=sk),
        batch_size=256,
    ))
    before = counts["n"]
    B = 16
    for i in range(B):
        pipe_k.ingest(FlowBatch.from_records(gen.records(128, t0 + i // 4)))
    fetches = counts["n"] - before
    advances = pipe_k.get_counters()["window_advances"]
    assert advances >= 2
    assert fetches <= -(-B // K) + 2 * advances, (fetches, advances)
    assert fetches < B, f"{fetches} fetches for {B} batches — ring defeated"
    c = pipe_k.get_counters()
    assert c["sketch_rows"] > 0
    assert c["jit_retraces"] == 0, c


def test_sketch_pool_budget(monkeypatch):
    """ISSUE 20 gate: the disaggregated sketch-memory pool rides the
    SAME transfer schedule as the slab plane — ≤3 fetches per batch
    (pool telemetry lanes travel in the existing counter block, wide
    rows in the existing drain transfers), K-ring <1 fetch/batch
    steady-state, zero retraces — while flushed exact rows stay
    bit-identical to the slab run and the HBM ledger reconciles over
    the four pooled planes (hot arena / wide arena / pending / meta)."""
    import deepflow_tpu.aggregator.window as window_mod
    from deepflow_tpu.aggregator.pipeline import L4Pipeline, PipelineConfig
    from deepflow_tpu.aggregator.sketchplane import PoolConfig, SketchConfig
    from deepflow_tpu.aggregator.window import WindowConfig
    from deepflow_tpu.datamodel.batch import FlowBatch
    from deepflow_tpu.ops.histogram import LogHistSpec
    from deepflow_tpu.profiling.ledger import DeviceMemoryLedger, plane_bytes

    counts = {"n": 0}
    real_fetch = window_mod.host_fetch

    def counting_fetch(x):
        counts["n"] += 1
        return real_fetch(x)

    monkeypatch.setattr(window_mod, "host_fetch", counting_fetch)

    pool_cfg = PoolConfig(compact_slots=3, wide_slots=1, cms_factor=4,
                          topk_factor=2, hist_factor=4)

    def mk_sk(pool):
        return SketchConfig(
            num_groups=4, hll_precision=7, cms_depth=2, cms_width=256,
            hist=LogHistSpec(bins=32, vmin=1.0, gamma=1.3),
            topk_rows=2, topk_cols=64, pending=8, pool=pool,
        )

    t0 = 1_700_000_000
    sched = (t0, t0 + 1, t0 + 4, t0 + 104, t0 + 105)

    # (a) per-batch budget with the pool ON; exact rows bit-identical
    # to the slab run on byte-identical traffic
    out = {}
    for name, pool in (("slab", None), ("pool", pool_cfg)):
        gen = SyntheticFlowGen(num_tuples=200, seed=23)
        pipe = L4Pipeline(PipelineConfig(
            window=WindowConfig(capacity=1 << 12, sketch=mk_sk(pool)),
            batch_size=256,
        ))
        docs = []
        for t in sched:
            before = counts["n"]
            docs += pipe.ingest(FlowBatch.from_records(gen.records(128, t)))
            if name == "pool":
                assert counts["n"] - before <= SYNC_BUDGET, t - t0
        docs += pipe.drain()
        c = pipe.get_counters()
        assert c["jit_retraces"] == 0, c
        if name == "pool":
            assert c["sketch_rows"] > 0
            assert pipe.pop_closed_sketches(), "pool closed no blocks"
            # ledger reconciliation: the pooled plane reports as four
            # attributable rows whose total equals the live bytes
            planes = pipe.wm.device_planes()
            for p in ("sketch_pool_hot", "sketch_pool_wide",
                      "sketch_pending", "sketch_meta"):
                assert plane_bytes(planes[p])[0] > 0, p
            assert "sketch" not in planes
            led = DeviceMemoryLedger()
            led.register("pipe", pipe.wm)
            rows = {r["plane"]: r for r in led.snapshot()}
            total = sum(plane_bytes(t_)[0] for t_ in planes.values())
            assert sum(r["bytes"] for r in rows.values()) == total
            # the compact arena is the resident plane; the worst-case
            # wide arena no longer scales with the ring (1 slot here)
            assert rows["sketch_pool_hot"]["bytes"] > 0
        out[name] = docs
    assert len(out["slab"]) == len(out["pool"])
    for a, b in zip(out["slab"], out["pool"]):
        np.testing.assert_array_equal(a.timestamp, b.timestamp)
        np.testing.assert_array_equal(a.tags, b.tags)
        assert a.meters.tobytes() == b.meters.tobytes()

    # (b) K=4 counter ring: <1 stats fetch/batch with the pool ON
    K, B = 4, 16
    gen = SyntheticFlowGen(num_tuples=200, seed=23)
    pipe_k = L4Pipeline(PipelineConfig(
        window=WindowConfig(capacity=1 << 12, stats_ring=K,
                            sketch=mk_sk(pool_cfg)),
        batch_size=256,
    ))
    before = counts["n"]
    for i in range(B):
        pipe_k.ingest(FlowBatch.from_records(gen.records(128, t0 + i // 4)))
    fetches = counts["n"] - before
    advances = pipe_k.get_counters()["window_advances"]
    assert advances >= 2
    assert fetches <= -(-B // K) + 2 * advances, (fetches, advances)
    assert fetches < B, f"{fetches} fetches for {B} batches — ring defeated"
    c = pipe_k.get_counters()
    assert c["jit_retraces"] == 0, c
    assert c["sketch_pool_spill"] == 0, c


def test_cascade_host_sync_budget(monkeypatch):
    """ISSUE 9 gate: the rollup cascade adds ZERO fetches — tier folds
    are advance-path device dispatches and the closed tier windows'
    rows ride the drain's existing two transfers — so the ≤3-fetch
    steady-state budget holds with the cascade ON, including the
    advances that close a 1m tier window; with a K=4 counter ring the
    steady state stays strictly below one fetch per batch; the fused
    step never retraces across tier closes; and the CB v5 cascade lane
    proves the tier folds actually ran."""
    import deepflow_tpu.aggregator.window as window_mod
    from deepflow_tpu.aggregator.cascade import CascadeConfig
    from deepflow_tpu.aggregator.pipeline import L4Pipeline, PipelineConfig
    from deepflow_tpu.aggregator.window import WindowConfig
    from deepflow_tpu.datamodel.batch import FlowBatch

    counts = {"n": 0}
    real_fetch = window_mod.host_fetch

    def counting_fetch(x):
        counts["n"] += 1
        return real_fetch(x)

    monkeypatch.setattr(window_mod, "host_fetch", counting_fetch)

    casc = CascadeConfig(intervals=(60,), capacity=1 << 12)
    gen = SyntheticFlowGen(num_tuples=200, seed=29)
    t0 = 1_700_000_040  # 40s into a minute: the 3rd advance closes a 1m tier

    # (a) per-batch mode: every ingest — including the minute-closing
    # advance and a 100-window jump — stays inside the same budget
    pipe = L4Pipeline(PipelineConfig(
        window=WindowConfig(capacity=1 << 12, cascade=casc), batch_size=256,
    ))
    for t in (t0, t0 + 1, t0 + 4, t0 + 25, t0 + 90, t0 + 190):
        before = counts["n"]
        pipe.ingest(FlowBatch.from_records(gen.records(128, t)))
        assert counts["n"] - before <= SYNC_BUDGET, t - t0
    c = pipe.get_counters()
    assert c["cascade_rows"] > 0, "cascade lane never moved — tiers not folding"
    assert c["jit_retraces"] == 0, c
    assert pipe.pop_tier_docbatches(), "minute boundary crossed, no tier docs"

    # (b) K=4 counter ring: <1 stats fetch per batch with the cascade on
    K = 4
    pipe_k = L4Pipeline(PipelineConfig(
        window=WindowConfig(capacity=1 << 12, stats_ring=K, cascade=casc),
        batch_size=256,
    ))
    before = counts["n"]
    B = 16
    for i in range(B):
        pipe_k.ingest(FlowBatch.from_records(gen.records(128, t0 + i // 4)))
    fetches = counts["n"] - before
    advances = pipe_k.get_counters()["window_advances"]
    assert advances >= 2
    assert fetches <= -(-B // K) + 2 * advances, (fetches, advances)
    assert fetches < B, f"{fetches} fetches for {B} batches — ring defeated"
    # one more full ring ACROSS the minute boundary: the tier-closing
    # advance costs the same ring drain + 2 advance fetches as any other
    before = counts["n"]
    for _ in range(K):
        pipe_k.ingest(FlowBatch.from_records(gen.records(128, t0 + 90)))
    assert counts["n"] - before <= SYNC_BUDGET
    c = pipe_k.get_counters()
    assert c["cascade_rows"] > 0
    assert c["jit_retraces"] == 0, c
    assert pipe_k.pop_tier_docbatches()


def test_sharded_cascade_host_sync_budget(monkeypatch):
    """The sharded twin: per-device tier folds + the host-merge drain
    keep the per-ingest fetch count ≤ SYNC_BUDGET regardless of device
    count — tier totals ride the bundled scalar vector, tier rows the
    concatenated row fetch."""
    import deepflow_tpu.aggregator.window as window_mod
    from deepflow_tpu.ops.histogram import LogHistSpec
    from deepflow_tpu.parallel.mesh import make_mesh
    from deepflow_tpu.parallel.sharded import (
        ShardedConfig,
        ShardedPipeline,
        ShardedWindowManager,
    )

    counts = {"n": 0}
    real_fetch = window_mod.host_fetch

    def counting_fetch(x):
        counts["n"] += 1
        return real_fetch(x)

    monkeypatch.setattr(window_mod, "host_fetch", counting_fetch)

    gen = SyntheticFlowGen(num_tuples=200, seed=31)
    t0 = 1_700_000_040
    for n_dev in (1, 2):
        mesh = make_mesh(n_dev)
        cfg = ShardedConfig(
            capacity_per_device=1 << 10, num_services=16, hll_precision=6,
            hist=LogHistSpec(bins=64, vmin=1.0, gamma=1.3),
            cascade=(60,), cascade_capacity=1 << 10,
        )
        wm = ShardedWindowManager(ShardedPipeline(mesh, cfg))
        for t in (t0, t0 + 1, t0 + 4, t0 + 25, t0 + 90):
            fb = gen.flow_batch(64 * n_dev, t)
            before = counts["n"]
            wm.ingest(fb.tags, fb.meters, fb.valid)
            assert counts["n"] - before <= SYNC_BUDGET, (n_dev, t - t0)
        before = counts["n"]
        wm.drain()
        assert counts["n"] - before <= SYNC_BUDGET
        c = wm.get_counters()
        assert c["cascade_rows"] > 0
        assert wm.pop_tier_docbatches()


def test_live_read_budget(monkeypatch):
    """ISSUE 10 gate: live snapshot reads add ZERO steady-state ingest
    fetches — a stream with `snapshot_open()` interleaved every N
    batches spends EXACTLY the same fetches inside ingest as the
    snapshot-free twin (the snapshot's own 2 pull-path fetches are
    accounted separately and stay ≤2 per read), produces bit-identical
    flushed output, and triggers zero retraces of the fused step."""
    import deepflow_tpu.aggregator.window as window_mod
    from deepflow_tpu.aggregator.pipeline import L4Pipeline, PipelineConfig
    from deepflow_tpu.aggregator.window import WindowConfig
    from deepflow_tpu.datamodel.batch import FlowBatch

    counts = {"n": 0}
    real_fetch = window_mod.host_fetch

    def counting_fetch(x):
        counts["n"] += 1
        return real_fetch(x)

    monkeypatch.setattr(window_mod, "host_fetch", counting_fetch)

    K = 4

    def build():
        return L4Pipeline(PipelineConfig(
            window=WindowConfig(capacity=1 << 12, stats_ring=K,
                                min_snapshot_interval=0.0),
            batch_size=256,
        ))

    base, live = build(), build()
    gen_a = SyntheticFlowGen(num_tuples=200, seed=37)
    gen_b = SyntheticFlowGen(num_tuples=200, seed=37)
    t0 = 1_700_000_000
    B = 16
    ingest_fetches = {"base": 0, "live": 0}
    snap_fetches = 0
    out = {"base": [], "live": []}
    for i in range(B):
        fa = FlowBatch.from_records(gen_a.records(128, t0 + i // 4))
        fb = FlowBatch.from_records(gen_b.records(128, t0 + i // 4))
        before = counts["n"]
        out["base"] += [d.tags.tobytes() for d in base.ingest(fa)]
        ingest_fetches["base"] += counts["n"] - before
        before = counts["n"]
        out["live"] += [d.tags.tobytes() for d in live.ingest(fb)]
        ingest_fetches["live"] += counts["n"] - before
        if (i + 1) % 4 == 0:
            # the live read: BETWEEN dispatches, never inside ingest
            before = counts["n"]
            snap = live.snapshot_open(force=True)
            got = counts["n"] - before
            assert got <= 2, f"snapshot took {got} fetches"
            snap_fetches += got
            assert snap.windows  # the open span is actually visible
    # the acceptance: steady-state ingest fetch budget UNCHANGED
    assert ingest_fetches["live"] == ingest_fetches["base"], ingest_fetches
    assert out["live"] == out["base"]  # flushed output bit-identical
    assert snap_fetches <= 2 * (B // 4)
    c = live.get_counters()
    assert c["snapshot_reads"] == B // 4
    assert c["jit_retraces"] == 0, c
    # K-ring still engaged: ingest fetches stay strictly below 1/batch
    advances = c["window_advances"]
    assert ingest_fetches["live"] <= -(-B // K) + 2 * advances
    assert ingest_fetches["live"] < B


def test_push_plane_budget(monkeypatch):
    """ISSUE 11 gate: with subscriptions + alert rules ACTIVE on the
    event bus, ingest-attributable host fetches are IDENTICAL to the
    passive baseline — the push plane's evaluations read the warm
    rate-limited snapshot and the (host-side) store, never the device —
    flushed output stays bit-identical, the fused step never retraces,
    and ONE evaluation serves N=100 watchers (evaluation count
    asserted: one per event batch, not one per watcher or per event)."""
    import deepflow_tpu.aggregator.window as window_mod
    from deepflow_tpu.aggregator.pipeline import L4Pipeline, PipelineConfig
    from deepflow_tpu.aggregator.window import WindowConfig
    from deepflow_tpu.feeder import (
        FeederConfig,
        FeederRuntime,
        PipelineFeedSink,
        encode_flowbatch_frames,
    )
    from deepflow_tpu.ingest.queues import PyOverwriteQueue
    from deepflow_tpu.integration.dfstats import (
        DEEPFLOW_SYSTEM_DB,
        DEEPFLOW_SYSTEM_TABLE,
        LIVE_METRIC_FLOW_BYTES,
        PipelineLiveSource,
        ensure_system_table,
    )
    from deepflow_tpu.querier.alerts import AlertEngine, AlertRule
    from deepflow_tpu.querier.events import QueryEventBus, WindowClosed
    from deepflow_tpu.querier.live import LiveRegistry, QueryResultCache
    from deepflow_tpu.querier.promql import query_range
    from deepflow_tpu.querier.subscribe import SubscriptionManager
    from deepflow_tpu.storage.store import ColumnarStore

    counts = {"n": 0}
    real_fetch = window_mod.host_fetch

    def counting_fetch(x):
        counts["n"] += 1
        return real_fetch(x)

    monkeypatch.setattr(window_mod, "host_fetch", counting_fetch)

    def build(name, bus):
        pipe = L4Pipeline(PipelineConfig(
            window=WindowConfig(capacity=1 << 12, stats_ring=4,
                                min_snapshot_interval=3600.0),
            batch_size=256, bucket_sizes=(64, 128, 256),
        ))
        q = PyOverwriteQueue(1 << 10)
        feeder = FeederRuntime(
            [q], PipelineFeedSink(pipe),
            FeederConfig(frames_per_queue=8, snapshot_interval_pumps=4),
            name=name, event_bus=bus,
        )
        return pipe, q, feeder

    bus = QueryEventBus(name="gate")
    pipe_b, q_b, feeder_b = build("gate_base", None)
    pipe_p, q_p, feeder_p = build("gate_push", bus)

    # the push stack: cache + subscriptions (100 watchers, ONE query)
    # + an alert rule, all wired to the bus the feeder publishes on
    store = ColumnarStore()
    ensure_system_table(store)
    reg = LiveRegistry()
    reg.register(DEEPFLOW_SYSTEM_DB, DEEPFLOW_SYSTEM_TABLE,
                 PipelineLiveSource(pipe_p))
    cache = QueryResultCache(max_entries=64)
    cache.attach_bus(bus)
    subs = SubscriptionManager(store, live=reg, cache=cache, bus=bus,
                               name="gate")
    N = 100
    SPAN, STEP = 8, 1
    got: list[list] = [[] for _ in range(N)]
    for i in range(N):
        sub, _ = subs.subscribe_promql(
            LIVE_METRIC_FLOW_BYTES, span_s=SPAN, step=STEP,
            db=DEEPFLOW_SYSTEM_DB, table=DEEPFLOW_SYSTEM_TABLE,
            callback=(lambda r, s, _i=i: got[_i].append(r)),
        )
    alerts = AlertEngine(store, live=reg, bus=bus, name="gate",
                         log_sink=False)
    alerts.add_rule(AlertRule(
        name="hot", query=LIVE_METRIC_FLOW_BYTES, comparator=">",
        threshold=0.0, for_s=0,
    ))
    table_batches = {"n": 0}
    bus.subscribe(
        lambda evs: table_batches.__setitem__(
            "n", table_batches["n"] + int(any(
                getattr(e, "table", None) == DEEPFLOW_SYSTEM_TABLE
                for e in evs
            ))
        ),
        name="counter",
    )

    from deepflow_tpu.ingest.replay import SyntheticFlowGen

    gen_a = SyntheticFlowGen(num_tuples=200, seed=41)
    gen_b = SyntheticFlowGen(num_tuples=200, seed=41)
    t0 = 1_700_000_000

    def feed(gen, q, feeder, t):
        fb = gen.flow_batch(128, t)
        for fr in encode_flowbatch_frames(fb, max_rows_per_frame=64):
            q.put(fr)
        return feeder.pump()

    # warmup OUTSIDE the measurement: compile the buckets and take the
    # one rate-limited snapshot each side (the generation every push
    # evaluation then reads at zero device cost)
    for t in (t0, t0 + 1):
        feed(gen_b, q_b, feeder_b, t)
        feed(gen_a, q_p, feeder_p, t)
    pipe_b.snapshot_open(force=True)
    pipe_p.snapshot_open(force=True)

    B = 16
    fetches = {"base": 0, "push": 0}
    out = {"base": [], "push": []}
    for i in range(B):
        t = t0 + 2 + i // 4
        before = counts["n"]
        out["base"] += [d.tags.tobytes() for d in feed(gen_b, q_b, feeder_b, t)]
        fetches["base"] += counts["n"] - before
        before = counts["n"]
        out["push"] += [d.tags.tobytes() for d in feed(gen_a, q_p, feeder_p, t)]
        fetches["push"] += counts["n"] - before
    before = counts["n"]
    out["base"] += [d.tags.tobytes() for d in feeder_b.flush()]
    fetches["base"] += counts["n"] - before
    before = counts["n"]
    out["push"] += [d.tags.tobytes() for d in feeder_p.flush()]
    fetches["push"] += counts["n"] - before

    # THE acceptance: ingest-attributable fetches IDENTICAL with the
    # whole push stack active, stream bit-identical, zero retraces
    assert fetches["push"] == fetches["base"], fetches
    assert out["push"] == out["base"]
    for pipe in (pipe_b, pipe_p):
        assert pipe.get_counters()["jit_retraces"] == 0
    assert feeder_p.get_counters()["events_published"] > 0

    # one evaluation per event batch — NOT per watcher, NOT per event
    sc = subs.get_counters()
    assert sc["evals"] == table_batches["n"] > 0, (sc, table_batches)
    assert sc["deliveries"] == sc["evals"] * N
    assert sc["amplification_x100"] == N * 100
    assert sc["eval_errors"] == 0 and sc["watcher_errors"] == 0
    assert alerts.get_counters()["evals"] == table_batches["n"]
    # push invalidation carried the cache: every drop was event-driven
    cc = cache.get_counters()
    assert cc["push_invalidations"] > 0
    assert cc["stale_invalidations"] == 0

    # non-trivial serve pin (post-run, outside the budget measurement):
    # a fresh snapshot generation + close event pushes OPEN-window
    # partials to every watcher, bit-exact vs a fresh pull evaluation
    pipe_p.snapshot_open(force=True)
    t_last = t0 + 2 + (B - 1) // 4
    bus.publish(WindowClosed(DEEPFLOW_SYSTEM_DB, DEEPFLOW_SYSTEM_TABLE, t_last))
    now = t_last + 1
    fresh = query_range(
        store, LIVE_METRIC_FLOW_BYTES, now - SPAN, now, STEP,
        db=DEEPFLOW_SYSTEM_DB, table=DEEPFLOW_SYSTEM_TABLE, live=reg,
        cache=False,
    )
    assert fresh, "open windows invisible — nothing was actually served"
    assert all(len(g) == sub.evals for g in got)
    assert got[0][-1] == fresh == got[N - 1][-1]
    assert alerts.state("hot") == "firing"  # the rule saw the live rows


def test_profiling_budget(monkeypatch):
    """ISSUE 12 gate: the device profiling plane is ALWAYS-ON and adds
    ZERO fetches — a §14-shaped feeder run with an aggressive profiling
    consumer (ledger walks + span quantiles + a ticking collector
    dogfooding tpu_hbm_*/span-p99 rows + ProfileSnapshot events every
    batch) spends EXACTLY the same ingest-attributable host fetches as
    the passive twin, produces bit-identical flushed output, and never
    retraces the fused step. Every profile read itself is fetch-free;
    the census's XLA analysis (which may compile via the AOT path) runs
    once post-measurement and must not disturb fetch accounting or the
    dispatch cache either. Wall time on a loaded CPU is not a
    deterministic gate; fetch parity is."""
    import deepflow_tpu.aggregator.window as window_mod
    from deepflow_tpu.aggregator.pipeline import L4Pipeline, PipelineConfig
    from deepflow_tpu.aggregator.window import WindowConfig
    from deepflow_tpu.feeder import (
        FeederConfig,
        FeederRuntime,
        PipelineFeedSink,
        encode_flowbatch_frames,
    )
    from deepflow_tpu.ingest.queues import PyOverwriteQueue
    from deepflow_tpu.integration.dfstats import system_sink
    from deepflow_tpu.profiling import default_ledger, profile_tick_sink
    from deepflow_tpu.querier.events import ProfileSnapshot, QueryEventBus
    from deepflow_tpu.storage.store import ColumnarStore
    from deepflow_tpu.utils.stats import StatsCollector

    counts = {"n": 0}
    real_fetch = window_mod.host_fetch

    def counting_fetch(x):
        counts["n"] += 1
        return real_fetch(x)

    monkeypatch.setattr(window_mod, "host_fetch", counting_fetch)

    def build(name):
        pipe = L4Pipeline(PipelineConfig(
            window=WindowConfig(capacity=1 << 12, stats_ring=4),
            batch_size=256, bucket_sizes=(64, 128, 256),
        ))
        q = PyOverwriteQueue(1 << 10)
        feeder = FeederRuntime(
            [q], PipelineFeedSink(pipe), FeederConfig(frames_per_queue=8),
            name=name,
        )
        return pipe, q, feeder

    pipe_b, q_b, feeder_b = build("prof_base")
    pipe_p, q_p, feeder_p = build("prof_on")

    # the profiling consumer stack on the profiled side: a collector
    # dogfooding the ledger + the pipeline's span quantiles into a
    # store, publishing ProfileSnapshot per tick on a bus
    store = ColumnarStore()
    bus = QueryEventBus(name="prof_gate")
    events: list = []
    bus.subscribe(lambda evs: events.extend(
        e for e in evs if isinstance(e, ProfileSnapshot)), name="obs")
    col = StatsCollector()
    col.register("tpu_hbm", default_ledger)
    col.register("tpu_pipeline_spans", pipe_p.tracer)
    col.add_sink(system_sink(store))
    col.add_sink(profile_tick_sink(bus))

    from deepflow_tpu.ingest.replay import SyntheticFlowGen

    gen_a = SyntheticFlowGen(num_tuples=200, seed=43)
    gen_b = SyntheticFlowGen(num_tuples=200, seed=43)
    t0 = 1_700_000_000

    def feed(gen, q, feeder, t):
        fb = gen.flow_batch(128, t)
        for fr in encode_flowbatch_frames(fb, max_rows_per_frame=64):
            q.put(fr)
        return feeder.pump()

    # warmup outside the measurement (bucket compiles)
    for t in (t0, t0 + 1):
        feed(gen_b, q_b, feeder_b, t)
        feed(gen_a, q_p, feeder_p, t)

    B = 16
    fetches = {"base": 0, "prof": 0}
    out = {"base": [], "prof": []}
    for i in range(B):
        t = t0 + 2 + i // 4
        before = counts["n"]
        out["base"] += [d.tags.tobytes() for d in feed(gen_b, q_b, feeder_b, t)]
        fetches["base"] += counts["n"] - before
        before = counts["n"]
        out["prof"] += [d.tags.tobytes() for d in feed(gen_a, q_p, feeder_p, t)]
        fetches["prof"] += counts["n"] - before
        # the aggressive profiling cadence: EVERY batch walks the
        # ledger + span quantiles and every 4th runs a full dogfood
        # tick (store insert + ProfileSnapshot publish) — all of it
        # must be fetch-free
        before = counts["n"]
        _ = default_ledger.get_counters()
        _ = pipe_p.tracer.get_counters()
        _ = pipe_p.profile_snapshot()  # no analysis — the hot-path face
        if (i + 1) % 4 == 0:
            col.tick(now=t)
        assert counts["n"] == before, "profile read performed a device fetch"
    before = counts["n"]
    out["base"] += [d.tags.tobytes() for d in feeder_b.flush()]
    fetches["base"] += counts["n"] - before
    before = counts["n"]
    out["prof"] += [d.tags.tobytes() for d in feeder_p.flush()]
    fetches["prof"] += counts["n"] - before

    # THE acceptance: fetch parity with profiling always-on + an active
    # consumer, bit-identical stream, zero fused-step retraces
    assert fetches["prof"] == fetches["base"], fetches
    assert out["prof"] == out["base"]
    for pipe in (pipe_b, pipe_p):
        assert pipe.get_counters()["jit_retraces"] == 0
    assert len(events) == B // 4  # one ProfileSnapshot per tick, data-timed
    assert all(e.time is not None for e in events)
    assert store.row_count("deepflow_system", "deepflow_system") > 0

    # post-measurement: the census analysis (AOT lower+compile) must
    # not touch the fetch seam or the dispatch cache
    before = counts["n"]
    rows = [r for r in pipe_p.profile_snapshot(analyze=True)["census"]
            if r.get("flops")]
    assert rows, "census analysis produced no rows"
    assert counts["n"] == before
    assert pipe_p.get_counters()["jit_retraces"] == 0


def test_lineage_tracing_budget(monkeypatch):
    """ISSUE 13 gate: the window lineage plane + freshness lanes add
    ZERO device fetches — a §14-shaped feeder run with the full lineage
    stack attached (receiver-admission stamps, pump/journal context,
    staged-upload + dispatch binding, advance/flush hops, freshness
    lags, an aggressive consumer draining spans + lag lanes every
    batch) spends EXACTLY the same ingest-attributable host fetches as
    the passive twin, produces a bit-identical flushed stream, and
    never retraces the fused step. Every lineage read (drain_spans,
    freshness counters, exemplars, live tree assembly) is itself
    fetch-free — device-side hops are DERIVED from the counter blocks
    the drain already fetches, the r14/r16 gate convention."""
    import deepflow_tpu.aggregator.window as window_mod
    from deepflow_tpu.aggregator.pipeline import L4Pipeline, PipelineConfig
    from deepflow_tpu.aggregator.window import WindowConfig
    from deepflow_tpu.feeder import (
        FeederConfig,
        FeederRuntime,
        PipelineFeedSink,
        encode_flowbatch_frames,
    )
    from deepflow_tpu.ingest.queues import PyOverwriteQueue
    from deepflow_tpu.tracing.lineage import FreshnessTracker, LineageTracker

    counts = {"n": 0}
    real_fetch = window_mod.host_fetch

    def counting_fetch(x):
        counts["n"] += 1
        return real_fetch(x)

    monkeypatch.setattr(window_mod, "host_fetch", counting_fetch)

    def build(name, lineage):
        pipe = L4Pipeline(PipelineConfig(
            window=WindowConfig(capacity=1 << 12, stats_ring=4),
            batch_size=256, bucket_sizes=(64, 128, 256),
        ))
        if lineage is not None:
            pipe.attach_lineage(lineage)
        q = PyOverwriteQueue(1 << 10)
        feeder = FeederRuntime(
            [q], PipelineFeedSink(pipe), FeederConfig(frames_per_queue=8),
            name=name, lineage=lineage,
        )
        return pipe, q, feeder

    fresh = FreshnessTracker(autoregister=False)
    lin = LineageTracker("tpu.pipeline", 1, freshness=fresh,
                         name="lineage_gate")
    pipe_b, q_b, feeder_b = build("lin_base", None)
    pipe_t, q_t, feeder_t = build("lin_traced", lin)

    from deepflow_tpu.ingest.replay import SyntheticFlowGen

    gen_a = SyntheticFlowGen(num_tuples=200, seed=47)
    gen_b = SyntheticFlowGen(num_tuples=200, seed=47)
    t0 = 1_700_000_000

    def feed(gen, q, feeder, t):
        fb = gen.flow_batch(128, t)
        for fr in encode_flowbatch_frames(fb, max_rows_per_frame=64):
            q.put(fr)
        return feeder.pump()

    # warmup outside the measurement (bucket compiles)
    for t in (t0, t0 + 1):
        feed(gen_b, q_b, feeder_b, t)
        feed(gen_a, q_t, feeder_t, t)

    B = 16
    fetches = {"base": 0, "traced": 0}
    out = {"base": [], "traced": []}
    for i in range(B):
        t = t0 + 2 + i // 4
        before = counts["n"]
        out["base"] += [d.tags.tobytes() for d in feed(gen_b, q_b, feeder_b, t)]
        fetches["base"] += counts["n"] - before
        before = counts["n"]
        out["traced"] += [
            d.tags.tobytes() for d in feed(gen_a, q_t, feeder_t, t)
        ]
        fetches["traced"] += counts["n"] - before
        # the aggressive consumer: EVERY batch drains spans, reads the
        # lag lanes + exemplars and assembles the live tree — all of it
        # must be fetch-free
        before = counts["n"]
        _ = lin.drain_spans()
        _ = fresh.get_counters()
        _ = fresh.exemplars()
        _ = lin.get_counters()
        _ = lin.assemble(t)
        assert counts["n"] == before, "lineage read performed a device fetch"
    before = counts["n"]
    out["base"] += [d.tags.tobytes() for d in feeder_b.flush()]
    fetches["base"] += counts["n"] - before
    before = counts["n"]
    out["traced"] += [d.tags.tobytes() for d in feeder_t.flush()]
    fetches["traced"] += counts["n"] - before

    # THE acceptance: fetch parity with the lineage plane attached and
    # an active consumer, bit-identical stream, zero fused-step
    # retraces (the r14/r16 convention)
    assert fetches["traced"] == fetches["base"], fetches
    assert out["traced"] == out["base"]
    for pipe in (pipe_b, pipe_t):
        assert pipe.get_counters()["jit_retraces"] == 0
    # the plane actually recorded: hops + lags exist for real windows
    c = lin.get_counters()
    assert c["hops_recorded"] > 0 and c["windows_tracked"] > 0
    assert fresh.get_counters().get("1s.flush_samples", 0) > 0
    lin.close()


def test_one_pass_sketch_budget(monkeypatch):
    """ISSUE 17 gate: the one-pass sketch fold changes the dispatch's
    SORT count, never its transfer or retrace behavior. With sketch +
    top-K + cascade all ON and a K=4 counter ring: every ingest stays
    inside the ≤3-fetch budget, total fetches stay strictly below one
    per batch, the fused step never retraces, the flushed stream AND
    every closed sketch block are bit-identical with the shared sort ON
    vs OFF — and the census's static sort attribution shows the point:
    ≤1 sort/dispatch shared, strictly fewer than the multi-sort
    oracle's."""
    import threading

    import deepflow_tpu.aggregator.window as window_mod
    from deepflow_tpu.aggregator.cascade import CascadeConfig
    from deepflow_tpu.aggregator.pipeline import L4Pipeline, PipelineConfig
    from deepflow_tpu.aggregator.sketchplane import SketchConfig
    from deepflow_tpu.aggregator.window import WindowConfig
    from deepflow_tpu.datamodel.batch import FlowBatch
    from deepflow_tpu.ops.histogram import LogHistSpec

    counts = {"n": 0}
    real_fetch = window_mod.host_fetch
    # count MAIN-THREAD fetches only: the conftest's mesh_harness
    # prewarm runs its in-parent oracle (its own ShardedWindowManagers)
    # on a daemon thread through this same seam, concurrently with the
    # first half of the suite — its fetches are not this test's budget
    main = threading.get_ident()

    def counting_fetch(x):
        if threading.get_ident() == main:
            counts["n"] += 1
        return real_fetch(x)

    monkeypatch.setattr(window_mod, "host_fetch", counting_fetch)

    sk = SketchConfig(
        num_groups=4, hll_precision=7, cms_depth=2, cms_width=256,
        hist=LogHistSpec(bins=32, vmin=1.0, gamma=1.3),
        topk_rows=2, topk_cols=64, pending=8,
    )
    casc = CascadeConfig(intervals=(60,), capacity=1 << 12)
    K = 4
    t0 = 1_700_000_040

    sorts = {}
    fetch_tot = {}
    out = {}
    blocks = {}
    B = 16
    for mode in ("1", "0"):
        # build-time knob capture: the pipeline's fused step closures
        # read DEEPFLOW_SHARED_SORT when constructed
        monkeypatch.setenv("DEEPFLOW_SHARED_SORT", mode)
        pipe = L4Pipeline(PipelineConfig(
            window=WindowConfig(capacity=1 << 12, stats_ring=K, sketch=sk,
                                cascade=casc),
            batch_size=256,
        ))
        gen = SyntheticFlowGen(num_tuples=200, seed=59)
        before_tot = counts["n"]
        docs = []
        for i in range(B):
            before = counts["n"]
            docs += [d.tags.tobytes() for d in pipe.ingest(
                FlowBatch.from_records(gen.records(128, t0 + (i // 4) * 25)))]
            assert counts["n"] - before <= SYNC_BUDGET, (mode, i)
        fetch_tot[mode] = counts["n"] - before_tot
        advances = pipe.get_counters()["window_advances"]
        assert advances >= 2
        assert fetch_tot[mode] <= -(-B // K) + 2 * advances, mode
        assert fetch_tot[mode] < B, (
            f"{fetch_tot[mode]} fetches for {B} batches — ring defeated")
        c = pipe.get_counters()
        assert c["sketch_rows"] > 0 and c["cascade_rows"] > 0
        assert c["jit_retraces"] == 0, c
        out[mode] = docs
        blocks[mode] = [
            (b.window, b.n_updates, b.hll.tobytes(), b.cms.tobytes(),
             b.hist.tobytes(), b.tk_votes.tobytes(), b.tk_hi.tobytes(),
             b.tk_lo.tobytes(), b.tk_ida.tobytes(), b.tk_idb.tobytes())
            for b in pipe.pop_closed_sketches()
        ]
        assert blocks[mode], "advances closed windows but no blocks drained"
        rows = [r for r in pipe.telemetry()["profile"]["census"]
                if r["step"] == "fused_step" and "sorts" in r]
        assert rows, "census never attributed sorts to the fused step"
        sorts[mode] = max(r["sorts"] for r in rows)

    # bit-identical output either way — the sort is shared, not skipped
    assert out["1"] == out["0"]
    assert blocks["1"] == blocks["0"]
    # identical transfer budget — the rewrite is sort-count-only
    assert fetch_tot["1"] == fetch_tot["0"], fetch_tot
    # THE acceptance: ≤1 sort per fused dispatch, strictly fewer than
    # the multi-sort oracle's (2 phases × topk_rows + per-batch sorts)
    assert sorts["1"] <= 1 < sorts["0"], sorts


def test_one_pass_sketch_budget_sharded(monkeypatch):
    """The sharded twin: the shared sort holds the same ≤3-fetch budget
    on the pmapped plane, with per-window blocks bit-identical to the
    multi-sort oracle's across 1- and 2-device meshes."""
    import threading

    import deepflow_tpu.aggregator.window as window_mod
    from deepflow_tpu.ops.histogram import LogHistSpec
    from deepflow_tpu.parallel.mesh import make_mesh
    from deepflow_tpu.parallel.sharded import (
        ShardedConfig,
        ShardedPipeline,
        ShardedWindowManager,
    )

    counts = {"n": 0}
    real_fetch = window_mod.host_fetch
    # main-thread fetches only (the conftest prewarm's in-parent oracle
    # shares this seam from a daemon thread — see the gate above)
    main = threading.get_ident()

    def counting_fetch(x):
        if threading.get_ident() == main:
            counts["n"] += 1
        return real_fetch(x)

    monkeypatch.setattr(window_mod, "host_fetch", counting_fetch)

    cfg = ShardedConfig(
        capacity_per_device=1 << 10, num_services=8, hll_precision=7,
        cms_depth=2, cms_width=256,
        hist=LogHistSpec(bins=32, vmin=1.0, gamma=1.3),
        topk_cols=64, sketch_pending=8,
    )
    t0 = 1_700_000_000
    for n_dev in (1, 2):
        gen = SyntheticFlowGen(num_tuples=300, seed=61)
        batches = [gen.flow_batch(128, t) for t in
                   (t0, t0 + 1, t0 + 1, t0 + 4)]
        got = {}
        for mode in ("1", "0"):
            monkeypatch.setenv("DEEPFLOW_SHARED_SORT", mode)
            wm = ShardedWindowManager(ShardedPipeline(make_mesh(n_dev), cfg))
            for fb in batches:
                before = counts["n"]
                wm.ingest(fb.tags, fb.meters, fb.valid)
                assert counts["n"] - before <= SYNC_BUDGET, (n_dev, mode)
            wm.drain()
            got[mode] = [
                (b.window, b.n_updates, b.hll.tobytes(), b.cms.tobytes(),
                 b.hist.tobytes(), b.tk_votes.tobytes(), b.tk_hi.tobytes())
                for b in sorted(wm.pop_closed_sketches(),
                                key=lambda b: b.window)
            ]
            assert got[mode], (n_dev, mode)
        assert got["1"] == got["0"], f"sharded {n_dev}-dev blocks diverged"


# ---------------------------------------------------------------------------
# bench.py never hides the device: off the chip, or on any backend
# failure, it exits non-zero with a parseable record that says why.

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_bench(extra_env: dict, timeout: int) -> tuple[int, dict]:
    env = {**os.environ, **extra_env}
    proc = subprocess.run(
        [sys.executable, "bench.py"],
        cwd=_REPO, env=env, capture_output=True, text=True, timeout=timeout,
    )
    lines = [ln for ln in proc.stdout.strip().splitlines() if ln.strip()]
    assert lines, f"bench.py printed nothing (stderr: {proc.stderr[-500:]})"
    return proc.returncode, json.loads(lines[-1])


_TINY_BENCH = {"BENCH_BATCH": "4096", "BENCH_UNIQUE_CAP": "1024",
               "BENCH_CYCLES": "1"}


def test_bench_refuses_the_cpu_platform():
    """A CPU run must never be read as a chip number: with only the CPU
    platform bench.py exits non-zero, reports value 0 and names the
    device it found."""
    rc, rec = _run_bench({"JAX_PLATFORMS": "cpu", **_TINY_BENCH}, timeout=300)
    assert rc != 0
    assert rec["metric"] == "flow_records_per_sec_per_chip"
    assert rec["value"] == 0.0
    assert rec["device"]["platform"] == "cpu"
    assert "CPU" in rec["error"]


def test_bench_exits_nonzero_on_backend_failure():
    """When the backend cannot initialize, bench.py exits non-zero with
    a parseable record (value 0 and the error), not a raw traceback —
    and never 0."""
    rc, rec = _run_bench({"JAX_PLATFORMS": "nonexistent", **_TINY_BENCH},
                         timeout=300)
    assert rc != 0
    assert rec["metric"] == "flow_records_per_sec_per_chip"
    assert rec["value"] == 0.0
    assert rec["device"] is None
    assert rec["error"]


# ---------------------------------------------------------------------------
# Multi-host mesh (ISSUE 14): the per-HOST budget under the REAL
# 2-process jax.distributed harness. Each process's window_mod.host_fetch
# seam is shimmed inside the subprocess (tests/mesh_harness.run_host):
# per-ingest fetch budget, ZERO data-path transfers touching a
# non-local device, and zero fused-step retraces after the buckets
# compile. Shares the memoized harness run with test_mesh_multiproc.


def test_mesh_per_host_fetch_budget_and_locality():
    import mesh_harness as mh

    for res in mh.mesh2_result():
        f = res["fetch"]
        assert f["n_ingests"] > 0
        # the single-host contract, unchanged at fleet scale: at most
        # 3 host fetches per ingest (steady-state ingests fetch 0; an
        # advancing drain pays its bundled 2 + snapshot/advance slack)
        assert f["n"] <= 3 * f["n_ingests"], f
        # the data path NEVER crosses hosts: every fetched array lives
        # exclusively on this process's local devices
        assert f["nonlocal"] == 0, f
        # steady same-shape ingest over the bucket set adds no pjit
        # cache entries once warm
        assert f["retraces"] == 0, f
        # every per-group fetch count is host-local accounting that
        # sums into the shim's total
        per_group = sum(
            rec["host_fetches"] for rec in res["groups"].values()
        )
        assert per_group == f["n"]


def test_rebalance_budget():
    """Elastic topology (ISSUE 15): the per-host budgets HOLD ACROSS A
    REBALANCE. On both the old and the new owner of the moved group: at
    most 3 host fetches per ingest, zero non-local transfers, and zero
    fused-step retraces — the adopted group's manager compiles its
    bucket set once during its first post-adopt steps and never again,
    and releasing a group must not invalidate the remaining group's
    caches. Steady state after the flip matches before: misroutes STOP
    incrementing once agents re-route (no lingering handoff traffic)
    and the wire drains to empty. Shares the memoized rebalance run
    with tests/test_mesh_rebalance.py."""
    import mesh_harness as mh

    r = mh.mesh_rebalance_result()
    for res in (r["p0"], r["p1"]):
        f = res["fetch"]
        assert f["n_ingests"] > 0
        assert f["n"] <= 3 * f["n_ingests"], f
        assert f["nonlocal"] == 0, f
        # zero retraces across the handover: every group's pjit cache
        # is the same size at the end of the run as it was once warm
        # (for the moved group on the old owner: at release)
        for g, (steady, end) in res["caches"].items():
            assert steady is not None, (res["process_index"], g)
            assert end == steady, (res["process_index"], g, steady, end)
    # no lingering handoff traffic: the misroute count the old owner
    # sampled at the last forwarded step IS the final count — once the
    # agents re-routed, nothing misroutes again — and the sender's
    # queue fully drained (flush() fenced every forwarded step)
    p1 = r["p1"]
    assert p1["misrouted_after_forwarding"] is not None
    assert p1["receiver"]["frames_misrouted"] == p1["misrouted_after_forwarding"]
    assert p1["sender"]["queue_depth"] == 0
    assert p1["sender"]["shed_frames"] == 0
    # the new owner serves the moved group at full budget post-flip:
    # its own receiver never misroutes and nothing rotted in the hold
    assert r["p0"]["receiver"]["frames_misrouted"] == 0
    assert r["p0"]["receiver"]["frames_held_dropped"] == 0


def test_fleet_export_budget(monkeypatch):
    """ISSUE 18 gate: the fleet wire sink is HOST-SIDE ONLY — a
    §14-shaped feeder run with the pipeline registered on a collector
    whose tick drives a live FleetSink → FleetAggregator TCP loop every
    batch spends EXACTLY the same ingest-attributable host fetches as
    the passive twin, produces a bit-identical flushed stream, and
    never retraces the fused step. Frame assembly + encode + send all
    read already-maintained host state (the r14/r16 gate convention)."""
    import deepflow_tpu.aggregator.window as window_mod
    from deepflow_tpu.aggregator.pipeline import L4Pipeline, PipelineConfig
    from deepflow_tpu.aggregator.window import WindowConfig
    from deepflow_tpu.feeder import (
        FeederConfig,
        FeederRuntime,
        PipelineFeedSink,
        encode_flowbatch_frames,
    )
    from deepflow_tpu.fleet import FleetAggregator, FleetExporter, FleetSink
    from deepflow_tpu.ingest.queues import PyOverwriteQueue
    from deepflow_tpu.tracing.lineage import FreshnessTracker
    from deepflow_tpu.utils.stats import StatsCollector

    counts = {"n": 0}
    real_fetch = window_mod.host_fetch

    def counting_fetch(x):
        counts["n"] += 1
        return real_fetch(x)

    monkeypatch.setattr(window_mod, "host_fetch", counting_fetch)

    def build(name):
        pipe = L4Pipeline(PipelineConfig(
            window=WindowConfig(capacity=1 << 12, stats_ring=4),
            batch_size=256, bucket_sizes=(64, 128, 256),
        ))
        q = PyOverwriteQueue(1 << 10)
        feeder = FeederRuntime(
            [q], PipelineFeedSink(pipe), FeederConfig(frames_per_queue=8),
            name=name,
        )
        return pipe, q, feeder

    pipe_b, q_b, feeder_b = build("fleet_base")
    pipe_t, q_t, feeder_t = build("fleet_traced")

    # the instrumented twin's full export loop: pipeline + freshness
    # registered on a PRIVATE collector, ticked every batch into a
    # FleetSink wired to a real aggregator listener over TCP
    agg = FleetAggregator(expiry_s=3600.0, autoregister=False)
    agg.start()
    col = StatsCollector()
    fresh = FreshnessTracker(autoregister=False)
    col.register("tpu_pipeline", pipe_t, group="0")
    exporter = FleetExporter(
        "gate-host", group="0", collector=col,
        hist_faces={"fresh": fresh},
    )
    sink = FleetSink(agg.endpoint(), exporter)
    col.add_sink(sink)

    gen_a = SyntheticFlowGen(num_tuples=200, seed=47)
    gen_b = SyntheticFlowGen(num_tuples=200, seed=47)
    t0 = 1_700_000_000

    def feed(gen, q, feeder, t):
        fb = gen.flow_batch(128, t)
        for fr in encode_flowbatch_frames(fb, max_rows_per_frame=64):
            q.put(fr)
        return feeder.pump()

    try:
        for t in (t0, t0 + 1):  # warmup outside the measurement
            feed(gen_b, q_b, feeder_b, t)
            feed(gen_a, q_t, feeder_t, t)

        B = 16
        fetches = {"base": 0, "traced": 0}
        out = {"base": [], "traced": []}
        for i in range(B):
            t = t0 + 2 + i // 4
            before = counts["n"]
            out["base"] += [
                d.tags.tobytes() for d in feed(gen_b, q_b, feeder_b, t)
            ]
            fetches["base"] += counts["n"] - before
            before = counts["n"]
            out["traced"] += [
                d.tags.tobytes() for d in feed(gen_a, q_t, feeder_t, t)
            ]
            fetches["traced"] += counts["n"] - before
            # the export tick: sample the pipeline face, build + encode
            # + queue one wire frame — ZERO device fetches
            before = counts["n"]
            col.tick(float(t))
            assert counts["n"] == before, "fleet export performed a fetch"
        before = counts["n"]
        out["base"] += [d.tags.tobytes() for d in feeder_b.flush()]
        fetches["base"] += counts["n"] - before
        before = counts["n"]
        out["traced"] += [d.tags.tobytes() for d in feeder_t.flush()]
        fetches["traced"] += counts["n"] - before

        # THE acceptance: fetch parity with the fleet sink live,
        # bit-identical stream, zero fused-step retraces
        assert fetches["traced"] == fetches["base"], fetches
        assert out["traced"] == out["base"]
        for pipe in (pipe_b, pipe_t):
            assert pipe.get_counters()["jit_retraces"] == 0
        assert col.n_source_errors == 0 and col.n_sink_errors == 0

        # the loop really exported: every tick shipped one frame and
        # the aggregator merged the pipeline's counters fleet-side
        assert sink.flush(30)
        sc = sink.get_counters()
        assert sc["frames_sent"] == B and sc["send_errors"] == 0
        deadline = time.time() + 30
        while agg.counters["frames_rx"] < B and time.time() < deadline:
            time.sleep(0.01)
        assert agg.counters["frames_rx"] == B
        merged = agg.merged_counters()
        assert any(k.startswith("tpu_pipeline{") for k in merged), merged
    finally:
        sink.close()
        agg.stop()


def test_wire_fanout_budget(monkeypatch):
    """ISSUE 19 gate: with 100 LIVE wire watchers (plus one real SSE
    client streaming off the RestServer), ingest-attributable host
    fetches are IDENTICAL to the passive baseline — wire fan-out is
    queue pops off the ONE shared evaluation, never extra device (or
    even store) reads — the flushed stream stays bit-identical, the
    fused step never retraces, and the evaluation count equals EVENT
    BATCHES, not watchers."""
    import threading
    import urllib.request
    from types import SimpleNamespace

    import deepflow_tpu.aggregator.window as window_mod
    from deepflow_tpu.aggregator.pipeline import L4Pipeline, PipelineConfig
    from deepflow_tpu.aggregator.window import WindowConfig
    from deepflow_tpu.controller.rest import RestServer
    from deepflow_tpu.feeder import (
        FeederConfig,
        FeederRuntime,
        PipelineFeedSink,
        encode_flowbatch_frames,
    )
    from deepflow_tpu.ingest.queues import PyOverwriteQueue
    from deepflow_tpu.integration.dfstats import (
        DEEPFLOW_SYSTEM_DB,
        DEEPFLOW_SYSTEM_TABLE,
        LIVE_METRIC_FLOW_BYTES,
        PipelineLiveSource,
        ensure_system_table,
    )
    from deepflow_tpu.querier.events import QueryEventBus, WindowClosed
    from deepflow_tpu.querier.live import LiveRegistry, QueryResultCache
    from deepflow_tpu.querier.promql import query_range
    from deepflow_tpu.querier.subscribe import SubscriptionManager
    from deepflow_tpu.storage.store import ColumnarStore
    from deepflow_tpu.wire import WireHub

    counts = {"n": 0}
    real_fetch = window_mod.host_fetch

    def counting_fetch(x):
        counts["n"] += 1
        return real_fetch(x)

    monkeypatch.setattr(window_mod, "host_fetch", counting_fetch)

    def build(name, bus):
        pipe = L4Pipeline(PipelineConfig(
            window=WindowConfig(capacity=1 << 12, stats_ring=4,
                                min_snapshot_interval=3600.0),
            batch_size=256, bucket_sizes=(64, 128, 256),
        ))
        q = PyOverwriteQueue(1 << 10)
        feeder = FeederRuntime(
            [q], PipelineFeedSink(pipe),
            FeederConfig(frames_per_queue=8, snapshot_interval_pumps=4),
            name=name, event_bus=bus,
        )
        return pipe, q, feeder

    bus = QueryEventBus(name="wgate")
    pipe_b, q_b, feeder_b = build("wgate_base", None)
    pipe_w, q_w, feeder_w = build("wgate_wire", bus)

    store = ColumnarStore()
    ensure_system_table(store)
    reg = LiveRegistry()
    reg.register(DEEPFLOW_SYSTEM_DB, DEEPFLOW_SYSTEM_TABLE,
                 PipelineLiveSource(pipe_w))
    cache = QueryResultCache(max_entries=64)
    cache.attach_bus(bus)
    subs = SubscriptionManager(store, live=reg, cache=cache, bus=bus,
                               name="wgate")
    hub = WireHub(subs, name="wgate")
    rest = RestServer(SimpleNamespace(wire=hub))

    N = 100
    SPAN, STEP = 8, 1
    conns = [
        hub.open_stream(promql=LIVE_METRIC_FLOW_BYTES, span_s=SPAN,
                        step=STEP, db=DEEPFLOW_SYSTEM_DB,
                        table=DEEPFLOW_SYSTEM_TABLE, maxlen=256)
        for _ in range(N)
    ]
    # ...and one REAL streaming client, through the actual HTTP lane
    sse_events: list = []

    def sse():
        url = (f"http://127.0.0.1:{rest.port}/v1/watch?"
               f"promql={LIVE_METRIC_FLOW_BYTES}&span_s={SPAN}"
               f"&db={DEEPFLOW_SYSTEM_DB}&table={DEEPFLOW_SYSTEM_TABLE}"
               f"&heartbeat_s=0.2")
        try:
            with urllib.request.urlopen(url, timeout=60) as r:
                for raw in r:
                    if raw.startswith(b"data: "):
                        sse_events.append(__import__("json").loads(raw[6:]))
        except OSError:
            pass

    sse_thread = threading.Thread(target=sse, daemon=True)
    sse_thread.start()
    deadline = time.time() + 30
    while (hub.get_counters()["connections_open"] < N + 1
           and time.time() < deadline):
        time.sleep(0.01)
    assert hub.get_counters()["sse_connections"] == 1
    # 101 watchers, ONE query → ONE subscription
    assert len(subs.list_subscriptions()) == 1

    table_batches = {"n": 0}
    bus.subscribe(
        lambda evs: table_batches.__setitem__(
            "n", table_batches["n"] + int(any(
                getattr(e, "table", None) == DEEPFLOW_SYSTEM_TABLE
                for e in evs
            ))
        ),
        name="counter",
    )

    from deepflow_tpu.ingest.replay import SyntheticFlowGen

    gen_a = SyntheticFlowGen(num_tuples=200, seed=43)
    gen_b = SyntheticFlowGen(num_tuples=200, seed=43)
    t0 = 1_700_000_000

    def feed(gen, q, feeder, t):
        fb = gen.flow_batch(128, t)
        for fr in encode_flowbatch_frames(fb, max_rows_per_frame=64):
            q.put(fr)
        return feeder.pump()

    for t in (t0, t0 + 1):
        feed(gen_b, q_b, feeder_b, t)
        feed(gen_a, q_w, feeder_w, t)
    pipe_b.snapshot_open(force=True)
    pipe_w.snapshot_open(force=True)

    B = 16
    fetches = {"base": 0, "wire": 0}
    out = {"base": [], "wire": []}
    for i in range(B):
        t = t0 + 2 + i // 4
        before = counts["n"]
        out["base"] += [d.tags.tobytes() for d in feed(gen_b, q_b, feeder_b, t)]
        fetches["base"] += counts["n"] - before
        before = counts["n"]
        out["wire"] += [d.tags.tobytes() for d in feed(gen_a, q_w, feeder_w, t)]
        fetches["wire"] += counts["n"] - before
    before = counts["n"]
    out["base"] += [d.tags.tobytes() for d in feeder_b.flush()]
    fetches["base"] += counts["n"] - before
    before = counts["n"]
    out["wire"] += [d.tags.tobytes() for d in feeder_w.flush()]
    fetches["wire"] += counts["n"] - before

    # THE acceptance: 101 live wire clients cost the ingest path ZERO
    assert fetches["wire"] == fetches["base"], fetches
    assert out["wire"] == out["base"]
    for pipe in (pipe_b, pipe_w):
        assert pipe.get_counters()["jit_retraces"] == 0

    # evals == event batches — NOT 101× (per watcher), NOT per event
    sc = subs.get_counters()
    assert sc["evals"] == table_batches["n"] > 0, (sc, table_batches)
    assert sc["deliveries"] == sc["evals"] * (N + 1)
    assert sc["eval_errors"] == 0 and sc["watcher_errors"] == 0

    # post-run, outside the budget: the final close event reaches every
    # lane bit-exact — in-process queues AND the real SSE stream
    pipe_w.snapshot_open(force=True)
    t_last = t0 + 2 + (B - 1) // 4
    bus.publish(WindowClosed(DEEPFLOW_SYSTEM_DB, DEEPFLOW_SYSTEM_TABLE,
                             t_last))
    now = t_last + 1
    fresh = query_range(
        store, LIVE_METRIC_FLOW_BYTES, now - SPAN, now, STEP,
        db=DEEPFLOW_SYSTEM_DB, table=DEEPFLOW_SYSTEM_TABLE, live=reg,
        cache=False,
    )
    assert fresh, "open windows invisible — nothing was actually served"
    import json as _json

    norm = _json.loads(_json.dumps(fresh, default=str))
    for conn in conns:
        last = item = conn.poll()
        while item is not None:
            last, item = item, conn.poll()
        assert _json.loads(_json.dumps(last, default=str)) == norm
        assert conn.watcher.dropped == 0
    deadline = time.time() + 30
    while not sse_events and time.time() < deadline:
        time.sleep(0.01)
    assert sse_events and sse_events[-1] == norm
    for conn in conns:
        hub.close_conn(conn)
    hub.close()
    rest.stop()
    subs.close()
    sse_thread.join(timeout=10)
