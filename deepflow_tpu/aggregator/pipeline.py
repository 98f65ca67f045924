"""Flow-metrics rollup pipelines (L4 network + L7 application) — the
end-to-end device slice.

Composes: fanout (fill_l4_stats / fill_l7_stats) → key fingerprint →
windowed stash merge → flush → DocBatch emission. This is the TPU
replacement for the reference chains QuadrupleGenerator::inject_flow →
Collector::collect_l4 → Stash::add → flush_stats and
L7QuadrupleGenerator → L7Collector::collect_l7 (SURVEY §3.1), collapsed
into one jit step per batch plus a host-driven window controller.

Since r7 the whole per-batch slice — optional pre-reduce, fanout,
fingerprint, late-arrival gate, window bookkeeping, ring append — runs
as ONE jitted call per batch (`RollupPipeline._build_step`): the ~37 tag
columns upload as a single packed [T, N] matrix (every pytree leaf is a
separate transfer with its own fixed cost) and the only per-batch
download is the 5-scalar stats vector the window controller reads
(window.py module docstring has the full sync budget).
"""

from __future__ import annotations

import dataclasses
import itertools
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..datamodel.batch import DocBatch, FlowBatch, StagingBuffer, StagingRing
from ..datamodel.code import DOC_KEY_PACK, RAW_TAG_PACK, DocumentFlag, pack_tag_words
from ..datamodel.schema import APP_METER, FLOW_METER, TAG_SCHEMA, MeterSchema
from ..ops.hashing import fingerprint64_words
from ..utils.spans import SPAN_INGEST_STAGE, JitCacheMonitor
from ..utils.stats import register_countable
from .fanout import FANOUT_LANES, FanoutConfig, fanout_l4, fanout_l7
from .stash import _append_impl
from .window import (
    FlushedWindow,
    WindowConfig,
    WindowManager,
    batch_counter_block,
    sketch_inputs_from_columns,
    sketch_span_bounds,
)
from .sketchplane import SketchConfig, sketch_plane_step

#: census service-key ordinal — one per pipeline instance, so profile
#: attribution never aliases across concurrently-live pipelines
_PIPELINE_SEQ = itertools.count(1)

_KEY_COLS = np.nonzero(TAG_SCHEMA.key_mask)[0].astype(np.int32)
# DOC_KEY_PACK covers exactly the TAG_SCHEMA key columns — drift between
# the schema and the packing widths table fails at import, not at runtime.
assert set(DOC_KEY_PACK.field_names()) == {
    f.name for f in TAG_SCHEMA.fields if f.key
}, "DOC_KEY_WIDTHS out of sync with TAG_SCHEMA key columns"


def _doc_fingerprint(doc_tags, with_excess: bool = False):
    """(hi, lo[, excess]) over a [T, N] doc tag matrix via the packed-word
    plan: the key columns are bin-packed into ~22 u32 words built once
    (datamodel/code.py), and both murmur seeds fold the words instead
    of 32 raw columns. Row extraction from the
    column-major matrix is free (contiguous [N] slices).

    With `with_excess`, also returns the packing-guard excess word
    ([N] u32, zero for rows whose tag values honor the declared
    DOC_KEY_WIDTHS) so the fused step can count contract violations in
    the device counter block."""
    cols = {f: doc_tags[TAG_SCHEMA.index(f)] for f in DOC_KEY_PACK.field_names()}
    words = pack_tag_words(cols, DOC_KEY_PACK, jnp)
    hi, lo = fingerprint64_words(words)
    if with_excess:
        # the excess word is the last packed word whenever the plan has
        # narrow fields (pack_tag_words contract)
        excess = words[-1] if DOC_KEY_PACK.packed else jnp.zeros_like(hi)
        return hi, lo, excess
    return hi, lo


# What a row of the sketch plane is (PR 33): the fused step and
# `make_ingest_step` update the plane from the batch's rows as they came,
# ahead of the pre-reduce, so `sketch_rows`, a block's `n_updates` and its
# histogram count RECORDS. Before, they counted pre-reduced rows (one a
# flow a batch), which depended on where the feeder cut its batches. A
# deployment that promises one update a record asks for this by name.
SKETCH_ROWS_ARE_RECORDS = True


def batch_prereduce(tags, meters, valid, interval, cap, sum_cols, max_cols):
    """Batch-local pre-reduce BEFORE fanout: group raw rows by their
    full tag fingerprint (incl. timestamp) and reduce meters. Exact:
    identical raw tag rows produce identical doc rows in every fanout
    lane, and the lanes' meter transforms are column permutations/
    copies, which commute with per-column sum/max. This
    collapses the dup factor (10k-tuple rollup workloads repeat keys
    within a batch) so the fold sorts ~1 row/record instead of 4.
    Returns (tags, meters [cap, M], valid, dropped) — rows beyond `cap`
    unique keys are shed; callers count `dropped` (newest-shed
    stance)."""
    from ..ops.segment import groupby_reduce

    names = sorted(tags)
    cols = [jnp.asarray(tags[k], jnp.uint32) for k in names]
    tags_t = jnp.stack(cols)
    # fingerprint the PACKED words, not the raw columns: ~23 fold rounds
    # instead of 37 per seed, built once for both seeds (the
    # [T, N] stack stays only as the groupby payload — r5 bisect V2
    # already showed hashing through it wastes a materialization)
    hi, lo = fingerprint64_words(pack_tag_words(tags, RAW_TAG_PACK, jnp))
    slot = jnp.asarray(tags["timestamp"], jnp.uint32) // jnp.uint32(interval)
    g = groupby_reduce(
        slot, hi, lo, tags_t, meters, valid,
        sum_cols, max_cols, out_capacity=cap,
    )
    r_tags = {k: g.tags[i] for i, k in enumerate(names)}
    dropped = jnp.maximum(g.num_segments - cap, 0)
    return r_tags, jnp.transpose(g.meters), g.seg_valid, dropped


def make_ingest_step(fanout_config: FanoutConfig, interval: int = 1, app: bool = False,
                     batch_unique_cap: int | None = None, fold_mode: str = "full",
                     sketch_config: "SketchConfig | None" = None, delay: int = 2):
    """Build the pure device step pair: FlowBatch columns → stash.

    Returns (append, fold):

      (stash, acc) = append(stash, acc, offset, tags, meters, valid)
      (stash, acc) = fold(stash, acc)

    With `sketch_config` set (ISSUE 8), append grows the per-window
    sketch plane in the same traced step:

      (stash, acc, sk) = append(stash, acc, offset, sk, tags, meters,
                                valid, start_window)

    where `sk` is a sketchplane.SketchState and `start_window` the
    host's open-span gate (the plane derives its close bound from the
    batch itself, exactly like the window managers — `delay` must match
    the manager's).

    `append` runs per batch: fanout → fingerprint → one
    dynamic_update_slice into the accumulator ring at `offset` (a traced
    scalar the host advances). `fold` is the amortized sort+reduce over
    [S + A] rows, fired by the host every accum_batches batches and
    before every window flush — this is what replaced the per-batch
    re-sort of the whole stash (see AccumState, stash.py). The benchmark
    times the (append ×K, fold ×1) cycle; RollupPipeline drives the same
    functions from WindowManager. `app` selects the L7 path (fanout_l7 +
    APP_METER) — fanout and meter schema are coupled by construction so
    they cannot drift apart. `fold_mode` ("full" | "merge") picks the
    fold kernel: the full [S+A] re-sort or the incremental rank-merge
    (stash.py — bit-exact, fold-sort work scales with the ring instead
    of the stash).
    """
    fanout_fn = fanout_l7 if app else fanout_l4
    meter_schema = APP_METER if app else FLOW_METER
    sum_cols = tuple(int(i) for i in np.nonzero(meter_schema.sum_mask)[0])
    max_cols = tuple(int(i) for i in np.nonzero(meter_schema.max_mask)[0])
    sum_cols_np = np.asarray(sum_cols, np.int32)
    max_cols_np = np.asarray(max_cols, np.int32)

    from ..ops.segment import SENTINEL_SLOT
    from .stash import (
        _append_impl,
        _fold_impl,
        _merge_fold_impl,
        check_fold_mode,
    )

    check_fold_mode(fold_mode)

    def _base_append(stash, acc, offset, tags, meters, valid):
        if batch_unique_cap is not None:
            tags, meters, valid, dropped = batch_prereduce(
                tags, meters, valid, interval, batch_unique_cap,
                sum_cols_np, max_cols_np,
            )
            stash = dataclasses.replace(
                stash, dropped_overflow=stash.dropped_overflow + dropped
            )
        doc_tags, doc_meters, ts, doc_valid = fanout_fn(tags, meters, valid, fanout_config)
        hi, lo = _doc_fingerprint(doc_tags)  # packed key words, no key_mat take
        window = (ts // jnp.uint32(interval)).astype(jnp.uint32)
        acc = _append_impl(acc, window, hi, lo, doc_tags, doc_meters, doc_valid, offset)
        return stash, acc

    if sketch_config is None:
        append = _base_append
    else:
        meter_ix = meter_schema.index
        # one-pass knob captured at BUILD time (ISSUE 17): the caller
        # jits this closure fresh per plane instance, so capturing here
        # pins the path for the closure's whole life — a retrace on a
        # new bucket shape cannot silently flip it mid-stream
        from ..ops.segment import _use_shared_sort

        shared_sort = _use_shared_sort()

        def append(stash, acc, offset, sk, tags, meters, valid, start_window):
            # the plane takes the rows as they came, ahead of the
            # pre-reduce: a record counts once (RollupPipeline._build_step)
            ts = jnp.asarray(tags["timestamp"], jnp.uint32)
            base_w, close_w = sketch_span_bounds(
                start_window, ts, valid, interval=interval, delay=delay
            )
            inp = sketch_inputs_from_columns(
                tags, meters, sk.hll.shape[1], meter_ix
            )
            sk = sketch_plane_step(
                sk, sketch_config.hist,
                window=ts // jnp.uint32(interval), valid=valid,
                base_w=base_w, close_w=close_w,
                shared_sort=shared_sort, **inp,
            )
            stash, acc = _base_append(stash, acc, offset, tags, meters, valid)
            return stash, acc, sk

    if fold_mode == "merge":
        def fold(stash, acc):
            new_stash, new_acc, _fold_lanes = _merge_fold_impl(
                stash, acc, jnp.uint32(SENTINEL_SLOT), sum_cols, max_cols
            )
            return new_stash, new_acc
    else:
        def fold(stash, acc):
            return _fold_impl(stash, acc, sum_cols, max_cols)

    return append, fold


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    fanout: FanoutConfig = FanoutConfig()
    window: WindowConfig = WindowConfig()
    batch_size: int = 4096  # static pad size for flow batches
    # batch-local pre-reduce before fanout (batch_prereduce); None = off
    batch_unique_cap: int | None = None
    # Shape buckets (ISSUE 4): when set, each ingested batch pads to the
    # smallest bucket ≥ its row count instead of to batch_size. The
    # fused step compiles ONCE per bucket (JitCacheMonitor's
    # expected_compiles budget covers them — anything beyond is still a
    # retrace), so mixed-size feeder traffic never recompiles in steady
    # state. Must be sorted unique; batches larger than max(buckets) are
    # a caller error (the feeder slices to max(buckets)).
    bucket_sizes: tuple[int, ...] | None = None

    def __post_init__(self):
        if self.bucket_sizes is not None:
            bs = tuple(self.bucket_sizes)
            if not bs or list(bs) != sorted(set(bs)) or bs[0] <= 0:
                raise ValueError(
                    f"bucket_sizes must be sorted unique positive ints, got {bs}"
                )


# The name from before the L7 pipeline; tests and the verify skill use it.
L4PipelineConfig = PipelineConfig


@dataclasses.dataclass
class StagedBatch:
    """A bucket-padded batch whose device upload has been dispatched
    (RollupPipeline.stage) but whose fused step has not yet run — the
    double-buffer unit the feeder runtime holds one of."""

    tag_mat: jnp.ndarray  # [T, B] u32 packed tag matrix (device)
    meters: jnp.ndarray  # [B, M] f32 (device)
    valid: jnp.ndarray  # [B] bool (device)
    padded_rows: int  # B — the bucket this batch padded to
    # the host buffer the three arrays were uploaded from: not written
    # again until this batch's step has run (StagingRing.acquire)
    source: StagingBuffer
    # lineage plane (ISSUE 13): the batch's host-side event-time bounds
    # (valid rows only), captured in stage() BEFORE upload — t_max <
    # t_min means "not computed" (no lineage attached)
    t_min: int = 0
    t_max: int = -1


class RollupPipeline:
    """Single-granularity (e.g. 1s) rollup pipeline: fanout → fingerprint
    → windowed stash merge, with host-driven window flushes.

    The per-batch device slice is ONE jitted call (see module docstring);
    WindowManager.ingest_step drives the window protocol around it."""

    fanout_fn = staticmethod(fanout_l4)
    meter_schema: MeterSchema = FLOW_METER

    def __init__(self, config: PipelineConfig = PipelineConfig()):
        self.config = config
        self.wm = WindowManager(config.window, TAG_SCHEMA, self.meter_schema)
        self.tracer = self.wm.tracer  # host stage spans (utils/spans)
        # retrace gate for the fused step: one expected compile per
        # shape bucket; any growth beyond that is a real retrace
        self._jit = JitCacheMonitor(
            expected_compiles=len(config.bucket_sizes or ()) or 1
        )
        self._tag_names: tuple | None = None  # fixed on first batch
        self._step = None
        # the host memory every batch is written into, once, in the
        # upload's layout — by stage(FlowBatch) and by the feeder's sink
        self.staging = StagingRing(self.meter_schema.num_fields)
        # closed-window sketch blocks (ISSUE 8): DocBatch is the exact
        # writer format, so blocks accumulate here for the sketch sink
        # (integration/dfstats.sketch_system_sink) / querier instead.
        # BOUNDED: a deployment that never drains pop_closed_sketches
        # must not leak a block per window forever — beyond the cap the
        # oldest block drops and is counted (same drop-oldest-counted
        # stance as the device pending buffer).
        self.closed_sketches: list = []
        self.max_held_sketches = 512
        if config.window.sketch is not None:
            # the cap is one of bytes too (PR 33): at a deployment's
            # widths a block is tens of megabytes unpacked (43 MB at 512
            # groups, p = 14), and 512 of them would be 22 GB of host
            # memory; ~2.4 GB of blocks, and never fewer than four
            block_bytes = 4 * config.window.sketch.block_width
            self.max_held_sketches = max(4, min(512, (2 << 30) // block_bytes))
        self.sketch_blocks_dropped = 0
        # rollup-cascade tier outputs (ISSUE 9): merged tier sketch
        # blocks held for the sketch sink, same bounded stance
        self.closed_tier_sketches: list = []
        self.tier_sketch_blocks_dropped = 0
        if config.window.cascade is not None:
            # the server's datasource listing reflects which tiers this
            # cascade serves (dfctl datasource / REST /v1/datasources);
            # lazy import — the aggregator must not hard-depend on the
            # server layer
            from ..server.datasource import register_cascade_tiers

            register_cascade_tiers(
                self.meter_schema.name, config.window.cascade.intervals,
                owner=self,
            )
        # self-telemetry registration (reference RegisterCountable stance:
        # every component registers at construction; weakly held, so
        # short-lived pipelines deregister themselves). Handles kept so
        # close() can deregister eagerly (ISSUE 12 lifecycle).
        self._stats_srcs = [
            register_countable(
                "tpu_pipeline", self,
                kind=type(self).__name__,
                interval=f"{config.window.interval}s",
            ),
            register_countable(
                "tpu_pipeline_spans", self.tracer,
                kind=type(self).__name__,
                interval=f"{config.window.interval}s",
            ),
        ]
        # device profiling plane (ISSUE 12): the step-cost census — per
        # bucket shape, the fused step's abstract args + compile wall
        # time captured at first dispatch (metadata only; the expensive
        # XLA analysis runs lazily on the profile pull). The HBM ledger
        # registration lives on the WindowManager, which owns the
        # planes — the pipeline's Profilable face just delegates.
        from ..profiling.census import default_census

        self._census = default_census
        # per-INSTANCE service key: two concurrently-live pipelines of
        # the same class/interval may have different fused-step
        # signatures (sketch on/off), and a shared key would silently
        # attribute one pipeline's shapes/analysis to the other
        self._census_service = (
            f"{type(self).__name__}:{config.window.interval}s"
            f"#{next(_PIPELINE_SEQ)}"
        )
        # window lineage plane (ISSUE 13): opt-in via attach_lineage
        self._lineage = None

    def attach_lineage(self, tracker) -> None:
        """Wire a tracing/lineage.LineageTracker through this pipeline:
        stage() stamps the upload hop and captures the batch's host
        event-time bounds, ingest_staged binds them to the dispatch, and
        the wrapped WindowManager records advance/flush/tier/snapshot
        hops + freshness lags. Host wall stamps only — zero new device
        fetches (CI-gated)."""
        self._lineage = tracker
        self.wm.attach_lineage(tracker)

    def _build_step(self, names: tuple):
        """One fused device step per batch: [T, N] packed tags → stats +
        ring append. `names` orders the packed matrix rows (static)."""
        m = self.meter_schema
        sum_cols = np.nonzero(m.sum_mask)[0].astype(np.int32)
        max_cols = np.nonzero(m.max_mask)[0].astype(np.int32)
        cap_u = self.config.batch_unique_cap
        interval = self.config.window.interval
        delay = self.config.window.delay
        fanout_cfg = self.config.fanout
        fanout_fn = self.fanout_fn
        sketch_cfg = self.config.window.sketch
        m_ix = m.index

        # one-pass knob captured at step-BUILD time (ISSUE 17) — same
        # retrace-stability stance as make_ingest_step's sketch append
        from ..ops.segment import _use_shared_sort

        shared_sort = _use_shared_sort()

        def _sketch(sk, tags, meters, valid, start_window):
            """Per-window plane update from the RAW flow rows (ISSUE 8):
            pre-fanout, so a flow counts once — doc-lane replication
            would multiply every CMS/top-K weight by FANOUT_LANES — and
            since PR 33 ahead of the pre-reduce too, so a RECORD counts
            once: rows the pre-reduce had merged gave the histogram one
            mean latency and `n_updates` one row for however many of a
            flow's records one batch happened to hold, which made both
            depend on where the feeder cut its batches. Traced into the
            same fused step — zero extra dispatches or fetches."""
            ts = jnp.asarray(tags["timestamp"], jnp.uint32)
            base_w, close_w = sketch_span_bounds(
                start_window, ts, valid, interval=interval, delay=delay
            )
            inp = sketch_inputs_from_columns(tags, meters, sk.hll.shape[1], m_ix)
            return sketch_plane_step(
                sk, sketch_cfg.hist,
                window=ts // jnp.uint32(interval), valid=valid,
                base_w=base_w, close_w=close_w,
                shared_sort=shared_sort, **inp,
            )

        def step(acc, offset, start_window, stash_valid, stash_evict,
                 feeder_shed, fold_lanes, casc_lanes, snap_lanes, sk,
                 tag_mat, meters, valid):
            # the stages carry names (jax.named_scope: metadata only) so
            # a device profile can say which one an op belongs to
            tags = {k: tag_mat[i] for i, k in enumerate(names)}
            aux = None
            if sk is not None:
                with jax.named_scope("step.sketch"):
                    sk = _sketch(sk, tags, meters, valid, start_window)
            if cap_u is not None:
                with jax.named_scope("step.prereduce"):
                    tags, meters, valid, aux = batch_prereduce(
                        tags, meters, valid, interval, cap_u, sum_cols, max_cols
                    )
            with jax.named_scope("step.fanout"):
                doc_tags, doc_meters, ts, doc_valid = fanout_fn(
                    tags, meters, valid, fanout_cfg
                )
            with jax.named_scope("step.fingerprint"):
                hi, lo, excess = _doc_fingerprint(doc_tags, with_excess=True)
                # packing-guard hits: doc rows whose tag values overflow the
                # declared DOC_KEY_WIDTHS contract (datamodel/code.py)
                excess_hits = jnp.sum((excess != 0) & doc_valid)
            with jax.named_scope("step.counter_block"):
                gated, window, block = batch_counter_block(
                    ts, doc_valid, start_window, interval, aux=aux,
                    excess_hits=excess_hits, stash_valid=stash_valid,
                    stash_evictions=stash_evict, ring_fill=offset,
                    feeder_shed=feeder_shed, fold_rows=fold_lanes[0],
                    fold_blocks=fold_lanes[1],
                    sketch_rows=None if sk is None else sk.rows,
                    sketch_shed=None if sk is None else sk.shed,
                    cascade_rows=casc_lanes[0], cascade_shed=casc_lanes[1],
                    snapshot_reads=snap_lanes[0], snapshot_bytes=snap_lanes[1],
                )
            with jax.named_scope("step.append"):
                acc = _append_impl(
                    acc, window, hi, lo, doc_tags, doc_meters, gated, offset
                )
            if sk is None:
                return acc, block
            return acc, block, sk

        if sketch_cfg is None:
            # keep the sketch-free signature (and jit cache key) identical
            # to the pre-ISSUE-8 step: None is not a pytree leaf we want
            # in the dispatch path
            def step_plain(acc, offset, start_window, stash_valid, stash_evict,
                           feeder_shed, fold_lanes, casc_lanes, snap_lanes,
                           tag_mat, meters, valid):
                return step(acc, offset, start_window, stash_valid,
                            stash_evict, feeder_shed, fold_lanes, casc_lanes,
                            snap_lanes, None, tag_mat, meters, valid)

            return jax.jit(step_plain, donate_argnums=(0,))
        return jax.jit(step, donate_argnums=(0, 9))

    def _pad_target(self, rows: int) -> int:
        """Static pad size for a batch of `rows`: the smallest bucket
        that fits (bucketed mode) or the fixed batch_size."""
        buckets = self.config.bucket_sizes
        if not buckets:
            return self.config.batch_size
        for b in buckets:
            if rows <= b:
                return b
        raise ValueError(
            f"batch of {rows} rows exceeds the largest shape bucket "
            f"{buckets[-1]}; the feeder must slice to max(bucket_sizes)"
        )

    def stage(self, batch: "FlowBatch | StagingBuffer") -> "StagedBatch | None":
        """START the host→device upload of a staging buffer's tag matrix
        + meters + valid (JAX device puts are async) WITHOUT dispatching
        the fused step. The feeder runtime hands over the buffer it
        assembled and stages batch i+1 while batch i's dispatch is still
        in flight — the upload overlaps compute, mirroring async_drain
        on the output side. A FlowBatch is first written into a buffer
        of its bucket by the same writer, as its one chunk. Returns None
        for an all-padding batch."""
        with self.tracer.span(SPAN_INGEST_STAGE):
            if not isinstance(batch, StagingBuffer):
                buf = self.staging.acquire(
                    self._pad_target(batch.size),
                    self._tag_names or tuple(sorted(batch.tags)),
                )
                buf.write(batch.tags, batch.meters, batch.valid)
                buf.finish()
                batch = buf
            return self._stage(batch)

    def _stage(self, buf: StagingBuffer) -> "StagedBatch | None":
        if buf.n_valid == 0:
            return None
        lin = self._lineage
        t_min, t_max, s0 = 0, -1, 0.0
        if lin is not None:
            # host event-time bounds BEFORE the upload (numpy — free);
            # the dispatch binds them to the lineage window span
            ts = buf.tag_mat[buf.names.index("timestamp"), : buf.rows]
            if buf.n_valid < buf.rows:
                ts = ts[buf.valid[: buf.rows]]
            t_min, t_max = int(ts.min()), int(ts.max())
            s0 = lin.clock()
        if self._tag_names is None:
            self._tag_names = buf.names
            self._step = self._build_step(self._tag_names)
            self._jit.attach(self._step)
        elif buf.names != self._tag_names:
            raise ValueError(
                f"staging buffer's tag rows {buf.names} are not the order "
                f"the fused step was built with {self._tag_names}"
            )
        # three uploads: the ~37 tag columns travel as ONE matrix
        tag_mat = jnp.asarray(buf.tag_mat)
        meters = jnp.asarray(buf.meters)
        valid = jnp.asarray(buf.valid)
        self.wm.bytes_uploaded += (
            tag_mat.nbytes + meters.nbytes + valid.nbytes
        )
        if lin is not None:
            lin.note_stage(s0)
        staged = StagedBatch(tag_mat=tag_mat, meters=meters, valid=valid,
                             padded_rows=buf.bucket, source=buf,
                             t_min=t_min, t_max=t_max)
        buf.uploaded(staged)
        return staged

    def ingest(self, batch: FlowBatch, feeder_shed: int = 0) -> list[DocBatch]:
        """Feed one decoded flow batch; returns any closed windows."""
        staged = self.stage(batch)
        if staged is None:
            # idle heartbeat: skip the upload/append (it would burn ring
            # rows and force empty folds); still settle any deferred
            # async-drain buffers so closed windows aren't held up
            return self._convert_flushed(self.wm.settle())
        return self.ingest_staged(staged, feeder_shed=feeder_shed)

    def ingest_staged(
        self, staged: "StagedBatch", feeder_shed: int = 0
    ) -> list[DocBatch]:
        """Dispatch the fused step for an already-staged batch."""
        # with the pre-reduce on, the append writes a FANOUT_LANES×cap_u
        # block (static groupby output) regardless of batch rows
        cap_u = self.config.batch_unique_cap
        rows = FANOUT_LANES * (cap_u or staged.padded_rows)
        # size the accumulator ring for the LARGEST bucket up front so a
        # small first bucket doesn't build a ring a later one replaces
        max_rows = FANOUT_LANES * (
            cap_u
            or (self.config.bucket_sizes or (self.config.batch_size,))[-1]
        )
        shed = jnp.uint32(feeder_shed)

        def dispatch(acc, offset, start_window):
            # stash lanes read at dispatch time (post any fold) — device
            # handles, no transfer; they fill the counter block's
            # occupancy/eviction/fold/cascade lanes inside the same
            # fused call. The sketch plane rides the same dispatch when on.
            st = self.wm.state
            casc = self.wm._cascade_lanes()
            snap = self.wm._snapshot_lanes()
            args = (acc, offset, start_window, st.valid, st.dropped_overflow,
                    shed, self.wm._fold_lanes_dev, casc, snap)
            if self.wm.sk is not None:
                args = args + (self.wm.sk,)
            args = args + (staged.tag_mat, staged.meters, staged.valid)
            # census capture (ISSUE 12): first dispatch of a bucket shape
            # records the abstract arg shapes BEFORE the step consumes
            # its donated buffers — ShapeDtypeStructs only, no compile,
            # no transfer, once per bucket
            if not self._census.seen(self._census_service, "fused_step",
                                     staged.padded_rows):
                self._census.observe(
                    self._census_service, "fused_step", staged.padded_rows,
                    self._step, args,
                )
            out = self._step(*args)
            # the counter block is an output nothing donates: ready once
            # this step has run, i.e. has read the staged arrays
            staged.source.dispatched(out[1])
            return out

        window_span = None
        if self._lineage is not None and staged.t_max >= staged.t_min:
            iv = self.config.window.interval
            window_span = (staged.t_min // iv, staged.t_max // iv)
        compiles0 = sum(self._jit.poll())
        t0 = time.perf_counter()
        flushed = self.wm.ingest_step(
            dispatch, rows, ring_rows=max_rows, window_span=window_span
        )
        wall_s = time.perf_counter() - t0
        if sum(self._jit.poll()) > compiles0:
            # the monitor saw the pjit cache grow on this dispatch: the
            # wall time above IS the bucket's compile + first-execute
            # tax — attribute it (steady-state dispatches skip this)
            self._census.note_compile(
                self._census_service, "fused_step", staged.padded_rows, wall_s
            )
        return self._convert_flushed(flushed)

    def drain(self) -> list[DocBatch]:
        return self._convert_flushed(self.wm.flush_all())

    def snapshot_open(self, *, force: bool = False):
        """Live read plane (ISSUE 10): pull a read-only OpenSnapshot of
        the open window span (rate-limited; see
        WindowManager.snapshot_open). Ingest is untouched — the read
        happens between dispatches and costs 2 pull-path fetches."""
        return self.wm.snapshot_open(force=force)

    def _convert_flushed(self, flushed: list[FlushedWindow]) -> list[DocBatch]:
        """FlushedWindows → writer DocBatches; closed sketch blocks are
        captured into `closed_sketches` (sketch-only windows — every
        exact row shed — produce a block but no DocBatch)."""
        from .sketchplane import hold_blocks

        out = []
        blocks = []
        for f in flushed:
            if f.sketches is not None:
                blocks.append(f.sketches)
            if f.count:
                out.append(self._to_docbatch(f))
        self.sketch_blocks_dropped += hold_blocks(
            self.closed_sketches, blocks, self.max_held_sketches
        )
        return out

    def pop_closed_sketches(self) -> list:
        """Drain the accumulated WindowSketchBlocks (oldest first)."""
        out, self.closed_sketches = self.closed_sketches, []
        return out

    def pop_tier_windows(self) -> list[FlushedWindow]:
        """Drain the cascade's closed tier windows (ISSUE 9) — raw
        FlushedWindow form with tier ≥ 1 and the tier interval set."""
        return self.wm.pop_tier_windows()

    def pop_tier_docbatches(self) -> list[tuple[int, DocBatch]]:
        """Closed cascade tier windows as (tier_interval_s, DocBatch)
        pairs, oldest first. Merged tier sketch blocks are captured
        into `closed_tier_sketches` (a sketch-only tier window — every
        exact row shed — contributes a block but no DocBatch, the same
        coverage contract as tier 0)."""
        from .sketchplane import hold_blocks

        out = []
        blocks = []
        for f in self.wm.pop_tier_windows():
            if f.sketches is not None:
                blocks.append(f.sketches)
            if f.count:
                out.append((f.interval, self._to_docbatch(f)))
        self.tier_sketch_blocks_dropped += hold_blocks(
            self.closed_tier_sketches, blocks, self.max_held_sketches
        )
        return out

    def _to_docbatch(self, f: FlushedWindow) -> DocBatch:
        ts = np.full((f.count,), f.start_time, dtype=np.uint32)
        return DocBatch(
            tags=f.tags,
            meters=f.meters,
            timestamp=ts,
            valid=np.ones((f.count,), dtype=bool),
            tag_schema=TAG_SCHEMA,
            meter_schema=self.meter_schema,
        )

    def get_counters(self) -> dict:
        """Countable face: fetch-free (see WindowManager.get_counters)
        plus the fused-step jit compile/retrace counters."""
        out = self.wm.get_counters()
        out.update(self._jit.get_counters())
        # held closed-window blocks + the drop-oldest overflow counter:
        # a rising dropped count means nobody drains pop_closed_sketches
        out["sketch_blocks_held"] = len(self.closed_sketches)
        out["sketch_blocks_dropped"] = self.sketch_blocks_dropped
        out["tier_sketch_blocks_held"] = len(self.closed_tier_sketches)
        out["tier_sketch_blocks_dropped"] = self.tier_sketch_blocks_dropped
        return out

    # -- device profiling plane (ISSUE 12) --------------------------------
    def device_planes(self) -> dict:
        """Profilable face — delegates to the owning WindowManager (the
        manager holds every device plane; it is also the one registered
        on the HBM ledger, so the flat tpu_hbm_* lanes never
        double-count a pipeline-wrapped manager)."""
        return self.wm.device_planes()

    def profile_snapshot(self, *, analyze: bool = False) -> dict:
        """The per-pipeline profile record: per-plane HBM bytes + the
        step census rows for THIS pipeline's fused step. With
        `analyze=True` the census rows carry the XLA cost/memory
        analysis (may compile — pull path only)."""
        from ..profiling.ledger import plane_bytes

        return {
            "hbm_bytes": {
                name: plane_bytes(tree)[0]
                for name, tree in self.wm.device_planes().items()
            },
            "census": [
                r for r in self._census.snapshot(analyze=analyze)
                if r["service"] == self._census_service
            ],
        }

    def close(self) -> None:
        """Eager profiling/telemetry teardown (weakrefs would get there
        eventually; close() makes it synchronous): the manager leaves
        the HBM ledger and the pipeline's Countable rows stop."""
        self.wm.close()
        from ..utils.stats import default_collector

        for src in self._stats_srcs:
            default_collector.deregister(src)

    def telemetry(self) -> dict:
        """JSON-able snapshot: the counter-block-backed
        counters plus the per-stage span summary and, since ISSUE 12, the
        device profile record (per-plane HBM bytes + step census, no
        analysis — absence-tolerant consumers)."""
        return {
            "counters": self.get_counters(),
            "spans": self.tracer.summary(),
            "profile": self.profile_snapshot(),
        }

    @property
    def counters(self) -> dict:
        out = dict(self.wm.counters)
        out.update(self._jit.get_counters())
        # legacy name for the CB_PREREDUCE_SHED lane ("prereduce_shed"
        # in get_counters) — kept as the probe-facing alias, computed
        # from the same source so the two cannot drift
        out["prereduce_dropped"] = out.pop("prereduce_shed")
        return out

    @property
    def flags(self) -> DocumentFlag:
        if self.config.window.interval == 1:
            return DocumentFlag.PER_SECOND_METRICS
        return DocumentFlag.NONE


class DualGranularityPipeline:
    """SECOND + MINUTE rollups from one flow stream — ONE device
    dispatch per batch (ISSUE 9).

    The reference runs one SubQuadGen per granularity over the same
    TaggedFlow queue (MetricsType::SECOND|MINUTE,
    quadruple_generator.rs:275-298) and the 1m docs land in the *.1m
    tables that feed the downsampler chain (datasource/handle.go
    1m→1h→1d). The r6–r12 reproduction paid for that with a SECOND full
    device ingest per batch; this shim instead rides the rollup cascade
    (aggregator/cascade.py): the minute series is the 1m tier — a
    device-side fold of closed 1s windows — so dual-granularity traffic
    costs one fused dispatch per batch plus a per-advance tier fold.
    The old double-ingest survives as `DoubleIngestPipeline`, kept as
    the conformance oracle.

    ingest() returns (flags, DocBatch) pairs: PER_SECOND_METRICS for 1s
    windows, NONE for 1m — exactly what encode_docbatch/table routing
    (metrics_tables.route_table_ids) key off. Minute docs for a minute
    M surface once every 1s window of M has closed (≈ delay seconds
    after the minute ends) — earlier than the old minute pipe's
    minute_delay, never later than the data allows.

    One documented semantic change: minute ADMISSION now equals the 1s
    delay — a row too late for its second is too late for its minute
    (the cascade folds closed seconds; there is no separate minute
    gate). The old pipeline admitted rows up to `minute_delay` late
    into 1m docs its own 1s tier had already dropped; `minute_delay`
    stays in the signature for call-site compatibility but only widens
    nothing. Under identical streams whose lateness stays within the 1s
    delay, minute meters are bit-exact vs the double-ingest
    (tests/test_cascade.py pins it, late minute-boundary rows included).
    """

    def __init__(
        self,
        config: PipelineConfig = PipelineConfig(),
        *,
        minute_delay: int = 10,
        app: bool = False,
        cascade: "CascadeConfig | None" = None,
    ):
        from .cascade import CascadeConfig

        cls = L7Pipeline if app else L4Pipeline
        if config.window.cascade is None:
            # the minute tier keeps the 1s stash's capacity — the same
            # per-granularity bound the old minute pipe had
            casc = cascade or CascadeConfig(
                intervals=(60,), capacity=config.window.capacity
            )
            config = dataclasses.replace(
                config,
                window=dataclasses.replace(config.window, cascade=casc),
            )
        elif cascade is not None and cascade != config.window.cascade:
            raise ValueError(
                f"conflicting cascade configs: the window config carries "
                f"{config.window.cascade} but cascade={cascade} was also "
                "passed — silently preferring one would drop tiers"
            )
        if 60 not in config.window.cascade.intervals:
            raise ValueError(
                "DualGranularityPipeline needs a 1m cascade tier (its "
                f"contract IS the minute series); got intervals="
                f"{config.window.cascade.intervals}"
            )
        self.pipe = cls(config)
        self.minute_delay = minute_delay  # compat knob — see docstring
        # coarser-than-minute tier batches (1h…) do NOT ride the
        # (flags, DocBatch) stream: route_table_ids only distinguishes
        # PER_SECOND vs NONE, so emitting them there would land hourly
        # docs in the *_1m tables and double-count the minute series.
        # They accumulate here for store-side writers (the derived
        # network_1h tables the datasource listing names).
        self.coarse_tiers: list[tuple[int, DocBatch]] = []

    # compat alias: telemetry consumers address `.second`
    @property
    def second(self) -> RollupPipeline:
        return self.pipe

    def _tier_docs(self) -> list[tuple[DocumentFlag, DocBatch]]:
        from .sketchplane import hold_blocks

        out = []
        coarse = []
        for interval, db in self.pipe.pop_tier_docbatches():
            if interval == 60:
                out.append((DocumentFlag.NONE, db))
            else:
                coarse.append((interval, db))
        # bounded drop-oldest like every other held buffer — an
        # undrained coarse-tier consumer must not leak a batch per hour
        hold_blocks(self.coarse_tiers, coarse, 512)
        return out

    def ingest(self, batch) -> list[tuple[DocumentFlag, DocBatch]]:
        out = [(self.pipe.flags, db) for db in self.pipe.ingest(batch)]
        return out + self._tier_docs()

    def drain(self) -> list[tuple[DocumentFlag, DocBatch]]:
        out = [(self.pipe.flags, db) for db in self.pipe.drain()]
        return out + self._tier_docs()

    @property
    def counters(self) -> dict:
        c = self.pipe.counters
        # the "minute" face survives for dashboards that key on it; the
        # minute plane is now the cascade's lanes inside the single
        # pipeline's counters
        return {
            "second": c,
            "minute": {
                "cascade_rows": c.get("cascade_rows", 0),
                "cascade_shed": c.get("cascade_shed", 0),
                "tier_windows": c.get("cascade_tier_windows", 0),
            },
        }

    def telemetry(self) -> dict:
        t = self.pipe.telemetry()
        return {"second": t, "minute": {"counters": self.counters["minute"]}}


class DoubleIngestPipeline:
    """The pre-ISSUE-9 dual-granularity implementation: a full second
    device ingest into a parallel minute pipeline. Kept ONLY as the
    conformance oracle (tests/test_cascade.py pins cascade 1m meters
    bit-exact against it) — new code wants `DualGranularityPipeline`,
    which produces the same (flags, DocBatch) stream from one dispatch
    per batch."""

    def __init__(
        self,
        config: PipelineConfig = PipelineConfig(),
        *,
        minute_delay: int = 10,
        app: bool = False,
    ):
        cls = L7Pipeline if app else L4Pipeline
        self.second = cls(config)
        minute_window = dataclasses.replace(
            config.window, interval=60, delay=minute_delay, cascade=None
        )
        self.minute = cls(dataclasses.replace(config, window=minute_window))

    def ingest(self, batch) -> list[tuple[DocumentFlag, DocBatch]]:
        out = [(self.second.flags, db) for db in self.second.ingest(batch)]
        out += [(self.minute.flags, db) for db in self.minute.ingest(batch)]
        return out

    def drain(self) -> list[tuple[DocumentFlag, DocBatch]]:
        out = [(self.second.flags, db) for db in self.second.drain()]
        out += [(self.minute.flags, db) for db in self.minute.drain()]
        return out

    @property
    def counters(self) -> dict:
        return {"second": self.second.counters, "minute": self.minute.counters}

    def telemetry(self) -> dict:
        return {
            "second": self.second.telemetry(),
            "minute": self.minute.telemetry(),
        }


class L4Pipeline(RollupPipeline):
    """network / network_map rollup (FlowMeter docs) — the RollupPipeline
    defaults, named for symmetry with L7Pipeline."""


class L7Pipeline(RollupPipeline):
    """application / application_map rollup (AppMeter docs) — the TPU
    replacement for L7QuadrupleGenerator → L7Collector
    (l7_quadruple_generator.rs:93-253, collector.rs:694-821)."""

    fanout_fn = staticmethod(fanout_l7)
    meter_schema = APP_METER
