"""Sharded pipeline — batch-dim data parallelism + collective sketch merge.

The scaling model (ARCHITECTURE.md §6, SURVEY §2.3):

  * The flow batch is sharded over the flattened (host, chip) mesh — each
    device runs the *identical* fanout→fingerprint→stash-merge step on its
    shard. Exact document stashes never merge across devices (the
    reference's `global_thread_id`/`_tid` tag isolates per-pipeline docs
    the same way, document.rs:293; cross-shard aggregation belongs to the
    query layer).
  * Sketch planes (HLL registers, count-min counters, latency histograms)
    merge *in-network* at window close: `pmax`/`psum` over `chip` (ICI)
    for the per-second view, then over `host` (DCN) for the pod-wide
    1-minute rollup (BASELINE config 5). Merges are elementwise max/add,
    so the collectives are bandwidth-optimal ring reductions XLA schedules
    on ICI without host involvement.
"""

from __future__ import annotations

import dataclasses
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

from .. import chaos
from ..aggregator import window as window_mod
from ..aggregator.fanout import FANOUT_LANES, FanoutConfig
from ..aggregator.pipeline import make_ingest_step
from ..aggregator.sketchplane import (
    PoolConfig,
    SENTINEL_WIN,
    SketchConfig,
    SketchState,
    _drain_impl as _sketch_drain_impl,
    _flatten_open,
    _pool_mode,
    hold_blocks,
    sketch_init,
    sketch_plane_step,
    unpack_drained,
)
from ..aggregator.window import sketch_inputs_from_columns
from ..utils.retry import (
    RetryPolicy,
    decorrelated_rng,
    is_dispatch_transient,
    retry_call,
)
from ..utils.spans import (
    SPAN_FLUSH_DRAIN,
    SPAN_INGEST_DISPATCH,
    SPAN_QUERY_SNAPSHOT,
    SPAN_WINDOW_ADVANCE,
    SPAN_WINDOW_FOLD,
    SpanTracer,
)
from ..utils.stats import register_countable
from ..aggregator.stash import (
    AccumState,
    StashState,
    _fold_counted_impl,
    _merge_fold_impl,
    accum_init,
    check_fold_mode,
    plan_append,
    stash_init,
)
from ..datamodel.schema import FLOW_METER, TAG_SCHEMA
from ..ops.histogram import LogHistSpec


# ISSUE 8 unification: the span-global SketchPlanes (hll/cms/hist reset
# at every close) became the PER-WINDOW plane shared with the
# single-chip path — aggregator/sketchplane.SketchState, one ring slot
# per open window plus a pending buffer of closed packed blocks. The
# old attribute names (.hll/.cms/.hist) survive on the new state (with
# a leading [R] ring dim), and `window_close` still returns the merged
# cross-mesh view, so existing consumers keep working; per-window
# blocks additionally drain through `ShardedWindowManager` at every
# advance (host-merged across devices — exactly the drain pattern the
# exact rows already use).
SketchPlanes = SketchState


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class MergedSketchView:
    """Cross-mesh merged view of the open ring (window_close output)."""

    hll: jnp.ndarray  # [G, m] i32
    cms: jnp.ndarray  # [depth, width] i32
    hist: jnp.ndarray  # [G, B] i32


@dataclasses.dataclass(frozen=True)
class ShardedConfig:
    fanout: FanoutConfig = FanoutConfig()
    interval: int = 1
    capacity_per_device: int = 1 << 12
    num_services: int = 256
    hll_precision: int = 10
    cms_depth: int = 4
    cms_width: int = 1 << 14
    hist: LogHistSpec = LogHistSpec(bins=512, vmin=1.0, gamma=1.04)
    # per-window sketch ring (ISSUE 8): slots for simultaneously-open
    # windows — must cover delay//interval + 2 of the window manager
    # driving this pipeline (validated there, loudly); the default
    # covers delay ≤ 6·interval. Top-K lane shapes and the closed-block
    # pending buffer follow sketchplane.SketchConfig
    sketch_ring: int = 8
    topk_rows: int = 2
    topk_cols: int = 1 << 9
    sketch_pending: int = 16
    # pooled sketch memory (ISSUE 20): when set, each device's sketch
    # ring allocates from a shared compact/wide slot pool instead of
    # per-slot slabs — the sharded twin of SketchConfig.pool (same
    # geometry validation, promotion, and spill accounting per device)
    sketch_pool: PoolConfig | None = None
    # batches accumulated per device between sort+reduce folds
    # (same amortization as WindowConfig.accum_batches)
    accum_batches: int = 8
    # per-device batch-local pre-reduce before fanout;
    # None = off. Bounds each batch's unique raw keys; overflow is shed
    # and counted in the device stash's overflow counter.
    batch_unique_cap: int | None = None
    # fold strategy (ISSUE 5) — same contract as WindowConfig.fold_mode:
    # "full" re-sorts the [S+A] concat per device, "merge" rank-merges
    # the sorted accumulator against the standing stash order and
    # span-bounds the advance fold. Bit-exact (tests/test_merge_fold.py).
    fold_mode: str = "full"
    # multi-resolution rollup cascade (ISSUE 9): coarser-tier intervals
    # maintained PER DEVICE as folds of that device's closed windows
    # (host-merge at drain — the same per-device-exact stance as tier
    # 0); () = off. Tier flush rows ride the advance drain's bundled
    # transfers, so the ≤3-fetch budget is unchanged.
    cascade: tuple[int, ...] = ()
    cascade_capacity: int = 1 << 12

    def __post_init__(self):
        check_fold_mode(self.fold_mode)
        if self.cascade:
            from ..aggregator.cascade import CascadeConfig

            CascadeConfig(
                intervals=self.cascade, capacity=self.cascade_capacity
            ).validate_base(self.interval)

    def sketch_config(self) -> SketchConfig:
        return SketchConfig(
            num_groups=self.num_services,
            hll_precision=self.hll_precision,
            cms_depth=self.cms_depth,
            cms_width=self.cms_width,
            hist=self.hist,
            topk_rows=self.topk_rows,
            topk_cols=self.topk_cols,
            pending=self.sketch_pending,
            pool=self.sketch_pool,
        )


class ShardedPipeline:
    """shard_map'd ingest step + collective window-close merges.

    `mesh` may be a `parallel.topology.MeshTopology` instead of a raw
    Mesh (ISSUE 14): the pipeline then compiles against the topology's
    fully-addressable per-group mesh for `shard_group` — same
    ("host", "chip") axis names, so every shard_map body below is
    unchanged — and carries the topology through to checkpoint meta
    (per-host restore validation) and Countable labels."""

    def __init__(self, mesh, config: ShardedConfig = ShardedConfig(),
                 *, shard_group: int = 0):
        from .topology import MeshTopology

        if isinstance(mesh, MeshTopology):
            self.topology: MeshTopology | None = mesh
            self.shard_group = shard_group
            mesh = mesh.group_mesh(shard_group)
        else:
            self.topology = None
            self.shard_group = shard_group
        self.mesh = mesh
        self.config = config
        self.n_devices = mesh.devices.size
        self.axes = tuple(mesh.axis_names)  # ("host", "chip")
        self._tag_names: tuple | None = None  # fixed on first step()
        self._step = self._build_step()
        self._fold = self._build_fold()
        self._close = self._build_window_close()
        self._flush = self._build_flush()
        self._flush_range = self._build_flush_range()
        self._sketch_drain = self._build_sketch_drain()
        self._snapshot = self._build_snapshot()
        # per-ratio tier-fold kernels (ISSUE 9), built on first use —
        # the cascade fires only on window advances
        self._tier_fold_cache: dict[int, object] = {}

    # -- state ----------------------------------------------------------
    def init_state(self) -> tuple[StashState, SketchPlanes]:
        c = self.config
        d = self.n_devices

        def dev_axis(x):
            return jnp.broadcast_to(x[None], (d,) + x.shape)

        stash = jax.tree.map(dev_axis, stash_init(c.capacity_per_device, TAG_SCHEMA, FLOW_METER))
        sketches = jax.tree.map(
            dev_axis, sketch_init(c.sketch_config(), c.sketch_ring)
        )
        spec = NamedSharding(self.mesh, P(self.axes))
        stash = jax.tree.map(lambda x: jax.device_put(x, spec), stash)
        sketches = jax.tree.map(lambda x: jax.device_put(x, spec), sketches)
        return stash, sketches

    def init_acc(self, doc_rows_per_device: int) -> AccumState:
        """Per-device accumulator ring, sized accum_batches × one batch's
        fanout rows (lazy — the batch shape is only known at first ingest)."""
        d = self.n_devices
        cap = self.config.accum_batches * doc_rows_per_device
        acc = accum_init(cap, TAG_SCHEMA, FLOW_METER)
        acc = jax.tree.map(lambda x: jnp.broadcast_to(x[None], (d,) + x.shape), acc)
        spec = NamedSharding(self.mesh, P(self.axes))
        return jax.tree.map(lambda x: jax.device_put(x, spec), acc)

    # -- step -----------------------------------------------------------
    def _build_step(self):
        c = self.config
        # only the append half is driven here — _build_fold assembles the
        # modal fold kernels directly (it needs the fold_rows scalar)
        base_append, _ = make_ingest_step(
            c.fanout, c.interval, batch_unique_cap=c.batch_unique_cap
        )
        t_idx = TAG_SCHEMA.index
        m_idx = FLOW_METER.index
        # one-pass knob captured at step-BUILD time (ISSUE 17): the
        # sharded twin pins the same path as the single-chip step for
        # the life of this jitted closure
        from ..ops.segment import _use_shared_sort

        shared_sort = _use_shared_sort()

        def device_step(stash, acc, offset, sk, tag_mat, meters, valid,
                        start_window, close_below):
            # block shapes: stash [1, S, ...], tag_mat [1, T, n] — one
            # packed matrix, not a dict of columns: every pytree leaf is
            # a separate host→device upload with its own fixed cost, so
            # ~25 tag columns per step add up; packed, the step ships 3
            # arrays total
            stash1 = jax.tree.map(lambda x: x[0], stash)
            acc1 = jax.tree.map(lambda x: x[0], acc)
            sk1 = jax.tree.map(lambda x: x[0], sk)
            tags1 = {k: tag_mat[0, i] for i, k in enumerate(self._tag_names)}
            meters1, valid1 = meters[0], valid[0]

            new_stash, new_acc = base_append(stash1, acc1, offset, tags1, meters1, valid1)

            # Per-window sketch plane (ISSUE 8) from the raw flow shard.
            # The sharded window protocol is HOST-driven (the manager
            # decides advances from host-visible timestamps BEFORE
            # dispatch), so the open/close span bounds arrive as
            # replicated scalars instead of being derived in-step —
            # every device closes the same windows at the same batch,
            # even when its own shard never saw the advancing timestamp.
            ts = jnp.asarray(tags1["timestamp"], jnp.uint32)
            inp = sketch_inputs_from_columns(
                tags1, meters1, sk1.hll.shape[1], m_idx
            )
            new_sk = sketch_plane_step(
                sk1, c.hist,
                window=ts // jnp.uint32(c.interval), valid=valid1,
                base_w=start_window, close_w=close_below,
                shared_sort=shared_sort, **inp,
            )

            expand = lambda x: x[None]
            return (
                jax.tree.map(expand, new_stash),
                jax.tree.map(expand, new_acc),
                jax.tree.map(expand, new_sk),
            )

        pspec = P(self.axes)
        mapped = shard_map(
            device_step,
            mesh=self.mesh,
            in_specs=(pspec, pspec, P(), pspec, pspec, pspec, pspec, P(), P()),
            out_specs=(pspec, pspec, pspec),
        )
        return jax.jit(mapped, donate_argnums=(0, 1, 3))

    def _build_fold(self):
        sum_cols = tuple(int(i) for i in np.nonzero(FLOW_METER.sum_mask)[0])
        max_cols = tuple(int(i) for i in np.nonzero(FLOW_METER.max_mask)[0])
        merge = self.config.fold_mode == "merge"

        def device_fold(stash, acc, hi_window):
            stash1 = jax.tree.map(lambda x: x[0], stash)
            acc1 = jax.tree.map(lambda x: x[0], acc)
            if merge:
                new_stash, new_acc, lanes = _merge_fold_impl(
                    stash1, acc1, hi_window, sum_cols, max_cols
                )
            else:
                # full mode ignores the span bound (the managers never
                # span-fold in full mode — host-side guard)
                new_stash, new_acc, lanes = _fold_counted_impl(
                    stash1, acc1, sum_cols, max_cols
                )
            expand = lambda x: x[None]
            return (
                jax.tree.map(expand, new_stash),
                jax.tree.map(expand, new_acc),
                lanes[:1],  # fold_rows; the trip count has no lane here
            )

        pspec = P(self.axes)
        mapped = shard_map(
            device_fold,
            mesh=self.mesh,
            in_specs=(pspec, pspec, P()),
            out_specs=(pspec, pspec, pspec),
        )
        return jax.jit(mapped, donate_argnums=(0, 1))

    def step(self, stash, acc, offset, sketches, tags, meters, valid,
             start_window: int = 0, close_below: int = 0):
        """tags: {f: [D*n]} u32 (device-shardable), meters [D*n, M],
        valid [D*n]. Leading dim must be divisible by the device count.
        `offset` is the per-device accumulator write position (host-tracked,
        identical on every device). `start_window`/`close_below` drive
        the per-window sketch plane (ISSUE 8): the host's open-span
        start and — on an advancing batch — the new span start, which
        closes every older sketch slot into the pending buffer inside
        this same dispatch (0 = close nothing). Callers whose batches
        span more than `sketch_ring` windows must pass them, or sketch
        slots may alias (the exact stash is unaffected either way)."""
        d = self.n_devices

        def shard_batch(x):
            return x.reshape((d, -1) + x.shape[1:])

        if self._tag_names is None:
            self._tag_names = tuple(sorted(tags))
        # pack the ~25 tag columns into ONE upload (see device_step)
        mat = np.stack(
            [np.asarray(tags[k], dtype=np.uint32) for k in self._tag_names]
        )  # [T, D*n]
        t, total = mat.shape
        tag_mat = jnp.asarray(
            np.ascontiguousarray(mat.reshape(t, d, total // d).transpose(1, 0, 2))
        )  # [D, T, n]
        meters = shard_batch(jnp.asarray(meters))
        valid = shard_batch(jnp.asarray(valid))
        return self._step(
            stash, acc, jnp.int32(offset), sketches, tag_mat, meters, valid,
            jnp.uint32(start_window), jnp.uint32(close_below),
        )

    def fold(self, stash, acc, hi_window=None):
        """Amortized per-device fold of accumulated rows into the stash
        (host fires it at accum_batches cadence and before flushes).
        Returns (stash, acc, fold_rows [D] u32 — rows each device's fold
        keyed-sort touched). `hi_window` (fold_mode="merge" only)
        span-bounds the fold to acc rows with slot < hi_window; the rest
        stay accumulated — callers must NOT reset their fill cursor."""
        if hi_window is not None and self.config.fold_mode != "merge":
            raise ValueError("span-bounded fold requires fold_mode='merge'")
        from ..ops.segment import SENTINEL_SLOT

        hi = jnp.uint32(SENTINEL_SLOT if hi_window is None else hi_window)
        return self._fold(stash, acc, hi)

    # -- window close ---------------------------------------------------
    def _build_window_close(self):
        axes = self.axes

        def close(sk: SketchState):
            sk1 = jax.tree.map(lambda x: x[0], sk)
            # fold the open ring (slot axis) first, then merge across
            # every chip in the pod — register max / counter add are
            # associative, so ring-then-mesh equals any other order
            hll_l = jnp.max(sk1.hll, axis=0)
            cms_l = jnp.sum(sk1.cms, axis=0)
            hist_l = jnp.sum(sk1.hist, axis=0)
            hll_global = lax.pmax(hll_l, axes)
            cms_global = lax.psum(cms_l, axes)
            hist_global = lax.psum(hist_l, axes)
            # pod-wide 1m rollup path (DCN tier only): reduce over hosts
            # of the already-ICI-merged per-host planes.
            hll_host = lax.pmax(hll_l, axes[1])  # ICI
            hll_pod_1m = lax.pmax(hll_host, axes[0])  # DCN
            expand = lambda x: x[None]
            global_view = MergedSketchView(
                hll=expand(hll_global), cms=expand(cms_global), hist=expand(hist_global)
            )
            return global_view, expand(hll_pod_1m)

        pspec = P(self.axes)
        mapped = shard_map(
            close,
            mesh=self.mesh,
            in_specs=(pspec,),
            out_specs=(pspec, pspec),
        )
        return jax.jit(mapped)

    def window_close(self, sketches):
        """Merge the open sketch ring across the mesh; returns
        (sketches, globally-merged MergedSketchView replicated per
        device, pod-wide 1m HLL).

        ISSUE 8 semantics change: per-window state is authoritative now,
        so this VIEW no longer resets the local planes (slots reset when
        their window closes in-step; the first tuple element returns the
        planes unchanged for call-site compatibility). The view covers
        every still-open window — the per-window closed blocks drain
        through ShardedWindowManager instead."""
        view, pod_1m = self._close(sketches)
        return sketches, view, pod_1m

    def _build_sketch_drain(self):
        """Per-device pending-drain (+ forced close below a bound) —
        the sketch twin of _build_flush_range: one device call, outputs
        fetched by the manager bundled into the flush drain's existing
        transfers."""

        def dr(sk, close_w):
            sk1 = jax.tree.map(lambda x: x[0], sk)
            new_sk, pend, pend_win, n, wide_rows, wide_wins = (
                _sketch_drain_impl(sk1, close_w)
            )
            expand = lambda x: x[None]
            return (
                jax.tree.map(expand, new_sk),
                pend[None], pend_win[None], n[None],
                wide_rows[None], wide_wins[None],
            )

        pspec = P(self.axes)
        mapped = shard_map(
            dr,
            mesh=self.mesh,
            in_specs=(pspec, P()),
            out_specs=(pspec, pspec, pspec, pspec, pspec, pspec),
        )
        return jax.jit(mapped, donate_argnums=(0,))

    def sketch_drain(self, sketches, close_below):
        """Close every sketch slot below `close_below` on every device
        and hand back the pending blocks: (sketches, pend [D, P, WIDE],
        pend_win [D, P], pend_n [D], wide_rows [D, Pw, WIDE],
        wide_wins [D, Pw]). The wide arrays are zero-size in slab mode;
        in pool mode they carry each wide pool slot's in-place drained
        block (win == SENTINEL_WIN rows are dead — host filters)."""
        return self._sketch_drain(sketches, jnp.uint32(close_below))

    # -- live read plane (ISSUE 10) --------------------------------------
    def _build_snapshot(self):
        """READ-ONLY per-device snapshot of the open span: the sharded
        twin of stash.stash_snapshot_range fused with the open-slot
        sketch flatten — one device call, NO donation (the live stash
        and plane are untouched), outputs fetched by the manager in the
        drain's 2-transfer shape."""
        from ..aggregator.stash import _snapshot_range_impl

        def snap(stash, sk, lo):
            stash1 = jax.tree.map(lambda x: x[0], stash)
            sk1 = jax.tree.map(lambda x: x[0], sk)
            packed, total = _snapshot_range_impl(
                stash1, lo, jnp.uint32(0xFFFFFFFF)
            )
            blocks = _flatten_open(sk1)
            return packed[None], total[None], blocks[None], sk1.win[None]

        pspec = P(self.axes)
        mapped = shard_map(
            snap,
            mesh=self.mesh,
            in_specs=(pspec, pspec, P()),
            out_specs=(pspec, pspec, pspec, pspec),
        )
        return jax.jit(mapped)

    def snapshot_open_ranges(self, stash, sketches, lo_window):
        """Dispatch the read-only snapshot: (packed [D, S, 3+T+M],
        totals [D], blocks [D, R, WIDE], wins [D, R])."""
        return self._snapshot(stash, sketches, jnp.uint32(lo_window))

    # -- doc flush ------------------------------------------------------
    def _build_flush(self):
        from ..aggregator.stash import stash_flush

        def flush(stash, window_idx):
            stash1 = jax.tree.map(lambda x: x[0], stash)
            new_state, out = stash_flush(stash1, window_idx)
            expand = lambda x: x[None]
            return jax.tree.map(expand, new_state), jax.tree.map(expand, out)

        pspec = P(self.axes)
        mapped = shard_map(
            flush,
            mesh=self.mesh,
            in_specs=(pspec, P()),
            out_specs=(pspec, pspec),
        )
        return jax.jit(mapped)

    def flush_window(self, stash, window_idx):
        """Flush one closed window from every device stash.

        Returns (new_stash, out) where out's arrays carry a leading
        device dim ([D, S] mask/slot/keys, [D, S, T] tags, ...). Exact
        doc stashes are per-device (the reference isolates per-pipeline
        docs the same way via global_thread_id, document.rs:293); the
        host compacts all shards into one DocBatch.

        This is the per-window oracle shape; the production drain is
        `flush_range` (all closed windows in one call).
        """
        if self.config.fold_mode == "merge":
            # stash_flush punches sentinel holes mid-prefix, silently
            # breaking the canonical layout the rank-merge binary-search
            # requires — merge mode must drain through flush_range
            raise ValueError(
                "flush_window (per-window oracle) breaks the canonical "
                "stash layout fold_mode='merge' requires; use flush_range"
            )
        return self._flush(stash, jnp.asarray(window_idx, dtype=jnp.uint32))

    def _build_flush_range(self):
        from ..aggregator.stash import _flush_range_impl

        # merge mode drains through the compacting flush so each device
        # stash keeps the canonical layout the rank-merge requires
        compact = self.config.fold_mode == "merge"

        def fr(stash, lo, hi):
            stash1 = jax.tree.map(lambda x: x[0], stash)
            new_state, packed, total = _flush_range_impl(
                stash1, lo, hi, compact=compact
            )
            expand = lambda x: x[None]
            return jax.tree.map(expand, new_state), packed[None], total[None]

        pspec = P(self.axes)
        mapped = shard_map(
            fr,
            mesh=self.mesh,
            in_specs=(pspec, P(), P()),
            out_specs=(pspec, pspec, pspec),
        )
        return jax.jit(mapped, donate_argnums=(0,))

    def flush_range(self, stash, lo_window, hi_window):
        """Flush every window in [lo, hi) from every device stash in ONE
        device call. Returns (new_stash, packed [D, S, 3+T+M] u32 row
        matrices, totals [D] i32) — the host fetches the totals plus one
        [D, max(totals)] row block instead of (windows × leaves)
        transfers (aggregator/stash.stash_flush_range layout)."""
        return self._flush_range(
            stash,
            jnp.asarray(lo_window, dtype=jnp.uint32),
            jnp.asarray(hi_window, dtype=jnp.uint32),
        )

    # -- rollup cascade (ISSUE 9) ---------------------------------------
    def init_tier_state(self) -> tuple[list[StashState], jnp.ndarray]:
        """Per-device tier stashes (one per cascade interval) + the
        per-device [D, 2] cascade counter lanes, replicated/sharded like
        every other device plane."""
        c = self.config
        d = self.n_devices
        spec = NamedSharding(self.mesh, P(self.axes))

        def shard(x):
            return jax.device_put(
                jnp.broadcast_to(x[None], (d,) + x.shape), spec
            )

        tiers = [
            jax.tree.map(
                shard, stash_init(c.cascade_capacity, TAG_SCHEMA, FLOW_METER)
            )
            for _ in c.cascade
        ]
        lanes = jax.device_put(jnp.zeros((d, 2), jnp.uint32), spec)
        return tiers, lanes

    def init_tier_acc(self, child_rows: int) -> tuple[AccumState, jnp.ndarray]:
        """Per-device tier accumulator ring + [D] fill cursors (the
        cascade's append/amortize ring — aggregator/cascade.tier_step),
        sized to the child stash."""
        d = self.n_devices
        spec = NamedSharding(self.mesh, P(self.axes))
        acc = accum_init(child_rows, TAG_SCHEMA, FLOW_METER)
        acc = jax.tree.map(
            lambda x: jax.device_put(
                jnp.broadcast_to(x[None], (d,) + x.shape), spec
            ),
            acc,
        )
        fills = jax.device_put(jnp.zeros((d,), jnp.int32), spec)
        return acc, fills

    def tier_step_fn(self, ratio: int):
        """shard_map'd cascade tier step for one child→parent ratio:
        (tier_stash [D,…], acc [D,…], fill [D], lanes [D, 2], packed
        [D, S, 3+T+M], total [D], hi) → (tier_stash, acc, fill, lanes).
        One jitted kernel per ratio, cached — the same append-or-fold
        step as the single-chip cascade (tier_step), run independently
        per device (exact tiers never merge across devices; cross-shard
        aggregation stays a query-layer concern, the tier-0 stance)."""
        from ..ops.segment import _use_shared_sort

        # build-time knob capture, the sharded convention (_build_step)
        shared_sort = _use_shared_sort()
        fn = self._tier_fold_cache.get(("step", ratio, shared_sort))
        if fn is not None:
            return fn
        from ..aggregator.cascade import _tier_step_impl, tier_prefix

        sum_cols = tuple(int(i) for i in np.nonzero(FLOW_METER.sum_mask)[0])
        max_cols = tuple(int(i) for i in np.nonzero(FLOW_METER.max_mask)[0])
        nt = TAG_SCHEMA.num_fields

        def dev(tier, acc, fill, lanes, packed, total, hi):
            tier1 = jax.tree.map(lambda x: x[0], tier)
            acc1 = jax.tree.map(lambda x: x[0], acc)
            new_tier, new_acc, new_fill, new_lanes = _tier_step_impl(
                tier1, acc1, fill[0], lanes[0], packed[0], total[0], hi,
                ratio=ratio, num_tags=nt,
                sum_cols_t=sum_cols, max_cols_t=max_cols,
                prefix=tier_prefix(packed.shape[1]),
                shared_sort=shared_sort,
            )
            expand = lambda x: x[None]
            return (
                jax.tree.map(expand, new_tier),
                jax.tree.map(expand, new_acc),
                new_fill[None], new_lanes[None],
            )

        pspec = P(self.axes)
        mapped = shard_map(
            dev,
            mesh=self.mesh,
            in_specs=(pspec, pspec, pspec, pspec, pspec, pspec, P()),
            out_specs=(pspec, pspec, pspec, pspec),
        )
        fn = jax.jit(mapped, donate_argnums=(0, 1, 3))
        self._tier_fold_cache[("step", ratio, shared_sort)] = fn
        return fn

    def tier_ring_fold_fn(self):
        """shard_map'd tier ring fold: merge each device's tier
        accumulator into its tier stash (runs before every tier flush
        and at checkpoint — the settle rule)."""
        from ..ops.segment import _use_shared_sort

        # build-time knob capture: with shared sort ON the fold
        # rank-merges the ring against the tier stash's dispatch-owned
        # canonical order instead of a second full keyed sort (ISSUE 20)
        shared_sort = _use_shared_sort()
        fn = self._tier_fold_cache.get(("ring_fold", shared_sort))
        if fn is not None:
            return fn
        from ..aggregator.cascade import _ring_fold_impl

        sum_cols = tuple(int(i) for i in np.nonzero(FLOW_METER.sum_mask)[0])
        max_cols = tuple(int(i) for i in np.nonzero(FLOW_METER.max_mask)[0])

        def dev(tier, acc, lanes):
            tier1 = jax.tree.map(lambda x: x[0], tier)
            acc1 = jax.tree.map(lambda x: x[0], acc)
            new_tier, new_acc, new_lanes = _ring_fold_impl(
                tier1, acc1, lanes[0], sum_cols, max_cols,
                shared_sort=shared_sort,
            )
            expand = lambda x: x[None]
            return (
                jax.tree.map(expand, new_tier),
                jax.tree.map(expand, new_acc),
                new_lanes[None],
            )

        pspec = P(self.axes)
        mapped = shard_map(
            dev,
            mesh=self.mesh,
            in_specs=(pspec, pspec, pspec),
            out_specs=(pspec, pspec, pspec),
        )
        fn = jax.jit(mapped, donate_argnums=(0, 1, 2))
        self._tier_fold_cache[("ring_fold", shared_sort)] = fn
        return fn

    def tier_flush_range_fn(self):
        """shard_map'd tier-stash flush — ALWAYS compacting (ISSUE 20):
        the cascade tier stashes must keep the canonical sorted-prefix
        layout the shared-sort ring fold rank-merges against, whatever
        tier 0's fold_mode says. Same output rows as `flush_range`."""
        fn = self._tier_fold_cache.get("tier_flush")
        if fn is not None:
            return fn
        from ..aggregator.stash import _flush_range_impl

        def fr(stash, lo, hi):
            stash1 = jax.tree.map(lambda x: x[0], stash)
            new_state, packed, total = _flush_range_impl(
                stash1, lo, hi, compact=True
            )
            expand = lambda x: x[None]
            return jax.tree.map(expand, new_state), packed[None], total[None]

        pspec = P(self.axes)
        mapped = shard_map(
            fr,
            mesh=self.mesh,
            in_specs=(pspec, P(), P()),
            out_specs=(pspec, pspec, pspec),
        )
        fn = jax.jit(mapped, donate_argnums=(0,))
        self._tier_fold_cache["tier_flush"] = fn
        return fn


class ShardedWindowManager:
    """Host-driven window controller for the mesh path — the sharded twin
    of aggregator/window.WindowManager (same open-span/late-drop/flush
    protocol, quadruple_generator.rs:275-352), producing writer-ready
    DocBatches from the per-device stashes at every window close.
    """

    def __init__(self, pipe: ShardedPipeline, delay: int = 2,
                 *, tracer: SpanTracer | None = None,
                 min_snapshot_interval: float = 0.25):
        self.pipe = pipe
        self.interval = pipe.config.interval
        self.delay = delay
        self.min_snapshot_interval = min_snapshot_interval
        self._sk_cfg = pipe.config.sketch_config()
        ring_needed = delay // pipe.config.interval + 2
        if pipe.config.sketch_ring < ring_needed:
            raise ValueError(
                f"sketch_ring={pipe.config.sketch_ring} cannot hold the "
                f"{ring_needed} simultaneously-open windows of "
                f"delay={delay}/interval={pipe.config.interval} — per-window "
                "sketch slots would alias"
            )
        self.stash, self.sketches = pipe.init_state()
        self.acc = None  # per-device accumulator, sized on first batch
        self.fill = 0  # host-tracked per-device accumulator rows
        self.start_window: int | None = None
        self.drop_before_window = 0
        self.total_docs_in = 0
        self.total_flushed = 0
        self.n_advances = 0
        # last fold's keyed-sort row count: device [D] handle updated by
        # every fold, host mirror refreshed by the advance drain's
        # EXISTING totals fetch (bundled — no new steady-state sync)
        self.fold_rows = 0
        self._fold_rows_dev = None
        # merged sketch views of the last closed window (None until one closes)
        self.global_view = None
        self.pod_1m = None
        # per-window sketch tier (ISSUE 8): closed blocks host-merged
        # across devices, in window order. BOUNDED drop-oldest-counted
        # (like the device pending buffer) so an undrained consumer
        # cannot leak a block per window forever.
        self.closed_sketches: list = []
        self.max_held_sketches = 512
        self.sketch_blocks_closed = 0
        self.sketch_blocks_dropped = 0
        # pooled sketch memory (ISSUE 20): summed-over-devices spill/
        # promotion/occupancy mirrors, updated at advance drains via the
        # bundled scalar fetch (zero when the pool is off)
        self.sketch_pool_spill = 0
        self.sketch_promotions = 0
        self.sketch_pool_occ = 0
        # rollup cascade (ISSUE 9): per-device tier stashes + watermarks
        # + the [D, 2] device counter lanes; host mirrors ride the
        # advance drain's bundled totals fetch
        self._cascade_intervals = tuple(pipe.config.cascade)
        self.tier_stashes: list = []
        self.tier_accs: list = []
        self.tier_fills: list = []
        self.tier_watermarks: list[int] = []
        self._tier_ratios: list[int] = []
        self.cascade_lanes = None
        self.cascade_rows = 0
        self.cascade_shed = 0
        self._tier_pending_blocks: list[dict] = []
        self.tier_flushed: list = []  # [(interval_s, DocBatch)]
        self.max_held_tier_windows = 4096
        self.tier_windows_dropped = 0
        self.tier_windows_flushed = 0
        self.closed_tier_sketches: list = []
        self.tier_sketch_blocks_dropped = 0
        if self._cascade_intervals:
            res = (self.interval,) + self._cascade_intervals
            self._tier_ratios = [
                res[i + 1] // res[i] for i in range(len(self._cascade_intervals))
            ]
            self.tier_stashes, self.cascade_lanes = pipe.init_tier_state()
            self.tier_accs = [None] * len(self._cascade_intervals)
            self.tier_fills = [None] * len(self._cascade_intervals)
            self.tier_watermarks = [0] * len(self._cascade_intervals)
            self._tier_pending_blocks = [{} for _ in self._cascade_intervals]
            from ..server.datasource import register_cascade_tiers

            register_cascade_tiers("flow", self._cascade_intervals, owner=self)
        # device↔host transfer accounting through the shared host_fetch
        # seam (aggregator/window.py) — the perf gate shims that seam
        # and asserts the per-ingest budget on this path too
        self.host_fetches = 0
        self.bytes_fetched = 0
        self.bytes_uploaded = 0
        # live read plane (ISSUE 10): pull-only open-span snapshots
        # (read-only per-device pack, host-merged) — rate-limited like
        # the single-chip twin; the sharded path has no device counter
        # block, so the host ints are the only accounting
        self.snapshot_reads = 0
        self.snapshot_bytes = 0
        self.snapshot_seq = 0
        self._snapshot_cache = None
        # transient-failure policy (ISSUE 6) — the single-chip
        # WindowManager's twin: dispatch + fetch retry with
        # decorrelated backoff+jitter; same admission-time-only caveat
        # (utils/retry.py)
        self.retry_policy = RetryPolicy()
        self._retry_rng = decorrelated_rng(0x5A4DED)
        self.dispatch_retries = 0
        self.fetch_retries = 0
        self.tracer = tracer if tracer is not None else SpanTracer(
            service="deepflow_tpu.sharded_pipeline"
        )
        # window lineage plane (ISSUE 13): optional per-window hop
        # recorder — host wall stamps only, zero new device fetches
        # (the sharded path computes its window spans from the host
        # timestamp arrays it already gates on)
        self.lineage = None
        # multi-host placement labels (ISSUE 14): with a MeshTopology,
        # rows carry the shard group + process so a fleet dashboard can
        # tell hosts apart without scraping hostnames
        topo_tags = {}
        if pipe.topology is not None:
            topo_tags = {
                "group": str(pipe.shard_group),
                "process": str(pipe.topology.process_index),
            }
        self._stats_srcs = [
            register_countable(
                "tpu_sharded_pipeline", self, devices=str(pipe.n_devices),
                **topo_tags,
            ),
            register_countable(
                "tpu_sharded_pipeline_spans", self.tracer,
                devices=str(pipe.n_devices), **topo_tags,
            ),
        ]
        # device profiling plane (ISSUE 12): weakly registered on the
        # process-wide HBM ledger with the device count, so the ledger
        # reports bytes/device next to the [D]-leading totals
        from ..profiling.ledger import register_profilable

        self._ledger_src = register_profilable(
            "sharded_window_manager", self, devices=pipe.n_devices,
            interval=f"{self.interval}s",
            cascade=str(bool(self._cascade_intervals)),
        )

    def _fetch(self, x) -> np.ndarray:
        """Every device→host transfer goes through the window module's
        host_fetch seam (late-bound so the CI shim counts it), with
        per-manager count + byte accounting on top. Transient fetch
        failures retry with backoff (the handle stays valid)."""

        def once():
            chaos.maybe_fail(chaos.SITE_FETCH)
            return window_mod.host_fetch(x)

        def on_retry(_attempt, _exc):
            self.fetch_retries += 1

        arr = retry_call(once, self.retry_policy, on_retry=on_retry,
                         rng=self._retry_rng)
        self.host_fetches += 1
        self.bytes_fetched += arr.nbytes
        return arr

    def get_counters(self) -> dict:
        """Countable face — host ints only, safe from a ticking thread.

        `flow_in` counts PRE-fanout flow rows (the sharded late gate
        runs on raw flows host-side); the single-chip `doc_in` counts
        post-fanout doc rows — deliberately different names so the two
        planes cannot be misread as the same funnel stage."""
        return {
            "flow_in": self.total_docs_in,
            "flushed_doc": self.total_flushed,
            "drop_before_window": self.drop_before_window,
            "acc_fill": self.fill,
            "window_advances": self.n_advances,
            # summed-over-devices rows the last DRAINED fold keyed-sort
            # touched (full mode: live stash + ring; merge mode: folded
            # acc rows only). Mirrored at advance drains — capacity
            # folds between advances update it at the next drain, never
            # with an extra fetch (fetch-free Countable contract).
            "fold_rows": self.fold_rows,
            "host_fetches": self.host_fetches,
            "bytes_fetched": self.bytes_fetched,
            "bytes_uploaded": self.bytes_uploaded,
            "dispatch_retries": self.dispatch_retries,
            "fetch_retries": self.fetch_retries,
            # per-window sketch tier (ISSUE 8): closed blocks merged
            # across devices so far, blocks awaiting a consumer, and
            # the drop-oldest overflow count (non-zero = nobody drains
            # pop_closed_sketches)
            "sketch_blocks_closed": self.sketch_blocks_closed,
            "sketch_blocks_held": len(self.closed_sketches),
            "sketch_blocks_dropped": self.sketch_blocks_dropped,
            # pooled sketch memory (ISSUE 20): cumulative spill +
            # promotion counts and the occupancy gauge, summed over
            # devices (all 0 with the pool off)
            "sketch_pool_spill": self.sketch_pool_spill,
            "sketch_promotions": self.sketch_promotions,
            "sketch_pool_occ": self.sketch_pool_occ,
            # rollup-cascade lanes (ISSUE 9): summed-over-devices rows
            # the tier folds consumed / tier-stash sheds (mirrored at
            # advance drains via the bundled totals fetch), plus the
            # host-side tier-window accounting
            "cascade_rows": self.cascade_rows,
            "cascade_shed": self.cascade_shed,
            "cascade_tier_windows": self.tier_windows_flushed,
            "tier_windows_held": len(self.tier_flushed),
            "tier_windows_dropped": self.tier_windows_dropped,
            # live read plane (ISSUE 10): pull-only snapshot accounting
            "snapshot_reads": self.snapshot_reads,
            "snapshot_bytes": self.snapshot_bytes,
        }

    def attach_lineage(self, tracker) -> None:
        """Wire a tracing/lineage.LineageTracker (the single-chip
        WindowManager.attach_lineage twin)."""
        self.lineage = tracker

    def pop_closed_sketches(self) -> list:
        """Drain the host-merged closed WindowSketchBlocks (window
        order). The sketch twin of the DocBatches `ingest` returns."""
        out, self.closed_sketches = self.closed_sketches, []
        return out

    def telemetry(self) -> dict:
        """JSON-able counters + span summary (bench snapshot shape) +
        the per-plane HBM byte record (ISSUE 12)."""
        from ..profiling.ledger import plane_bytes

        return {
            "counters": self.get_counters(),
            "spans": self.tracer.summary(),
            "profile": {
                "hbm_bytes": {
                    name: plane_bytes(tree)[0]
                    for name, tree in self.device_planes().items()
                },
                "devices": self.pipe.n_devices,
            },
        }

    # -- device profiling plane (ISSUE 12) --------------------------------
    def device_planes(self) -> dict:
        """Profilable face — every [D]-leading device plane this manager
        owns (the sharded twin of WindowManager.device_planes; same
        enumeration-is-ownership contract, pinned by the sharded
        reconciliation test)."""
        planes: dict[str, object] = {
            "stash": self.stash,
            "accumulator": self.acc,  # None until the first batch
            "lanes": [self._fold_rows_dev],
        }
        if _pool_mode(self.sketches):
            # pooled sketch memory (ISSUE 20): same four-way split as
            # the single-chip twin — hot pool, wide arena, pending ring,
            # and routing/meta — so per-pool HBM attribution matches
            sk = self.sketches
            planes["sketch_pool_hot"] = [
                sk.p_hll, sk.p_cms, sk.p_hist, sk.p_tkv,
                sk.p_tkh, sk.p_tkl, sk.p_tia, sk.p_tib,
            ]
            planes["sketch_pool_wide"] = [
                sk.hll, sk.cms, sk.hist, sk.tk_votes,
                sk.tk_hi, sk.tk_lo, sk.tk_ida, sk.tk_idb,
            ]
            planes["sketch_pending"] = [sk.pend, sk.pend_win]
            planes["sketch_meta"] = [
                sk.win, sk.count, sk.slot_of, sk.wide_close,
                sk.wide_count, sk.rows, sk.shed, sk.pend_n,
                sk.pool_spill, sk.pool_promos, sk.promote_fill,
            ]
        else:
            planes["sketch"] = self.sketches
        if self._tier_ratios:
            planes["cascade"] = [
                self.tier_stashes, self.tier_accs, self.tier_fills,
                self.cascade_lanes,
            ]
        return planes

    def close(self) -> None:
        """Eager profiling/telemetry teardown — the manager leaves the
        HBM ledger and its Countable rows stop (weakrefs remain the
        backstop for callers that just drop the reference)."""
        from ..profiling.ledger import default_ledger
        from ..utils.stats import default_collector

        default_ledger.deregister(self._ledger_src)
        for src in self._stats_srcs:
            default_collector.deregister(src)

    def _fold(self):
        """Full-set fold (kernel per pipe.config.fold_mode): the ring
        empties and the fill cursor resets."""
        if self.fill == 0 or self.acc is None:
            return
        with self.tracer.span(SPAN_WINDOW_FOLD):
            self.stash, self.acc, self._fold_rows_dev = self.pipe.fold(
                self.stash, self.acc
            )
        self.fill = 0

    def _fold_span(self, hi_window: int):
        """Span-bounded advance fold (fold_mode="merge"): fold only acc
        rows with slot < hi_window; `fill` stays put (consumed rows turn
        sentinel in place — the next full fold reclaims the ring)."""
        if self.fill == 0 or self.acc is None:
            return
        with self.tracer.span(SPAN_WINDOW_FOLD):
            self.stash, self.acc, self._fold_rows_dev = self.pipe.fold(
                self.stash, self.acc, hi_window=np.uint32(hi_window)
            )

    def _drain_range(self, lo: int, hi: int):
        """Flush [lo, hi) from every device stash in one fused call and
        regroup the packed rows into per-window DocBatches; the sketch
        tier's closed blocks (ISSUE 8) drain in the SAME two transfers
        (pend counts ride the bundled scalar vector, packed blocks +
        window ids ride the row-block fetch as one concatenated u32
        array) and are host-merged across devices by window into
        `closed_sketches`.

        Host pays: ONE [3D] scalar fetch + ONE concatenated block fetch
        — independent of how many windows closed (previously: a full
        slot+valid plane scan plus 3 plane fetches PER window)."""
        from ..aggregator.stash import unpack_flush_rows
        from ..datamodel.batch import DocBatch
        from ..datamodel.schema import FLOW_METER, TAG_SCHEMA

        self.stash, packed, totals = self.pipe.flush_range(
            self.stash, np.uint32(lo), np.uint32(hi)
        )
        # forced close at `hi`: every device closes the same windows at
        # this drain even if its shard never saw the advancing timestamp
        (self.sketches, pend, pend_win, pend_n,
         wide_rows, wide_wins) = self.pipe.sketch_drain(self.sketches, hi)
        d = self.pipe.n_devices
        # pooled wide slots (ISSUE 20): Pw > 0 only in pool mode; their
        # per-device close counts ride the scalar vector and the (tiny)
        # [D, Pw] arena joins the row fetch only when something closed
        has_wide = wide_rows.shape[1] > 0
        # rollup cascade (ISSUE 9): fold this drain's packed flush rows
        # into the per-device tier stashes and flush every tier window
        # that closed — pure dispatches; outputs join the two bundled
        # transfers below. Each entry: (tier idx, interval, packed
        # [D, St, C], totals [D], lo_t, hi_t).
        #
        # TWIN CONTRACT with TierCascade.on_advance (cascade.py): this
        # loop mirrors it over [D]-shaped state — lazy ring sizing with
        # a pre-growth fold, tier_step, the hi_t <= watermark early
        # break, the MANDATORY ring fold before every tier flush, and
        # tier chaining. A semantic change to either loop must land in
        # both (the kernels themselves are already shared).
        tier_flushes = []
        if self._tier_ratios:
            src, src_total, src_hi = packed, totals, int(hi)
            for i, ratio in enumerate(self._tier_ratios):
                from ..aggregator.cascade import tier_ring_rows

                child_rows = src.shape[1]
                ring_rows = tier_ring_rows(child_rows)
                if (self.tier_accs[i] is None
                        or self.tier_accs[i].slot.shape[1] < ring_rows):
                    if self.tier_accs[i] is not None:
                        # fold pending rows before replacing the ring
                        (self.tier_stashes[i], _old,
                         self.cascade_lanes) = self.pipe.tier_ring_fold_fn()(
                            self.tier_stashes[i], self.tier_accs[i],
                            self.cascade_lanes,
                        )
                    self.tier_accs[i], self.tier_fills[i] = (
                        self.pipe.init_tier_acc(ring_rows)
                    )
                step_fn = self.pipe.tier_step_fn(ratio)
                (self.tier_stashes[i], self.tier_accs[i],
                 self.tier_fills[i], self.cascade_lanes) = step_fn(
                    self.tier_stashes[i], self.tier_accs[i],
                    self.tier_fills[i], self.cascade_lanes,
                    src, src_total, jnp.uint32(src_hi),
                )
                hi_t = src_hi // ratio
                if hi_t <= self.tier_watermarks[i]:
                    break  # nothing closed here → nothing deeper either
                # flushed parents must see every appended child row
                (self.tier_stashes[i], self.tier_accs[i],
                 self.cascade_lanes) = self.pipe.tier_ring_fold_fn()(
                    self.tier_stashes[i], self.tier_accs[i],
                    self.cascade_lanes,
                )
                self.tier_fills[i] = jax.tree.map(
                    jnp.zeros_like, self.tier_fills[i]
                )
                lo_t = self.tier_watermarks[i]
                # always-compacting tier flush (ISSUE 20): keeps the
                # canonical layout the shared-sort ring fold requires
                self.tier_stashes[i], t_packed, t_totals = (
                    self.pipe.tier_flush_range_fn()(
                        self.tier_stashes[i],
                        jnp.uint32(lo_t), jnp.uint32(hi_t),
                    )
                )
                tier_flushes.append(
                    (i, self._cascade_intervals[i], t_packed, t_totals,
                     lo_t, hi_t)
                )
                self.tier_watermarks[i] = hi_t
                src, src_total, src_hi = t_packed, t_totals, hi_t
        # fold_rows + sketch pend counts + cascade lanes + tier totals
        # ride the totals fetch — ONE scalar vector, zero additional
        # host syncs regardless of tier count
        fr_dev = self._fold_rows_dev
        if fr_dev is None:
            fr_dev = jnp.zeros((d,), jnp.uint32)
        scal_parts = [totals, fr_dev.astype(jnp.int32),
                      pend_n.astype(jnp.int32)]
        if has_wide:
            scal_parts.append(
                jnp.sum(wide_wins != jnp.uint32(SENTINEL_WIN), axis=1)
                .astype(jnp.int32)
            )
        if self._tier_ratios:
            scal_parts.append(self.cascade_lanes.astype(jnp.int32).reshape(-1))
        scal_parts += [tf[3] for tf in tier_flushes]
        pool_on = _pool_mode(self.sketches)
        if pool_on:
            # pool telemetry lanes (ISSUE 20) ride the SAME bundled
            # vector — the sharded mirror of the single-chip CB v7
            # spill/occupancy/promotion lanes, fetch-free like the rest
            occ = (
                jnp.sum(self.sketches.slot_of != jnp.int32(-1), axis=-1)
                + jnp.sum(
                    self.sketches.wide_close != jnp.uint32(SENTINEL_WIN),
                    axis=-1,
                )
            ).astype(jnp.int32)
            scal_parts += [
                self.sketches.pool_spill.astype(jnp.int32),
                self.sketches.pool_promos.astype(jnp.int32),
                occ,
            ]
        bundled = self._fetch(jnp.concatenate(scal_parts))
        if pool_on:
            self.sketch_pool_spill = int(bundled[-3 * d : -2 * d].sum())
            self.sketch_promotions = int(bundled[-2 * d : -d].sum())
            self.sketch_pool_occ = int(bundled[-d:].sum())
        totals_np = bundled[:d]
        self.fold_rows = int(bundled[d : 2 * d].sum())
        pend_np = bundled[2 * d : 3 * d]
        o = 3 * d
        if has_wide:
            wide_np = bundled[o : o + d]
            o += d
        else:
            wide_np = np.zeros((d,), np.int64)
        n_wide = int(wide_np.sum())
        if self._tier_ratios:
            lanes_np = bundled[o : o + 2 * d].reshape(d, 2)
            self.cascade_rows = int(lanes_np[:, 0].sum())
            self.cascade_shed = int(lanes_np[:, 1].sum())
            o += 2 * d
        tier_totals_np = [bundled[o + j * d : o + (j + 1) * d]
                          for j in range(len(tier_flushes))]
        max_t = int(totals_np.max())
        max_p = int(pend_np.max())
        tier_max = [int(t.max()) for t in tier_totals_np]
        if max_t == 0 and max_p == 0 and n_wide == 0 and not tier_flushes:
            # nothing flushed and no tier closed. With tier_flushes
            # non-empty the drain must continue even when every count
            # is zero: the watermarks already advanced, so a tier
            # window whose exact rows were all shed (sketch-only
            # coverage) must release its merged parent block NOW or it
            # leaks forever.
            return []
        row_cols = packed.shape[2]
        wide = pend.shape[2]
        if max_t == 0 and max_p == 0 and n_wide == 0 and not any(tier_max):
            flat = np.zeros((0,), np.uint32)  # nothing to transfer
        else:
            flat_parts = [
                packed[:, :max_t].reshape(-1),
                pend[:, :max_p].reshape(-1),
                pend_win[:, :max_p].reshape(-1),
            ]
            if n_wide:
                # whole [D, Pw] arena — Pw is tiny, so shipping every
                # row and filtering SENTINEL wins on host is cheaper
                # than a device-side compaction dispatch
                flat_parts += [wide_rows.reshape(-1), wide_wins.reshape(-1)]
            for (_, _, t_packed, _, _, _), tm in zip(tier_flushes, tier_max):
                flat_parts.append(t_packed[:, :tm].reshape(-1))
            flat = self._fetch(jnp.concatenate(flat_parts))
        nb = d * max_t * row_cols
        npend = d * max_p * wide
        block = flat[:nb].reshape(d, max_t, row_cols)
        pend_rows = flat[nb : nb + npend].reshape(d, max_p, wide)
        pend_wins = flat[nb + npend : nb + npend + d * max_p].reshape(d, max_p)
        to = nb + npend + d * max_p
        w_rows = w_wins = None
        if n_wide:
            pw, wide_w = wide_rows.shape[1], wide_rows.shape[2]
            w_rows = flat[to : to + d * pw * wide_w].reshape(d, pw, wide_w)
            to += d * pw * wide_w
            w_wins = flat[to : to + d * pw].reshape(d, pw)
            to += d * pw
        tier_blocks = []
        for tm in tier_max:
            tier_blocks.append(
                flat[to : to + d * tm * row_cols].reshape(d, tm, row_cols)
            )
            to += d * tm * row_cols
        merged: dict[int, object] = {}
        for dev in range(d):
            n = int(pend_np[dev])
            for blk in unpack_drained(
                pend_rows[dev, :n], pend_wins[dev, :n], self._sk_cfg
            ):
                have = merged.get(blk.window)
                merged[blk.window] = blk if have is None else have.merge(blk)
        if n_wide:
            # drained wide pool slots (ISSUE 20): merge into the same
            # per-window dict — a window promoted on one device and
            # compact on another unifies here by the r12 algebra
            for dev in range(d):
                keep = w_wins[dev] != np.uint32(SENTINEL_WIN)
                for blk in unpack_drained(
                    w_rows[dev][keep], w_wins[dev][keep], self._sk_cfg
                ):
                    have = merged.get(blk.window)
                    merged[blk.window] = (
                        blk if have is None else have.merge(blk)
                    )
        ordered = [merged[w] for w in sorted(merged)]
        self.sketch_blocks_closed += len(ordered)
        self.sketch_blocks_dropped += hold_blocks(
            self.closed_sketches, ordered, self.max_held_sketches
        )
        if self._tier_ratios:
            # closed child blocks feed the parent merge BEFORE tier
            # windows are built, so a parent closing in this same drain
            # sees every child (merge order immaterial — r12 pins)
            for blk in ordered:
                self._feed_tier_block(0, blk.window, blk)
            self._take_tier_windows(tier_flushes, tier_totals_np, tier_blocks)
        if max_t == 0:
            return []
        per_dev = [
            unpack_flush_rows(block[d, : int(t)], TAG_SCHEMA.num_fields)
            for d, t in enumerate(totals_np)
        ]
        flushed = self._group_rows_by_window(per_dev, self.interval)
        for db in flushed:
            self.total_flushed += db.size
        if self.lineage is not None and flushed:
            self.lineage.note_flush_windows(
                [(int(db.timestamp[0]) // self.interval, db.size)
                 for db in flushed]
            )
        return flushed

    def _group_rows_by_window(self, per_dev, interval: int):
        """Device-major regroup of unpacked flush rows into per-window
        DocBatches — the same row order the per-window flush_window loop
        produced. Shared by the tier-0 drain and the cascade tiers."""
        from ..datamodel.batch import DocBatch
        from ..datamodel.schema import FLOW_METER, TAG_SCHEMA

        flushed = []
        for w in sorted({int(w) for win, *_ in per_dev for w in np.unique(win)}):
            tag_parts = [tags[win == w] for win, _, _, tags, _ in per_dev]
            met_parts = [met[win == w] for win, _, _, _, met in per_dev]
            tags_out = np.concatenate(tag_parts)
            n = tags_out.shape[0]
            flushed.append(
                DocBatch(
                    tags=tags_out,
                    meters=np.concatenate(met_parts),
                    timestamp=np.full((n,), w * interval, dtype=np.uint32),
                    valid=np.ones((n,), dtype=bool),
                    tag_schema=TAG_SCHEMA,
                    meter_schema=FLOW_METER,
                )
            )
        return flushed

    def _feed_tier_block(self, tier: int, window: int, blk) -> None:
        """Merge one closed child block into its parent's pending merge
        (the single-chip TierCascade.feed_block twin — the shared
        merge_into_parent helper keeps the two paths one semantics)."""
        from ..aggregator.cascade import merge_into_parent

        if tier >= len(self._tier_ratios):
            return
        merge_into_parent(
            self._tier_pending_blocks[tier], window,
            self._tier_ratios[tier], blk,
        )

    def _take_tier_windows(self, tier_flushes, tier_totals_np, tier_blocks):
        """Fetched tier rows → per-window tier DocBatches (host-merged
        across devices, window order) + the parents' merged sketch
        blocks; closed tier blocks cascade one level up."""
        from ..aggregator.stash import unpack_flush_rows as _unpack

        for (i, interval, _p, _t, lo_t, hi_t), t_np, rows in zip(
            tier_flushes, tier_totals_np, tier_blocks
        ):
            per_dev = [
                _unpack(rows[dev, : int(t)], TAG_SCHEMA.num_fields)
                for dev, t in enumerate(t_np)
            ]
            batches = self._group_rows_by_window(per_dev, interval)
            self.tier_windows_flushed += len(batches)
            if self.lineage is not None and batches:
                self.lineage.note_tier_windows(
                    [(interval, int(db.timestamp[0]) // interval, db.size)
                     for db in batches]
                )
            self.tier_windows_dropped += hold_blocks(
                self.tier_flushed, [(interval, db) for db in batches],
                self.max_held_tier_windows,
            )
            # marry + release this range's merged parent blocks
            pend = self._tier_pending_blocks[i]
            closed_blocks = []
            for w in sorted(pend):
                if lo_t <= w < hi_t:
                    closed_blocks.append(pend.pop(w))
            for blk in closed_blocks:
                self._feed_tier_block(i + 1, blk.window, blk)
            self.tier_sketch_blocks_dropped += hold_blocks(
                self.closed_tier_sketches, closed_blocks,
                self.max_held_sketches,
            )

    def pop_tier_docbatches(self) -> list:
        """Drain the cascade's closed tier windows as (tier_interval_s,
        DocBatch) pairs, oldest first (ISSUE 9). Merged tier sketch
        blocks accumulate in `closed_tier_sketches`."""
        out, self.tier_flushed = self.tier_flushed, []
        return out

    def settle_tier_rings(self) -> None:
        """Fold every tier accumulator ring into its stash (checkpoint
        rule — ring rows must reach the stash before a snapshot, so the
        rings never serialize)."""
        for i in range(len(self.tier_stashes)):
            if self.tier_accs[i] is not None:
                (self.tier_stashes[i], self.tier_accs[i],
                 self.cascade_lanes) = self.pipe.tier_ring_fold_fn()(
                    self.tier_stashes[i], self.tier_accs[i],
                    self.cascade_lanes,
                )
                self.tier_fills[i] = jax.tree.map(
                    jnp.zeros_like, self.tier_fills[i]
                )

    # -- live read plane (ISSUE 10) --------------------------------------
    def snapshot_open(self, *, force: bool = False):
        """Pull a read-only snapshot of the open window span from every
        device stash + open sketch slot, host-merged: exact rows
        concatenate device-major per window (the same order the real
        drain emits) and per-window sketch blocks merge by the r12
        algebra (register max / counter add / candidate union). The
        device state is untouched — no donation, no advance — so the
        later real flush supersedes these partials row-for-row.

        Same 2-transfer shape as the drain ([D] totals + one
        concatenated row block), rate-limited by
        `min_snapshot_interval`; returns aggregator.window.OpenSnapshot
        with partial=True FlushedWindows."""
        import time as _time

        now = _time.monotonic()
        cached = self._snapshot_cache
        if (
            not force
            and cached is not None
            and now - cached.taken_monotonic < self.min_snapshot_interval
        ):
            return cached
        with self.tracer.span(SPAN_QUERY_SNAPSHOT):
            snap = self._read_open_snapshot(now)
        self.snapshot_seq += 1
        snap.seq = self.snapshot_seq
        if self.lineage is not None and snap.windows:
            self.lineage.note_snapshot(
                [(w.window_idx, w.count) for w in snap.windows]
            )
        self._snapshot_cache = snap
        return snap

    def _read_open_snapshot(self, now: float):
        from ..aggregator.sketchplane import SENTINEL_WIN
        from ..aggregator.stash import unpack_flush_rows
        from ..aggregator.window import FlushedWindow, OpenSnapshot

        if self.start_window is None:
            self.snapshot_reads += 1
            return OpenSnapshot(windows=[], taken_monotonic=now)
        b0 = self.bytes_fetched
        self._fold()  # per-device ring rows → stashes (exact, no fetch)
        packed, totals, blocks, wins = self.pipe.snapshot_open_ranges(
            self.stash, self.sketches, self.start_window
        )
        d = self.pipe.n_devices
        totals_np = self._fetch(totals)
        max_t = int(totals_np.max())
        row_cols = packed.shape[2]
        r, wide = blocks.shape[1], blocks.shape[2]
        flat = self._fetch(
            jnp.concatenate(
                [
                    packed[:, :max_t].reshape(-1),
                    blocks.reshape(-1),
                    wins.reshape(-1),
                ]
            )
        )
        nb = d * max_t * row_cols
        rows = flat[:nb].reshape(d, max_t, row_cols)
        block_rows = flat[nb : nb + d * r * wide].reshape(d, r, wide)
        win_np = flat[nb + d * r * wide :].reshape(d, r)
        per_dev = [
            unpack_flush_rows(rows[dev, : int(t)], TAG_SCHEMA.num_fields)
            for dev, t in enumerate(totals_np)
        ]
        windows: list[FlushedWindow] = []
        for w in sorted({int(w) for win, *_ in per_dev for w in np.unique(win)}):
            hi = np.concatenate([h[win == w] for win, h, _, _, _ in per_dev])
            lo = np.concatenate([l[win == w] for win, _, l, _, _ in per_dev])
            tg = np.concatenate([t[win == w] for win, _, _, t, _ in per_dev])
            mt = np.concatenate([m[win == w] for win, _, _, _, m in per_dev])
            windows.append(
                FlushedWindow(
                    window_idx=w,
                    start_time=w * self.interval,
                    key_hi=hi, key_lo=lo, tags=tg, meters=mt,
                    count=int(tg.shape[0]), partial=True,
                )
            )
        # open sketch slots: host-merge per window across devices (the
        # r12 algebra), then the shared marry rule builds the final list
        merged: dict[int, object] = {}
        for dev in range(d):
            wd = win_np[dev]
            live = wd != np.uint32(SENTINEL_WIN)
            for blk in unpack_drained(
                block_rows[dev][live], wd[live], self._sk_cfg
            ):
                have = merged.get(blk.window)
                merged[blk.window] = blk if have is None else have.merge(blk)
        windows = window_mod.attach_open_sketch_blocks(
            windows, merged,
            interval=self.interval,
            num_tags=TAG_SCHEMA.num_fields,
            num_meters=FLOW_METER.num_fields,
        )
        self.snapshot_reads += 1
        self.snapshot_bytes += self.bytes_fetched - b0
        return OpenSnapshot(
            windows=windows,
            taken_monotonic=now,
            open_from=self.start_window * self.interval,
        )

    def ingest(self, tags, meters, valid):
        """Feed one flow batch (leading dim divisible by device count);
        returns DocBatches for any windows that closed."""
        ts_np = np.asarray(tags["timestamp"])
        valid_np = np.asarray(valid)
        if not valid_np.any():
            return []
        t_max = int(ts_np[valid_np].max())
        if self.start_window is None:
            t_min = int(ts_np[valid_np].min())
            self.start_window = max(0, min(t_min, t_max - self.delay)) // self.interval

        window_np = ts_np // self.interval
        late = valid_np & (window_np < self.start_window)
        n_late = int(late.sum())
        if n_late:
            self.drop_before_window += n_late
            valid = np.asarray(valid) & ~late
        self.total_docs_in += int(valid_np.sum()) - n_late

        # Window advance is decided before the merge: the batch at t_max
        # belongs to the new window, so closing sketch planes first keeps
        # its contributions out of the closing view and inside the fresh
        # one (doc flush still happens after the merge — late rows within
        # `delay` must land in their window before it flushes).
        new_start = max(t_max - self.delay, 0) // self.interval
        advancing = self.start_window < new_start
        close_us, adv_wall = 0, 0.0
        if advancing:
            # the advance's work is split around the append (sketch close
            # BEFORE, fold AFTER) — measured here, emitted below as ONE
            # window.advance span so counts match `window_advances` and
            # single-chip attribution
            adv_wall = time.time()
            t0 = time.perf_counter()
            self.sketches, self.global_view, self.pod_1m = (
                self.pipe.window_close(self.sketches)
            )
            close_us = int((time.perf_counter() - t0) * 1e6)

        per_dev = int(ts_np.shape[0]) // self.pipe.n_devices
        # with the pre-reduce on, every append writes a 4×cap_u block
        # (groupby output capacity is static) regardless of batch size
        cap_u = self.pipe.config.batch_unique_cap
        rows_per_device = FANOUT_LANES * (cap_u if cap_u else per_dev)
        cap = int(self.acc.slot.shape[1]) if self.acc is not None else None
        plan = plan_append(self.fill, cap, rows_per_device)
        if plan == "init":
            self._fold()  # pending rows must reach the stash before the ring is replaced
            if self.fill:
                # plan_append 'init' contract (stash.py): replacing a
                # ring with pending rows silently loses them — trip
                # loudly if a refactor ever bypasses the full fold here
                raise AssertionError(
                    f"accumulator ring re-init with {self.fill} pending "
                    "per-device rows — fold before replacing the ring"
                )
            self.acc = self.pipe.init_acc(max(rows_per_device, 1))
            self.fill = 0
        elif plan == "fold":
            self._fold()
        # .nbytes reads metadata only — np.asarray here would force a
        # device→host transfer per column when callers pass jnp arrays
        nb = lambda a: getattr(a, "nbytes", 0)
        self.bytes_uploaded += (
            sum(nb(v) for v in tags.values()) + nb(meters) + nb(valid)
        )
        def dispatch_once():
            # chaos fires before the sharded step — donated stash/acc/
            # sketch buffers are untouched when a retried fault raises
            chaos.maybe_fail(chaos.SITE_DISPATCH)
            return self.pipe.step(
                self.stash, self.acc, self.fill, self.sketches, tags, meters,
                valid,
                # sketch-plane span bounds (ISSUE 8): the host's gate,
                # and — when this batch advances — the new span start so
                # the step closes the outgoing windows' sketch slots
                # before their ring positions are reclaimed
                start_window=self.start_window or 0,
                close_below=new_start if advancing else 0,
            )

        def on_retry(_attempt, _exc):
            self.dispatch_retries += 1

        lin = self.lineage
        d0 = lin.clock() if lin is not None else 0.0
        with self.tracer.span(SPAN_INGEST_DISPATCH):
            # admission-time-only classification: the step donates its
            # buffers, so a mid-flight UNAVAILABLE/ABORTED must NOT
            # retry against consumed arrays
            self.stash, self.acc, self.sketches = retry_call(
                dispatch_once, self.retry_policy, on_retry=on_retry,
                rng=self._retry_rng, classify=is_dispatch_transient,
            )
        if lin is not None:
            # bind this batch's window span (ts_np is already host —
            # the sharded gate computed it above, no transfer)
            live = valid_np & ~late if n_late else valid_np
            span = None
            if live.any():
                ts_live = ts_np[live]
                span = (int(ts_live.min()) // self.interval,
                        int(ts_live.max()) // self.interval)
            lin.note_dispatch(span, d0)
        self.fill += rows_per_device

        flushed = []
        if advancing:
            t0 = time.perf_counter()
            # flushed windows must see every accumulated row of the
            # closing span; merge mode folds ONLY that span
            if self.pipe.config.fold_mode == "merge":
                self._fold_span(new_start)
            else:
                self._fold()
            self.tracer.record(
                SPAN_WINDOW_ADVANCE,
                close_us + int((time.perf_counter() - t0) * 1e6),
                start_s=adv_wall,
            )
            if lin is not None:
                # sharded advances are decided host-side pre-dispatch:
                # the dispatch stamp above is the derived time base
                lin.note_advance(self.start_window, new_start, (d0, d0))
            with self.tracer.span(SPAN_FLUSH_DRAIN):
                flushed = self._drain_range(self.start_window, new_start)
            self.start_window = new_start
            self.n_advances += 1
        return flushed

    def make_feeder(self, queues, bucket_sizes, config=None, *,
                    journal_dir=None, **kw):
        """Wire this shard group behind a feeder runtime (ISSUE 4: one
        feeder per shard group): TAGGEDFLOW flowframes from `queues`
        coalesce into bucket-shaped flow batches whose sizes divide the
        mesh's device count (feeder/runtime.ShardedFeedSink).

        `journal_dir` (ISSUE 14, per-host ownership): open this host's
        crc-framed FrameJournal under it — the filename carries the
        shard group AND process index (MeshTopology.host_path), so
        kill-and-recover replays ONLY this host's frames. Requires the
        pipeline to have been built from a MeshTopology."""
        from ..feeder import FeederConfig, FeederRuntime, ShardedFeedSink

        if journal_dir is not None:
            if "journal" in kw:
                raise ValueError("pass journal= or journal_dir=, not both")
            from pathlib import Path

            from ..feeder.journal import FrameJournal

            topo = self.pipe.topology
            if topo is None:
                raise ValueError(
                    "journal_dir= needs a MeshTopology-built pipeline — "
                    "per-host journal naming derives from the process index"
                )
            path = topo.host_path(
                Path(journal_dir) / "feeder.journal", group=self.pipe.shard_group
            )
            kw["journal"] = FrameJournal(path)
        return FeederRuntime(
            queues, ShardedFeedSink(self, bucket_sizes),
            config or FeederConfig(), **kw,
        )

    def drain(self):
        """Flush every open window (shutdown path). Advances the open
        span past each drained window so a straggler ingest cannot
        re-open and re-emit it (same invariant as WindowManager.flush_all)."""
        from ..ops.segment import SENTINEL_SLOT

        # shutdown fold stays OUTSIDE window.advance: the span count
        # must equal `window_advances` (cross-path attribution contract;
        # WindowManager.flush_all behaves the same)
        self._fold()
        with self.tracer.span(SPAN_FLUSH_DRAIN):
            flushed = self._drain_range(0, int(SENTINEL_SLOT))
        for db in flushed:
            if self.start_window is not None:
                w = int(db.timestamp[0]) // self.interval
                self.start_window = max(self.start_window, w + 1)
        return flushed
