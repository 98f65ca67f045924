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
from jax.experimental.layout import Layout, with_layout_constraint
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
    WindowSketchBlock,
    _drain_impl as _sketch_drain_impl,
    _flatten_open,
    _pool_mode,
    hold_blocks,
    sketch_init,
    sketch_plane_step,
    unpack_drained,
)
from ..aggregator.window import sketch_inputs_from_columns
from ..utils import hostpool
from ..utils.retry import (
    RetryPolicy,
    decorrelated_rng,
    is_dispatch_transient,
    retry_call,
)
from ..utils.spans import (
    FLUSH_SPAN_NAMES,
    SPAN_FLUSH_DRAIN,
    SPAN_FLUSH_FETCH,
    SPAN_FLUSH_JOIN,
    SPAN_FLUSH_RESERVE,
    SPAN_FLUSH_ROWS,
    SPAN_FLUSH_SKETCH,
    SPAN_FLUSH_SKETCH_MERGE,
    SPAN_FLUSH_SPLIT,
    SPAN_FLUSH_WAIT,
    SPAN_INGEST_DISPATCH,
    SPAN_INGEST_STAGE,
    SPAN_QUERY_SNAPSHOT,
    SPAN_STATS_FETCH,
    SPAN_WINDOW_ADVANCE,
    SPAN_WINDOW_CLOSE_COLLECTIVE,
    SPAN_WINDOW_FOLD,
    JitCacheMonitor,
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
from ..ops.segment import SENTINEL_SLOT, out_blocks_total


# ISSUE 8 unification: the span-global SketchPlanes (hll/cms/hist reset
# at every close) became the PER-WINDOW plane shared with the
# single-chip path — aggregator/sketchplane.SketchState, one ring slot
# per open window plus a pending buffer of closed packed blocks. The
# old attribute names (.hll/.cms/.hist) survive on the new state (with
# a leading [R] ring dim), and `window_close` still returns the merged
# cross-mesh view, so existing consumers keep working; per-window
# blocks additionally drain through `ShardedWindowManager` at every
# advance (merged across devices by window, on the devices with the
# pool off, on the host with it on: `_merged_block_slots`).
SketchPlanes = SketchState


def _row_tiled(state: StashState) -> StashState:
    """A device's stash with its two matrices held to the plain
    `[rows, S]` layout before they are packed for a flush.

    A mesh-sharded `[D, T, S]` array lies on its device as `[1, T, S]`,
    and the TPU lays that out with the size-1 axis second-minor
    (`{2,0,1:T(1,128)}`). Left to itself the compiler then feeds
    `_pack_window_range`'s `[3+T+M, S]` concatenate five operands each
    transposed on its own, every one padded to 128 lanes: 12.9 GB of
    temporaries at 2^22 rows where the one-chip program takes 4.3, and
    the program does not load beside the stash (PERF.md section 7 row 1,
    PR 32's four-chip rehearsal). Held to `{1,0}` the concatenate is the
    one-chip program's: 4.3 GB (compiled for a described v5e:2x2, PR 36).
    The values are untouched; the CPU backend has one layout anyway."""
    plain = Layout(major_to_minor=(0, 1))
    return dataclasses.replace(
        state,
        tags=with_layout_constraint(state.tags, plain),
        meters=with_layout_constraint(state.meters, plain),
    )


def _merged_block_slots(config: "ShardedConfig", n_devices: int) -> int:
    """Windows a sketch drain merges on the devices (0: none, the host
    merges each device's blocks).

    Only full-width pend rows merge there: a compact pool row packs four
    HLL registers to a word, so a max over its words is not the max of
    its registers, and a window can be compact on one device and wide on
    another; with the pool on every block goes to the host as before.
    Otherwise a drain closes only windows of its open span [S, S + ring)
    (`sketch_plane_step` sheds rows past the ring, the host gate those
    before S), and a device at most `sketch_pending` of them: so many
    slots always hold every window of the drain."""
    if config.sketch_pool is not None:
        return 0
    return min(n_devices * config.sketch_pending, config.sketch_ring)


def _merge_closed_blocks(pend, pend_win, pend_n, cfg: SketchConfig, axes,
                         slots: int):
    """One device's drained blocks merged with every other device's,
    by window, inside the drain's shard_map body: `WindowSketchBlock.merge`'s
    algebra on the devices, so that the host fetches one block a window.

    The windows are the sorted union of the devices' live `pend_win`
    (a device that had no row for a window holds no block of it, and
    may hold its others at other positions); each device lines its own
    blocks up under them, an absent one as zeros: registers 0, counters
    0, no candidates. Then, across the mesh: HLL registers `pmax` (as
    int32, the host's type; registers are never negative, so 0 is the
    identity); `n_updates`, count-min and histogram counters summed as
    their 16-bit halves apart, since the u32 words of four devices can
    add up past 2^32 where the host adds them as int64; the top-K lanes
    gathered in device order, for the host's candidate union.

    Returns (rows [slots, W] u32, wins [slots] u32 ascending, SENTINEL
    past the last, n i32), the same on every device. A row is the max
    registers, the low halves' sums and the high halves' sums of
    [n_updates ‖ count-min ‖ histogram], then each device's five top-K
    lanes (`_merged_planes` and `_merged_candidates` unpack it)."""
    p = pend.shape[0]
    gm = cfg.num_groups * cfg.hll_m
    counted = 1 + gm + cfg.cms_depth * cfg.cms_width + cfg.num_groups * cfg.hist.bins
    sentinel = jnp.uint32(SENTINEL_WIN)
    mine = jnp.where(jnp.arange(p) < pend_n, pend_win, sentinel)
    every = jnp.sort(lax.all_gather(mine, axes, tiled=True))
    first = jnp.concatenate([every[:1] != sentinel,
                             (every[1:] != every[:-1]) & (every[1:] != sentinel)])
    at = jnp.where(first, jnp.cumsum(first) - 1, slots)
    wins = jnp.full((slots,), sentinel).at[at].set(every, mode="drop")
    match = (mine[None, :] == wins[:, None]) & (wins[:, None] != sentinel)
    rows = jnp.where(jnp.any(match, axis=1)[:, None],
                     pend[jnp.argmax(match, axis=1)], jnp.uint32(0))
    hll = lax.bitcast_convert_type(rows[:, 1:1 + gm], jnp.int32)
    hll = lax.bitcast_convert_type(lax.pmax(hll, axes), jnp.uint32)
    sums = jnp.concatenate([rows[:, :1], rows[:, 1 + gm:counted]], axis=1)
    lo = lax.psum(sums & jnp.uint32(0xFFFF), axes)
    hi = lax.psum(sums >> jnp.uint32(16), axes)
    cands = rows[:, counted:]
    if cands.shape[1]:
        cands = lax.all_gather(cands, axes, axis=1).reshape(slots, -1)
    merged = jnp.concatenate([hll, lo, hi, cands], axis=1)
    return merged, wins, jnp.sum(first).astype(jnp.int32)


def _merged_planes(row: np.ndarray, cfg: SketchConfig) -> dict:
    """A fetched row of `_merge_closed_blocks`: its window's `n_updates`
    and planes in `WindowSketchBlock`'s types, registers int32 (a view),
    counters int64 (the low halves' sum + the high halves' sum << 16:
    the host merge's int64 sum)."""
    g, gm = cfg.num_groups, cfg.num_groups * cfg.hll_m
    dw = cfg.cms_depth * cfg.cms_width
    n = 1 + dw + g * cfg.hist.bins
    sums = np.left_shift(row[gm + n:gm + 2 * n], 16, dtype=np.int64)
    sums += row[gm:gm + n]
    return {
        "n_updates": int(sums[0]),
        "hll": row[:gm].view(np.int32).reshape(g, cfg.hll_m),
        "cms": sums[1:1 + dw].reshape(cfg.cms_depth, cfg.cms_width),
        "hist": sums[1 + dw:].reshape(g, cfg.hist.bins),
    }


def _merged_candidates(row: np.ndarray, cfg: SketchConfig, n_devices: int) -> dict:
    """The same row's candidate union: devices 0..D-1, each one's lanes
    with votes > 0, in the order `WindowSketchBlock.merge` concatenates
    them."""
    k = cfg.topk_rows * cfg.topk_cols
    tk = row[row.shape[0] - 5 * n_devices * k:].reshape(n_devices, 5, k)
    votes = tk[:, 0].astype(np.int32).astype(np.int64)
    keep = votes > 0
    hi, lo, ida, idb = (tk[:, i][keep].astype(np.uint32) for i in range(1, 5))
    return {"tk_hi": hi, "tk_lo": lo, "tk_ida": ida, "tk_idb": idb,
            "tk_votes": votes[keep]}


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
        # windows a drain merges on the devices; 0 = the host merges
        self.merged_block_slots = _merged_block_slots(config, self.n_devices)
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

        # the jitted programs' names are what a profile's "XLA Modules"
        # line shows (`jit_<name>`): the step's, the fold's and the range
        # flush's begin as their one-chip twins' do (`jit_step…`,
        # `jit__fold…`, `jit__flush_range…`), so a reader that groups
        # modules by prefix finds the sharded programs where it finds
        # those (chipbench/trace_groups.json)
        def step_sharded(stash, acc, offset, sk, tag_mat, meters, valid,
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
            step_sharded,
            mesh=self.mesh,
            in_specs=(pspec, pspec, P(), pspec, pspec, pspec, pspec, P(), P()),
            out_specs=(pspec, pspec, pspec),
        )
        return jax.jit(mapped, donate_argnums=(0, 1, 3))

    def _build_fold(self):
        sum_cols = tuple(int(i) for i in np.nonzero(FLOW_METER.sum_mask)[0])
        max_cols = tuple(int(i) for i in np.nonzero(FLOW_METER.max_mask)[0])
        merge = self.config.fold_mode == "merge"

        def _fold_sharded(stash, acc, hi_window):
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
                lanes[None, :2],  # [fold_rows, fold_blocks] a device
            )

        pspec = P(self.axes)
        mapped = shard_map(
            _fold_sharded,
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
        return self.step_staged(
            stash, acc, offset, sketches, self.stage(tags, meters, valid),
            start_window, close_below,
        )

    def stage(self, tags, meters, valid):
        """A batch's three uploads, dealt over the mesh: (tag_mat
        [D, T, n] u32, meters [D, n, M], valid [D, n]) for
        `step_staged`."""
        d = self.n_devices
        spec = NamedSharding(self.mesh, P(self.axes))

        def deal(x):
            # each device's share goes from the host to that device; an
            # array put on the default device first would reach the
            # others through it
            x = x if isinstance(x, jax.Array) else np.asarray(x)
            return jax.device_put(x.reshape((d, -1) + x.shape[1:]), spec)

        if self._tag_names is None:
            self._tag_names = tuple(sorted(tags))
        # pack the ~25 tag columns into ONE upload (see step_sharded)
        mat = np.stack(
            [np.asarray(tags[k], dtype=np.uint32) for k in self._tag_names]
        )  # [T, D*n]
        t, total = mat.shape
        tag_mat = jax.device_put(
            np.ascontiguousarray(mat.reshape(t, d, total // d).transpose(1, 0, 2)),
            spec,
        )  # [D, T, n]
        return tag_mat, deal(meters), deal(valid)

    def step_staged(self, stash, acc, offset, sketches, staged,
                    start_window: int = 0, close_below: int = 0):
        """Dispatch the step on what `stage` uploaded."""
        return self._step(
            stash, acc, jnp.int32(offset), sketches, *staged,
            jnp.uint32(start_window), jnp.uint32(close_below),
        )

    def fold(self, stash, acc, hi_window=None):
        """Amortized per-device fold of accumulated rows into the stash
        (host fires it at accum_batches cadence and before flushes).
        Returns (stash, acc, lanes [D, 2] u32 — the rows each device's
        fold keyed-sort touched and the trip count of its output loop).
        `hi_window` (fold_mode="merge" only) span-bounds the fold to acc
        rows with slot < hi_window; the rest stay accumulated — callers
        must NOT reset their fill cursor."""
        if hi_window is not None and self.config.fold_mode != "merge":
            raise ValueError("span-bounded fold requires fold_mode='merge'")
        hi = jnp.uint32(SENTINEL_SLOT if hi_window is None else hi_window)
        return self._fold(stash, acc, hi)

    # -- window close ---------------------------------------------------
    def _build_window_close(self):
        axes = self.axes

        def window_close_sharded(sk: SketchState):
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
            window_close_sharded,
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
        transfers. Where `merged_block_slots` is over 0 (the pool off)
        the devices' closed blocks merge by window across the mesh in
        the same call (`_merge_closed_blocks`), and what comes back in
        place of each device's own pend is the merged blocks, the same
        on every device."""
        axes, slots = self.axes, self.merged_block_slots
        sk_cfg = self.config.sketch_config()

        def sketch_drain_sharded(sk, close_w):
            sk1 = jax.tree.map(lambda x: x[0], sk)
            new_sk, pend, pend_win, n, wide_rows, wide_wins = (
                _sketch_drain_impl(sk1, close_w)
            )
            if slots:
                pend, pend_win, n = _merge_closed_blocks(
                    pend, pend_win, n, sk_cfg, axes, slots
                )
            expand = lambda x: x[None]
            return (
                jax.tree.map(expand, new_sk),
                pend[None], pend_win[None], n[None],
                wide_rows[None], wide_wins[None],
            )

        pspec = P(self.axes)
        mapped = shard_map(
            sketch_drain_sharded,
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
        block (win == SENTINEL_WIN rows are dead — host filters). With
        `merged_block_slots` over 0 the three pend outputs are the
        merged blocks instead: [D, slots, W] rows, their windows
        ascending, and their count, each device's copy the same."""
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
            stash1 = _row_tiled(jax.tree.map(lambda x: x[0], stash))
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

        def _flush_range_sharded(stash, lo, hi):
            stash1 = _row_tiled(jax.tree.map(lambda x: x[0], stash))
            new_state, packed, total = _flush_range_impl(
                stash1, lo, hi, compact=compact
            )
            expand = lambda x: x[None]
            return jax.tree.map(expand, new_state), packed[None], total[None]

        pspec = P(self.axes)
        mapped = shard_map(
            _flush_range_sharded,
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
            stash1 = _row_tiled(jax.tree.map(lambda x: x[0], stash))
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


class _DevicePages:
    """The first `counts[d]` rows (along axis 1) of device d's slice of
    the mesh-sharded `x [D, S, ...]`, for every device: what a sharded
    drain fetches of one matrix.

    The pages are `window._PagedRows`' (fixed shape along axis 1, one
    `_take_page` program a matrix shape whatever the counts; `x` itself
    where it is under one page), cut at the largest count. What is
    fetched of a page is each device's OWN shard, and only where that
    device still has rows in it: `handles` are those single-device
    arrays, and the host gets each as it lies on its device. Fetching a
    page as one `[D, page, ...]` array would have the runtime assemble
    the shards into a fresh host array first, a copy (on a TPU a
    transpose) of every row that `join_rows` then copies again."""

    def __init__(self, x, counts, page_rows: int | None = None,
                 views: bool = False):
        self.counts = [int(c) for c in counts]
        # `join` hands each device's rows on as a list of views of the
        # fetched pages, joined into nothing
        self.views = views
        paged = window_mod._PagedRows(
            x, max(self.counts, default=0), axis=1, page_rows=page_rows
        )
        self.page, self.last_start = paged.page, paged.last_start
        self.row_bytes = x.nbytes // max(x.shape[0] * x.shape[1], 1)
        self.n_pages = len(paged.pages)
        self._no_rows = ((0,) + x.shape[2:], x.dtype)
        self.handles, self._where = [], []
        for i, pg in enumerate(paged.pages):
            shards = sorted(
                pg.addressable_shards, key=lambda sh: sh.index[0].start or 0
            )
            for dev, sh in enumerate(shards):
                if i * self.page < self.counts[dev]:
                    self.handles.append(sh.data)
                    self._where.append((dev, i))
        # what the last join saw and did, as `_PagedRows` keeps them
        self.order: str | None = None
        self.landed = False
        self.joined_bytes = self.copied_bytes = self.pooled_bytes = 0

    @property
    def rows_fetched(self) -> int:
        return len(self.handles) * self.page

    @property
    def rows_live(self) -> int:
        return sum(self.counts)

    def _cuts(self, fetched: list) -> list[list[np.ndarray]]:
        """Device by device, the live rows of its fetched shards."""
        cuts: list[list[np.ndarray]] = [[] for _ in self.counts]
        for (dev, i), arr in zip(self._where, fetched):
            s = i * self.page
            at = min(s, self.last_start)  # where the page really starts
            cuts[dev].append(arr[0][s - at : min(self.counts[dev], s + self.page) - at])
        return cuts

    def _copy(self, cut: list, out: np.ndarray) -> np.ndarray:
        """The cuts written into `out` (`hostpool.copy_cuts`: over a few
        threads where the copy is large), counted."""
        self.copied_bytes += out.nbytes
        if hostpool.copy_cuts(cut, out) > 1:
            self.pooled_bytes += out.nbytes
        return out

    def _fresh(self, cut: list) -> np.ndarray:
        """The cuts joined into a fresh array of their memory order."""
        out = window_mod._fresh_for(cut)
        self.joined_bytes += out.nbytes
        return self._copy(cut, out)

    def join(self, fetched: list) -> list:
        """Each device's rows as one host array (a view of the fetched
        shard where one page held them), or with `views` as a list of
        its rows, each a view of a fetched shard."""
        out = []
        self.joined_bytes = self.copied_bytes = self.pooled_bytes = 0
        for cut in self._cuts(fetched):
            if self.views:
                out.append([row for c in cut for row in c])
            elif not cut:
                out.append(np.zeros(*self._no_rows))
            elif len(cut) == 1:
                out.append(cut[0])
            else:
                out.append(self._fresh(cut))
        return out

    def join_rows(self, fetched: list, dst: np.ndarray | None = None) -> np.ndarray:
        """Every device's rows as ONE host matrix, device-major (device
        d's rows start at sum(counts[:d])), in the shards' memory order:
        written into `dst` where that reserved destination holds them
        and has their order (`window._PagedRows.join`'s rule), else into
        a fresh array; a view where one shard held them all."""
        cut = [c for dev in self._cuts(fetched) for c in dev]
        self.order, self.landed, self.joined_bytes = None, False, 0
        self.copied_bytes = self.pooled_bytes = 0
        if not cut:
            return np.zeros(*self._no_rows)
        self.order = window_mod._memory_order(cut[0])
        n = self.rows_live
        if (dst is not None and n <= dst.shape[0]
                and window_mod._memory_order(dst) == self.order):
            self.landed = True
            return self._copy(cut, dst[:n])
        if len(cut) == 1:
            return cut[0]
        return self._fresh(cut)


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
        self.step_done = None  # a handle that is ready once the last step ran
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
        self._fold_rows_dev = None  # [D, 2]: fold_rows, fold_blocks
        # what the one-chip manager reads from its per-batch counter
        # block, mirrored here once a drain from the same bundled fetch
        # and summed over devices: the share of the stashes' blocks the
        # last fold's output loops ran, the stashes' live rows as the
        # advance's fold left them (before the range flush takes the
        # closing windows out), segments the stashes shed, rows the
        # sketch planes took and shed, and the document rows the folds
        # took out of the rings (`doc_in`: post-fanout, post-pre-reduce)
        self.fold_blocks_run_sum = 0
        self.fold_blocks_total_sum = 0
        self._fold_blocks_total = pipe.n_devices * out_blocks_total(
            pipe.config.capacity_per_device
        )
        self.stash_live_rows_sum = 0
        self.stash_capacity_rows_sum = 0
        self.stash_evictions = 0
        self.sketch_rows = 0
        self.sketch_shed = 0
        self.doc_in = 0
        # [D] u32, ring rows folded so far (it wraps; the drains add its
        # steps), and what the last drain read of it
        self._doc_rows_dev = None
        self._doc_rows_seen = np.zeros((pipe.n_devices,), np.int64)
        # the drains' paged row fetch and host half, under the one-chip
        # manager's names (window.WindowManager.get_counters)
        self.flush_pages = 0
        self.flush_rows_fetched = 0
        self.flush_rows_live = 0
        self.flush_rows_reserved = 0
        self.flush_host_write_bytes = 0
        self.flush_host_pass_bytes = 0
        self.flush_pooled_bytes = 0
        self.sketch_bytes_fetched = 0
        self.sketch_bytes_live = 0
        # exact rows handed over, every device's partial rows counted,
        # and the devices that had rows for a drain, summed over drains
        self.flush_partial_rows = 0
        self.flush_devices_with_rows = 0
        # (window, rows each device gave it), one entry a base-interval
        # DocBatch handed over, oldest first: a DocBatch's rows are
        # device-major, and this says where each device's partial rows
        # begin. Bounded like `closed_sketches` (drop-oldest)
        self.partial_row_counts: list = []
        self._drain_rows_per_window = 0  # of the last drain, all devices
        self._drain_order = "C"  # memory order its pages came in
        # retrace gate for the sharded step: one expected compile a batch
        # shape (a feeder sink names its buckets: `expect_batch_shapes`)
        self._jit = JitCacheMonitor(pipe._step)
        # merged sketch views of the last closed window (None until one closes)
        self.global_view = None
        self.pod_1m = None
        # per-window sketch tier (ISSUE 8): closed blocks merged across
        # devices, in window order. BOUNDED drop-oldest-counted
        # (like the device pending buffer) so an undrained consumer
        # cannot leak a block per window forever.
        self.closed_sketches: list = []
        self.max_held_sketches = 512
        self.sketch_blocks_closed = 0
        self.sketch_blocks_dropped = 0
        # of the closed blocks, those that came off the devices merged
        self.sketch_blocks_device_merged = 0
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
        self.bytes_fetched += (
            sum(a.nbytes for a in arr) if isinstance(arr, list) else arr.nbytes
        )
        return arr

    def expect_batch_shapes(self, n: int) -> None:
        """The batch shapes this manager will be fed (a feeder's
        buckets): the step compiles once for each, and only a compile
        beyond them counts as a retrace."""
        self._jit.expected_compiles = max(1, int(n))

    def get_counters(self) -> dict:
        """Countable face — host ints only, safe from a ticking thread.

        `flow_in` counts PRE-fanout flow rows (the sharded late gate
        runs on raw flows host-side); the single-chip `doc_in` counts
        post-fanout doc rows — deliberately different names so the two
        planes cannot be misread as the same funnel stage. `doc_in` is
        here too since PR 36, counted where the rows exist: document
        rows the folds took out of the devices' rings, as of the last
        drain."""
        xla_compiles, xla_compile_us = self.tracer.compile_lanes()
        flush_compiles, flush_compile_us = self.tracer.compile_lanes(FLUSH_SPAN_NAMES)
        return {
            # backend compiles charged to this manager's spans, all of
            # them and those under flush.drain (a close that compiles
            # for a document count shows here); jit_compiles /
            # jit_retraces watch the sharded step alone
            "xla_compiles": xla_compiles,
            "xla_compile_us": xla_compile_us,
            "flush_compiles": flush_compiles,
            "flush_compile_us": flush_compile_us,
            **self._jit.get_counters(),
            "flow_in": self.total_docs_in,
            "doc_in": self.doc_in,
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
            "fold_blocks_run_sum": self.fold_blocks_run_sum,
            "fold_blocks_total_sum": self.fold_blocks_total_sum,
            "stash_live_rows_sum": self.stash_live_rows_sum,
            "stash_capacity_rows_sum": self.stash_capacity_rows_sum,
            "stash_evictions": self.stash_evictions,
            "host_fetches": self.host_fetches,
            "bytes_fetched": self.bytes_fetched,
            "bytes_uploaded": self.bytes_uploaded,
            # the drains' paged row fetch (fetched − live is the
            # over-fetch, under one page a device a part) and host half
            "flush_pages": self.flush_pages,
            "flush_rows_fetched": self.flush_rows_fetched,
            "flush_rows_live": self.flush_rows_live,
            "flush_rows_reserved": self.flush_rows_reserved,
            "flush_host_write_bytes": self.flush_host_write_bytes,
            "flush_host_pass_bytes": self.flush_host_pass_bytes,
            "flush_pooled_bytes": self.flush_pooled_bytes,
            "flush_partial_rows": self.flush_partial_rows,
            "flush_devices_with_rows": self.flush_devices_with_rows,
            "sketch_bytes_fetched": self.sketch_bytes_fetched,
            "sketch_bytes_live": self.sketch_bytes_live,
            "sketch_rows": self.sketch_rows,
            "sketch_shed": self.sketch_shed,
            "dispatch_retries": self.dispatch_retries,
            "fetch_retries": self.fetch_retries,
            # per-window sketch tier (ISSUE 8): closed blocks merged
            # across devices so far, blocks awaiting a consumer, and
            # the drop-oldest overflow count (non-zero = nobody drains
            # pop_closed_sketches)
            "sketch_blocks_closed": self.sketch_blocks_closed,
            "sketch_blocks_device_merged": self.sketch_blocks_device_merged,
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
        """Drain the merged closed WindowSketchBlocks (window
        order). The sketch twin of the DocBatches `ingest` returns."""
        out, self.closed_sketches = self.closed_sketches, []
        return out

    def pop_partial_row_counts(self) -> list:
        """Drain the (window, [D] partial rows a device) records of the
        base-interval DocBatches handed over so far (window order)."""
        out, self.partial_row_counts = self.partial_row_counts, []
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
            "lanes": [self._fold_rows_dev, self._doc_rows_dev, self.step_done],
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
        self._dispatch_fold()
        self.fill = 0

    def _fold_span(self, hi_window: int):
        """Span-bounded advance fold (fold_mode="merge"): fold only acc
        rows with slot < hi_window; `fill` stays put (consumed rows turn
        sentinel in place — the next full fold reclaims the ring)."""
        if self.fill == 0 or self.acc is None:
            return
        self._dispatch_fold(np.uint32(hi_window))

    def _ring_rows(self):
        """[D] u32 document rows in the devices' rings (a dispatch, no
        fetch): invalid rows are sentinel-keyed at append time."""
        return jnp.sum(
            self.acc.slot != jnp.uint32(SENTINEL_SLOT), axis=1, dtype=jnp.uint32
        )

    def _dispatch_fold(self, hi_window=None) -> None:
        """The fold's dispatch, and the rows it took out of the rings
        added to the device-side `doc_in` handle the next drain's bundled
        fetch reads (a running sum, so that the add is a program of every
        fold after the first and is compiled with the first close). A
        full fold empties the rings; a span-bounded one leaves rows
        behind."""
        with self.tracer.span(SPAN_WINDOW_FOLD):
            took = self._ring_rows()
            self.stash, self.acc, self._fold_rows_dev = self.pipe.fold(
                self.stash, self.acc, hi_window=hi_window
            )
            if hi_window is not None:
                took = took - self._ring_rows()
            self._doc_rows_dev = (
                took if self._doc_rows_dev is None else self._doc_rows_dev + took
            )

    def _reserve_rows(self, packed, windows: int) -> np.ndarray | None:
        """The host matrix this drain's exact rows, every device's, will
        be joined into, made and touched BEFORE the blocking scalar
        fetch, while the devices run the fold and the range flush:
        `window.WindowManager._reserve_rows`' rule over the rows of all
        devices (the last drain's rows a window x the `windows` this one
        can hold rows of, plus `reserve_rows`' margin, never more than
        the stashes, in the memory order that drain's shards came in).
        None with no history or where the expected rows fit one page."""
        d, size, cols = packed.shape
        rows = min(window_mod.reserve_rows(self._drain_rows_per_window * windows),
                   d * size)
        if rows <= min(window_mod.PAGE_ROWS, size):
            return None
        with self.tracer.span(SPAN_FLUSH_RESERVE):
            dst, workers = hostpool.touched_rows(rows, cols, self._drain_order)
        self.flush_host_write_bytes += dst.nbytes
        self.flush_host_pass_bytes += dst.nbytes
        self.flush_pooled_bytes += dst.nbytes * (workers > 1)
        return dst

    def _fetch_parts(self, parts: "list[_DevicePages]", dst) -> list:
        """Every shard of every page of every part of a drain in ONE
        fetch. The first part is the exact rows: it comes back as one
        device-major matrix (joined into `dst` where that holds it), the
        others as a list of each device's rows. No pages, no fetch."""
        handles = [h for part in parts for h in part.handles]
        got = iter(())
        if handles:
            with self.tracer.span(SPAN_FLUSH_FETCH):
                got = iter(self._fetch(handles))
        with self.tracer.span(SPAN_FLUSH_JOIN):
            take = lambda part: [next(got) for _ in part.handles]
            return [parts[0].join_rows(take(parts[0]), dst)] + [
                part.join(take(part)) for part in parts[1:]
            ]

    def _drain_range(self, lo: int, hi: int):
        """Flush [lo, hi) from every device stash in one fused call and
        regroup the packed rows into per-window DocBatches; the sketch
        tier's closed blocks (ISSUE 8) drain in the SAME two transfers,
        merged across devices by window (one merged block a window off
        device 0 where the drain merged them, else each device's, merged
        here) into `closed_sketches`.

        Host pays: ONE scalar vector (`flush.wait`, the manager's one
        counter sync: `stats.fetch`) + ONE list of fixed-size pages
        (`flush.rows`: each matrix is read through `_DevicePages`, so
        nothing dispatched here has a shape that depends on a count,
        and each device's shard of a page comes to the host as it lies
        on the device) — independent of how many windows closed. The
        exact rows are written to host memory once, device-major, into
        a destination reserved under the wait (`_reserve_rows`), and a
        drain that closed one window hands that matrix on as views."""
        from ..aggregator.stash import unpack_flush_rows

        d = self.pipe.n_devices
        # the stashes' live rows as the advance's fold left them
        occ = jnp.sum(self.stash.valid, axis=1, dtype=jnp.uint32)
        self.stash, packed, totals = self.pipe.flush_range(
            self.stash, np.uint32(lo), np.uint32(hi)
        )
        # forced close at `hi`: every device closes the same windows at
        # this drain even if its shard never saw the advancing timestamp
        (self.sketches, pend, pend_win, pend_n,
         wide_rows, wide_wins) = self.pipe.sketch_drain(self.sketches, hi)
        # pooled wide slots (ISSUE 20): Pw > 0 only in pool mode; their
        # per-device close counts ride the scalar vector and the (tiny)
        # [D, Pw] arena joins the row fetch only when something closed
        has_wide = wide_rows.shape[1] > 0
        tier_flushes = self._cascade_on_drain(packed, totals, int(hi))
        # everything the host needs to know before it fetches a row rides
        # ONE u32 vector of [D]-lanes: the counts, the fold's and the
        # sketch plane's lanes, the stashes' live rows and sheds, the
        # rings' folded rows — zero additional host syncs whatever is on
        with self.tracer.span(SPAN_FLUSH_WAIT):
            u32 = lambda x: x.astype(jnp.uint32).reshape(-1)
            zeros = jnp.zeros((d,), jnp.uint32)
            lanes = self._fold_rows_dev
            lanes = jnp.zeros((d, 2), jnp.uint32) if lanes is None else lanes
            doc_rows = self._doc_rows_dev
            scal_parts = [
                u32(totals), u32(lanes[:, 0]), u32(lanes[:, 1]), u32(pend_n),
                occ, u32(self.stash.dropped_overflow),
                u32(self.sketches.rows), u32(self.sketches.shed),
                zeros if doc_rows is None else doc_rows,
            ]
            if has_wide:
                scal_parts.append(u32(
                    jnp.sum(wide_wins != jnp.uint32(SENTINEL_WIN), axis=1)
                ))
            if self._tier_ratios:
                scal_parts.append(u32(self.cascade_lanes))
            scal_parts += [u32(tf[3]) for tf in tier_flushes]
            pool_on = _pool_mode(self.sketches)
            if pool_on:
                # pool telemetry lanes (ISSUE 20) ride the SAME bundled
                # vector — the sharded mirror of the single-chip CB v7
                # spill/occupancy/promotion lanes
                scal_parts += [
                    u32(self.sketches.pool_spill),
                    u32(self.sketches.pool_promos),
                    u32(
                        jnp.sum(self.sketches.slot_of != jnp.int32(-1), axis=-1)
                        + jnp.sum(
                            self.sketches.wide_close != jnp.uint32(SENTINEL_WIN),
                            axis=-1,
                        )
                    ),
                ]
            vec = jnp.concatenate(scal_parts)
            # windows this drain can hold rows of: an advance's hi - lo,
            # at most the open span (`drain` names a wider range)
            windows = min(int(hi) - int(lo), self.delay // self.interval + 1)
            reserved = self._reserve_rows(packed, windows)
            with self.tracer.span(SPAN_STATS_FETCH):
                bundled = self._fetch(vec).astype(np.int64)
            lane = iter(bundled.reshape(-1, d))
            totals_np = next(lane)
            self.fold_rows = int(next(lane).sum())
            self.fold_blocks_run_sum += int(next(lane).sum())
            self.fold_blocks_total_sum += self._fold_blocks_total
            pend_np = next(lane)
            if self.pipe.merged_block_slots:
                # the merged blocks, the same on every device: device 0's
                pend_np[1:] = 0
            self.stash_live_rows_sum += int(next(lane).sum())
            self.stash_capacity_rows_sum += d * packed.shape[1]
            self.stash_evictions = int(next(lane).sum())
            self.sketch_rows = int(next(lane).sum())
            self.sketch_shed = int(next(lane).sum())
            doc_rows_np = next(lane)
            self.doc_in += int(((doc_rows_np - self._doc_rows_seen) % (1 << 32)).sum())
            self._doc_rows_seen = doc_rows_np
            n_wide = int(next(lane).sum()) if has_wide else 0
            if self._tier_ratios:
                lanes_np = np.concatenate([next(lane), next(lane)]).reshape(d, 2)
                self.cascade_rows = int(lanes_np[:, 0].sum())
                self.cascade_shed = int(lanes_np[:, 1].sum())
            tier_totals_np = [next(lane) for _ in tier_flushes]
            if pool_on:
                self.sketch_pool_spill = int(next(lane).sum())
                self.sketch_promotions = int(next(lane).sum())
                self.sketch_pool_occ = int(next(lane).sum())
        total = int(totals_np.sum())
        if (total == 0 and not pend_np.any() and n_wide == 0
                and not tier_flushes):
            # nothing flushed and no tier closed. With tier_flushes
            # non-empty the drain must continue even when every count
            # is zero: the watermarks already advanced, so a tier
            # window whose exact rows were all shed (sketch-only
            # coverage) must release its merged parent block NOW or it
            # leaks forever.
            return []
        with self.tracer.span(SPAN_FLUSH_ROWS):
            exact = _DevicePages(packed, totals_np)
            # a page of ONE block: a device that holds one closed block
            # sends that block, not all of `pend`; merged blocks are
            # read where they were fetched
            blocks = _DevicePages(pend, pend_np, page_rows=1,
                                  views=bool(self.pipe.merged_block_slots))
            sk_parts = [(blocks, int(pend_np.sum()))]
            parts = [exact, blocks, _DevicePages(pend_win, pend_np)]
            if n_wide:
                # whole [D, Pw] arena — Pw is tiny, so shipping every
                # row and filtering SENTINEL wins on host is cheaper
                # than a device-side compaction dispatch
                pw = [wide_rows.shape[1]] * d
                sk_parts.append((_DevicePages(wide_rows, pw), n_wide))
                parts += [sk_parts[-1][0], _DevicePages(wide_wins, pw)]
            parts += [_DevicePages(tf[2], t)
                      for tf, t in zip(tier_flushes, tier_totals_np)]
            self.flush_pages += sum(p.n_pages for p in parts)
            self.flush_rows_fetched += sum(p.rows_fetched for p in parts)
            self.flush_rows_live += sum(p.rows_live for p in parts)
            for part, wanted in sk_parts:
                self.sketch_bytes_fetched += part.rows_fetched * part.row_bytes
                self.sketch_bytes_live += wanted * part.row_bytes
            got = iter(self._fetch_parts(parts, reserved))
            self.flush_host_write_bytes += sum(p.joined_bytes for p in parts)
            self.flush_host_pass_bytes += sum(p.copied_bytes for p in parts)
            self.flush_pooled_bytes += sum(p.pooled_bytes for p in parts)
            self._drain_rows_per_window = -(-total // max(windows, 1))
            self._drain_order = exact.order or self._drain_order
            if exact.landed:
                self.flush_rows_reserved += total
            self.flush_partial_rows += total
            self.flush_devices_with_rows += int((totals_np > 0).sum())
            rows = next(got)
            pend_rows, pend_wins = next(got), next(got)
            wide = (next(got), next(got)) if n_wide else None
            tier_blocks = list(got)
        with self.tracer.span(SPAN_FLUSH_SPLIT):
            with self.tracer.span(SPAN_FLUSH_SKETCH):
                if self.pipe.merged_block_slots:
                    ordered = self._device_merged_blocks(pend_rows[0], pend_wins[0])
                else:
                    ordered = self._host_merged_blocks(pend_rows, pend_wins, wide)
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
                self._take_tier_windows(tier_flushes, tier_blocks)
            if total == 0:
                return []
            flushed = self._group_rows_by_window(
                unpack_flush_rows(rows, TAG_SCHEMA.num_fields), totals_np,
                self.interval,
            )
        for db in flushed:
            self.total_flushed += db.size
        if self.lineage is not None and flushed:
            self.lineage.note_flush_windows(
                [(int(db.timestamp[0]) // self.interval, db.size)
                 for db in flushed]
            )
        return flushed

    def _device_merged_blocks(self, rows, wins) -> list:
        """The drain's blocks as the devices merged them, one a window
        in window order, each unpacked once; the candidate union over
        the devices' gathered top-K lanes is the merge's host work left
        (`flush.sketch_merge`). A window no device updated is dropped,
        as `unpack_drained` drops such a block."""
        cfg, d = self._sk_cfg, self.pipe.n_devices
        planes = [_merged_planes(row, cfg) for row in rows]
        blocks = []
        with self.tracer.span(SPAN_FLUSH_SKETCH_MERGE):
            for i, plane in enumerate(planes):
                cands = _merged_candidates(rows[i], cfg, d)
                if plane["n_updates"] or len(cands["tk_hi"]):
                    blocks.append(WindowSketchBlock(
                        window=int(wins[i]), config=cfg, **plane, **cands))
        self.sketch_blocks_device_merged += len(blocks)
        return blocks

    def _host_merged_blocks(self, pend_rows, pend_wins, wide) -> list:
        """Each device's drained blocks unpacked and merged by window on
        the host (the pool on: `_merged_block_slots`), in window order."""
        d = self.pipe.n_devices
        per_dev = [
            unpack_drained(pend_rows[dev], pend_wins[dev], self._sk_cfg)
            for dev in range(d)
        ]
        if wide is not None:
            # drained wide pool slots (ISSUE 20) merge into the
            # same per-window dict — a window promoted on one
            # device and compact on another unifies here by the
            # r12 algebra
            for dev in range(d):
                keep = wide[1][dev] != np.uint32(SENTINEL_WIN)
                per_dev.append(unpack_drained(
                    wide[0][dev][keep], wide[1][dev][keep], self._sk_cfg
                ))
        merged: dict[int, object] = {}
        with self.tracer.span(SPAN_FLUSH_SKETCH_MERGE):
            for blk in (b for blocks_ in per_dev for b in blocks_):
                have = merged.get(blk.window)
                merged[blk.window] = blk if have is None else have.merge(blk)
        return [merged[w] for w in sorted(merged)]

    def _cascade_on_drain(self, packed, totals, hi: int) -> list:
        """Rollup cascade (ISSUE 9): fold this drain's packed flush rows
        into the per-device tier stashes and flush every tier window
        that closed — pure dispatches; the outputs join the drain's two
        transfers. Returns one entry a tier that flushed: (tier idx,
        interval, packed [D, St, C], totals [D], lo_t, hi_t).

        TWIN CONTRACT with TierCascade.on_advance (cascade.py): this
        loop mirrors it over [D]-shaped state — lazy ring sizing with
        a pre-growth fold, tier_step, the hi_t <= watermark early
        break, the MANDATORY ring fold before every tier flush, and
        tier chaining. A semantic change to either loop must land in
        both (the kernels themselves are already shared)."""
        tier_flushes = []
        if not self._tier_ratios:
            return tier_flushes
        from ..aggregator.cascade import tier_ring_rows

        src, src_total, src_hi = packed, totals, hi
        for i, ratio in enumerate(self._tier_ratios):
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
        return tier_flushes

    def _group_rows_by_window(self, unpacked, counts, interval: int):
        """One device-major matrix of unpacked flush rows (device d's
        `counts[d]` rows start at sum(counts[:d]), each device's in
        (window, stash position) order) → per-window DocBatches whose
        rows are device-major too: the order the per-window
        flush_window loop produced. A window whose rows lie together in
        the matrix (a drain that closed one window: all of it) is handed
        on as views of it; one whose rows are a run a device is
        concatenated, once. Shared by the tier-0 drain and the cascade
        tiers."""
        from ..datamodel.batch import DocBatch

        win, _hi, _lo, tags, meters = unpacked
        runs: dict[int, list[tuple[int, int]]] = {}
        per_dev: dict[int, list[int]] = {}
        off = 0
        for dev, n in enumerate(int(c) for c in counts):
            if n:
                w = win[off : off + n]
                bounds = np.flatnonzero(np.r_[True, w[1:] != w[:-1]]).tolist() + [n]
                for a, b in zip(bounds, bounds[1:]):
                    runs.setdefault(int(w[a]), []).append((off + a, off + b))
                    per_dev.setdefault(int(w[a]), [0] * len(counts))[dev] += b - a
            off += n
        if interval == self.interval:
            hold_blocks(self.partial_row_counts, sorted(per_dev.items()), 4096)
        flushed = []
        for w in sorted(runs):
            spans = runs[w]
            if all(b == a2 for (_, b), (a2, _) in zip(spans, spans[1:])):
                a, b = spans[0][0], spans[-1][1]
                tags_out, meters_out = tags[a:b], meters[a:b]
            else:
                tags_out = np.concatenate([tags[a:b] for a, b in spans])
                meters_out = np.concatenate([meters[a:b] for a, b in spans])
                self.flush_host_write_bytes += tags_out.nbytes + meters_out.nbytes
            n = tags_out.shape[0]
            flushed.append(
                DocBatch(
                    tags=tags_out,
                    meters=meters_out,
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

    def _take_tier_windows(self, tier_flushes, tier_blocks):
        """Fetched tier rows (each tier's a list of its devices' rows) →
        per-window tier DocBatches (host-merged across devices, window
        order) + the parents' merged sketch blocks; closed tier blocks
        cascade one level up."""
        from ..aggregator.stash import unpack_flush_rows as _unpack

        for (i, interval, _p, _t, lo_t, hi_t), rows in zip(
            tier_flushes, tier_blocks
        ):
            counts = [r.shape[0] for r in rows]
            joined = rows[0] if len(rows) == 1 else np.concatenate(rows)
            batches = self._group_rows_by_window(
                _unpack(joined, TAG_SCHEMA.num_fields), counts, interval
            )
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
        # the open rows in fixed-size pages, as the drain reads them: no
        # program here has a shape that depends on a row count
        exact = _DevicePages(packed, totals_np)
        got = self._fetch(exact.handles + [blocks, wins])
        rows = exact.join_rows(got[:-2])
        block_rows, win_np = got[-2], got[-1]
        win, key_hi, key_lo, tags, meters = unpack_flush_rows(
            rows, TAG_SCHEMA.num_fields
        )
        # device by device: (offset, its rows' windows)
        offs = np.concatenate([[0], np.cumsum(exact.counts)])
        per_dev = [(int(offs[dev]), win[offs[dev] : offs[dev + 1]])
                   for dev in range(d)]
        windows: list[FlushedWindow] = []
        for w in sorted({int(w) for _, wd in per_dev for w in np.unique(wd)}):
            at = np.concatenate(
                [off + np.flatnonzero(wd == w) for off, wd in per_dev]
            )
            windows.append(
                FlushedWindow(
                    window_idx=w,
                    start_time=w * self.interval,
                    key_hi=key_hi[at], key_lo=key_lo[at],
                    tags=tags[at], meters=meters[at],
                    count=int(at.shape[0]), partial=True,
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
            # single-chip attribution; the collective's dispatch is a
            # span of its own besides
            adv_wall = time.time()
            t0 = time.perf_counter()
            with self.tracer.span(SPAN_WINDOW_CLOSE_COLLECTIVE):
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
        with self.tracer.span(SPAN_INGEST_STAGE):
            staged = self.pipe.stage(tags, meters, valid)

        def dispatch_once():
            # chaos fires before the sharded step — donated stash/acc/
            # sketch buffers are untouched when a retried fault raises
            chaos.maybe_fail(chaos.SITE_DISPATCH)
            return self.pipe.step_staged(
                self.stash, self.acc, self.fill, self.sketches, staged,
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
        self._jit.poll()
        # ready once this step has run: a one-column read of the ring it
        # wrote (its own outputs are donated to the next step). Taken
        # here, on every path into the manager, so that the program
        # behind it is compiled by whatever warms the step up
        self.step_done = self.acc.slot[:, :1]
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
