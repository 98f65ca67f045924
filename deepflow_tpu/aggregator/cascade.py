"""Device-side multi-resolution rollup cascade (ISSUE 9).

The reference server keeps 1s AND 1m series and downsamples 1m→1h→1d
(datasource/handle.go); the old `DualGranularityPipeline` reproduced
that by ingesting every batch TWICE — a full second device dispatch
into a parallel minute pipeline — doubling the hot-path work r6–r12
spent five PRs shrinking. This module replaces the second ingest with a
*fold of closed tier-0 windows*, the split-resolution-across-tiers
design of "Sketch Disaggregation Across Time and Space" (PAPERS.md):

  * **Exact meters**: every window advance already compacts the closing
    1s windows into ONE packed [S, 3+T+M] u32 flush matrix on device
    (stash.stash_flush_range). The cascade consumes that SAME device
    array before the host fetches it: one jitted sort + segment-reduce
    re-keys each row to its parent window (slot // ratio, key words
    unchanged — doc fingerprints carry no timestamp, fanout.py zeroes
    it) and merges it into a bounded per-tier StashState with exactly
    tier 0's overflow semantics (newest-window shed, counted). A 1m
    tier window therefore closes as the fold of its ≤60 closed 1s
    windows; the 1h tier folds closed 1m flush rows the same way.

  * **Sketches**: closed 1s `WindowSketchBlock`s merge host-side per
    parent window via the existing r12 algebra (HLL register max / CMS
    add / hist add / top-K candidate union — all pinned associative +
    commutative in tests/test_sketches.py), so merge-of-60 equals
    build-over-60 and the minute tier keeps the shed-degrades-detail-
    not-coverage contract.

  * **Host-sync budget**: tier folds and tier flushes are extra device
    DISPATCHES on the advance path only; their outputs ride the advance
    drain's existing two transfers (the scalar fetch widens by one lane
    per tier, the row fetch concatenates tier rows) — the ≤3-fetch
    steady-state budget is untouched (tests/test_perf_gate.py gates it
    with the cascade ON, single-chip and sharded).

Tier-close rule: parent window p of a ratio-r tier closes when every
child window < (p+1)·r has closed, i.e. when tier 0's advance target
`hi` satisfies p < hi // r. Late-row admission is therefore tier 0's:
a row too late for its second is too late for its minute (the old
double-ingest's separate `minute_delay` gate no longer exists — the
compat shim documents this).

Counter lanes: the cascade maintains a device [2] u32 lane vector
(cumulative rows folded into tiers, cumulative tier-stash overflow
sheds) that rides the fused append step's counter block (CB v5,
CB_CASCADE_ROWS / CB_CASCADE_SHED) — zero extra fetches.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from jax import lax

from ..datamodel.schema import MeterSchema, TagSchema
from ..ops.segment import SENTINEL_SLOT, _use_shared_sort, _varying_like
from .sketchplane import WindowSketchBlock
from .stash import (
    AccumState,
    StashState,
    _append_impl,
    _merge_impl,
    _sorted_merge_reduce,
    accum_init,
    stash_flush_range,
    stash_init,
    unpack_flush_rows,
)

_U32_MAX = np.uint32(0xFFFFFFFF)


@dataclasses.dataclass(frozen=True)
class CascadeConfig:
    """Tier layout: `intervals` are the coarser resolutions to maintain
    above the owning manager's base interval, ascending, each an exact
    multiple of the previous (e.g. (60,) for a 1m tier over a 1s
    pipeline, (60, 3600) to add an hourly tier). `capacity` bounds each
    tier's stash rows — overflow sheds newest-window keys, counted
    (the exact stance tier 0 has)."""

    intervals: tuple[int, ...] = (60,)
    capacity: int = 1 << 14

    def __post_init__(self):
        if not self.intervals:
            raise ValueError("CascadeConfig.intervals must name ≥1 tier")
        if list(self.intervals) != sorted(set(self.intervals)):
            raise ValueError(
                f"cascade intervals must be ascending unique, got {self.intervals}"
            )
        if self.capacity <= 0:
            raise ValueError("cascade capacity must be positive")

    def validate_base(self, base_interval: int) -> None:
        prev = base_interval
        for iv in self.intervals:
            if iv % prev != 0 or iv <= prev:
                raise ValueError(
                    f"cascade tier {iv}s is not a proper multiple of the "
                    f"previous resolution {prev}s — parent windows would "
                    "not align with child window boundaries"
                )
            prev = iv

    def meta(self) -> dict:
        """JSON-able form for checkpoint meta (v5)."""
        return {"intervals": list(self.intervals), "capacity": self.capacity}

    @classmethod
    def from_meta(cls, m: dict) -> "CascadeConfig":
        return cls(intervals=tuple(m["intervals"]), capacity=m["capacity"])


def _parent_columns(packed, hi, *, ratio: int, num_tags: int):
    """Traced: split a packed flush matrix into parent-keyed fold
    columns — (parent, key_hi, key_lo, tags [T, P], meters [M, P],
    valid). Rows with window < `hi` are exactly the rows that flushed
    (dead rows carry SENTINEL, still-open rows windows ≥ hi — the
    advance protocol guarantees lo ≤ every live slot); each re-keys to
    its parent window (window // ratio, key words unchanged — doc
    fingerprints carry no timestamp)."""
    cols = jnp.transpose(packed)  # [3+T+M, P]
    slot = cols[0]
    valid = slot < hi
    parent = jnp.where(valid, slot // jnp.uint32(ratio), _U32_MAX)
    tags = cols[3 : 3 + num_tags]
    meters = lax.bitcast_convert_type(cols[3 + num_tags :], jnp.float32)
    return parent, cols[1], cols[2], tags, meters, valid


def _acc_valid(acc) -> jnp.ndarray:
    return acc.slot != jnp.uint32(SENTINEL_SLOT)


def _ring_fold_impl(tier: StashState, acc, lanes, sum_cols_t, max_cols_t,
                    shared_sort: bool = False):
    """Merge the tier accumulator ring into the tier stash and reset
    it. Overflow sheds count into lanes[1] (CB_CASCADE_SHED).

    With `shared_sort` (static; the DEEPFLOW_SHARED_SORT knob, ISSUE
    20) the fold reuses the dispatch-owned order: the tier stash is
    ALREADY (slot, key)-sorted — every producer keeps the canonical
    layout (groupby reduces, compact=True tier flushes) — so only the
    ring's [A] rows sort and rank-merge against the standing run
    (stash._sorted_merge_reduce, the merge-fold body) instead of a
    second full [S+A] 3-key sort. Bit-exact vs the full-sort path
    (same reduce, same overflow stance)."""
    prev_dropped = tier.dropped_overflow
    if shared_sort:
        valid = _acc_valid(acc)
        na_sl = jnp.where(valid, acc.slot, jnp.uint32(SENTINEL_SLOT))
        na_hi = jnp.where(valid, acc.key_hi, _U32_MAX)
        na_lo = jnp.where(valid, acc.key_lo, _U32_MAX)
        a_iota = jnp.arange(acc.capacity, dtype=jnp.int32)
        a_sl, a_hi, a_lo, a_perm = lax.sort(
            (na_sl, na_hi, na_lo, a_iota), num_keys=3
        )
        new_tier = _sorted_merge_reduce(
            tier, na_sl, na_hi, na_lo, a_sl, a_hi, a_lo, a_perm,
            acc.tags, acc.meters, sum_cols_t, max_cols_t,
        )
    else:
        new_tier = _merge_impl(
            tier, acc.slot, acc.key_hi, acc.key_lo, acc.tags, acc.meters,
            _acc_valid(acc), sum_cols_t, max_cols_t,
        )
    new_acc = dataclasses.replace(
        acc, slot=jnp.full((acc.capacity,), SENTINEL_SLOT, dtype=jnp.uint32)
    )
    shed = (new_tier.dropped_overflow - prev_dropped).astype(jnp.uint32)
    return new_tier, new_acc, lanes + jnp.stack([jnp.uint32(0), shed])


tier_ring_fold = partial(
    jax.jit,
    static_argnames=("sum_cols_t", "max_cols_t", "shared_sort"),
    donate_argnums=(0, 1, 2),
)(_ring_fold_impl)


def _tier_step_impl(tier: StashState, acc, fill, lanes, packed, total, hi,
                    *, ratio: int, num_tags: int, sum_cols_t, max_cols_t,
                    prefix: int, shared_sort: bool = False):
    """One advance's closed rows into the tier — tier 0's own
    append/amortize architecture one level up.

    A naive per-advance merge re-sorts (and re-gathers the full payload
    of) the whole tier stash for every advance, even though a steady
    1-window advance flushes a few thousand rows. Instead the step
    APPENDS: the flushed rows sit in the positional prefix [0, total)
    of `packed` (flush compaction), so when total ≤ `prefix` the step
    copies packed[:prefix] — parent-re-keyed, out-of-range rows
    sentinel-masked — into the tier accumulator ring at the
    device-resident `fill` cursor (one dynamic_update_slice, the same
    bandwidth-bound shape as the ingest append) and the expensive merge
    runs once per ~A/prefix advances. `lax.cond` picks between:

      * append       — total ≤ prefix and the ring has room;
      * fold+append  — total ≤ prefix, ring full: merge the ring into
                       the stash first, then append at 0;
      * direct fold  — total > prefix (multi-window jump / shutdown
                       drain): merge ring + the FULL packed matrix in
                       one sort, ring resets.

    All control state (`fill`) is device-resident — the host never
    needs to know which branch ran. Bit-exact by construction: every
    closed row either lands in the ring (and merges at the next fold)
    or merges directly; `tier_ring_fold` runs before every tier flush
    so flushed parents always see every child. Lane 0 counts rows at
    arrival, lane 1 tier-stash sheds at folds."""
    hi = jnp.asarray(hi, jnp.uint32)
    total = jnp.asarray(total, jnp.int32)
    A = acc.capacity
    prev_dropped = tier.dropped_overflow

    pp, ph, pl, pt, pm, pv = _parent_columns(
        packed[:prefix], hi, ratio=ratio, num_tags=num_tags
    )
    n_small = jnp.sum(pv).astype(jnp.uint32)
    zero_fill = _varying_like(jnp.int32(0), fill)

    def append(tier, acc, fill, lanes):
        acc = _append_impl(acc, pp, ph, pl, pt, pm, pv, fill)
        return tier, acc, fill + prefix, lanes + jnp.stack(
            [n_small, jnp.uint32(0)]
        )

    def fold_then_append(tier, acc, fill, lanes):
        tier, acc, lanes = _ring_fold_impl(
            tier, acc, lanes, sum_cols_t, max_cols_t,
            shared_sort=shared_sort,
        )
        return append(tier, acc, zero_fill, lanes)

    def direct_fold(tier, acc, fill, lanes):
        fp, fh, fl, ft, fm, fv = _parent_columns(
            packed, hi, ratio=ratio, num_tags=num_tags
        )
        new_tier = _merge_impl(
            tier,
            jnp.concatenate([acc.slot, fp]),
            jnp.concatenate([acc.key_hi, fh]),
            jnp.concatenate([acc.key_lo, fl]),
            jnp.concatenate([acc.tags, ft], axis=1),
            jnp.concatenate([acc.meters, fm], axis=1),
            jnp.concatenate([_acc_valid(acc), fv]),
            sum_cols_t, max_cols_t,
        )
        new_acc = dataclasses.replace(
            acc,
            slot=_varying_like(
                jnp.full((A,), SENTINEL_SLOT, dtype=jnp.uint32), acc.slot
            ),
        )
        shed = (new_tier.dropped_overflow - prev_dropped).astype(jnp.uint32)
        folded = jnp.sum(fv).astype(jnp.uint32)
        return new_tier, new_acc, zero_fill, lanes + jnp.stack(
            [folded, shed]
        )

    if prefix >= packed.shape[0]:
        # degenerate geometry (tiny child stash): always direct-fold
        return direct_fold(tier, acc, fill, lanes)
    return lax.cond(
        total > prefix,
        direct_fold,
        lambda t, a, f, l: lax.cond(
            f + prefix > A, fold_then_append, append, t, a, f, l
        ),
        tier, acc, fill, lanes,
    )


tier_step = partial(
    jax.jit,
    static_argnames=("ratio", "num_tags", "sum_cols_t", "max_cols_t",
                     "prefix", "shared_sort"),
    donate_argnums=(0, 1, 3),
)(_tier_step_impl)


def tier_prefix(child_capacity: int) -> int:
    """Per-advance append width: HALF the child stash. The prefix must
    cover a typical advance's flushed rows or the step degenerates to
    the direct-fold branch every time (a multi-window advance can
    flush a large fraction of live keys — 1/8 proved too tight under
    the §14 workload); half covers everything short of a full-stash
    drain while still halving the worst-case sort."""
    return max(child_capacity // 2, 256)


def tier_ring_rows(child_capacity: int) -> int:
    """Tier accumulator ring capacity: 4 appends between merges — the
    amortization factor on the merge's full-stash payload rewrite."""
    return 4 * tier_prefix(child_capacity)


def merge_into_parent(pending: dict, window: int, ratio: int,
                      block: WindowSketchBlock) -> None:
    """THE parent-block merge, shared by TierCascade and the sharded
    manager: re-window the child block onto its parent index
    (merge() asserts same-window, so the first child anchors a copy)
    and fold it into the pending merge via the r12 algebra."""
    parent = window // ratio
    reblk = dataclasses.replace(block, window=parent)
    have = pending.get(parent)
    pending[parent] = reblk if have is None else have.merge(reblk)


@dataclasses.dataclass
class TierFlush:
    """One tier's closed-window flush handles, produced at an advance
    and drained (fetched) with the same transfers as the tier-0 rows."""

    tier: int  # 0-based index into CascadeConfig.intervals
    interval: int  # seconds per tier window
    packed: jnp.ndarray  # [S, 3+T+M] u32 device handle
    total: jnp.ndarray  # scalar i32 device handle
    lo: int  # closed parent-window range [lo, hi)
    hi: int


class TierCascade:
    """Per-manager cascade state: one bounded StashState per tier, the
    host watermarks (parent windows flushed so far), the device counter
    lanes and the host-side per-parent sketch merge. Single-chip; the
    sharded twin lives in parallel/sharded.py (per-device tier fold,
    host-merge at drain)."""

    def __init__(self, config: CascadeConfig, base_interval: int,
                 tag_schema: TagSchema, meter_schema: MeterSchema):
        config.validate_base(base_interval)
        self.config = config
        self.base_interval = base_interval
        self.tag_schema = tag_schema
        self.meter_schema = meter_schema
        self.num_tags = tag_schema.num_fields
        self.sum_cols = tuple(int(i) for i in np.nonzero(meter_schema.sum_mask)[0])
        self.max_cols = tuple(int(i) for i in np.nonzero(meter_schema.max_mask)[0])
        # child→tier window ratio per tier (tier 0 folds base windows)
        res = (base_interval,) + tuple(config.intervals)
        self.ratios = tuple(res[i + 1] // res[i] for i in range(len(config.intervals)))
        self.tiers: list[StashState] = [
            stash_init(config.capacity, tag_schema, meter_schema)
            for _ in config.intervals
        ]
        # per-tier accumulator ring + device fill cursor (tier 0's
        # append/amortize architecture one level up — see tier_step):
        # ring capacity = the child stash size, so ~8 steady advances
        # append before one merge. Sized lazily per tier because tier
        # i>0's child is the PREVIOUS tier's stash, not tier 0's.
        self.accs: list[AccumState | None] = [None] * len(config.intervals)
        self.fills: list[jnp.ndarray] = [
            jnp.zeros((), jnp.int32) for _ in config.intervals
        ]
        # first parent window NOT yet flushed, per tier (host ints)
        self.watermarks: list[int] = [0] * len(config.intervals)
        # device [rows, shed] lane vector — rides the counter block
        self.lanes_dev = jnp.zeros((2,), jnp.uint32)
        # host-side sketch tier: parent window → merged child block,
        # per tier (tier i's closed blocks feed tier i+1's pending)
        self.pending_blocks: list[dict[int, WindowSketchBlock]] = [
            {} for _ in config.intervals
        ]
        self.tier_windows_flushed = 0  # host counter (all tiers)

    # -- device side (advance path) --------------------------------------
    def on_advance(self, packed, total, hi: int) -> list[TierFlush]:
        """Fold the advance's packed flush matrix through the tiers and
        flush every tier window that closed. `packed`/`total` are the
        tier-0 flush matrix + its device row count; `hi` tier 0's new
        span start (windows < hi closed). Pure device dispatches —
        nothing here fetches; the returned TierFlush handles ride the
        drain's bundled transfers.

        TWIN CONTRACT: ShardedWindowManager._drain_range mirrors this
        loop over per-device state — a semantic change here (ring
        sizing, the close rule, the pre-flush ring fold, chaining)
        must land there too."""
        out: list[TierFlush] = []
        src, src_total, src_hi = packed, total, int(hi)
        # per-dispatch knob capture, the single-chip convention (the
        # sharded twin captures at build time)
        shared_sort = _use_shared_sort()
        for i, ratio in enumerate(self.ratios):
            child_rows = src.shape[0]
            ring_rows = tier_ring_rows(child_rows)
            if self.accs[i] is None or self.accs[i].capacity < ring_rows:
                if self.accs[i] is not None:
                    # a grown child stash would overflow the old ring —
                    # fold pending rows in before replacing it
                    self.tiers[i], _old, self.lanes_dev = tier_ring_fold(
                        self.tiers[i], self.accs[i], self.lanes_dev,
                        sum_cols_t=self.sum_cols, max_cols_t=self.max_cols,
                        shared_sort=shared_sort,
                    )
                self.accs[i] = accum_init(
                    ring_rows, self.tag_schema, self.meter_schema
                )
                self.fills[i] = jnp.zeros((), jnp.int32)
            self.tiers[i], self.accs[i], self.fills[i], self.lanes_dev = (
                tier_step(
                    self.tiers[i], self.accs[i], self.fills[i],
                    self.lanes_dev, src, src_total, np.uint32(src_hi),
                    ratio=ratio, num_tags=self.num_tags,
                    sum_cols_t=self.sum_cols, max_cols_t=self.max_cols,
                    prefix=tier_prefix(child_rows),
                    shared_sort=shared_sort,
                )
            )
            hi_t = src_hi // ratio
            if hi_t <= self.watermarks[i]:
                break  # nothing closed at this tier → nothing deeper either
            # the flushed parents must see every appended child row —
            # the amortized merge runs now (once per tier close)
            self.tiers[i], self.accs[i], self.lanes_dev = tier_ring_fold(
                self.tiers[i], self.accs[i], self.lanes_dev,
                sum_cols_t=self.sum_cols, max_cols_t=self.max_cols,
                shared_sort=shared_sort,
            )
            self.fills[i] = jnp.zeros((), jnp.int32)
            lo_t = self.watermarks[i]
            # compact=True UNCONDITIONALLY (ISSUE 20): the tier stash
            # must keep the canonical sorted-prefix layout the
            # shared-sort ring fold rank-merges against. Safe — the
            # watermark protocol guarantees lo_t ≤ every live parent
            # slot, and the flushed output is identical either way.
            self.tiers[i], t_packed, t_total = stash_flush_range(
                self.tiers[i], np.uint32(lo_t), np.uint32(hi_t),
                compact=True,
            )
            out.append(TierFlush(
                tier=i, interval=self.config.intervals[i],
                packed=t_packed, total=t_total, lo=lo_t, hi=hi_t,
            ))
            self.watermarks[i] = hi_t
            src, src_total, src_hi = t_packed, t_total, hi_t
        return out

    # -- host side (drain path) ------------------------------------------
    def feed_block(self, tier: int, window: int, block: WindowSketchBlock) -> None:
        """Merge one closed child block into its parent's pending merge
        (tier 0 children feed tier index 0; a closed tier-i window's
        merged block feeds tier i+1). The merge is the r12 algebra —
        register max / counter add / candidate union — so fold order
        never matters."""
        if tier >= len(self.ratios):
            return
        merge_into_parent(
            self.pending_blocks[tier], window, self.ratios[tier], block
        )

    def take_tier_windows(self, tf: TierFlush, rows: np.ndarray, total: int):
        """Fetched tier flush rows → FlushedWindow list (window order),
        marrying each parent's merged sketch block; parents in [lo, hi)
        whose exact rows were all shed but whose children had sketch
        blocks become sketch-only windows (count == 0 — the same
        coverage contract as tier 0). Closed blocks cascade one level
        up before leaving."""
        from .window import FlushedWindow  # cycle: window.py imports us

        i = tf.tier
        flushed: list[FlushedWindow] = []
        if total:
            win, key_hi, key_lo, tags, meters = unpack_flush_rows(
                rows, self.num_tags
            )
            bounds = np.flatnonzero(
                np.r_[True, win[1:] != win[:-1]]
            ).tolist() + [total]
            for a, b in zip(bounds, bounds[1:]):
                w = int(win[a])
                flushed.append(FlushedWindow(
                    window_idx=w, start_time=w * tf.interval,
                    key_hi=key_hi[a:b], key_lo=key_lo[a:b],
                    tags=tags[a:b], meters=meters[a:b], count=b - a,
                    tier=i + 1, interval=tf.interval,
                ))
        for f in flushed:
            f.sketches = self.pending_blocks[i].pop(f.window_idx, None)
        exact = {f.window_idx for f in flushed}
        for w in sorted(self.pending_blocks[i]):
            if tf.lo <= w < tf.hi and w not in exact:
                blk = self.pending_blocks[i].pop(w)
                flushed.append(FlushedWindow(
                    window_idx=w, start_time=w * tf.interval,
                    key_hi=np.zeros((0,), np.uint32),
                    key_lo=np.zeros((0,), np.uint32),
                    tags=np.zeros((0, self.num_tags), np.uint32),
                    meters=np.zeros(
                        (0, self.meter_schema.num_fields), np.float32
                    ),
                    count=0, sketches=blk, tier=i + 1, interval=tf.interval,
                ))
        flushed.sort(key=lambda f: f.window_idx)
        for f in flushed:
            if f.sketches is not None:
                self.feed_block(i + 1, f.window_idx, f.sketches)
        self.tier_windows_flushed += len(flushed)
        return flushed

    # -- shutdown / checkpoint -------------------------------------------
    def settle_rings(self) -> None:
        """Fold every tier accumulator ring into its stash — the
        checkpoint rule the main ingest ring follows too: ring rows
        must reach the stash before a snapshot, so the rings need no
        serialization (restore re-initializes them empty). Merge
        output order is deterministic given contents (the fold sorts
        by (slot, key)), so fold batching never shows in flush rows."""
        for i in range(len(self.tiers)):
            if self.accs[i] is not None:
                self.tiers[i], self.accs[i], self.lanes_dev = tier_ring_fold(
                    self.tiers[i], self.accs[i], self.lanes_dev,
                    sum_cols_t=self.sum_cols, max_cols_t=self.max_cols,
                    shared_sort=_use_shared_sort(),
                )
                self.fills[i] = jnp.zeros((), jnp.int32)

    def flush_hi(self) -> int:
        """The tier-0 `hi` that closes every tier window (flush_all)."""
        return int(_U32_MAX)

    def get_counters(self) -> dict:
        """Host ints only (the fetch-free Countable stance) — the device
        lane mirrors live on the owning manager (CB v5)."""
        return {
            "cascade_tiers": len(self.config.intervals),
            "cascade_tier_windows": self.tier_windows_flushed,
            "cascade_pending_blocks": sum(
                len(p) for p in self.pending_blocks
            ),
        }


# ---------------------------------------------------------------------------
# checkpoint support (format v5) — block (de)serialization for the
# host-side pending sketch merges; tier stashes pack through the same
# pack_u32_columns layout as tier 0 (checkpoint.py drives it).

_BLOCK_FIELDS = ("hll", "cms", "hist", "tk_hi", "tk_lo", "tk_ida",
                 "tk_idb", "tk_votes")


def pending_block_arrays(pending: list[dict]) -> tuple[list, dict]:
    """(meta list, arrays dict) for every pending parent block — open
    minute/hour windows' partially-merged sketches must survive a
    checkpoint or a mid-minute kill silently drops the already-folded
    children's approximate state (the recovery pin's exact scenario).
    `pending` is the per-tier parent→block dict list (TierCascade's or
    the sharded manager's — both share this layout)."""
    meta, arrays = [], {}
    for tier, pend in enumerate(pending):
        for w, blk in sorted(pend.items()):
            key = f"cascblk_{tier}_{w}"
            meta.append({"tier": tier, "window": w, "key": key,
                         "n_updates": blk.n_updates})
            for f in _BLOCK_FIELDS:
                arrays[f"{key}_{f}"] = np.asarray(getattr(blk, f))
    return meta, arrays


def restore_pending_blocks(pending: list[dict], meta: list, arrays: dict,
                           sketch_config) -> None:
    for m in meta:
        key = m["key"]
        blk = WindowSketchBlock(
            window=int(m["window"]), config=sketch_config,
            n_updates=int(m["n_updates"]),
            **{f: arrays[f"{key}_{f}"] for f in _BLOCK_FIELDS},
        )
        pending[int(m["tier"])][int(m["window"])] = blk


__all__ = [
    "CascadeConfig",
    "TierCascade",
    "TierFlush",
    "tier_step",
    "tier_ring_fold",
    "tier_prefix",
    "tier_ring_rows",
    "merge_into_parent",
    "pending_block_arrays",
    "restore_pending_blocks",
]
