"""Device-resident document stash.

The TPU analogue of the reference's per-window `HashMap<StashKey, Document>`
(collector.rs:806-822) and `QuadrupleStash` (quadruple_generator.rs:233):
a fixed-capacity, HBM-resident table of (window slot, 64-bit key, tag row,
meter row), kept sorted by (slot, key) as an invariant *by construction* —
every merge re-sorts the concatenation of stash and batch, reduces
duplicate keys with the schema's SUM/MAX ops, and keeps the first
`capacity` segments. Sentinel-keyed rows (empty / flushed) sort to the end
and are reclaimed by the same compaction.

Overflow policy: segments beyond capacity are dropped and counted
(`dropped_overflow`). Because the sort is (slot, key)-ordered, drops land
on the *newest* window's keys — older windows (about to flush) are never
evicted. This mirrors the reference's backpressure stance of shedding
newest data under overload (OverwriteQueue, libs/queue/queue.go:139)
while protecting closing windows.

Two fold strategies share this file (ARCHITECTURE.md "Fold strategies"):
the full-sort fold (`_fold_impl` — re-sorts the [S+A] concat, the
oracle) and the incremental merge-fold (`_merge_fold_impl` — sorts only
the accumulator and rank-merges it against the standing stash order,
optionally span-bounded for window advances). `WindowConfig.fold_mode`
picks one; they are pinned bit-exact against each other.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from jax import lax

from ..datamodel.schema import MeterSchema, TagSchema
from ..ops.segment import (
    SENTINEL_SLOT,
    groupby_reduce,
    groupby_reduce_sorted,
    merge_order,
    merge_ranks,
    out_blocks_run,
)

_U32_MAX = np.uint32(0xFFFFFFFF)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class StashState:
    slot: jnp.ndarray  # [S] u32 absolute window index (SENTINEL = empty)
    key_hi: jnp.ndarray  # [S] u32
    key_lo: jnp.ndarray  # [S] u32
    tags: jnp.ndarray  # [T, S] u32 (column-major — see ops/segment.py)
    meters: jnp.ndarray  # [M, S] f32
    valid: jnp.ndarray  # [S] bool
    dropped_overflow: jnp.ndarray  # scalar i32, running count of shed segments

    @property
    def capacity(self) -> int:
        return self.slot.shape[0]


def stash_init(capacity: int, tag_schema: TagSchema, meter_schema: MeterSchema) -> StashState:
    return StashState(
        slot=jnp.full((capacity,), SENTINEL_SLOT, dtype=jnp.uint32),
        key_hi=jnp.zeros((capacity,), dtype=jnp.uint32),
        key_lo=jnp.zeros((capacity,), dtype=jnp.uint32),
        tags=jnp.zeros((tag_schema.num_fields, capacity), dtype=jnp.uint32),
        meters=jnp.zeros((meter_schema.num_fields, capacity), dtype=jnp.float32),
        valid=jnp.zeros((capacity,), dtype=bool),
        dropped_overflow=jnp.zeros((), dtype=jnp.int32),
    )


def _merge_impl(state: StashState, slot, key_hi, key_lo, tags_t, meters_t, valid, sum_cols_t, max_cols_t):
    s = state.capacity
    sum_cols = np.asarray(sum_cols_t, dtype=np.int32)
    max_cols = np.asarray(max_cols_t, dtype=np.int32)

    # groupby_reduce consumes row-major meters; the stash keeps its
    # column-major layout (free column selection at flush), so the fold
    # transposes here — at fold scale this replaces the row-gather the
    # reduce no longer performs, and XLA folds it into that copy.
    with jax.named_scope("fold.concat"):
        all_slot = jnp.concatenate([state.slot, slot])
        all_hi = jnp.concatenate([state.key_hi, key_hi])
        all_lo = jnp.concatenate([state.key_lo, key_lo])
        all_tags = jnp.concatenate([state.tags, tags_t], axis=1)
        all_meters = jnp.transpose(
            jnp.concatenate([state.meters, meters_t], axis=1)
        )
        all_valid = jnp.concatenate([state.valid, valid])

    # the group-by names its own stages (fold.sort / .segments /
    # .reduce / .compact, ops/segment.py)
    g = groupby_reduce(
        all_slot, all_hi, all_lo, all_tags, all_meters, all_valid,
        sum_cols, max_cols, out_capacity=s,
    )

    dropped = jnp.maximum(g.num_segments - s, 0)
    new_state = StashState(
        slot=g.slot,
        key_hi=g.key_hi,
        key_lo=g.key_lo,
        tags=g.tags,
        meters=g.meters,
        valid=g.seg_valid,
        dropped_overflow=state.dropped_overflow + dropped,
    )
    return new_state


_merge = partial(
    jax.jit, static_argnames=("sum_cols_t", "max_cols_t"), donate_argnums=(0,)
)(_merge_impl)


def stash_merge(
    state: StashState,
    slot,
    key_hi,
    key_lo,
    tags,
    meters,
    valid,
    meter_schema: MeterSchema,
) -> StashState:
    """Merge a doc batch into the stash (one sort of [S+N] rows).

    tags/meters are column-major ([T, N] / [M, N])."""
    sum_cols = tuple(int(i) for i in np.nonzero(meter_schema.sum_mask)[0])
    max_cols = tuple(int(i) for i in np.nonzero(meter_schema.max_mask)[0])
    return _merge(state, slot, key_hi, key_lo, tags, meters, valid, sum_cols, max_cols)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class AccumState:
    """Raw-row accumulator in front of the stash.

    The reference pays a hash-map probe per document per batch
    (Stash::add, collector.rs:810). A sort-based stash that re-sorts
    [S+N] rows per batch pays the whole O((S+N) log(S+N)) sort per batch
    instead — measured on v5e the sort is overhead-dominated (3.3 ms at
    32k rows but only 4.0 ms at 131k, PERF.md), so the TPU-native shape
    is: *append* each batch into this fixed ring (one
    dynamic_update_slice, bandwidth-bound) and amortize ONE sort+reduce
    over many batches (`collector_fold`), triggered on capacity or
    window close. Invalid rows are sentinel-keyed at append time, so
    the accumulator needs no separate validity lane.
    """

    slot: jnp.ndarray  # [A] u32 (SENTINEL = empty / invalid)
    key_hi: jnp.ndarray  # [A] u32
    key_lo: jnp.ndarray  # [A] u32
    tags: jnp.ndarray  # [T, A] u32
    meters: jnp.ndarray  # [M, A] f32

    @property
    def capacity(self) -> int:
        return self.slot.shape[0]


def accum_init(capacity: int, tag_schema: TagSchema, meter_schema: MeterSchema) -> AccumState:
    return AccumState(
        slot=jnp.full((capacity,), SENTINEL_SLOT, dtype=jnp.uint32),
        key_hi=jnp.zeros((capacity,), dtype=jnp.uint32),
        key_lo=jnp.zeros((capacity,), dtype=jnp.uint32),
        tags=jnp.zeros((tag_schema.num_fields, capacity), dtype=jnp.uint32),
        meters=jnp.zeros((meter_schema.num_fields, capacity), dtype=jnp.float32),
    )


def _append_impl(acc: AccumState, slot, key_hi, key_lo, tags_t, meters_t, valid, offset):
    slot = jnp.where(valid, slot, jnp.uint32(SENTINEL_SLOT))
    upd = jax.lax.dynamic_update_slice
    return AccumState(
        slot=upd(acc.slot, slot, (offset,)),
        key_hi=upd(acc.key_hi, key_hi, (offset,)),
        key_lo=upd(acc.key_lo, key_lo, (offset,)),
        tags=upd(acc.tags, tags_t, (0, offset)),
        meters=upd(acc.meters, meters_t, (0, offset)),
    )


accum_append = jax.jit(_append_impl, donate_argnums=(0,))


def _fold_impl(state: StashState, acc: AccumState, sum_cols_t, max_cols_t):
    """One sort+reduce over [S + A] rows → fresh stash + empty accumulator."""
    new_state = _merge_impl(
        state,
        acc.slot,
        acc.key_hi,
        acc.key_lo,
        acc.tags,
        acc.meters,
        acc.slot != jnp.uint32(SENTINEL_SLOT),
        sum_cols_t,
        max_cols_t,
    )
    # Only the slot lane needs clearing — sentinel slots make key/tag/meter
    # bytes unreachable, and the next appends overwrite them in place.
    new_acc = dataclasses.replace(
        acc, slot=jnp.full((acc.capacity,), SENTINEL_SLOT, dtype=jnp.uint32)
    )
    return new_state, new_acc


collector_fold = partial(
    jax.jit, static_argnames=("sum_cols_t", "max_cols_t"), donate_argnums=(0, 1)
)(_fold_impl)


def stash_fold(
    state: StashState, acc: AccumState, meter_schema: MeterSchema
) -> tuple[StashState, AccumState]:
    """Schema-keyed wrapper over collector_fold."""
    sum_cols = tuple(int(i) for i in np.nonzero(meter_schema.sum_mask)[0])
    max_cols = tuple(int(i) for i in np.nonzero(meter_schema.max_mask)[0])
    return collector_fold(state, acc, sum_cols, max_cols)


# ---------------------------------------------------------------------------
# Incremental merge-fold (ISSUE 5). The full-sort fold above re-sorts
# the whole [S+A] stash+accumulator concatenation on every trigger even
# though the stash is ALREADY sorted by (slot, key) — the fold-dominated
# windowed advance pays O((S+A) log(S+A)) 3-key
# compare-exchange for state it holds sorted. The merge-fold sorts only
# the accumulator's [A] rows, rank-merges them against the stash
# (ops/segment.merge_ranks — searchsorted-based merge ranks, then one
# single-key sort or scatter), and feeds the merged run to the SAME
# segment reduce, so it is bit-exact vs `_fold_impl` including the
# overflow stance (tests/test_merge_fold.py).
#
# It requires the CANONICAL stash layout: live rows form a positional,
# (slot, key)-ascending prefix; dead rows (sentinel slot) fill the tail.
# Every producer preserves it — `groupby_reduce` emits segments that
# way, `stash_init` starts empty, and `stash_flush_range(compact=True)`
# re-establishes it after punching out a closed-window prefix. The
# per-window `stash_flush` oracle does NOT (it leaves holes in place);
# fold_mode="merge" managers only ever drain through the compacting
# range flush.


def check_fold_mode(mode: str) -> str:
    """THE fold_mode membership check — every config/entry point shares
    it so a third mode lands everywhere at once."""
    if mode not in ("full", "merge"):
        raise ValueError(f"fold_mode must be 'full' or 'merge', got {mode!r}")
    return mode


def _fold_lanes(fold_rows, new_state: StashState):
    """The fold's two telemetry scalars as one [2] u32 vector:
    [fold_rows, fold_blocks]. fold_blocks is the trip count of the
    group-by's output loop (ops/segment.py): the stash keeps the first
    `capacity` segments, so its live rows are min(num_seg, capacity).
    They ride the device counter block's CB_FOLD_ROWS / CB_FOLD_BLOCKS
    lanes, zero extra host syncs."""
    blocks = out_blocks_run(jnp.sum(new_state.valid), new_state.capacity)
    return jnp.stack([fold_rows.astype(jnp.uint32), blocks.astype(jnp.uint32)])


def _fold_counted_impl(state: StashState, acc: AccumState, sum_cols_t, max_cols_t):
    """`_fold_impl` + the fold's telemetry lanes (`_fold_lanes`);
    fold_rows: live rows the fold's keyed sort touched (whole stash +
    whole accumulator — the full-sort fold re-sorts everything)."""
    fold_rows = jnp.sum(state.valid) + jnp.sum(acc.slot != jnp.uint32(SENTINEL_SLOT))
    new_state, new_acc = _fold_impl(state, acc, sum_cols_t, max_cols_t)
    return new_state, new_acc, _fold_lanes(fold_rows, new_state)


collector_fold_counted = partial(
    jax.jit, static_argnames=("sum_cols_t", "max_cols_t"), donate_argnums=(0, 1)
)(_fold_counted_impl)


def stash_fold_counted(
    state: StashState, acc: AccumState, meter_schema: MeterSchema
) -> tuple[StashState, AccumState, jnp.ndarray]:
    """Schema-keyed `collector_fold_counted` → (state, acc, fold_lanes)
    with fold_lanes = [fold_rows, fold_blocks] (`_fold_lanes`)."""
    sum_cols = tuple(int(i) for i in np.nonzero(meter_schema.sum_mask)[0])
    max_cols = tuple(int(i) for i in np.nonzero(meter_schema.max_mask)[0])
    return collector_fold_counted(state, acc, sum_cols, max_cols)


@jax.jit
def stash_canonicalize(state: StashState) -> StashState:
    """Re-establish the canonical layout (live rows = (slot, key)-
    ascending positional prefix; dead rows sentinel-keyed behind) with
    ONE 3-key sort, preserving every live row's content bit-for-bit.
    Restore-time only (ISSUE 20): pre-v6 checkpoints could hold
    cascade tier stashes with mid-prefix holes — their tier flushes
    never compacted — and the shared-sort ring fold rank-merges
    against the standing order, so a restored tier must be re-sorted
    once before it re-enters the fold path."""
    sl = jnp.where(state.valid, state.slot, jnp.uint32(SENTINEL_SLOT))
    hi = jnp.where(state.valid, state.key_hi, jnp.uint32(_U32_MAX))
    lo = jnp.where(state.valid, state.key_lo, jnp.uint32(_U32_MAX))
    iota = jnp.arange(state.capacity, dtype=jnp.int32)
    _, _, _, order = lax.sort((sl, hi, lo, iota), num_keys=3)
    return StashState(
        slot=jnp.take(sl, order),
        key_hi=jnp.take(state.key_hi, order),
        key_lo=jnp.take(state.key_lo, order),
        tags=jnp.take(state.tags, order, axis=1),
        meters=jnp.take(state.meters, order, axis=1),
        valid=jnp.take(state.valid, order),
        dropped_overflow=state.dropped_overflow,
    )


def _sorted_merge_reduce(state: StashState, na_sl, na_hi, na_lo,
                         a_sl, a_hi, a_lo, a_perm, acc_tags, acc_meters,
                         sum_cols_t, max_cols_t) -> StashState:
    """Rank-merge one SORTED normalized run against the canonical
    (sorted-prefix) stash and segment-reduce the merged order — the
    shared body of the incremental merge-fold AND the cascade's
    shared-sort ring fold (ISSUE 20). `na_*` are the run's normalized
    lanes in ORIGINAL (unsorted) position — invalid rows re-keyed to
    SENTINEL/U32_MAX; `a_sl/a_hi/a_lo/a_perm` the same lanes sorted
    with their permutation. Payload lanes (`acc_tags` [T, A],
    `acc_meters` [M, A]) stay column-major and unsorted — the merged
    order routes through `a_perm`. Requires the canonical stash layout
    (live rows = (slot, key)-ascending positional prefix)."""
    s = state.capacity

    # normalized stash keys — already sorted by the canonical invariant
    ns_sl = jnp.where(state.valid, state.slot, jnp.uint32(SENTINEL_SLOT))
    ns_hi = jnp.where(state.valid, state.key_hi, jnp.uint32(_U32_MAX))
    ns_lo = jnp.where(state.valid, state.key_lo, jnp.uint32(_U32_MAX))

    with jax.named_scope("fold.merge_ranks"):
        rank_s, rank_a = merge_ranks((ns_sl, ns_hi, ns_lo), (a_sl, a_hi, a_lo))
    # order maps merged position → concat([stash, acc]) row; the acc
    # payload routes through a_perm so downstream gathers hit original
    # ring rows (the reduce's tag/meter payloads are never pre-sorted)
    with jax.named_scope("fold.merge_order"):
        order = merge_order(
            rank_s, rank_a, jnp.arange(s, dtype=jnp.int32), s + a_perm
        )

    with jax.named_scope("fold.concat"):
        cat_sl = jnp.concatenate([ns_sl, na_sl])
        cat_hi = jnp.concatenate([ns_hi, na_hi])
        cat_lo = jnp.concatenate([ns_lo, na_lo])
        cat_tags = jnp.concatenate([state.tags, acc_tags], axis=1)
        # same transpose-at-fold stance as _merge_impl (module layout note)
        cat_meters = jnp.transpose(
            jnp.concatenate([state.meters, acc_meters], axis=1)
        )

    with jax.named_scope("fold.merge_order"):
        m_sl, m_hi, m_lo = (jnp.take(c, order) for c in (cat_sl, cat_hi, cat_lo))
    g = groupby_reduce_sorted(
        m_sl,
        m_hi,
        m_lo,
        order,
        cat_tags,
        cat_meters,
        np.asarray(sum_cols_t, dtype=np.int32),
        np.asarray(max_cols_t, dtype=np.int32),
        out_capacity=s,
    )

    dropped = jnp.maximum(g.num_segments - s, 0)
    return StashState(
        slot=g.slot,
        key_hi=g.key_hi,
        key_lo=g.key_lo,
        tags=g.tags,
        meters=g.meters,
        valid=g.seg_valid,
        dropped_overflow=state.dropped_overflow + dropped,
    )


def _merge_fold_impl(state: StashState, acc: AccumState, hi_window, sum_cols_t, max_cols_t):
    """Rank-merge fold: sort [A], merge against the sorted [S] stash,
    reduce the merged run — no full keyed re-sort of the stash lanes.

    `hi_window` bounds the fold span: only acc rows with slot <
    hi_window fold (sentinel-keyed rows never do — SENTINEL ≥ any hi);
    the rest stay accumulated in the ring, untouched. Pass
    SENTINEL_SLOT for the full-set fold (every live row folds, the ring
    empties — same contract as `_fold_impl`). Requires the canonical
    stash layout (see the section comment above); returns
    (new_state, new_acc, fold_lanes) where fold_lanes = [fold_rows,
    fold_blocks] (`_fold_lanes`) and fold_rows counts the acc rows this
    fold's keyed sort actually touched.

    One-pass scoping note (ISSUE 17): this sort is NOT a candidate for
    the sketch plane's shared batch sort — it runs once per FOLD (every
    accum_batches batches, over the acc ring's accumulated rows), not
    per ingest dispatch, and its key space is the doc fingerprint over
    post-fanout rows, not the plane's raw-flow key. The per-dispatch
    sorts the shared-sort rewrite collapses are the sketch plane's
    (sketchplane.sketch_plane_step); the fold's amortized sort already
    IS the one sort of its own dispatch bucket (census-attributed in
    pipeline.telemetry()["profile"])."""
    a = acc.capacity
    hi_window = jnp.asarray(hi_window, dtype=jnp.uint32)

    fold_mask = acc.slot < hi_window
    # normalized acc keys: out-of-span / invalid rows sort last, exactly
    # like groupby_reduce's invalid-row re-keying in the full-sort fold
    na_sl = jnp.where(fold_mask, acc.slot, jnp.uint32(SENTINEL_SLOT))
    na_hi = jnp.where(fold_mask, acc.key_hi, jnp.uint32(_U32_MAX))
    na_lo = jnp.where(fold_mask, acc.key_lo, jnp.uint32(_U32_MAX))
    a_iota = jnp.arange(a, dtype=jnp.int32)
    with jax.named_scope("fold.sort"):
        a_sl, a_hi, a_lo, a_perm = lax.sort(
            (na_sl, na_hi, na_lo, a_iota), num_keys=3
        )

    new_state = _sorted_merge_reduce(
        state, na_sl, na_hi, na_lo, a_sl, a_hi, a_lo, a_perm,
        acc.tags, acc.meters, sum_cols_t, max_cols_t,
    )
    # consumed rows turn sentinel in place; out-of-span rows stay. Their
    # ring slots are reclaimed when the next FULL fold resets the host
    # fill cursor (plan_append cadence), not here.
    new_acc = dataclasses.replace(
        acc, slot=jnp.where(fold_mask, jnp.uint32(SENTINEL_SLOT), acc.slot)
    )
    return new_state, new_acc, _fold_lanes(jnp.sum(fold_mask), new_state)


collector_merge_fold = partial(
    jax.jit, static_argnames=("sum_cols_t", "max_cols_t"), donate_argnums=(0, 1)
)(_merge_fold_impl)


def stash_merge_fold(
    state: StashState,
    acc: AccumState,
    meter_schema: MeterSchema,
    hi_window=None,
) -> tuple[StashState, AccumState, jnp.ndarray]:
    """Schema-keyed merge-fold → (state, acc, fold_lanes). `hi_window`
    None = full-set fold (ring empties — callers reset their fill
    cursor); otherwise only acc rows with slot < hi_window fold (the
    span-bounded window advance — callers must NOT reset fill)."""
    sum_cols = tuple(int(i) for i in np.nonzero(meter_schema.sum_mask)[0])
    max_cols = tuple(int(i) for i in np.nonzero(meter_schema.max_mask)[0])
    hi = SENTINEL_SLOT if hi_window is None else np.uint32(hi_window)
    return collector_merge_fold(state, acc, jnp.uint32(hi), sum_cols, max_cols)


def plan_append(fill: int, capacity: int | None, rows: int) -> str:
    """Host-side accumulator decision shared by the window managers:
    'init' — no ring yet or one too small for this batch (caller must
    fold pending rows BEFORE replacing the ring, or they are lost);
    'fold' — ring exists but this batch won't fit behind `fill`;
    'ok' — append at `fill`."""
    if capacity is None or rows > capacity:
        return "init"
    if fill + rows > capacity:
        return "fold"
    return "ok"


@jax.jit
def stash_flush(state: StashState, window_idx) -> tuple[StashState, dict]:
    """Close a window: emit rows of `window_idx`, reclaim their slots.

    Returns (new_state, out) where out holds full-capacity arrays plus a
    `mask` of emitted rows (static shapes; host compacts). The stash keeps
    its sort invariant trivially — holes are sentinel rows reclaimed by the
    next merge's compaction.

    This is the per-window oracle shape; the production drain is
    `stash_flush_range` (ONE device call + ONE packed fetch for every
    closed window at once — a fetch per window made the per-window
    loop the windowed path's floor).
    """
    window_idx = jnp.asarray(window_idx, dtype=jnp.uint32)
    mask = state.valid & (state.slot == window_idx)
    out = {
        "mask": mask,
        "slot": state.slot,
        "key_hi": state.key_hi,
        "key_lo": state.key_lo,
        "tags": state.tags,
        "meters": state.meters,
        "count": jnp.sum(mask.astype(jnp.int32)),
    }
    new_state = dataclasses.replace(
        state,
        slot=jnp.where(mask, jnp.uint32(SENTINEL_SLOT), state.slot),
        valid=state.valid & ~mask,
    )
    return new_state, out


# Packed flush-row layout: [window, key_hi, key_lo, tags…, meters(bitcast)…]
FLUSH_META_COLS = 3


def pack_u32_columns(slot, key_hi, key_lo, tags, meters, valid=None):
    """Shared packed-u32 layout: [K+T+M, S] with rows slot, key_hi,
    key_lo, (valid,) tags…, bitcast(meters)…; K = FLUSH_META_COLS, +1
    with the optional valid lane (checkpoint format). Every builder of
    this layout (flush range, checkpoint stash/acc) goes through here
    so the row offsets the unpackers hard-code cannot drift."""
    meta = [slot[None, :], key_hi[None, :], key_lo[None, :]]
    if valid is not None:
        meta.append(valid.astype(jnp.uint32)[None, :])
    return jnp.concatenate(
        meta + [tags, jax.lax.bitcast_convert_type(meters, jnp.uint32)], axis=0
    )


def _pack_window_range(state: StashState, lo, hi):
    """Traced: pack every live row in [lo, hi) into a row-major
    [S, 3+T+M] u32 matrix ordered by (window, stash position) — THE
    packed-row builder shared by the mutating range flush and the
    read-only live snapshot (ISSUE 10), so the two emit bit-identical
    rows for the same stash by construction. Returns (mask, packed,
    total)."""
    lo = jnp.asarray(lo, dtype=jnp.uint32)
    hi = jnp.asarray(hi, dtype=jnp.uint32)
    mask = state.valid & (state.slot >= lo) & (state.slot < hi)
    # Stable (window, position) compaction: selected rows first,
    # ascending window, original stash order within a window. Other rows
    # rank as SENTINEL (> any real window — slots are < hi ≤ SENTINEL).
    with jax.named_scope("flush.order"):
        rank = jnp.where(mask, state.slot, jnp.uint32(SENTINEL_SLOT))
        iota = jnp.arange(state.capacity, dtype=jnp.int32)
        _, order = jax.lax.sort((rank, iota), num_keys=1)
    with jax.named_scope("flush.pack"):
        cols = pack_u32_columns(
            state.slot, state.key_hi, state.key_lo, state.tags, state.meters
        )  # [3+T+M, S]
        packed = jnp.take(cols, order, axis=1).T  # row-major [S, 3+T+M]
    total = jnp.sum(mask.astype(jnp.int32))
    return mask, packed, total


def _flush_range_impl(state: StashState, lo_window, hi_window, *, compact: bool = False):
    """Close every window in [lo_window, hi_window): compact their rows
    to the front of ONE row-major [S, 3+T+M] u32 matrix (window-id,
    key, tags, bit-cast meters per row) and reclaim their slots.

    Rows are ordered by (window, stash position) — exactly the order the
    sequential ascending per-window `stash_flush` loop emits, so the two
    paths are bit-identical (pinned by tests/test_flush_range.py). The
    host fetches the row count, then the fixed-size pages of `packed`
    that cover [0, total) and cuts them to `total` itself — two transfers
    per window advance, independent of how many windows closed, and no
    program whose shape depends on `total` (window.py `_PagedRows`).

    `compact` (static) re-establishes the CANONICAL layout the
    merge-fold requires (live rows = sorted positional prefix): on a
    canonical input every flushed row sits in the positional prefix
    [0, total) — the closing windows hold the smallest live slots — so
    one roll of every lane by `total` moves the surviving run to the
    front and the freshly-dead prefix behind the tail. Requires
    lo_window ≤ every live slot (the window managers' advance protocol
    guarantees it: older windows were flushed by earlier advances).
    The flushed OUTPUT is identical either way."""
    mask, packed, total = _pack_window_range(state, lo_window, hi_window)
    iota = jnp.arange(state.capacity, dtype=jnp.int32)
    new_slot = jnp.where(mask, jnp.uint32(SENTINEL_SLOT), state.slot)
    new_valid = state.valid & ~mask
    if compact:
        with jax.named_scope("flush.compact"):
            idx = (iota + total) % state.capacity
            new_state = StashState(
                slot=jnp.take(new_slot, idx),
                key_hi=jnp.take(state.key_hi, idx),
                key_lo=jnp.take(state.key_lo, idx),
                tags=jnp.take(state.tags, idx, axis=1),
                meters=jnp.take(state.meters, idx, axis=1),
                valid=jnp.take(new_valid, idx),
                dropped_overflow=state.dropped_overflow,
            )
    else:
        new_state = dataclasses.replace(state, slot=new_slot, valid=new_valid)
    return new_state, packed, total


stash_flush_range = jax.jit(
    _flush_range_impl, donate_argnums=(0,), static_argnames=("compact",)
)


def _snapshot_range_impl(state: StashState, lo_window, hi_window):
    """READ-ONLY twin of `_flush_range_impl` (ISSUE 10 live read plane):
    pack every live row in [lo, hi) — same order, same layout, same
    unpack — WITHOUT reclaiming slots, advancing anything, or
    compacting. The stash is untouched (no donation), so a snapshot can
    interleave anywhere between ingest dispatches and the later real
    flush of the same windows emits bit-identical rows plus whatever
    arrived after the snapshot. Returns (packed, total)."""
    _, packed, total = _pack_window_range(state, lo_window, hi_window)
    return packed, total


# NO donation: the live stash stays valid — the snapshot writes into a
# fresh output buffer (the "double buffer": the read never aliases the
# plane the next append dispatch consumes).
stash_snapshot_range = jax.jit(_snapshot_range_impl)


def unpack_flush_rows(rows: np.ndarray, num_tags: int):
    """Split fetched packed flush rows ([n, 3+T+M] u32, host) back into
    (window, key_hi, key_lo, tags [n, T], meters [n, M] f32).

    Every output is a VIEW of `rows`: nothing is copied. The meters are
    the row's last M words read as float32 (a `.view` between two 4-byte
    dtypes needs no contiguity), so like the tags they keep the
    matrix's strides, whatever its memory order (row-major from the CPU
    backend, column-major from a TPU), and share its memory: whoever
    holds an output holds the whole matrix, and a write through one
    shows in `rows`."""
    t0 = FLUSH_META_COLS
    return (
        rows[:, 0],
        rows[:, 1],
        rows[:, 2],
        rows[:, t0 : t0 + num_tags],
        rows[:, t0 + num_tags :].view(np.float32),
    )
