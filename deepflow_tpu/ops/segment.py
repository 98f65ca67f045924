"""Sort-based group-by reduction — the aggregation hot loop.

Replaces the reference's HashMap stash merge (`Stash::add`,
collector.rs:810; `SubQuadGen::inject_flow`, quadruple_generator.rs:544)
with a fully static-shape XLA pattern:

    lax.sort((slot, key_hi, key_lo, iota), num_keys=3)
      → head flags from key-change deltas → segment ids (one cumsum)
      → a suffix scan (TPU) or segment_sum / segment_max (CPU) of the
        meter rows in sorted order
      → the head positions in order (one single-key sort)
      → a loop over the LIVE segments in blocks of OUT_BLOCK_ROWS: head
        look-ups and representative-row gathers at that block's heads,
        written into outputs that start as dead rows. The trip count is
        a device scalar, so the shapes stay static.

Layout at the interface: tags stay column-major ([T, N] with the row
axis minor — it maps rows onto the 128-wide vector lanes and keeps
column selection free); the METER payload is row-major [N, M] since r6,
because the reduce consumes rows — one row-gather of [N, M] moves M
contiguous elements per index (~17x better than M strided
lane-gathers). The batch pre-reduce hot path produces
[N, M] natively (FlowBatch.meters), so no transpose is ever
materialized at 2M rows; the stash fold transposes its column-major
state at the call site, where XLA folds it into the downstream
gather/copy.

Kernel selection is measurement-driven (PERF.md, round 4, v5e):
  * round-3 segmented `associative_scan`: 5.4-35 ms at 32k rows and
    superlinear compile times — replaced by this kernel.
  * round-2 row-major segment ops: 4.9 ms at 32k; this kernel is the
    same reduction with the gathers restricted to segment heads and
    `num_segments` capped at the stash capacity instead of N.
  * the sort itself costs 3.3 ms at 32k but only 4.0 ms at 131k — it is
    overhead-dominated at batch sizes, which is why the stash
    accumulates raw rows and amortizes ONE big sort over many batches
    (see aggregator/stash.py).

Everything is O(N log N) compare-exchange on u32 lanes plus linear
segment passes — no data-dependent shapes, no scatter (XLA lowers
scatter poorly on TPU: a 65k-row scatter-add measured 4 ms, as much as
the whole sort).
"""

from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# Sentinel slot value for invalid rows: sorts after every real window.
SENTINEL_SLOT = np.uint32(0xFFFFFFFF)


def _use_pallas_reduce() -> bool:
    """The Pallas suffix-scan reduce replaces the per-row scatter
    segment ops on TPU; XLA ops stay for CPU (fast there,
    and the conformance suite pins the two paths equal).
    DEEPFLOW_SEGREDUCE=pallas|xla overrides."""
    mode = os.environ.get("DEEPFLOW_SEGREDUCE", "auto")
    if mode == "pallas":
        return True
    if mode == "xla":
        return False
    return jax.default_backend() not in ("cpu",)


def _use_merge_scatter() -> bool:
    """Merged-order construction for the incremental merge-fold
    (aggregator/stash.py): default is a single-key `lax.sort` over the
    precomputed merge ranks (2 lanes, 1 u32 key — ~a third of the
    compare work of the 3-key fold sort it replaces, and the primitive
    this repo trusts on TPU). DEEPFLOW_MERGE_SCATTER=1 switches to the
    truly-linear one-scatter construction for on-chip A/B — scatter
    lowers poorly on TPU historically (module docstring), but this one
    is a plain unique-index i32 scatter, not a scatter-add, so it is
    worth measuring."""
    return os.environ.get("DEEPFLOW_MERGE_SCATTER", "0") == "1"


def _use_shared_sort() -> bool:
    """One-pass sketch fold (ISSUE 17): the sketch plane computes the
    batch's keyed sort permutation ONCE per fused dispatch and threads
    the sorted lanes through both fold phases, every top-K hash row and
    the count-min run dedup — 4 sorts/dispatch → 1 with sketch+topk ON.
    Bit-exact vs the multi-sort oracle (pinned in
    tests/test_sketch_onepass.py), so it defaults ON.
    DEEPFLOW_SHARED_SORT=0 restores the per-consumer sorts for A/B."""
    return os.environ.get("DEEPFLOW_SHARED_SORT", "1") != "0"


_U32_MAX = np.uint32(0xFFFFFFFF)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class Grouped:
    """Result of one group-by reduce over N input rows. Payloads are
    column-major; key/flag lanes have leading dim `cap` (the requested
    output capacity); `seg_valid` marks live segments (a prefix —
    segments are emitted in sorted key order)."""

    slot: jnp.ndarray  # [cap] u32 — window index per segment
    key_hi: jnp.ndarray  # [cap] u32
    key_lo: jnp.ndarray  # [cap] u32
    tags: jnp.ndarray  # [T, cap] u32 — representative (first) row's tags
    meters: jnp.ndarray  # [M, cap] f32 — reduced
    seg_valid: jnp.ndarray  # [cap] bool
    num_segments: jnp.ndarray  # scalar i32 — live segment count (may exceed cap)


def groupby_reduce(
    slot,
    key_hi,
    key_lo,
    tags_t,
    meters_rows,
    valid,
    sum_cols: np.ndarray,
    max_cols: np.ndarray,
    out_capacity: int | None = None,
) -> Grouped:
    """Group rows by (slot, key_hi, key_lo) and reduce meters.

    Args:
      slot/key_hi/key_lo: [N] u32. Invalid rows are re-keyed to sentinel.
      tags_t: [T, N] u32; meters_rows: [N, M] f32 ROW-major (one meter
        row per record — see the module docstring on layout); valid:
        [N] bool.
      sum_cols / max_cols: static np arrays of meter row indices, a
        partition of range(M) (from MeterSchema.sum_mask/max_mask).
      out_capacity: static output size; segments beyond it (in ascending
        (slot, key) order) are dropped from the output but still counted
        in num_segments so callers can account overflow. Defaults to N.
    """
    n = slot.shape[0]

    # stage names for device profiles (metadata only). The fused step's
    # pre-reduce is this same group-by: there they read
    # step.prereduce/fold.sort, … under the step's own scope.
    with jax.named_scope("fold.sort"):
        slot = jnp.where(valid, slot, jnp.uint32(SENTINEL_SLOT))
        key_hi = jnp.where(valid, key_hi, jnp.uint32(_U32_MAX))
        key_lo = jnp.where(valid, key_lo, jnp.uint32(_U32_MAX))

        iota = jnp.arange(n, dtype=jnp.int32)
        s_slot, s_hi, s_lo, perm = lax.sort(
            (slot, key_hi, key_lo, iota), num_keys=3
        )
    return groupby_reduce_sorted(
        s_slot, s_hi, s_lo, perm, tags_t, meters_rows,
        sum_cols, max_cols, out_capacity=out_capacity,
    )


# Output rows one trip of the group-by's output loop makes (PERF.md §6,
# PR 29). Every stage whose result is as wide as the output capacity —
# the head look-ups, the representative-row gathers, the select, mask
# and transpose of the reduced meters — runs in blocks of this many
# segments under a trip count the device takes from the live segment
# count, so a stash that is 4% live pays for 4% of its rows. A power of
# two; a capacity below it is one block, one it does not divide is
# padded inside and cut statically. Read on the v5e at 8,192 / 16,384 /
# 32,768: the post-sort phase of a 2^22 + 2^20-row fold with 2.19M live
# segments took 337 / 371 / 371 ms, of a 2^21 + 2^20-row one with 118k
# 78 / 82 / 81 ms, the step's pre-reduce 3.5 / 3.6 / 4.7 ms.
OUT_BLOCK_ROWS = 8192


def out_block_rows(cap: int) -> int:
    return max(1, min(OUT_BLOCK_ROWS, int(cap)))


def out_blocks_total(cap: int) -> int:
    """Blocks the output loop runs when every row of `cap` is live."""
    return -(-int(cap) // out_block_rows(cap))


def out_blocks_run(num_segments, cap: int):
    """The output loop's trip count (traced i32): blocks that hold a live
    segment, of `out_blocks_total(cap)`."""
    blk = out_block_rows(cap)
    return (jnp.minimum(num_segments, cap).astype(jnp.int32) + (blk - 1)) // blk


def _varying_like(const, ref):
    """`const` typed like `ref`: under shard_map state varies over the
    mesh axes; a loop's carry must enter with the type it leaves, and
    every `cond` branch must return that same type."""
    vma = tuple(jax.typeof(ref).vma)
    return lax.pcast(const, vma, to="varying") if vma else const


def _reduce_rows(meters_rows, perm, seg_id, cap_pad: int,
                 sum_cols: np.ndarray, max_cols: np.ndarray):
    """The row side of the per-segment SUM / MAX of the meter rows, N
    rows wide, in sorted order. Returns `heads(k0, seg, first_pos)` →
    [M, K]: the reduced meters of the K consecutive segments `seg`
    (= k0 + arange(K)); columns of absent segments are unspecified
    (callers mask by their live-segment prefix)."""
    m = meters_rows.shape[1]
    if not m:
        return lambda k0, seg, first_pos: jnp.zeros((0, seg.shape[0]), meters_rows.dtype)

    is_sum = np.zeros((m,), bool)
    is_sum[sum_cols] = True

    def select(ps, pm):
        # Full-width segment ops + per-column select, NOT subset-indexed
        # ops: `meters_rows[:, sum_cols]` materializes a strided copy of
        # [N, |subset|] before each op, which costs more than running the
        # op over all M lanes and discarding the unwanted half (measured
        # ~16% off the whole fold at 588k rows).
        if not max_cols.size:
            return ps.T
        if not sum_cols.size:
            return pm.T
        return jnp.where(jnp.asarray(is_sum)[None, :], ps, pm).T  # [M, K]

    # One row-gather moves all M meter lanes of a row at once.
    sorted_rows = jnp.take(meters_rows, perm, axis=0)  # [N, M]
    if _use_pallas_reduce():
        # On TPU both ops fuse into ONE scatter-free Pallas suffix-scan
        # pass (segreduce_pallas.py); a block of segments
        # then costs its own head look-ups.
        from .segreduce_pallas import segment_heads, sorted_segment_scan

        scan = sorted_segment_scan(sorted_rows, seg_id)
        return lambda k0, seg, first_pos: select(*segment_heads(scan, first_pos, seg))

    # (segment_max yields -inf for empty segments; the caller's
    # seg_valid mask zeroes those columns, so no isfinite rewrite — it
    # would also mask NaNs from genuinely corrupt meters.) The scatter
    # costs per ROW; a block reads its own slice of the result.
    ps = (
        jax.ops.segment_sum(
            sorted_rows, seg_id, num_segments=cap_pad, indices_are_sorted=True
        )
        if sum_cols.size
        else None
    )
    pm = (
        jax.ops.segment_max(
            sorted_rows, seg_id, num_segments=cap_pad, indices_are_sorted=True
        )
        if max_cols.size
        else None
    )

    def heads(k0, seg, first_pos):
        cut = lambda x: None if x is None else lax.dynamic_slice_in_dim(
            x, k0, seg.shape[0], axis=0
        )
        return select(cut(ps), cut(pm))

    return heads


def groupby_reduce_sorted(
    s_slot,
    s_hi,
    s_lo,
    perm,
    tags_t,
    meters_rows,
    sum_cols: np.ndarray,
    max_cols: np.ndarray,
    out_capacity: int | None = None,
) -> Grouped:
    """The post-sort phase of `groupby_reduce`, for callers that already
    hold the key lanes in sorted order — the incremental merge-fold
    (aggregator/stash.py) constructs them with a rank-merge instead of a
    full keyed re-sort, then reuses this exact reduce so the two fold
    paths cannot drift.

    The row side is N rows wide: head flags, segment ids, the meter rows
    in sorted order and their suffix scan. The output side costs what
    the LIVE segments cost: live segments are ids [0, num_seg) and a
    prefix of the output, so one loop makes the output in blocks of
    `OUT_BLOCK_ROWS` segments and runs `out_blocks_run(num_seg, cap)`
    times — a trip count, not a shape: one program for every live count.
    Rows past the last live block keep the dead row's constants.

    Args:
      s_slot/s_hi/s_lo: [N] u32 key lanes in ascending (slot, hi, lo)
        order, PRE-normalized — invalid rows keyed
        (SENTINEL_SLOT, U32_MAX, U32_MAX) so they sort last.
      perm: [N] i32 mapping sorted position → original row index into
        tags_t ([T, N]) / meters_rows ([N, M]), exactly what `lax.sort`
        with an iota payload produces.
    """
    n = s_slot.shape[0]
    cap = int(out_capacity) if out_capacity is not None else n
    sum_cols = np.asarray(sum_cols, np.int32)
    max_cols = np.asarray(max_cols, np.int32)
    blk = out_block_rows(cap)
    cap_pad = out_blocks_total(cap) * blk
    t, m = tags_t.shape[0], meters_rows.shape[1]

    with jax.named_scope("fold.segments"):
        head = jnp.concatenate(
            [
                jnp.ones((1,), dtype=bool),
                (s_slot[1:] != s_slot[:-1]) | (s_hi[1:] != s_hi[:-1]) | (s_lo[1:] != s_lo[:-1]),
            ]
        )
        # Sentinel rows sort after every live row, so live rows are a prefix
        # and live segments are exactly segment ids [0, num_seg).
        live_row = s_slot != jnp.uint32(SENTINEL_SLOT)
        live_head = head & live_row
        num_seg = jnp.sum(live_head.astype(jnp.int32))
        seg_id = jnp.cumsum(head.astype(jnp.int32)) - 1  # [N] ascending
        # Dead rows get an out-of-range id so every segment op drops them.
        # It must be `n`, not `cap`: live overflow segments carry ids in
        # [cap, num_seg) and the id sequence must stay ascending for the
        # indices_are_sorted hint below to be honest.
        seg_id = jnp.where(live_row, seg_id, n)
        head_pos = _head_positions(live_head, cap_pad)

    with jax.named_scope("fold.reduce"):
        heads = _reduce_rows(meters_rows, perm, seg_id, cap_pad, sum_cols, max_cols)

    n_live = jnp.minimum(num_seg, cap)

    def block(b, out):
        o_slot, o_hi, o_lo, o_tags, o_meters = out
        k0 = b * blk
        seg = k0 + jnp.arange(blk, dtype=jnp.int32)
        live = seg < n_live
        with jax.named_scope("fold.segments"):
            first_pos = lax.dynamic_slice_in_dim(head_pos, k0, blk)
        with jax.named_scope("fold.reduce"):
            meters = jnp.where(live[None, :], heads(k0, seg, first_pos), 0)
        with jax.named_scope("fold.compact"):
            fp = jnp.where(live, first_pos, 0).astype(jnp.int32)
            slot = jnp.where(live, jnp.take(s_slot, fp), jnp.uint32(SENTINEL_SLOT))
            hi = jnp.where(live, jnp.take(s_hi, fp), 0)
            lo = jnp.where(live, jnp.take(s_lo, fp), 0)
            rep_orig = jnp.take(perm, fp)
            tags = jnp.where(live[None, :], jnp.take(tags_t, rep_orig, axis=1), 0)
            upd = lax.dynamic_update_slice
            return (
                upd(o_slot, slot, (k0,)),
                upd(o_hi, hi, (k0,)),
                upd(o_lo, lo, (k0,)),
                upd(o_tags, tags, (0, k0)),
                upd(o_meters, meters, (0, k0)),
            )

    with jax.named_scope("fold.compact"):
        dead = (
            jnp.full((cap_pad,), SENTINEL_SLOT, dtype=s_slot.dtype),
            jnp.zeros((cap_pad,), s_hi.dtype),
            jnp.zeros((cap_pad,), s_lo.dtype),
            jnp.zeros((t, cap_pad), tags_t.dtype),
            jnp.zeros((m, cap_pad), meters_rows.dtype),
        )
        dead = tuple(_varying_like(x, s_slot) for x in dead)
    out = lax.fori_loop(0, out_blocks_run(num_seg, cap), block, dead)
    out_slot, out_hi, out_lo, out_tags, out_meters = (x[..., :cap] for x in out)

    return Grouped(
        slot=out_slot,
        key_hi=out_hi,
        key_lo=out_lo,
        tags=out_tags,
        meters=out_meters,
        seg_valid=jnp.arange(cap, dtype=jnp.int32) < n_live,
        num_segments=num_seg,
    )


def _head_positions(live_head, cap_pad: int):
    """First sorted position of every segment, [cap_pad] i32; what it
    says of an absent segment is unspecified (callers mask). Live
    segments are numbered in sorted order, so the live heads' positions
    in ascending order ARE their first positions: one single-key sort of
    N i32, dead and non-head rows keyed behind. A binary search of
    `seg_id` for a block's ids was the other way; on the v5e it cost
    2.6–3 ms for every block of 16,384 segments, and this whole phase
    721 ms against 371 with the sort at 5.2M rows with 134 such blocks
    live (PERF.md §6, PR 29)."""
    n = live_head.shape[0]
    pos = lax.sort(jnp.where(live_head, jnp.arange(n, dtype=jnp.int32), n))
    return pos[:cap_pad] if n >= cap_pad else jnp.pad(pos, (0, cap_pad - n), constant_values=n)


# ---------------------------------------------------------------------------
# Rank-merge primitives for the incremental merge-fold (ISSUE 5).
#
# Two sequences already sorted by the same lexicographic (slot, hi, lo)
# u32 triple merge in O(A log S + S log A) comparisons: each element's
# merged position ("merge rank") is its own index plus the count of
# other-sequence elements before it, found by a vectorized binary
# search. Ranks are a permutation of [0, S+A) by construction, so the
# merged order follows from one cheap single-key sort (or one scatter —
# `_use_merge_scatter`), never a full keyed re-sort of both sequences.


def _lex_less(a_sl, a_hi, a_lo, b_sl, b_hi, b_lo):
    """Elementwise lexicographic (slot, hi, lo) u32 triple compare."""
    return (a_sl < b_sl) | (
        (a_sl == b_sl) & ((a_hi < b_hi) | ((a_hi == b_hi) & (a_lo < b_lo)))
    )


def lex_searchsorted(keys, queries, *, side: str):
    """`jnp.searchsorted` generalized to a lexicographic u32 triple.

    keys: (slot, hi, lo) arrays [N], ascending under `_lex_less`.
    queries: (slot, hi, lo) arrays [Q]. Returns [Q] i32 insertion
    points (side="left": count of keys strictly less; side="right":
    count of keys less-or-equal). Vectorized binary search — a static
    ceil(log2(N+1)) unroll of one 3-lane gather + compare per step, so
    Q queries cost O(Q log N) instead of packing 96-bit keys into a
    scalar the 32-bit lanes cannot hold.
    """
    if side not in ("left", "right"):
        raise ValueError(f"side must be 'left' or 'right', got {side!r}")
    k_sl, k_hi, k_lo = keys
    q_sl, q_hi, q_lo = queries
    n = int(k_sl.shape[0])
    lo = jnp.zeros(q_sl.shape, jnp.int32)
    if n == 0:
        return lo
    hi = jnp.full(q_sl.shape, n, jnp.int32)
    for _ in range(n.bit_length()):
        mid = (lo + hi) >> 1
        m_sl = jnp.take(k_sl, mid)
        m_hi = jnp.take(k_hi, mid)
        m_lo = jnp.take(k_lo, mid)
        if side == "left":
            go_right = _lex_less(m_sl, m_hi, m_lo, q_sl, q_hi, q_lo)
        else:
            go_right = ~_lex_less(q_sl, q_hi, q_lo, m_sl, m_hi, m_lo)
        active = lo < hi
        lo = jnp.where(active & go_right, mid + 1, lo)
        hi = jnp.where(active & ~go_right, mid, hi)
    return lo


def merge_ranks(first, second):
    """Merged positions for two key-sorted (slot, hi, lo) sequences.

    Tie-break: `first` elements precede equal `second` elements, and
    each sequence keeps its internal order — exactly the order a STABLE
    `lax.sort` over their concatenation (first then second) produces,
    which is what makes the merge-fold bit-exact against the full-sort
    fold. Returns (rank_first [S], rank_second [A]), together a
    permutation of [0, S+A).
    """
    nf = int(first[0].shape[0])
    ns = int(second[0].shape[0])
    rank_f = jnp.arange(nf, dtype=jnp.int32) + lex_searchsorted(
        second, first, side="left"
    )
    rank_s = jnp.arange(ns, dtype=jnp.int32) + lex_searchsorted(
        first, second, side="right"
    )
    return rank_f, rank_s


def merge_order(rank_f, rank_s, payload_f, payload_s):
    """Invert merge ranks into a gather order: returns [S+A] i32 where
    position p holds the payload of the element whose merged rank is p.
    Default: single-u32-key 2-lane sort; DEEPFLOW_MERGE_SCATTER=1 uses
    the linear unique-index scatter instead (on-chip A/B knob)."""
    rank = jnp.concatenate([rank_f, rank_s])
    payload = jnp.concatenate([payload_f, payload_s]).astype(jnp.int32)
    if _use_merge_scatter():
        return (
            jnp.zeros((rank.shape[0],), jnp.int32)
            .at[rank]
            .set(payload, unique_indices=True)
        )
    _, order = lax.sort((rank.astype(jnp.uint32), payload), num_keys=1)
    return order
