"""Scatter-free sorted segmented sum+max — the Pallas hot-loop kernel.

The r5 bisection on the chip showed TPU segment reductions pay a
per-ROW scatter cost regardless of lane width: at 2M rows,
`segment_sum` ≈ 10 ms, `segment_max` ≈ 29 ms — 39 ms of the 82 ms
append. This kernel replaces both with one streaming pass:

  * rows arrive in sorted-key order (the groupby invariant), so each
    segment is a contiguous run;
  * per block of B rows, a segmented Hillis-Steele SUFFIX scan in VMEM
    (log2(B) doubling passes, sum and max together) leaves, at every
    row i, the reduction of rows i..min(end-of-segment, end-of-block);
  * the value at a segment's HEAD row is its in-block total; the value
    at each block's row 0 is the block's leading-run partial;
  * cross-block carries combine in XLA over ONE ROW PER BLOCK
    (n/B rows, three orders of magnitude smaller than n) into a table
    keyed by block; a gather at a segment's head position plus one
    look-up in that table is its total, so reading K segments costs K
    look-ups (`segment_heads`) and nothing is as wide as the caller's
    output capacity.

The payload arrives already gathered into sorted order (one XLA
`take` upstream) and lane-padded to the 128-wide f32 tile. An r6
variant that gathered rows inside the kernel by per-row DMA of M lanes
was refused by the v5e compiler (a VMEM slice of 62 lanes is not
aligned to the 128-lane tiling) and was deleted in PR 22: made to
compile by padding the payload first, it moves more bytes than this.

No scatter touches the [N, M] payload; everything wide is sequential
VMEM streaming (MXU-free, VPU + bandwidth bound).

Semantics replaced: reference `Stash::add` hash-merge loops
(collector.rs:810, quadruple_generator.rs:544) — same SUM/MAX per-key
fold, vectorized.

Exactness: within-segment summation is tree-ordered instead of linear.
For the integer-valued meter lanes this framework folds (packet/byte/
count deltas well under 2^24), f32 tree sums are bit-exact; the
conformance suite pins the pallas path against the XLA ops directly.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128  # f32 lane tile; meter payloads are padded up to this
_NEG = np.float32(-3.4e38)  # practical -inf that survives where()


def _interpret() -> bool:
    """Interpret mode is for the CPU platform only; on a chip the
    kernel compiles or the program fails."""
    return jax.default_backend() == "cpu"


def _suffix_scan(seg, x, block: int):
    """Segmented Hillis-Steele suffix scan over one VMEM-resident block:
    seg [B, 1] i32 ascending, x [B, LANES] f32 → (suffix_sum,
    suffix_max), each row i holding the fold of i..end-of-run."""
    s = x
    m = x
    k = 1
    while k < block:
        seg_shift = jnp.concatenate(
            [seg[k:], jnp.full((k, 1), -1, jnp.int32)], axis=0
        )
        same = seg_shift == seg  # [B, 1]
        s_shift = jnp.concatenate(
            [s[k:], jnp.zeros((k, LANES), jnp.float32)], axis=0
        )
        m_shift = jnp.concatenate(
            [m[k:], jnp.full((k, LANES), _NEG, jnp.float32)], axis=0
        )
        s = s + jnp.where(same, s_shift, jnp.float32(0))
        m = jnp.maximum(m, jnp.where(same, m_shift, _NEG))
        k *= 2
    return s, m


def _suffix_kernel(seg_ref, rows_ref, sum_ref, max_ref, *, block: int):
    s, m = _suffix_scan(seg_ref[:], rows_ref[:], block)
    sum_ref[:] = s
    max_ref[:] = m


def _block_suffix(rows: jnp.ndarray, seg2d: jnp.ndarray, block: int):
    """rows [N, LANES] f32 (N % block == 0), seg2d [N, 1] i32 →
    (suffix_sum, suffix_max), both [N, LANES]."""
    n = rows.shape[0]
    grid = (n // block,)
    # under shard_map the outputs vary over the same mesh axes as the rows
    out = jax.ShapeDtypeStruct(
        (n, LANES), jnp.float32, vma=jax.typeof(rows).vma
    )
    return pl.pallas_call(
        partial(_suffix_kernel, block=block),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((block, LANES), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((block, LANES), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((block, LANES), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_shape=[out, out],
        interpret=_interpret(),
        name="segreduce_suffix_scan",  # the kernel's name in a device profile
    )(seg2d, rows)


class SegmentScan(NamedTuple):
    """Row side of the reduce (N rows wide), ready for head look-ups."""

    suf_sum: jnp.ndarray  # [N, LANES] in-block suffix sums
    suf_max: jnp.ndarray  # [N, LANES] in-block suffix maxima
    carry_sum: jnp.ndarray  # [N/B, LANES] a carry run's total, at its first block
    carry_max: jnp.ndarray  # [N/B, LANES]
    carry_seg: jnp.ndarray  # [N/B] i32 segment a block's leading run continues, else -1
    block: int
    lanes: int  # M, the payload's own width


def sorted_segment_scan(
    rows: jnp.ndarray, seg_id: jnp.ndarray, *, block: int = 2048
) -> SegmentScan:
    """Suffix-scan `rows` [N, M] f32 grouped by the ASCENDING `seg_id`
    [N] (dead rows carry an id past every live one and sort last), and
    fold the cross-block carries into a table of one row per block.
    Everything here is N or N/B rows wide; nothing is as wide as the
    caller's output. `segment_heads` reads segment totals from it."""
    n, m = rows.shape
    if m > LANES:
        raise ValueError(
            f"meter payload has {m} lanes but the suffix-scan kernel streams "
            f"a single {LANES}-wide tile; widen via lane-chunk tiling before "
            f"growing a meter schema past {LANES} columns"
        )
    blk = int(min(block, max(8, 1 << (n - 1).bit_length())))
    pad_rows = (-n) % blk
    if pad_rows:
        seg_id = jnp.pad(seg_id, (0, pad_rows), constant_values=np.int32(2**31 - 1))
        rows = jnp.pad(rows, ((0, pad_rows), (0, 0)))
        n += pad_rows
    seg_id = seg_id.astype(jnp.int32)

    if m < LANES:
        rows = jnp.pad(rows, ((0, 0), (0, LANES - m)))
    suf_sum, suf_max = _block_suffix(rows, seg_id[:, None], blk)

    # cross-block carries: one row per block — the block's leading-run
    # partial belongs to the segment still open at the block boundary
    nb = n // blk
    j = jnp.arange(nb, dtype=jnp.int32)
    starts = j * blk
    first_seg = jnp.take(seg_id, starts)
    prefix_sum = jnp.take(suf_sum, starts, axis=0)  # [nb, LANES]
    prefix_max = jnp.take(suf_max, starts, axis=0)
    # a block whose row 0 IS a head contributes through the head's own
    # suffix, not as a carry (its leading run equals the head suffix —
    # double count)
    prev = jnp.take(seg_id, jnp.maximum(starts - 1, 0))
    continues = (j > 0) & (first_seg == prev)
    # consecutive carry blocks of ONE segment form a run; its total
    # lands at the run's first block, which is the block after the one
    # that holds the segment's head — where `segment_heads` looks it up.
    # One scatter over n/B rows (not sorted: masked blocks get an
    # out-of-range id in place), in ascending block order per run.
    same_run = continues & jnp.roll(continues, 1) & (first_seg == jnp.roll(first_seg, 1))
    run_first = jax.lax.cummax(jnp.where(continues & ~same_run, j, 0))
    run_id = jnp.where(continues, run_first, nb)
    carry_sum = jax.ops.segment_sum(
        jnp.where(continues[:, None], prefix_sum, 0.0), run_id, num_segments=nb
    )
    carry_max = jax.ops.segment_max(
        jnp.where(continues[:, None], prefix_max, _NEG), run_id, num_segments=nb
    )
    carry_max = jnp.where(jnp.isfinite(carry_max), carry_max, _NEG)
    carry_seg = jnp.where(continues, first_seg, -1)
    return SegmentScan(suf_sum, suf_max, carry_sum, carry_max, carry_seg, blk, m)


def segment_heads(scan: SegmentScan, first_pos: jnp.ndarray, seg: jnp.ndarray):
    """Totals of the segments `seg` [K] i32 whose first rows are at
    `first_pos` [K] → (sums, maxs), both [K, M]. Costs K look-ups,
    whatever N is: the in-block totals at the heads, plus the carry run
    that starts in the block after a head's and continues its segment.

    CONTRACT: rows of ABSENT segments are garbage — whatever `first_pos`
    says of an absent id points into another segment, so its totals
    bleed in (NOT the 0 / -inf identities the XLA segment ops emit).
    Callers MUST mask by their live-segment prefix (groupby_reduce's
    seg_valid does); never detect emptiness from these values."""
    n = scan.suf_sum.shape[0]
    nb = scan.carry_seg.shape[0]
    fp = jnp.clip(first_pos, 0, n - 1)
    base_sum = jnp.take(scan.suf_sum, fp, axis=0)  # [K, LANES]
    base_max = jnp.take(scan.suf_max, fp, axis=0)

    nxt = fp // scan.block + 1  # the block after the head's
    carried = nxt < nb
    nxt = jnp.minimum(nxt, nb - 1)
    carried &= jnp.take(scan.carry_seg, nxt) == seg
    carry_sum = jnp.where(carried[:, None], jnp.take(scan.carry_sum, nxt, axis=0), 0.0)
    carry_max = jnp.where(carried[:, None], jnp.take(scan.carry_max, nxt, axis=0), _NEG)

    m = scan.lanes
    return (base_sum + carry_sum)[:, :m], jnp.maximum(base_max, carry_max)[:, :m]


def sorted_segment_sum_max(
    rows: jnp.ndarray,
    seg_id: jnp.ndarray,
    num_segments: int,
    first_pos: jnp.ndarray,
    *,
    block: int = 2048,
):
    """`sorted_segment_scan` and `segment_heads` for all of segments
    [0, num_segments) at once, `first_pos` [num_segments] being their
    first occurrence indices → (sums, maxs), both [num_segments, M].
    The group-by reads the heads block by block instead (segment.py)."""
    scan = sorted_segment_scan(rows, seg_id, block=block)
    seg = jnp.arange(int(num_segments), dtype=jnp.int32)
    return segment_heads(scan, first_pos, seg)
