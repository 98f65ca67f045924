"""Scatter-free sorted segmented sum+max — the Pallas hot-loop kernel.

The r5 bisection (PERF.md §9) showed TPU segment reductions pay a
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
    (n/B rows, three orders of magnitude smaller than n), then a
    [cap]-row gather at the segment head positions finishes the job.

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


def sorted_segment_sum_max(
    rows: jnp.ndarray,
    seg_id: jnp.ndarray,
    num_segments: int,
    first_pos: jnp.ndarray,
    *,
    block: int = 2048,
):
    """Segment sum AND max of `rows` [N, M] f32 grouped by the ASCENDING
    `seg_id` [N] (dead rows carry an id ≥ num_segments and must sort
    last). `first_pos` [num_segments] are the first occurrence indices
    (searchsorted upstream). Returns (sums, maxs), both
    [num_segments, M].

    CONTRACT: rows of ABSENT segments are garbage — searchsorted points
    an absent id at the next live segment's head, so its totals bleed
    in (NOT the 0 / -inf identities the XLA segment ops emit). Callers
    MUST mask by their live-segment prefix (groupby_reduce's seg_valid
    does); never detect emptiness from these values."""
    n, m = rows.shape
    if m > LANES:
        raise ValueError(
            f"meter payload has {m} lanes but the suffix-scan kernel streams "
            f"a single {LANES}-wide tile; widen via lane-chunk tiling before "
            f"growing a meter schema past {LANES} columns"
        )
    cap = int(num_segments)
    blk = int(min(block, max(8, 1 << (n - 1).bit_length())))
    pad_rows = (-n) % blk
    if pad_rows:
        seg_id = jnp.pad(seg_id, (0, pad_rows), constant_values=np.int32(2**31 - 1))
        rows = jnp.pad(rows, ((0, pad_rows), (0, 0)))
        n += pad_rows
    seg2d = seg_id.astype(jnp.int32)[:, None]

    if m < LANES:
        rows = jnp.pad(rows, ((0, 0), (0, LANES - m)))
    suf_sum, suf_max = _block_suffix(rows, seg2d, blk)

    # in-block totals at the segment heads
    fp = jnp.clip(first_pos, 0, n - 1)
    base_sum = jnp.take(suf_sum, fp, axis=0)  # [cap, LANES]
    base_max = jnp.take(suf_max, fp, axis=0)

    # cross-block carries: one row per block — the block's leading-run
    # partial belongs to the segment still open at the block boundary
    nb = n // blk
    starts = jnp.arange(nb, dtype=jnp.int32) * blk
    first_seg = jnp.take(seg_id, starts).astype(jnp.int32)
    prefix_sum = jnp.take(suf_sum, starts, axis=0)  # [nb, LANES]
    prefix_max = jnp.take(suf_max, starts, axis=0)
    # a block whose row 0 IS a head contributes through base_*, not as
    # a carry (its leading run equals the head suffix — double count)
    prev = jnp.take(seg_id, jnp.maximum(starts - 1, 0)).astype(jnp.int32)
    continues = (jnp.arange(nb) > 0) & (first_seg == prev)
    carry_seg = jnp.where(continues, first_seg, np.int32(2**31 - 1))
    # carry_seg is NOT sorted (masked blocks get a big id in place), so
    # no indices_are_sorted hint; at n/B rows the scatter cost is noise
    carry_sum = jax.ops.segment_sum(
        jnp.where(continues[:, None], prefix_sum, 0.0),
        carry_seg, num_segments=cap,
    )
    carry_max = jax.ops.segment_max(
        jnp.where(continues[:, None], prefix_max, _NEG),
        carry_seg, num_segments=cap,
    )
    carry_max = jnp.where(jnp.isfinite(carry_max), carry_max, _NEG)

    out_sum = (base_sum + carry_sum)[:, :m]
    out_max = jnp.maximum(base_max, carry_max)[:, :m]
    return out_sum, out_max
