"""Single-pass fused sketch update — the Pallas kernel behind
DEEPFLOW_FUSED_SKETCH (ISSUE 17, tentpole b).

The shared-sort rewrite (aggregator/sketchplane.py) already collapses
the sketch plane's sorts to one; what remains on the XLA path is a
fan of scatters over the sorted batch — HLL register max, count-min
run-head adds, and a segment-max/min pair per top-K hash row. The FPGA
sketch accelerators (PAPERS.md: HLL on FPGA 2005.13332, the streaming
top-K engine 2511.16797) get their throughput from doing all of these
in ONE pass over the stream against on-chip banked state. This kernel
is that shape on the TPU: one sequential sweep over the sorted rows
with the whole plane state resident in VMEM, per-row lanes riding in
SMEM (the `segreduce_pallas.py` perm-in-SMEM idiom).

Per sorted row i (skipping rows the phase mask excludes):

  * HLL:   hll[slot·G + gid, reg] = max(old, rho) — idempotent, so the
           original-vs-sorted order change is invisible;
  * CMS:   at run HEADS only, cms[slot·D + d, col_d] += run_weight —
           one banked add per (window, key) run instead of per row
           (adds commute → totals bit-identical);
  * top-K: a streaming best-challenger table per hash row:
           strictly-greater run weight replaces the bucket's candidate,
           which reproduces the XLA path's first-heaviest-run stable
           tie-break because rows arrive in the shared sort order.

The weighted-MJRTY vote epilogue is NOT in the kernel — both the XLA
presorted path and this kernel feed the same `ops.topk._apply_challengers`,
so the two paths share their tail by construction and the parity pin
(tests/test_sketch_onepass.py) covers exactly the divergent half.

Exactness note on the challenger table: buckets whose heaviest run
weight is 0 report got=False here but got=True (hw=0) on the XLA path.
A zero-weight challenger is provably a vote NO-OP (votes are always
≥ 0: same-key adds 0; a take needs challenged < 0, impossible at
hw = 0), so the applied lanes — the only thing that escapes the step —
are still bit-identical; the fuzz pins lanes, not the intermediates.

Shape guard: the state must fit the VMEM budget and the per-row SMEM
lanes must stay small. Unsupported shapes fall back LOUDLY to the XLA
presorted path — a warning once per shape plus a module counter
(`FUSED_SKETCH_FALLBACKS`, asserted in tier-1) — never silently
(ADVICE.md #2, the m≤LANES stance of segreduce_pallas).

Default OFF until on-chip numbers land (PERF.md §25 reserves the A/B
columns — the §15 flip-the-default convention); interpret-mode parity
runs on CPU in tier-1 either way.
"""

from __future__ import annotations

import warnings
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: rows above this would bloat the SMEM-resident per-row lanes past the
#: scalar memory budget (~13 lanes × 4 B × N)
MAX_FUSED_ROWS = 1 << 15
#: VMEM budget for the resident plane state (HLL + CMS + 5 challenger
#: lanes), conservative slice of the ~16 MB/core VMEM
MAX_STATE_BYTES = 8 << 20

#: count of guarded fallbacks to the XLA presorted path — degradation
#: must be loud and countable, never silent
FUSED_SKETCH_FALLBACKS = 0
_WARNED_SHAPES: set = set()


def fused_sketch_guard(
    n: int, ring: int, g: int, m: int, d_cms: int, w_cms: int,
    d_tk: int, c_tk: int,
) -> bool:
    """Trace-time (static shapes) support check. False → the caller
    takes the XLA presorted path; the miss is warned once per shape and
    counted in FUSED_SKETCH_FALLBACKS."""
    global FUSED_SKETCH_FALLBACKS
    state_bytes = 4 * (
        ring * g * m + ring * d_cms * w_cms + 5 * d_tk * ring * c_tk
    )
    reasons = []
    if d_tk < 1:
        reasons.append("top-K lane disabled (nothing to fuse the sort for)")
    if n > MAX_FUSED_ROWS:
        reasons.append(f"batch rows {n} > MAX_FUSED_ROWS {MAX_FUSED_ROWS}")
    if state_bytes > MAX_STATE_BYTES:
        reasons.append(
            f"plane state {state_bytes} B > MAX_STATE_BYTES {MAX_STATE_BYTES}"
        )
    if not reasons:
        return True
    FUSED_SKETCH_FALLBACKS += 1
    key = (n, ring, g, m, d_cms, w_cms, d_tk, c_tk)
    if key not in _WARNED_SHAPES:
        _WARNED_SHAPES.add(key)
        warnings.warn(
            "DEEPFLOW_FUSED_SKETCH: falling back to the XLA presorted "
            "path for shape %r: %s" % (key, "; ".join(reasons)),
            stacklevel=2,
        )
    return False


def _fused_kernel(
    s_slot, s_gid, s_reg, s_rho, s_live, w_head, rw,
    cms_slot, tk_col, s_hi, s_lo, s_ia, s_ib,
    hll_in, cms_in,  # alias the hll_ref/cms_ref outputs — same storage
    hll_ref, cms_ref, bw_ref, bh_ref, bl_ref, ba_ref, bb_ref,
    *, n: int, g: int, d_cms: int, w_cms: int, d_tk: int, c_tk: int,
):
    """One sequential sweep over the sorted batch. State refs:
    hll [R·G, m] (aliased in/out), cms [R·D, W] (aliased in/out),
    challenger tables [d, R·C] (fresh outputs, built here)."""
    del hll_in, cms_in  # input_output_aliases: state reads go via out refs
    z = lambda ref: jnp.zeros(ref.shape, ref.dtype)
    bw_ref[:] = z(bw_ref)
    bh_ref[:] = z(bh_ref)
    bl_ref[:] = z(bl_ref)
    ba_ref[:] = z(ba_ref)
    bb_ref[:] = z(bb_ref)

    def body(i, carry):
        slot = s_slot[i]
        live = s_live[i] != 0

        @pl.when(live)
        def _():
            # HLL register max (idempotent — order-free)
            row = slot * g + s_gid[i]
            reg = s_reg[i]
            old = hll_ref[row, reg]
            hll_ref[row, reg] = jnp.maximum(old, s_rho[i])

        # CMS run-head add: w_head is 0 off-head / for fully-masked
        # runs, so gating on it alone preserves the oracle's totals
        @pl.when(w_head[i] != 0)
        def _():
            for dd in range(d_cms):
                crow = slot * d_cms + dd
                ccol = cms_slot[dd, i]
                cms_ref[crow, ccol] = cms_ref[crow, ccol] + w_head[i]

        # streaming best-challenger per hash row: strictly greater run
        # weight replaces — first-seen wins ties, which IS the XLA
        # path's min-position stable tie-break under the shared order
        @pl.when(live)
        def _():
            for rr in range(d_tk):
                b = slot * c_tk + tk_col[rr, i]

                @pl.when(rw[i] > bw_ref[rr, b])
                def _():
                    bw_ref[rr, b] = rw[i]
                    bh_ref[rr, b] = s_hi[i]
                    bl_ref[rr, b] = s_lo[i]
                    ba_ref[rr, b] = s_ia[i]
                    bb_ref[rr, b] = s_ib[i]

        return carry

    jax.lax.fori_loop(0, n, body, 0)


def sketch_update_fused(
    hll, cms, *, tk_shape, s_slot, s_gid, s_reg, s_rho, s_mask, w_head,
    rw, cms_slots, s_hi, s_lo, s_ia, s_ib,
):
    """hll [R, G, m] i32 and cms [R, D, W] i32 updated in one fused
    pass over the SORTED batch lanes; returns (hll, cms, challengers)
    where `challengers` is the `ops.topk._apply_challengers` input list
    (one (got, h_hi, h_lo, h_ia, h_ib, hw) per hash row, flat [R·C]).

    `tk_shape` is the static (topk_rows, topk_cols) pair. `cms_slots`
    [D, N] carries the ops.cms.row_slots values (they already embed the
    d·W row offset — they split into the kernel's [R·D, W] banked
    layout here). Callers pass shapes through `fused_sketch_guard`
    first."""
    # tk_col derives here (not at the call site) so the kernel and the
    # XLA presorted path share the same bucket_cols avalanche
    from .topk import bucket_cols

    ring, g, m = hll.shape
    d_cms, w_cms = cms.shape[1], cms.shape[2]
    d_tk, c_tk = tk_shape
    n = s_slot.shape[0]

    i32 = lambda x: jnp.asarray(x).astype(jnp.int32)
    # strip the per-depth w·d offset: the banked layout addresses
    # (slot·D + d, col) instead of flat slot·D·W + row_slots
    offs = (jnp.arange(d_cms, dtype=jnp.int32) * w_cms)[:, None]
    cms_col = i32(cms_slots) - offs
    tk_col = jnp.stack([bucket_cols(s_hi, s_lo, r, c_tk) for r in range(d_tk)])

    out_shape = [
        jax.ShapeDtypeStruct((ring * g, m), jnp.int32),
        jax.ShapeDtypeStruct((ring * d_cms, w_cms), jnp.int32),
        jax.ShapeDtypeStruct((d_tk, ring * c_tk), jnp.int32),
        jax.ShapeDtypeStruct((d_tk, ring * c_tk), jnp.uint32),
        jax.ShapeDtypeStruct((d_tk, ring * c_tk), jnp.uint32),
        jax.ShapeDtypeStruct((d_tk, ring * c_tk), jnp.uint32),
        jax.ShapeDtypeStruct((d_tk, ring * c_tk), jnp.uint32),
    ]
    smem = lambda: pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = lambda: pl.BlockSpec(memory_space=pltpu.VMEM)
    hll2, cms2, bw, bh, bl, ba, bb = pl.pallas_call(
        partial(
            _fused_kernel, n=n, g=g, d_cms=d_cms, w_cms=w_cms,
            d_tk=d_tk, c_tk=c_tk,
        ),
        in_specs=[
            smem(),  # s_slot
            smem(),  # s_gid
            smem(),  # s_reg
            smem(),  # s_rho
            smem(),  # s_live
            smem(),  # w_head
            smem(),  # rw
            smem(),  # cms_col [D, N]
            smem(),  # tk_col [d, N]
            smem(),  # s_hi
            smem(),  # s_lo
            smem(),  # s_ia
            smem(),  # s_ib
            vmem(),  # hll state
            vmem(),  # cms state
        ],
        out_specs=[vmem() for _ in out_shape],
        out_shape=out_shape,
        # the plane state updates in place: inputs 13/14 alias outputs
        # 0/1 (positions count pallas_call operands, kernel order)
        input_output_aliases={13: 0, 14: 1},
        interpret=jax.default_backend() == "cpu",
        name="sketch_fused_update",  # the kernel's name in a device profile
    )(
        i32(s_slot), i32(s_gid), i32(s_reg), i32(s_rho),
        i32(s_mask), i32(w_head), i32(rw), cms_col, tk_col,
        jnp.asarray(s_hi, jnp.uint32), jnp.asarray(s_lo, jnp.uint32),
        jnp.asarray(s_ia, jnp.uint32), jnp.asarray(s_ib, jnp.uint32),
        hll.reshape(ring * g, m), cms.reshape(ring * d_cms, w_cms),
    )

    challengers = []
    for r in range(d_tk):
        got = bw[r] > 0
        hw = jnp.maximum(bw[r], 0)
        challengers.append((got, bh[r], bl[r], ba[r], bb[r], hw))
    return (
        hll2.reshape(ring, g, m),
        cms2.reshape(ring, d_cms, w_cms),
        challengers,
    )
