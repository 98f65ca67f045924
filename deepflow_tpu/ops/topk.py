"""Invertible top-K heavy-hitter sketch — bucketed key recovery on device.

The exact stash answers "what did every flow do"; under high-cardinality
traffic it sheds. This sketch answers the question that survives the
shed: *which K keys were heaviest* — without ever flushing the key
space. Design follows the invertible-sketch / streaming top-K line
(PAPERS.md: "A Fast and Compact Invertible Sketch for Network-Wide
Heavy Flow Detection", "A streaming algorithm and hardware accelerator
for top-K flow detection"):

  * `[rows, cols]` buckets; each key maps to one bucket per row via an
    avalanche of its 64-bit fingerprint (no extra hashing per row).
  * Each bucket runs a weighted MJRTY (Boyer–Moore majority vote):
    matching keys add their weight to the bucket's vote, non-matching
    keys subtract; a vote crossing zero replaces the stored key. A key
    whose weight dominates its bucket in any row survives with its key
    bits *stored in the bucket* — that is the inversion: candidates are
    read straight out of the sketch.
  * Batch updates vectorize by aggregating the batch per (bucket, key)
    first — one 3-key sort + segment reductions (the ingest hot path's
    own machinery) — then applying ONE vote update per bucket with the
    bucket's heaviest batch key as the challenger. Within a batch only
    the heaviest challenger per bucket competes; lighter same-batch
    keys are absorbed into the next batch's aggregation. This keeps the
    update a fixed op count per row regardless of key skew, and it only
    *strengthens* the heavy-hitter guarantee (fewer spurious
    decrements).
  * Merge is bucket-wise MJRTY combination (same key: votes add;
    different keys: heavier survives with the vote difference) — the
    cross-shard close combines per-device sketches without any key
    exchange.

Frequencies are NOT read from the votes (votes are a survival signal,
not an estimate): `topk_select` estimates each recovered candidate via
the companion count-min plane of the same window — the classic
invertible pairing. Two u32 identity lanes (`id_a`, `id_b`) ride each
bucket so a recovered key also carries a human-readable flow preview
(e.g. client ip word + service port) without a reverse lookup.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def topk_init(rows: int, cols: int, ring: int = 1):
    """→ (votes, key_hi, key_lo, id_a, id_b) lane arrays, each
    [ring, rows, cols] (ring = per-window slots; 1 = a single sketch).
    votes <= 0 marks an empty bucket."""
    shape = (ring, rows, cols)
    z32 = jnp.zeros(shape, dtype=jnp.int32)
    zu = jnp.zeros(shape, dtype=jnp.uint32)
    return z32, zu, zu, zu, zu


def bucket_cols(key_hi, key_lo, row: int, cols: int, xp=jnp):
    """[N] i32 bucket column for hash row `row` (Kirsch–Mitzenmacher
    base + a different avalanche than the CMS rows, so the two sketches
    of one window never alias)."""
    assert cols & (cols - 1) == 0, "cols must be a power of two"
    h = xp.asarray(key_hi, xp.uint32) + xp.uint32(row + 1) * xp.asarray(
        key_lo, xp.uint32
    )
    h = h ^ (h >> xp.uint32(16))
    h = h * xp.uint32(0x7FEB352D)
    h = h ^ (h >> xp.uint32(15))
    h = h * xp.uint32(0x846CA68B)
    h = h ^ (h >> xp.uint32(16))
    return (h & xp.uint32(cols - 1)).astype(xp.int32)


def _apply_challengers(lanes, challengers):
    """Weighted-MJRTY vote epilogue, shared by both update paths (the
    fresh-sort oracle and the shared-sort presorted path): apply, per
    hash row, ONE challenger per flat [R*C] bucket. `challengers` is a list of
    (got, h_hi, h_lo, h_ia, h_ib, hw) tuples, one per hash row, with hw
    already clamped ≥ 0 and 0 wherever got is False."""
    votes, l_hi, l_lo, l_ia, l_ib = lanes
    r_ring, d, c = votes.shape
    for r, (got, h_hi, h_lo, h_ia, h_ib, hw) in enumerate(challengers):
        v = votes[:, r, :].reshape(-1)
        bh = l_hi[:, r, :].reshape(-1)
        bl = l_lo[:, r, :].reshape(-1)
        ba = l_ia[:, r, :].reshape(-1)
        bb = l_ib[:, r, :].reshape(-1)
        live = v > 0
        same = live & (bh == h_hi) & (bl == h_lo)
        challenged = jnp.where(live, v - hw, -hw)
        take = got & ~same & (challenged < 0)
        new_v = jnp.where(same, v + hw, jnp.where(take, -challenged, challenged))
        new_v = jnp.where(got, new_v, v)
        votes = votes.at[:, r, :].set(new_v.reshape(r_ring, c))
        l_hi = l_hi.at[:, r, :].set(jnp.where(take, h_hi, bh).reshape(r_ring, c))
        l_lo = l_lo.at[:, r, :].set(jnp.where(take, h_lo, bl).reshape(r_ring, c))
        l_ia = l_ia.at[:, r, :].set(jnp.where(take, h_ia, ba).reshape(r_ring, c))
        l_ib = l_ib.at[:, r, :].set(jnp.where(take, h_ib, bb).reshape(r_ring, c))
    return votes, l_hi, l_lo, l_ia, l_ib


def topk_update(lanes, slot, key_hi, key_lo, id_a, id_b, weight, valid):
    """One batch of weighted observations into the [R, d, C] lanes.

    `slot` is the per-row ring index ([N] i32); rows with slot outside
    [0, R) or valid=False are dropped. Traced — callers fuse this into
    their jitted ingest step. This is the multi-sort ORACLE: one fresh
    3-key sort per hash row. The shared-sort hot path
    (`topk_challengers_presorted`, driven from
    aggregator/sketchplane.py) is pinned bit-exact against it."""
    votes, l_hi, l_lo, l_ia, l_ib = lanes
    r_ring, d, c = votes.shape
    n = key_hi.shape[0]
    segs = r_ring * c
    key_hi = jnp.asarray(key_hi, jnp.uint32)
    key_lo = jnp.asarray(key_lo, jnp.uint32)
    w = jnp.where(valid, jnp.asarray(weight).astype(jnp.int32), 0)
    slot = jnp.asarray(slot, jnp.int32)
    ok = valid & (slot >= 0) & (slot < r_ring)
    iota = jnp.arange(n, dtype=jnp.int32)
    challengers = []
    for r in range(d):
        col = bucket_cols(key_hi, key_lo, r, c)
        seg = jnp.where(ok, slot * c + col, segs)
        # aggregate the batch per (bucket, key): one 3-key sort, then
        # run-level weight sums
        s_seg, s_hi, s_lo, s_w, s_ia, s_ib = lax.sort(
            (seg, key_hi, key_lo, w, jnp.asarray(id_a, jnp.uint32),
             jnp.asarray(id_b, jnp.uint32)),
            num_keys=3,
        )
        first = jnp.concatenate(
            [
                jnp.ones((1,), bool),
                (s_seg[1:] != s_seg[:-1])
                | (s_hi[1:] != s_hi[:-1])
                | (s_lo[1:] != s_lo[:-1]),
            ]
        )
        run_id = jnp.cumsum(first.astype(jnp.int32)) - 1
        run_w = jax.ops.segment_sum(s_w, run_id, num_segments=n)
        rw = run_w[run_id]  # per row: its (bucket, key)'s batch weight
        heavy_w = jax.ops.segment_max(rw, s_seg, num_segments=segs + 1)[:segs]
        # first row of the heaviest run per bucket (stable tie-break)
        in_seg = s_seg < segs
        is_heavy = in_seg & (rw == heavy_w[jnp.clip(s_seg, 0, segs - 1)])
        win_row = jax.ops.segment_min(
            jnp.where(is_heavy, iota, n), s_seg, num_segments=segs + 1
        )[:segs]
        got = win_row < n
        wr = jnp.clip(win_row, 0, n - 1)
        hw = jnp.where(got, jnp.maximum(heavy_w, 0), 0)
        challengers.append((got, s_hi[wr], s_lo[wr], s_ia[wr], s_ib[wr], hw))
    return _apply_challengers(lanes, challengers)


def topk_challengers_presorted(
    s_slot, s_hi, s_lo, s_ia, s_ib, rw, s_mask, r_ring: int, d: int, c: int
):
    """Per-hash-row challenger extraction from an ALREADY SORTED batch —
    zero sorts (ISSUE 17, shared-sort path).

    Inputs are the batch's lanes gathered through ONE shared
    (window, key_hi, key_lo)-stable sort permutation (the sketch
    plane's), with `rw` the per-row (window, key)-run weight sum under
    the phase mask `s_mask` (computed once upstream, shared with the
    count-min run dedup). Bit-exactness vs the per-row fresh sort of
    `topk_update` holds because a bucket only ever receives rows of ONE
    window (slot ↔ window is bijective within a phase span < R), so the
    shared order restricted to a bucket is the oracle's
    (key_hi, key_lo, original-position) order — same heaviest run, same
    stable first-row tie-break. Returns the `_apply_challengers` input
    list."""
    n = s_hi.shape[0]
    segs = r_ring * c
    iota = jnp.arange(n, dtype=jnp.int32)
    challengers = []
    for r in range(d):
        col = bucket_cols(s_hi, s_lo, r, c)
        seg = jnp.where(s_mask, s_slot * c + col, segs)
        heavy_w = jax.ops.segment_max(rw, seg, num_segments=segs + 1)[:segs]
        in_seg = seg < segs
        is_heavy = in_seg & (rw == heavy_w[jnp.clip(seg, 0, segs - 1)])
        win_row = jax.ops.segment_min(
            jnp.where(is_heavy, iota, n), seg, num_segments=segs + 1
        )[:segs]
        got = win_row < n
        wr = jnp.clip(win_row, 0, n - 1)
        hw = jnp.where(got, jnp.maximum(heavy_w, 0), 0)
        challengers.append((got, s_hi[wr], s_lo[wr], s_ia[wr], s_ib[wr], hw))
    return challengers


def topk_tile(lanes, cols: int):
    """Up-tile one slot's pooled compact lanes ([d, Cc] each) to
    [d, cols] wide buckets (ISSUE 20 promotion). `bucket_cols`' hash is
    width-independent, so a key's compact bucket is its wide bucket mod
    Cc — tiling copies every compact bucket (key bits, ids, votes) into
    each wide bucket that folds onto it, which keeps the key's own entry
    present in its true wide bucket. The copies landing in OTHER wide
    buckets are spurious candidates; they are harmless — each bucket
    runs its own MJRTY against the keys that actually hash there, and
    `topk_select` dedupes candidates by key before ranking."""
    votes, l_hi, l_lo, l_ia, l_ib = lanes
    cc = votes.shape[-1]
    assert cols % cc == 0 and cols & (cols - 1) == 0, (cc, cols)
    t = lambda x: jnp.tile(x, (1, cols // cc))
    return t(votes), t(l_hi), t(l_lo), t(l_ia), t(l_ib)


def topk_merge(a, b):
    """Bucket-wise MJRTY combine of two same-shape lane tuples: same key
    → votes add; different keys → the heavier key survives carrying the
    vote difference. Commutative up to dead buckets (an exact vote tie
    between different keys leaves votes=0 — empty either way)."""
    va, ha, la, aa, ab_ = a
    vb, hb, lb, ba, bb = b
    va_, vb_ = jnp.maximum(va, 0), jnp.maximum(vb, 0)
    same = (ha == hb) & (la == lb)
    take_b = ~same & (vb_ > va_)
    v = jnp.where(same, va_ + vb_, jnp.abs(va_ - vb_))
    pick = lambda x, y: jnp.where(take_b, y, x)
    return v, pick(ha, hb), pick(la, lb), pick(aa, ba), pick(ab_, bb)


def topk_candidates(votes, key_hi, key_lo, id_a, id_b):
    """Host-side inversion, step 1: read every surviving bucket
    (votes > 0) straight out of the sketch → flat np candidate arrays
    (key_hi, key_lo, id_a, id_b, votes)."""
    v = np.asarray(votes).reshape(-1)
    keep = v > 0
    flat = lambda x: np.asarray(x).reshape(-1)[keep]
    return flat(key_hi), flat(key_lo), flat(id_a), flat(id_b), v[keep]


def topk_select(cand_hi, cand_lo, cand_ia, cand_ib, estimates, k: int):
    """Host-side inversion, step 2: dedupe candidates by key, rank by
    the (caller-supplied, e.g. count-min) estimate, return the top-k
    row indices into the deduped arrays → (hi, lo, id_a, id_b, est)."""
    if len(cand_hi) == 0:
        z = np.zeros((0,), np.uint32)
        return z, z, z, z, np.zeros((0,), np.int64)
    key = cand_hi.astype(np.uint64) << np.uint64(32) | cand_lo.astype(np.uint64)
    _, first = np.unique(key, return_index=True)
    est = np.asarray(estimates)[first]
    rank = np.argsort(-est, kind="stable")[: max(0, k)]
    rows = first[rank]
    return (
        cand_hi[rows],
        cand_lo[rows],
        cand_ia[rows],
        cand_ib[rows],
        est[rank].astype(np.int64),
    )
