import jax
import jax.numpy as jnp
import numpy as np

from deepflow_tpu.ops.segment import SENTINEL_SLOT, groupby_reduce


def _np_reference(slot, hi, lo, tags, meters, valid, sum_cols, max_cols):
    """Dict-based oracle for the group-by."""
    groups = {}
    order = []
    for i in range(len(slot)):
        if not valid[i]:
            continue
        k = (int(slot[i]), int(hi[i]), int(lo[i]))
        if k not in groups:
            groups[k] = {"tags": tags[i], "sum": np.zeros(meters.shape[1]), "max": np.zeros(meters.shape[1])}
            order.append(k)
        groups[k]["sum"] += meters[i]
        groups[k]["max"] = np.maximum(groups[k]["max"], meters[i])
    out = {}
    for k, g in groups.items():
        m = np.zeros(meters.shape[1], dtype=np.float64)
        m[sum_cols] = g["sum"][sum_cols]
        m[max_cols] = g["max"][max_cols]
        out[k] = (g["tags"], m)
    return out


def _run_and_compare(n, t, m, n_keys, seed, valid_frac=1.0):
    rng = np.random.default_rng(seed)
    key_ids = rng.integers(0, n_keys, size=n)
    uniq_tags = rng.integers(0, 2**31, size=(n_keys, t), dtype=np.uint32)
    tags = uniq_tags[key_ids]
    slot = (rng.integers(0, 3, size=n)).astype(np.uint32)
    hi = uniq_tags[key_ids, 0]  # deterministic per-key pseudo-hash
    lo = uniq_tags[key_ids, 1 % t]
    meters = rng.integers(0, 1000, size=(n, m)).astype(np.float32)
    valid = rng.random(n) < valid_frac
    sum_cols = np.arange(0, m - 2, dtype=np.int32)
    max_cols = np.arange(m - 2, m, dtype=np.int32)

    g = jax.jit(
        lambda *a: groupby_reduce(*a, sum_cols=sum_cols, max_cols=max_cols)
    )(
        jnp.asarray(slot),
        jnp.asarray(hi),
        jnp.asarray(lo),
        jnp.asarray(tags.T),
        jnp.asarray(meters),  # row-major [N, M] since r6
        jnp.asarray(valid),
    )

    ref = _np_reference(slot, hi, lo, tags, meters, valid, sum_cols, max_cols)
    nseg = int(g.num_segments)
    assert nseg == len(ref)

    got_slots = np.asarray(g.slot)
    got_hi = np.asarray(g.key_hi)
    got_lo = np.asarray(g.key_lo)
    got_meters = np.asarray(g.meters).T
    got_tags = np.asarray(g.tags).T
    got_valid = np.asarray(g.seg_valid)
    assert got_valid[:nseg].all() and not got_valid[nseg:].any()

    seen = set()
    for j in range(nseg):
        k = (int(got_slots[j]), int(got_hi[j]), int(got_lo[j]))
        assert k in ref, k
        assert k not in seen
        seen.add(k)
        ref_tags, ref_meters = ref[k]
        np.testing.assert_array_equal(got_tags[j], ref_tags)
        np.testing.assert_allclose(got_meters[j], ref_meters, rtol=0, atol=0)
    # segments are emitted sorted by (slot, hi, lo)
    keys = [(int(got_slots[j]), int(got_hi[j]), int(got_lo[j])) for j in range(nseg)]
    assert keys == sorted(keys)


def test_groupby_small_exact():
    _run_and_compare(n=64, t=4, m=6, n_keys=7, seed=0)


def test_groupby_many_keys():
    _run_and_compare(n=512, t=8, m=10, n_keys=200, seed=1)


def test_groupby_with_invalid_rows():
    _run_and_compare(n=256, t=5, m=8, n_keys=31, seed=2, valid_frac=0.7)


def test_groupby_all_invalid():
    n, t, m = 16, 3, 4
    g = groupby_reduce(
        jnp.zeros(n, jnp.uint32),
        jnp.zeros(n, jnp.uint32),
        jnp.zeros(n, jnp.uint32),
        jnp.zeros((t, n), jnp.uint32),
        jnp.ones((n, m), jnp.float32),
        jnp.zeros(n, bool),
        sum_cols=np.arange(m, dtype=np.int32),
        max_cols=np.array([], dtype=np.int32),
    )
    assert int(g.num_segments) == 0
    assert not np.asarray(g.seg_valid).any()
    assert (np.asarray(g.slot) == SENTINEL_SLOT).all()


def test_groupby_single_key_all_rows():
    n, t, m = 128, 3, 4
    tags = np.tile(np.array([[7, 8, 9]], dtype=np.uint32), (n, 1))
    g = groupby_reduce(
        jnp.full((n,), 5, jnp.uint32),
        jnp.full((n,), 11, jnp.uint32),
        jnp.full((n,), 13, jnp.uint32),
        jnp.asarray(tags.T),
        jnp.ones((n, m), jnp.float32),
        jnp.ones(n, bool),
        sum_cols=np.array([0, 1], dtype=np.int32),
        max_cols=np.array([2, 3], dtype=np.int32),
    )
    assert int(g.num_segments) == 1
    np.testing.assert_array_equal(np.asarray(g.meters)[:, 0], [n, n, 1, 1])
    np.testing.assert_array_equal(np.asarray(g.tags)[:, 0], [7, 8, 9])


# ---------------------------------------------------------------------------
# PR 29: the output side runs in blocks over the live prefix. The blocked
# group-by against (1) the full-`cap` formulation it replaced, kept here as
# the reference, and (2) a NumPy group-by — bit for bit.

import functools

import pytest
from jax import lax

from deepflow_tpu.ops import segment
from deepflow_tpu.ops import segreduce_pallas as srp
from deepflow_tpu.ops.segment import Grouped, groupby_reduce_sorted

_T, _M = 3, 5
_SUM = np.array([0, 1, 2], np.int32)
_MAX = np.array([3, 4], np.int32)
_B = 64  # OUT_BLOCK_ROWS for the matrix below: small, so caps stay small


def _old_sorted_segment_sum_max(rows, seg_id, cap, first_pos, block=2048):
    """The head stage as it was: two [cap, 128] head gathers and the
    carries scattered into two more."""
    n, m = rows.shape
    blk = int(min(block, max(8, 1 << (n - 1).bit_length())))
    pad_rows = (-n) % blk
    if pad_rows:
        seg_id = jnp.pad(seg_id, (0, pad_rows), constant_values=np.int32(2**31 - 1))
        rows = jnp.pad(rows, ((0, pad_rows), (0, 0)))
        n += pad_rows
    rows = jnp.pad(rows, ((0, 0), (0, srp.LANES - m)))
    suf_sum, suf_max = srp._block_suffix(rows, seg_id.astype(jnp.int32)[:, None], blk)
    fp = jnp.clip(first_pos, 0, n - 1)
    base_sum = jnp.take(suf_sum, fp, axis=0)
    base_max = jnp.take(suf_max, fp, axis=0)
    nb = n // blk
    starts = jnp.arange(nb, dtype=jnp.int32) * blk
    first_seg = jnp.take(seg_id, starts).astype(jnp.int32)
    prefix_sum = jnp.take(suf_sum, starts, axis=0)
    prefix_max = jnp.take(suf_max, starts, axis=0)
    prev = jnp.take(seg_id, jnp.maximum(starts - 1, 0)).astype(jnp.int32)
    continues = (jnp.arange(nb) > 0) & (first_seg == prev)
    carry_seg = jnp.where(continues, first_seg, np.int32(2**31 - 1))
    carry_sum = jax.ops.segment_sum(
        jnp.where(continues[:, None], prefix_sum, 0.0), carry_seg, num_segments=cap)
    carry_max = jax.ops.segment_max(
        jnp.where(continues[:, None], prefix_max, srp._NEG), carry_seg, num_segments=cap)
    carry_max = jnp.where(jnp.isfinite(carry_max), carry_max, srp._NEG)
    return (base_sum + carry_sum)[:, :m], jnp.maximum(base_max, carry_max)[:, :m]


def _old_groupby_reduce_sorted(s_slot, s_hi, s_lo, perm, tags_t, meters_rows,
                               cap, pallas):
    """`groupby_reduce_sorted` before PR 29: a binary search, gathers and
    a select at every one of `cap` output rows."""
    n = s_slot.shape[0]
    head = jnp.concatenate([
        jnp.ones((1,), dtype=bool),
        (s_slot[1:] != s_slot[:-1]) | (s_hi[1:] != s_hi[:-1]) | (s_lo[1:] != s_lo[:-1]),
    ])
    live_row = s_slot != jnp.uint32(SENTINEL_SLOT)
    num_seg = jnp.sum((head & live_row).astype(jnp.int32))
    seg_id = jnp.where(live_row, jnp.cumsum(head.astype(jnp.int32)) - 1, n)
    first_pos = jnp.searchsorted(seg_id, jnp.arange(cap, dtype=jnp.int32))
    sorted_rows = jnp.take(meters_rows, perm, axis=0)
    if pallas:
        ps, pm = _old_sorted_segment_sum_max(sorted_rows, seg_id, cap, first_pos)
    else:
        ps = jax.ops.segment_sum(sorted_rows, seg_id, num_segments=cap, indices_are_sorted=True)
        pm = jax.ops.segment_max(sorted_rows, seg_id, num_segments=cap, indices_are_sorted=True)
    is_sum = np.zeros((_M,), bool)
    is_sum[_SUM] = True
    out_meters = jnp.where(jnp.asarray(is_sum)[None, :], ps, pm).T
    seg_valid = jnp.arange(cap, dtype=jnp.int32) < jnp.minimum(num_seg, cap)
    fp = jnp.where(seg_valid, first_pos, 0).astype(jnp.int32)
    return Grouped(
        slot=jnp.where(seg_valid, jnp.take(s_slot, fp), jnp.uint32(SENTINEL_SLOT)),
        key_hi=jnp.where(seg_valid, jnp.take(s_hi, fp), 0),
        key_lo=jnp.where(seg_valid, jnp.take(s_lo, fp), 0),
        tags=jnp.where(seg_valid[None, :], jnp.take(tags_t, jnp.take(perm, fp), axis=1), 0),
        meters=jnp.where(seg_valid[None, :], out_meters, 0),
        seg_valid=seg_valid,
        num_segments=num_seg,
    )


def _sorted_rows(seg_sizes, n, seed):
    """`n` sorted rows whose live prefix is segments of `seg_sizes` rows,
    behind a random permutation; integer-valued meters of both signs, so
    a sum is the same bits in any order (the package's exactness claim)."""
    rng = np.random.default_rng(seed)
    sizes = np.asarray(seg_sizes, np.int64)
    live = int(sizes.sum())
    assert live <= n
    seg = np.repeat(np.arange(sizes.size), sizes)
    slot = np.full(n, SENTINEL_SLOT, np.uint32)
    hi = np.full(n, 0xFFFFFFFF, np.uint32)
    lo = np.full(n, 0xFFFFFFFF, np.uint32)
    slot[:live] = 1 + seg // 50
    hi[:live] = seg
    lo[:live] = seg * 7 + 1
    perm = rng.permutation(n).astype(np.int32)
    tags_sorted = rng.integers(0, 2**31, size=(_T, n), dtype=np.uint32)
    meters_sorted = rng.integers(-500, 500, size=(n, _M)).astype(np.float32)
    tags = np.zeros_like(tags_sorted)
    tags[:, perm] = tags_sorted
    meters = np.zeros_like(meters_sorted)
    meters[perm] = meters_sorted
    return (slot, hi, lo, perm, tags, meters), (seg, tags_sorted, meters_sorted)


def _np_groupby(keys, sorted_view, n_seg, cap):
    slot, hi, lo = keys[:3]
    seg, tags_sorted, meters_sorted = sorted_view
    out = dict(
        slot=np.full(cap, SENTINEL_SLOT, np.uint32), key_hi=np.zeros(cap, np.uint32),
        key_lo=np.zeros(cap, np.uint32), tags=np.zeros((_T, cap), np.uint32),
        meters=np.zeros((_M, cap), np.float32), seg_valid=np.arange(cap) < min(n_seg, cap),
    )
    first = np.searchsorted(seg, np.arange(min(n_seg, cap)))
    ends = np.searchsorted(seg, np.arange(min(n_seg, cap)), side="right")
    for k, (a, b) in enumerate(zip(first, ends)):
        out["slot"][k], out["key_hi"][k], out["key_lo"][k] = slot[a], hi[a], lo[a]
        out["tags"][:, k] = tags_sorted[:, a]
        rows = meters_sorted[a:b].astype(np.float64)
        out["meters"][_SUM, k] = rows[:, _SUM].sum(axis=0)
        out["meters"][_MAX, k] = rows[:, _MAX].max(axis=0)
    return out


@functools.lru_cache(maxsize=None)
def _jitted(which, cap, kernel, block_rows):
    # one program a (cap, kernel): the live count is data, not a shape.
    # The platform switch and the block constant are read at trace time,
    # under the test's monkeypatch (the first call traces).
    if which == "new":
        return jax.jit(lambda *a: groupby_reduce_sorted(*a, _SUM, _MAX, out_capacity=cap))
    return jax.jit(lambda *a: _old_groupby_reduce_sorted(*a, cap, kernel == "pallas"))


def _check_blocked(monkeypatch, keys, sorted_view, n_seg, cap, kernel, block_rows):
    monkeypatch.setenv("DEEPFLOW_SEGREDUCE", kernel)
    monkeypatch.setattr(segment, "OUT_BLOCK_ROWS", block_rows)
    args = [jnp.asarray(x) for x in keys]
    new = _jitted("new", cap, kernel, block_rows)(*args)
    old = _jitted("old", cap, kernel, block_rows)(*args)
    want = _np_groupby(keys, sorted_view, n_seg, cap)
    assert int(new.num_segments) == int(old.num_segments) == n_seg
    for leaf in ("slot", "key_hi", "key_lo", "tags", "seg_valid"):
        got = np.asarray(getattr(new, leaf))
        np.testing.assert_array_equal(got, np.asarray(getattr(old, leaf)), err_msg=leaf)
        np.testing.assert_array_equal(got, want[leaf], err_msg=leaf)
    got = np.asarray(new.meters).view(np.uint32)
    np.testing.assert_array_equal(got, np.asarray(old.meters).view(np.uint32))
    # -0.0 and 0.0 are one value to the NumPy sum; the two device paths agree bit for bit above
    np.testing.assert_array_equal(np.asarray(new.meters), want["meters"])
    assert int(segment.out_blocks_run(n_seg, cap)) == -(-min(n_seg, cap) // min(block_rows, cap))


# cap: under one block (clamped), one the block does not divide (padded
# inside, cut statically), an odd one of many blocks, exactly two blocks
_CAPS = (40, 100, 4097, 2 * _B)


def _live_counts(cap):
    want = (0, 1, _B - 1, _B, _B + 1, 3 * _B + 7, cap - 1, cap, cap + 5)
    return sorted(set(want))


@pytest.mark.parametrize("kernel", ["pallas", "xla"])
@pytest.mark.parametrize(
    "cap,n_seg", [(c, k) for c in _CAPS for k in _live_counts(c)]
)
def test_blocked_output_is_the_full_cap_output_bit_for_bit(monkeypatch, cap, n_seg, kernel):
    """Live segments at every edge of a block and of the capacity, 0
    (every row dead) and `cap` + 5 (overflow counted in num_segments,
    the newest shed): keys, tags, meters, seg_valid, num_segments."""
    n = 2 * (max(cap, 3 * _B + 7) + 5) + 11
    sizes = 1 + (np.arange(n_seg) % 3 == 0)  # runs of 2, 1, 1 rows
    keys, view = _sorted_rows(sizes, n, seed=cap * 1000 + n_seg)
    _check_blocked(monkeypatch, keys, view, n_seg, cap, kernel, _B)


@pytest.mark.parametrize("kernel", ["pallas", "xla"])
@pytest.mark.parametrize("fill", ["dead_tail", "no_dead_row"])
def test_blocked_output_carries_a_segment_across_pallas_blocks(monkeypatch, kernel, fill):
    """The carry case: segment `_B` — the first row of output block 1 —
    starts mid-way through a 2048-row Pallas block, covers two whole
    ones and ends in the first row of the next; its neighbours continue
    across a boundary too. With `no_dead_row` the last segment runs to
    the end of the rows."""
    cap = 2 * _B
    sizes = np.ones(_B + 9, np.int64)
    sizes[3] = 2048 - 3 + 5  # opens in block 0, continues 5 rows into block 1
    sizes[_B] = 3 * 2048 + 1 - int(sizes[:_B].sum()) % 2048 + 2048
    n = 8 * 2048 - 100
    if fill == "no_dead_row":
        sizes[-1] = n - int(sizes[:-1].sum())
    start = int(sizes[:_B].sum())
    assert (start + int(sizes[_B]) - 1) % 2048 == 0 and sizes[_B] > 2 * 2048
    keys, view = _sorted_rows(sizes, n, seed=7)
    _check_blocked(monkeypatch, keys, view, sizes.size, cap, kernel, _B)


@pytest.mark.parametrize("cap", [100, 4097])
def test_default_block_is_clamped_to_a_small_capacity(monkeypatch, cap):
    """At the module's own OUT_BLOCK_ROWS a small capacity is one block."""
    assert segment.out_block_rows(cap) == cap and segment.out_blocks_total(cap) == 1
    assert segment.out_blocks_total(1 << 21) == (1 << 21) // segment.OUT_BLOCK_ROWS
    n_seg = cap - 3
    keys, view = _sorted_rows(np.full(n_seg, 2), 2 * cap + 8, seed=cap)
    _check_blocked(monkeypatch, keys, view, n_seg, cap, "xla", segment.OUT_BLOCK_ROWS)
