"""Pallas suffix-scan segmented reduce vs the XLA segment ops — the two
paths of ops/segment.py must agree exactly on integer-valued meters and
to 1 ulp on arbitrary floats (tree-order association). The CPU runs the
kernel in interpret mode; tests/test_tpu_compile.py asks the chip's
compiler for the same kernel at real widths."""

from __future__ import annotations

import numpy as np
import pytest

import jax.numpy as jnp

from deepflow_tpu.ops.segreduce_pallas import LANES, sorted_segment_sum_max


def _case(n, cap, n_keys, m=7, seed=0, integral=True, block=256):
    rng = np.random.default_rng(seed)
    seg = np.sort(rng.integers(0, n_keys, n)).astype(np.int32)
    n_live = n - n // 8  # tail of dead rows, ids past every live one
    seg[n_live:] = n
    if integral:
        rows = rng.integers(0, 1000, (n, m)).astype(np.float32)
    else:
        rows = rng.standard_normal((n, m)).astype(np.float32) * 1e3
    first_pos = np.searchsorted(seg, np.arange(cap)).astype(np.int32)

    got_s, got_m = sorted_segment_sum_max(
        jnp.asarray(rows), jnp.asarray(seg), cap, jnp.asarray(first_pos),
        block=block,
    )
    import jax

    want_s = jax.ops.segment_sum(jnp.asarray(rows), jnp.asarray(seg),
                                 num_segments=cap, indices_are_sorted=True)
    want_m = jax.ops.segment_max(jnp.asarray(rows), jnp.asarray(seg),
                                 num_segments=cap, indices_are_sorted=True)
    live = np.zeros(cap, bool)
    live[np.unique(seg[:n_live])[np.unique(seg[:n_live]) < cap]] = True
    return (np.asarray(got_s)[live], np.asarray(got_m)[live],
            np.asarray(want_s)[live], np.asarray(want_m)[live])


CASES = [
    (1024, 256, 100, 256),     # multi-block, segments span blocks
    (1024, 256, 3, 128),       # few huge segments (span many blocks)
    (777, 64, 40, 256),        # non-multiple-of-block row count
    (2048, 2048, 1500, 512),   # cap == n-scale, many singletons
    (512, 32, 1, 128),         # one segment spanning everything
]


@pytest.mark.parametrize("n,cap,n_keys,block", CASES)
def test_matches_xla_integral(n, cap, n_keys, block):
    gs, gm, ws, wm = _case(n, cap, n_keys, block=block)
    np.testing.assert_array_equal(gs, ws)
    np.testing.assert_array_equal(gm, wm)


def test_matches_xla_float_tolerance():
    gs, gm, ws, wm = _case(1024, 256, 50, integral=False, seed=3)
    np.testing.assert_allclose(gs, ws, rtol=1e-5)
    np.testing.assert_array_equal(gm, wm)  # max is order-free → exact


def test_full_lane_width():
    """m == LANES: no lane padding at all."""
    gs, gm, ws, wm = _case(512, 64, 20, m=LANES, block=128)
    np.testing.assert_array_equal(gs, ws)
    np.testing.assert_array_equal(gm, wm)


def test_meter_width_guard():
    """A meter schema wider than the kernel's lane tile must fail
    loudly (ADVICE.md #2), not mis-shape the hot-path reduce."""
    with pytest.raises(ValueError, match="lane"):
        sorted_segment_sum_max(
            jnp.zeros((16, LANES + 1), jnp.float32),
            jnp.zeros((16,), jnp.int32),
            4,
            jnp.zeros((4,), jnp.int32),
        )


def _groupby_inputs(seed=7, n=512, t=5, m=6):
    rng = np.random.default_rng(seed)
    slot = rng.integers(0, 3, n).astype(np.uint32)
    hi = rng.integers(0, 50, n).astype(np.uint32)
    lo = rng.integers(0, 2, n).astype(np.uint32)
    tags = rng.integers(0, 100, (t, n)).astype(np.uint32)
    meters = rng.integers(0, 500, (n, m)).astype(np.float32)
    valid = rng.random(n) < 0.9
    sum_cols = np.array([0, 1, 2, 3], np.int32)
    max_cols = np.array([4, 5], np.int32)
    return slot, hi, lo, tags, meters, valid, sum_cols, max_cols


def _run_groupby(monkeypatch, segreduce: str):
    monkeypatch.setenv("DEEPFLOW_SEGREDUCE", segreduce)
    from deepflow_tpu.ops.segment import groupby_reduce

    slot, hi, lo, tags, meters, valid, sum_cols, max_cols = _groupby_inputs()
    return groupby_reduce(jnp.asarray(slot), jnp.asarray(hi), jnp.asarray(lo),
                          jnp.asarray(tags), jnp.asarray(meters),
                          jnp.asarray(valid), sum_cols, max_cols,
                          out_capacity=128)


def test_groupby_reduce_pallas_path_matches(monkeypatch):
    """Force the pallas path through the full groupby_reduce and pin it
    against the XLA path on the same inputs."""
    g1 = _run_groupby(monkeypatch, "pallas")
    g2 = _run_groupby(monkeypatch, "xla")
    np.testing.assert_array_equal(np.asarray(g1.meters), np.asarray(g2.meters))
    np.testing.assert_array_equal(np.asarray(g1.slot), np.asarray(g2.slot))
    np.testing.assert_array_equal(np.asarray(g1.seg_valid), np.asarray(g2.seg_valid))
