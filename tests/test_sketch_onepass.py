"""One-pass sketch fold (ISSUE 17) — the shared-sort rewrite, pinned
bit-exact against the multi-sort oracle.

Two layers:

  * jaxpr-level sort attribution: the census's static sort counter on
    `sketch_plane_step` itself — shared ON pays exactly ONE sort where
    the oracle pays 2 phases × topk_rows, and a top-K-less plane pays
    ZERO either way (the shared sort must never ADD a sort);
  * WindowManager-level bit-exactness: identical flushed exact rows and
    identical sketch blocks (every lane) across oracle and shared
    runs of the same stream — seeded fuzz over batch sizes, bucket
    counts, sketch shapes and fold modes, with invalid rows and
    multi-window batches in the mix.

The census end-to-end gate (telemetry()["profile"]["census"] showing
sorts/dispatch 4 → 1 on the REAL fused step) lives with the budget
gates in tests/test_perf_gate.py::test_one_pass_sketch_budget.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepflow_tpu.aggregator.sketchplane import (
    SketchConfig,
    sketch_init,
    sketch_plane_step,
)
from deepflow_tpu.aggregator.window import WindowConfig, WindowManager
from deepflow_tpu.datamodel.schema import FLOW_METER, TAG_SCHEMA
from deepflow_tpu.ops.histogram import LogHistSpec
from deepflow_tpu.profiling.census import _count_sort_eqns

T0 = 1_700_000_000

SK = SketchConfig(
    num_groups=4, hll_precision=7, cms_depth=2, cms_width=256,
    hist=LogHistSpec(bins=32, vmin=1.0, gamma=1.3),
    topk_rows=2, topk_cols=64, pending=8,
)


def _doc_batch(keys, ts, valid=None, weights=None):
    """Raw doc rows for WindowManager.ingest keyed by small int ids
    (the tests/test_sketch_plane.py convention), plus per-row
    timestamps, weights and validity so one batch can span windows and
    carry masked rows."""
    n = len(keys)
    keys = np.asarray(keys, np.uint32)
    tags = np.zeros((TAG_SCHEMA.num_fields, n), np.uint32)
    tags[TAG_SCHEMA.index("ip0_w3")] = keys
    tags[TAG_SCHEMA.index("server_port")] = 443
    tags[TAG_SCHEMA.index("protocol")] = 6
    tags[TAG_SCHEMA.index("l3_epc_id1")] = keys % 5
    meters = np.zeros((FLOW_METER.num_fields, n), np.float32)
    meters[FLOW_METER.index("byte_tx")] = (
        np.full(n, 100.0, np.float32) if weights is None
        else np.asarray(weights, np.float32)
    )
    meters[FLOW_METER.index("rtt_sum")] = 10.0
    meters[FLOW_METER.index("rtt_count")] = 1.0
    ts = np.broadcast_to(np.asarray(ts, np.uint32), (n,))
    hi = keys * np.uint32(2654435761) + np.uint32(1)
    lo = keys ^ np.uint32(0x9E3779B9)
    v = np.ones(n, bool) if valid is None else np.asarray(valid, bool)
    return (ts, jnp.asarray(hi), jnp.asarray(lo), jnp.asarray(tags),
            jnp.asarray(meters), jnp.asarray(v))


def _fuzz_batches(rng, n_batches, size, key_space):
    """Seeded stream: few-key runs, per-row weights, ~10% invalid rows,
    every 3rd batch spanning two windows, advancing time."""
    batches = []
    t = T0
    for i in range(n_batches):
        keys = rng.integers(0, key_space, size).astype(np.uint32)
        ts = np.full(size, t, np.uint32)
        if i % 3 == 2:
            ts[size // 2:] = t + 1
        valid = rng.random(size) > 0.1
        weights = rng.integers(1, 500, size).astype(np.float32)
        batches.append((keys, ts, valid, weights))
        t += int(rng.integers(0, 3))
    return batches


def _run_variant(monkeypatch, batches, *, shared, sketch=SK,
                 fold_mode="full"):
    """One full WindowManager run of `batches` under the given knob
    setting (a dispatch-time env read — aggregator/window.py)."""
    monkeypatch.setenv("DEEPFLOW_SHARED_SORT", "1" if shared else "0")
    wm = WindowManager(WindowConfig(
        capacity=1 << 10, delay=2, sketch=sketch, fold_mode=fold_mode,
    ))
    out = []
    for keys, ts, valid, weights in batches:
        out.extend(wm.ingest(*_doc_batch(keys, ts, valid, weights)))
    out.extend(wm.flush_all())
    return out


_BLOCK_LANES = ("hll", "cms", "hist", "tk_votes", "tk_hi", "tk_lo",
                "tk_ida", "tk_idb")


def _assert_flush_identical(a_list, b_list, label):
    """Every flushed window bit-identical: exact rows AND every sketch
    block lane."""
    assert [f.window_idx for f in a_list] == [f.window_idx for f in b_list]
    for a, b in zip(a_list, b_list):
        assert a.count == b.count, (label, a.window_idx)
        np.testing.assert_array_equal(
            np.asarray(a.key_hi), np.asarray(b.key_hi), err_msg=label)
        if a.sketches is None:
            assert b.sketches is None, (label, a.window_idx)
            continue
        assert b.sketches is not None, (label, a.window_idx)
        assert a.sketches.n_updates == b.sketches.n_updates, label
        for lane in _BLOCK_LANES:
            np.testing.assert_array_equal(
                np.asarray(getattr(a.sketches, lane)),
                np.asarray(getattr(b.sketches, lane)),
                err_msg=f"{label}: window {a.window_idx} lane {lane}",
            )


# ---------------------------------------------------------------------------
# jaxpr-level sort attribution (satellite 1, unit half)


def _plane_sorts(cfg: SketchConfig, shared: bool) -> int:
    """Static sort count of ONE sketch_plane_step dispatch at a small
    shape — jax.make_jaxpr only, no compile, no execute."""
    ring, n = 4, 64
    sk = sketch_init(cfg, ring)
    u32 = lambda x: jnp.asarray(x, jnp.uint32)

    def step(sk, window, key_hi, key_lo, client_hi, client_lo, weight,
             rtt, id_a, id_b, valid, rtt_valid, group):
        return sketch_plane_step(
            sk, cfg.hist, window=window, valid=valid, base_w=u32(10),
            close_w=u32(11), group=group, client_hi=client_hi,
            client_lo=client_lo, key_hi=key_hi, key_lo=key_lo,
            weight=weight, rtt=rtt, rtt_valid=rtt_valid, id_a=id_a,
            id_b=id_b, shared_sort=shared,
        )

    jaxpr = jax.make_jaxpr(step)(
        sk, u32(np.full(n, 11)), u32(np.arange(n)), u32(np.arange(n)),
        u32(np.arange(n)), u32(np.arange(n)),
        jnp.ones(n, jnp.float32), jnp.ones(n, jnp.float32),
        u32(np.arange(n)), u32(np.arange(n)), jnp.ones(n, bool),
        jnp.ones(n, bool), jnp.zeros(n, jnp.int32),
    )
    return _count_sort_eqns(jaxpr.jaxpr)


def test_shared_sort_collapses_plane_sorts_to_one():
    """The tentpole's arithmetic: the oracle pays 2 phases × topk_rows
    fresh sorts per dispatch; the shared-sort path pays exactly ONE."""
    assert _plane_sorts(SK, shared=False) == 2 * SK.topk_rows == 4
    assert _plane_sorts(SK, shared=True) == 1


def test_shared_sort_never_adds_a_sort_without_topk():
    """With the top-K lane off the plane already needs zero sorts — the
    shared sort must not engage and ADD one."""
    cfg = SketchConfig(
        num_groups=4, hll_precision=7, cms_depth=2, cms_width=256,
        hist=LogHistSpec(bins=32, vmin=1.0, gamma=1.3),
        topk_rows=0, topk_cols=64, pending=8,
    )
    assert _plane_sorts(cfg, shared=False) == 0
    assert _plane_sorts(cfg, shared=True) == 0


# ---------------------------------------------------------------------------
# WindowManager-level bit-exactness


def test_shared_sort_bit_exact_vs_oracle(monkeypatch):
    """Same seeded stream — runs, skewed weights, invalid rows,
    window-spanning batches, window advances — flushed exact rows and
    every sketch block lane bit-identical with the shared sort ON vs
    the multi-sort oracle."""
    rng = np.random.default_rng(170)
    batches = _fuzz_batches(rng, n_batches=6, size=257, key_space=40)
    oracle = _run_variant(monkeypatch, batches, shared=False)
    shared = _run_variant(monkeypatch, batches, shared=True)
    assert any(f.sketches is not None for f in oracle)
    _assert_flush_identical(oracle, shared, "shared-vs-oracle")


@pytest.mark.parametrize(
    "seed,size,key_space,sketch,fold_mode",
    [
        (171, 193, 30, SK, "full"),
        (
            172, 320, 120,
            SketchConfig(
                num_groups=4, hll_precision=8, cms_depth=3, cms_width=512,
                hist=LogHistSpec(bins=64, vmin=1.0, gamma=1.2),
                topk_rows=3, topk_cols=128, pending=10,
            ),
            "merge",
        ),
    ],
)
def test_shared_sort_parity_fuzz(monkeypatch, seed, size, key_space,
                                 sketch, fold_mode):
    """The oracle and the shared sort produce bit-identical flushed
    streams and sketch blocks over seeded fuzz covering batch sizes,
    top-K bucket counts, count-min shapes and both fold modes."""
    rng = np.random.default_rng(seed)
    batches = _fuzz_batches(rng, n_batches=5, size=size,
                            key_space=key_space)
    kw = dict(sketch=sketch, fold_mode=fold_mode)
    oracle = _run_variant(monkeypatch, batches, shared=False, **kw)
    shared = _run_variant(monkeypatch, batches, shared=True, **kw)
    assert any(f.sketches is not None and f.sketches.tk_votes.size
               for f in oracle)
    _assert_flush_identical(oracle, shared, "shared-vs-oracle")
