"""ISSUE 5 incremental merge-fold conformance.

The merge-fold (aggregator/stash.stash_merge_fold) must be bit-exact
against the full-sort fold oracle (`_fold_impl`) — same stash lanes,
same overflow-drop counts, same garbage in the dead tail — at the stash
level (including span-bounded folds against a masked-accumulator
oracle) AND at the window-manager level (fold_mode="merge" vs "full"
managers fed identical streams produce identical flushed windows, drop
counters and shutdown drains), on the single-chip and sharded paths.
The compacting range flush must re-establish the canonical layout
(live rows = sorted positional prefix) the rank-merge requires, and
the plan_append 'init' hazard guard must trip loudly if the pre-init
fold is ever bypassed.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepflow_tpu.aggregator.stash import (
    AccumState,
    accum_init,
    stash_flush_range,
    stash_fold,
    stash_fold_counted,
    stash_init,
    stash_merge_fold,
)
from deepflow_tpu.aggregator.window import WindowConfig, WindowManager
from deepflow_tpu.datamodel.schema import (
    MergeOp,
    MeterField,
    MeterSchema,
    TagField,
    TagSchema,
)
from deepflow_tpu.ops.segment import SENTINEL_SLOT

TINY_METER = MeterSchema(
    "tiny",
    (
        MeterField("a", MergeOp.SUM),
        MeterField("b", MergeOp.SUM),
        MeterField("mx", MergeOp.MAX),
    ),
)
TINY_TAGS = TagSchema((TagField("k1"), TagField("k2")))
SENT = np.uint32(SENTINEL_SLOT)


def _clone(x):
    return jax.tree.map(jnp.array, x)


def _rand_acc(rng, cap, fill, n_windows=5, n_keys=8):
    """Accumulator ring with `fill` rows: random (window, key) pairs,
    non-trivial float bit patterns, ~20% sentinel-invalid rows mixed in
    (the append path sentinels gated-out rows in place)."""
    slot = np.full(cap, SENT, np.uint32)
    hi = np.zeros(cap, np.uint32)
    lo = np.zeros(cap, np.uint32)
    tags = np.zeros((2, cap), np.uint32)
    met = np.zeros((3, cap), np.float32)
    if fill:
        k = rng.integers(0, n_keys, fill).astype(np.uint32)
        slot[:fill] = rng.integers(1, 1 + n_windows, fill).astype(np.uint32)
        hi[:fill] = k
        lo[:fill] = k * 7 + 1
        tags[:, :fill] = np.stack([k, k + 13])
        met[:, :fill] = rng.normal(size=(3, fill)).astype(np.float32)
        inv = rng.random(fill) < 0.2
        slot[:fill][inv] = SENT
    return AccumState(
        slot=jnp.asarray(slot),
        key_hi=jnp.asarray(hi),
        key_lo=jnp.asarray(lo),
        tags=jnp.asarray(tags),
        meters=jnp.asarray(met),
    )


def _assert_state_equal(a, b, msg=""):
    for leaf in ("slot", "key_hi", "key_lo", "tags", "valid"):
        np.testing.assert_array_equal(
            np.asarray(getattr(a, leaf)), np.asarray(getattr(b, leaf)),
            err_msg=f"{msg} leaf {leaf}",
        )
    # float meters on exact bits (bit-exact acceptance)
    np.testing.assert_array_equal(
        np.asarray(a.meters).view(np.uint32),
        np.asarray(b.meters).view(np.uint32),
        err_msg=f"{msg} meters",
    )
    assert int(a.dropped_overflow) == int(b.dropped_overflow), msg


@pytest.mark.slow
def test_merge_fold_bitexact_vs_full_sort_fuzz():
    """Full-set merge-fold == full-sort fold on random stashes and
    accumulators, INCLUDING capacity-overflow trials (small stash caps
    force dropped_overflow > 0 on some draws — the drop set and count
    must match exactly)."""
    rng = np.random.default_rng(42)
    saw_overflow = 0
    for trial in range(25):
        scap = int(rng.integers(4, 48))
        acap = int(rng.integers(4, 64))
        state = stash_init(scap, TINY_TAGS, TINY_METER)
        # canonical non-empty stash: fold one random ring in first
        state, _ = stash_fold(
            state, _rand_acc(rng, acap, int(rng.integers(0, acap + 1))), TINY_METER
        )
        acc = _rand_acc(rng, acap, int(rng.integers(0, acap + 1)))

        fs, fa = stash_fold(_clone(state), _clone(acc), TINY_METER)
        ms, ma, rows = stash_merge_fold(_clone(state), _clone(acc), TINY_METER)
        _assert_state_equal(fs, ms, f"trial {trial}")
        np.testing.assert_array_equal(np.asarray(fa.slot), np.asarray(ma.slot))
        saw_overflow += int(fs.dropped_overflow) > 0
        # fold_rows counts the live acc rows the merge sorted
        assert int(rows[0]) == int((np.asarray(acc.slot) != SENT).sum())
    assert saw_overflow >= 3, "fuzz never exercised the overflow stance"


@pytest.mark.slow
def test_merge_fold_span_bounded_matches_masked_oracle():
    """Span-bounded fold == full-sort fold over (stash + acc rows with
    slot < hi); out-of-span rows stay accumulated untouched."""
    rng = np.random.default_rng(7)
    for trial in range(15):
        scap, acap = int(rng.integers(8, 40)), int(rng.integers(8, 48))
        state = stash_init(scap, TINY_TAGS, TINY_METER)
        state, _ = stash_fold(
            state, _rand_acc(rng, acap, int(rng.integers(4, acap + 1))), TINY_METER
        )
        acc = _rand_acc(rng, acap, int(rng.integers(0, acap + 1)))
        hi = int(rng.integers(1, 7))

        sl = np.asarray(acc.slot)
        oracle_acc = dataclasses.replace(
            _clone(acc),
            slot=jnp.asarray(np.where(sl < hi, sl, SENT).astype(np.uint32)),
        )
        os_, _ = stash_fold(_clone(state), oracle_acc, TINY_METER)
        ss, sa, rows = stash_merge_fold(
            _clone(state), _clone(acc), TINY_METER, hi_window=hi
        )
        _assert_state_equal(os_, ss, f"span trial {trial}")
        # consumed rows sentinel in place, the rest byte-identical
        np.testing.assert_array_equal(
            np.asarray(sa.slot), np.where(sl < hi, SENT, sl).astype(np.uint32)
        )
        np.testing.assert_array_equal(
            np.asarray(sa.meters).view(np.uint32),
            np.asarray(acc.meters).view(np.uint32),
        )
        assert int(rows[0]) == int((sl < hi).sum())


@pytest.mark.parametrize("live", [15, 16, 17, 32])
def test_merge_fold_equals_full_fold_at_an_output_block_boundary(monkeypatch, live):
    """PR 29: both folds make their output in blocks of OUT_BLOCK_ROWS
    segments under a trip count. A stash live up to a block boundary,
    one short of it, one past it and full, at 8-row blocks: full ≡ merge
    bit for bit, and both report the same trip count."""
    from deepflow_tpu.aggregator import stash as stash_mod
    from deepflow_tpu.ops import segment

    # the module's jitted folds read the constant when they trace: none
    # traced before may serve here, none traced here may serve later
    jitted = (stash_mod.collector_fold, stash_mod.collector_fold_counted,
              stash_mod.collector_merge_fold)
    for f in jitted:
        f.clear_cache()
    monkeypatch.setattr(segment, "OUT_BLOCK_ROWS", 8)
    rng = np.random.default_rng(live)
    scap, acap = 32, 27
    state = stash_init(scap, TINY_TAGS, TINY_METER)
    seed = _rand_acc(rng, acap, 0)
    k = np.arange(acap, dtype=np.uint32)
    seed = dataclasses.replace(
        seed, slot=jnp.asarray(np.where(k < min(live, acap), 2, SENT).astype(np.uint32)),
        key_hi=jnp.asarray(k), key_lo=jnp.asarray(k * 7 + 1),
    )
    state, _ = stash_fold(state, seed, TINY_METER)
    more = max(0, live - acap)  # the second ring brings what is still missing
    k2 = np.arange(acap, dtype=np.uint32)
    acc = dataclasses.replace(
        _rand_acc(rng, acap, acap),
        slot=jnp.asarray(np.where(k2 < more + 6, 2, SENT).astype(np.uint32)),
        # `more` new keys behind the first ring's, and six it already holds
        key_hi=jnp.asarray(np.where(k2 < more, acap + k2, k2 - more).astype(np.uint32)),
    )
    acc = dataclasses.replace(acc, key_lo=acc.key_hi * 7 + 1)
    fs, _, f_lanes = stash_fold_counted(_clone(state), _clone(acc), TINY_METER)
    ms, _, m_lanes = stash_merge_fold(_clone(state), _clone(acc), TINY_METER)
    _assert_state_equal(fs, ms, f"live {live}")
    assert int(np.asarray(fs.valid).sum()) == live
    assert int(f_lanes[1]) == int(m_lanes[1]) == -(-live // 8)
    for f in jitted:
        f.clear_cache()


def test_merge_fold_scatter_order_variant(monkeypatch):
    """DEEPFLOW_MERGE_SCATTER=1 (the linear one-scatter merged-order
    construction, the on-chip A/B knob) stays bit-exact. Uses unique
    shapes so the env flip cannot hit a cached sort-variant
    executable."""
    monkeypatch.setenv("DEEPFLOW_MERGE_SCATTER", "1")
    rng = np.random.default_rng(11)
    state = stash_init(37, TINY_TAGS, TINY_METER)
    state, _ = stash_fold(state, _rand_acc(rng, 29, 25), TINY_METER)
    acc = _rand_acc(rng, 29, 21)
    fs, _ = stash_fold(_clone(state), _clone(acc), TINY_METER)
    ms, _, _ = stash_merge_fold(_clone(state), _clone(acc), TINY_METER)
    _assert_state_equal(fs, ms, "scatter variant")


def test_flush_range_compact_keeps_canonical_layout():
    """compact=True: flushed output identical to the plain flush, and
    the surviving stash keeps live rows as a sorted positional prefix —
    the invariant the next merge-fold needs."""
    rng = np.random.default_rng(3)
    for trial in range(10):
        state = stash_init(48, TINY_TAGS, TINY_METER)
        state, _ = stash_fold(state, _rand_acc(rng, 40, int(rng.integers(8, 40))), TINY_METER)
        hi_w = int(rng.integers(2, 6))

        c_state, c_packed, c_total = stash_flush_range(
            _clone(state), np.uint32(0), np.uint32(hi_w), compact=True
        )
        n_state, n_packed, n_total = stash_flush_range(
            _clone(state), np.uint32(0), np.uint32(hi_w)
        )
        assert int(c_total) == int(n_total)
        np.testing.assert_array_equal(
            np.asarray(c_packed[: int(c_total)]), np.asarray(n_packed[: int(n_total)])
        )
        v = np.asarray(c_state.valid)
        live = int(v.sum())
        assert v[:live].all() and not v[live:].any(), "live rows not a prefix"
        keys = list(
            zip(
                np.asarray(c_state.slot)[:live].tolist(),
                np.asarray(c_state.key_hi)[:live].tolist(),
                np.asarray(c_state.key_lo)[:live].tolist(),
            )
        )
        assert keys == sorted(keys), "live prefix not (slot, key)-sorted"
        # and a merge-fold on the compacted state still matches the oracle
        acc = _rand_acc(rng, 40, int(rng.integers(0, 40)))
        fs, _ = stash_fold(_clone(c_state), _clone(acc), TINY_METER)
        ms, _, _ = stash_merge_fold(_clone(c_state), _clone(acc), TINY_METER)
        _assert_state_equal(fs, ms, f"post-compact trial {trial}")


# ---------------------------------------------------------------------------
# window-manager level: fold_mode="merge" vs "full" on identical streams


def _mgr_batch(ts_list, key_list):
    n = len(ts_list)
    ts = np.asarray(ts_list, dtype=np.uint32)
    hi = np.asarray(key_list, dtype=np.uint32)
    tags = np.stack([hi, hi + 1], axis=0).astype(np.uint32)
    meters = (
        np.arange(3 * n, dtype=np.float32).reshape(3, n) * 0.25 + hi[None, :]
    )
    return (
        jnp.asarray(ts),
        jnp.asarray(hi),
        jnp.asarray(hi * 3 + 1),
        jnp.asarray(tags),
        jnp.asarray(meters),
        jnp.ones(n, dtype=bool),
    )


def _flushed_key(flushed):
    return [
        (
            f.window_idx,
            f.count,
            f.key_hi.tolist(),
            f.key_lo.tolist(),
            f.tags.tolist(),
            f.meters.view(np.uint32).tolist(),
        )
        for f in flushed
    ]


@pytest.mark.parametrize(
    "extra", [{}, {"stats_ring": 4}, {"async_drain": True}]
)
def test_window_manager_merge_mode_matches_full_fuzz(extra):
    """Random streams (late rows, multi-window batches, growing batch
    sizes that force a mid-stream ring re-init) through a full-mode and
    a merge-mode manager: identical flushed windows at every step,
    identical counters, identical shutdown drain. Also runs under the
    K-batch counter ring and async_drain deferrals."""
    rng = np.random.default_rng(19)
    for seed in range(4):
        wms = {
            mode: WindowManager(
                WindowConfig(
                    interval=1, delay=2, capacity=256, accum_batches=4,
                    fold_mode=mode, **extra,
                ),
                TINY_TAGS,
                TINY_METER,
            )
            for mode in ("full", "merge")
        }
        t = 100 + seed
        got = {m: [] for m in wms}
        for step in range(12):
            t += int(rng.integers(0, 3))
            n = int(rng.integers(1, 14))
            if step == 7:
                n = 40  # > ring capacity → plan_append 'init' mid-stream
            ts = t + rng.integers(-3, 2, n)  # some late → gated drops
            ts = np.maximum(ts, 0)
            keys = rng.integers(0, 10, n)
            batch = _mgr_batch(ts.tolist(), keys.tolist())
            for m, wm in wms.items():
                got[m].extend(wm.ingest(*batch))
        for m, wm in wms.items():
            got[m].extend(wm.flush_all())
        assert _flushed_key(got["merge"]) == _flushed_key(got["full"]), (
            f"seed {seed} extra {extra}"
        )
        for field in ("drop_before_window", "total_docs_in", "total_flushed"):
            assert getattr(wms["merge"], field) == getattr(wms["full"], field)
        # nothing left on device in either mode
        for wm in wms.values():
            assert wm.counters["occupancy"] == 0


def test_window_manager_merge_mode_fold_rows_lane():
    """The CB_FOLD_ROWS lane mirrors span-bounded fold work: an advance
    in merge mode sorts only the closing span's acc rows, so its
    fold_rows mirror lands strictly below the full-sort manager's on
    the identical stream (which re-sorts every live row). Open-window
    rows legitimately stay in the ring — the stash alone no longer
    bounds fold work in merge mode."""
    wms = {
        mode: WindowManager(
            WindowConfig(interval=1, delay=3, capacity=512, fold_mode=mode),
            TINY_TAGS,
            TINY_METER,
        )
        for mode in ("full", "merge")
    }
    t0 = 1000
    # several open windows with distinct keys; then one advance batch
    # (closes windows t0..t0+2, window t0+3 stays open) and one more
    # dispatch so the post-advance block (fold_rows lane) is fetched
    batches = [
        _mgr_batch([t0 + i] * 20, list(range(20 * i, 20 * i + 20)))
        for i in range(4)
    ] + [_mgr_batch([t0 + 6], [999]), _mgr_batch([t0 + 6], [998])]
    for b in batches:
        for wm in wms.values():
            wm.ingest(*b)
    full_c = wms["full"].get_counters()
    merge_c = wms["merge"].get_counters()
    assert merge_c["fold_rows"] > 0
    # span-bounded: 3×20 closing rows vs the full fold's 80+ live rows
    assert merge_c["fold_rows"] < full_c["fold_rows"], (merge_c, full_c)


def test_ring_reinit_guard_trips_when_fold_bypassed():
    """plan_append 'init' hazard (stash.py docstring): if the pre-init
    fold is bypassed while rows are pending, the manager must raise
    instead of silently dropping them."""
    wm = WindowManager(
        WindowConfig(interval=1, delay=2, capacity=64, accum_batches=2),
        TINY_TAGS,
        TINY_METER,
    )
    wm.ingest(*_mgr_batch([50, 50], [1, 2]))  # ring sized 2×2, fill=2
    wm._fold = lambda: None  # simulate a refactor bypassing the fold
    with pytest.raises(AssertionError, match="pending"):
        wm.ingest(*_mgr_batch([50] * 8, list(range(8))))  # > ring → init


def test_sharded_ring_reinit_guard_trips_when_fold_bypassed():
    from deepflow_tpu.ingest.replay import SyntheticFlowGen
    from deepflow_tpu.parallel.mesh import make_mesh
    from deepflow_tpu.parallel.sharded import (
        ShardedConfig,
        ShardedPipeline,
        ShardedWindowManager,
    )

    wm = ShardedWindowManager(
        ShardedPipeline(
            make_mesh(1),
            ShardedConfig(capacity_per_device=1 << 10, num_services=16,
                          hll_precision=6, accum_batches=2),
        )
    )
    gen = SyntheticFlowGen(num_tuples=50, seed=2)
    fb = gen.flow_batch(16, 9000)
    wm.ingest(fb.tags, fb.meters, fb.valid)
    wm._fold = lambda: None
    big = gen.flow_batch(256, 9000)
    with pytest.raises(AssertionError, match="pending"):
        wm.ingest(big.tags, big.meters, big.valid)


def _docbatch_key(dbs):
    return [
        (
            int(db.timestamp[0]) if db.size else -1,
            db.size,
            np.asarray(db.tags).tolist(),
            np.asarray(db.meters).view(np.uint32).tolist(),
        )
        for db in dbs
    ]


@pytest.mark.slow
@pytest.mark.parametrize("n_dev", [1, 2])
def test_sharded_merge_mode_matches_full(n_dev):
    """ShardedWindowManager fold_mode="merge" vs "full" on identical
    flow streams (advancing windows, a growing batch forcing a ring
    re-init, a shutdown drain): identical DocBatches and counters."""
    from deepflow_tpu.ingest.replay import SyntheticFlowGen
    from deepflow_tpu.ops.histogram import LogHistSpec
    from deepflow_tpu.parallel.mesh import make_mesh
    from deepflow_tpu.parallel.sharded import (
        ShardedConfig,
        ShardedPipeline,
        ShardedWindowManager,
    )

    wms = {}
    for mode in ("full", "merge"):
        cfg = ShardedConfig(
            capacity_per_device=1 << 11, num_services=16, hll_precision=6,
            hist=LogHistSpec(bins=64, vmin=1.0, gamma=1.3), accum_batches=2,
            fold_mode=mode,
        )
        wms[mode] = ShardedWindowManager(
            ShardedPipeline(make_mesh(n_dev), cfg)
        )
    gen = SyntheticFlowGen(num_tuples=120, seed=13)
    t0 = 9000
    sizes = [32, 32, 32, 128, 32, 64]  # the 128 forces a ring re-init
    times = [t0, t0, t0 + 1, t0 + 4, t0 + 5, t0 + 9]
    batches = [
        gen.flow_batch(n * n_dev, t) for n, t in zip(sizes, times)
    ]
    got = {m: [] for m in wms}
    for fb in batches:
        for m, wm in wms.items():
            got[m].extend(wm.ingest(fb.tags, fb.meters, fb.valid))
    for m, wm in wms.items():
        got[m].extend(wm.drain())
    assert len(got["full"]) > 0
    assert _docbatch_key(got["merge"]) == _docbatch_key(got["full"])
    for field in ("flow_in", "flushed_doc", "drop_before_window"):
        assert (
            wms["merge"].get_counters()[field] == wms["full"].get_counters()[field]
        )
    # the fold_rows lane mirrored through the bundled drain fetch
    assert wms["merge"].get_counters()["fold_rows"] >= 0


def test_sharded_merge_mode_rejects_per_window_oracle_flush():
    """pipe.flush_window leaves sentinel holes mid-prefix — merge mode
    must refuse it loudly (silent canonical-layout corruption would
    make the next rank-merge emit wrong aggregates)."""
    from deepflow_tpu.parallel.mesh import make_mesh
    from deepflow_tpu.parallel.sharded import ShardedConfig, ShardedPipeline

    pipe = ShardedPipeline(
        make_mesh(1),
        ShardedConfig(capacity_per_device=1 << 8, num_services=16,
                      hll_precision=6, fold_mode="merge"),
    )
    stash, _ = pipe.init_state()
    with pytest.raises(ValueError, match="flush_range"):
        pipe.flush_window(stash, 1)


def test_stash_fold_counted_matches_plain_fold():
    """stash_fold_counted is the telemetry twin of stash_fold: identical
    state transition plus the touched-row scalar."""
    rng = np.random.default_rng(23)
    state = stash_init(32, TINY_TAGS, TINY_METER)
    state, _ = stash_fold(state, _rand_acc(rng, 24, 20), TINY_METER)
    acc = _rand_acc(rng, 24, 15)
    fs, fa = stash_fold(_clone(state), _clone(acc), TINY_METER)
    cs, ca, rows = stash_fold_counted(_clone(state), _clone(acc), TINY_METER)
    _assert_state_equal(fs, cs, "counted fold")
    live_stash = int(np.asarray(state.valid).sum())
    live_acc = int((np.asarray(acc.slot) != SENT).sum())
    assert int(rows[0]) == live_stash + live_acc
