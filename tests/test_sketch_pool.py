"""Disaggregated sketch-memory pool (ISSUE 20) — pool-vs-slab
equivalence, promotion algebra, geometry validation, checkpoint v6
round-trips (single-chip AND sharded, mid-promotion), v5-into-pooled
loud re-init, spill accounting, and the shared-sort ring-fold pin.

Equivalence contract per lane (ops/{hll,cms,histogram,topk}.py):
  - HLL: compact slots keep the FULL m registers as int8 — promotion
    is a widening cast, so pooled HLL planes are BIT-EXACT vs slab.
  - log-hist: compact bins are exact coarsenings (bin // factor) and
    expansion re-centers mass — total mass is conserved EXACTLY.
  - CMS: compact rows are genuinely narrower (lossy); expansion tiles
    each compact count into all `cms_factor` congruent wide slots, so
    RAW pooled mass is slab × cms_factor while point-query estimates
    stay overestimate-only. Pins compare estimates, never raw counts.
  - top-K: compact buckets tile the same way; heavy-hitter recovery
    is the pinned surface.
"""

from __future__ import annotations

import dataclasses
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepflow_tpu.aggregator.sketchplane import PoolConfig, SketchConfig
from deepflow_tpu.aggregator.window import WindowConfig, WindowManager
from deepflow_tpu.datamodel.schema import FLOW_METER, TAG_SCHEMA
from deepflow_tpu.ops.histogram import LogHistSpec

SK_SLAB = SketchConfig(
    num_groups=4, hll_precision=8, cms_depth=3, cms_width=512,
    hist=LogHistSpec(bins=64, vmin=1.0, gamma=1.2),
    topk_rows=2, topk_cols=128, pending=10,
)
POOL = PoolConfig(compact_slots=3, wide_slots=1, cms_factor=8,
                  topk_factor=4, hist_factor=8, promote_fill=0.5)
SK_POOL = dataclasses.replace(SK_SLAB, pool=POOL)
T0 = 1_700_000_000


def _wm(sketch, capacity=1 << 11, delay=2, stats_ring=1):
    return WindowManager(
        WindowConfig(capacity=capacity, delay=delay, stats_ring=stats_ring,
                     sketch=sketch)
    )


def _doc_batch(keys: np.ndarray, t: int, byte_w=100.0):
    n = len(keys)
    keys = np.asarray(keys, np.uint32)
    tags = np.zeros((TAG_SCHEMA.num_fields, n), np.uint32)
    tags[TAG_SCHEMA.index("ip0_w3")] = keys
    tags[TAG_SCHEMA.index("server_port")] = 443
    tags[TAG_SCHEMA.index("protocol")] = 6
    tags[TAG_SCHEMA.index("l3_epc_id1")] = keys % 4
    meters = np.zeros((FLOW_METER.num_fields, n), np.float32)
    meters[FLOW_METER.index("byte_tx")] = byte_w
    meters[FLOW_METER.index("rtt_sum")] = 10.0
    meters[FLOW_METER.index("rtt_count")] = 1.0
    ts = np.full(n, t, np.uint32)
    hi = keys * np.uint32(2654435761) + np.uint32(1)
    lo = keys ^ np.uint32(0x9E3779B9)
    return (ts, jnp.asarray(hi), jnp.asarray(lo), jnp.asarray(tags),
            jnp.asarray(meters), jnp.ones(n, bool))


def _run(wm, batches):
    out = []
    for keys, t in batches:
        out.extend(wm.ingest(*_doc_batch(keys, t)))
    out.extend(wm.flush_all())
    return out


def _blocks(flushed):
    return {f.window_idx: f.sketches for f in flushed
            if f.sketches is not None}


# -- pool-vs-slab equivalence --------------------------------------------


def test_pool_vs_slab_closed_blocks_equal_accuracy():
    """The tentpole acceptance shape at test scale: the pooled plane
    closes the same windows with the same coverage, bit-exact HLL,
    mass-conserving histograms, in-envelope CMS estimates and the same
    recovered heavies — at a fraction of the slab's resident bytes."""
    rng = np.random.default_rng(60)
    per_window = {}
    for t in (T0, T0 + 1, T0 + 2):
        k = np.concatenate([
            rng.integers(0, 250, 400).astype(np.uint32),
            np.repeat(np.arange(4, dtype=np.uint32), 60),  # real heavies
        ])
        rng.shuffle(k)
        per_window[t] = k
    batches = [(k, t) for t, k in per_window.items()]
    slab = _blocks(_run(_wm(SK_SLAB), batches))
    pool = _blocks(_run(_wm(SK_POOL), batches))
    assert set(slab) == set(pool) == set(per_window)
    for w, a in slab.items():
        b = pool[w]
        keys = per_window[w]
        assert a.n_updates == b.n_updates == len(keys)
        # HLL: full-m int8 compact registers → bit-exact
        np.testing.assert_array_equal(a.hll, b.hll)
        # log-hist: mass conserved exactly through coarsen/expand
        assert int(np.sum(a.hist)) == int(np.sum(b.hist))
        # CMS raw mass scales by cms_factor under tile expansion when
        # the window closed compact (estimates below are the real pin)
        assert int(np.sum(b.cms)) in (
            int(np.sum(a.cms)), int(np.sum(a.cms)) * POOL.cms_factor
        )
        # §17 accuracy envelope holds for the POOLED block
        true_distinct = len(np.unique(keys))
        assert abs(b.distinct() - true_distinct) / true_distinct < 0.15
        uniq, counts = np.unique(keys, return_counts=True)
        hi = uniq * np.uint32(2654435761) + np.uint32(1)
        lo = uniq ^ np.uint32(0x9E3779B9)
        est = b.estimate(hi, lo)
        true_bytes = counts * 100
        assert (est >= true_bytes).all()
        # compact CMS ε = e/width bound: overcount ≤ mass/(width/8)
        assert (est - true_bytes <= len(keys) * 100 / 8).all()
        heavy_true = set(uniq[np.argsort(-counts)][:3].tolist())
        heavy_rec = {t_["id_a"] for t_ in b.topk(5)}
        assert len(heavy_true & heavy_rec) >= 2
        assert abs(b.quantile(0.5) - 10.0) / 10.0 < 0.3


def test_promoted_window_matches_slab_build_over_full():
    """Merge-of-promoted == build-over-full, per lane: a window that
    starts compact, trips the saturation estimator mid-stream and
    finishes wide must close with the same answers as the slab plane
    fed the identical full stream — HLL bit-exact (promotion is a
    cast), hist mass exact, CMS/top-K within the envelope."""
    rng = np.random.default_rng(61)
    # two batches into ONE window: the first saturates the compact CMS
    # row (width 512/8 = 64 → well past promote_fill=0.5), the second
    # lands post-promotion in the wide slot
    first = rng.integers(0, 2000, 600).astype(np.uint32)
    second = np.concatenate([
        rng.integers(0, 2000, 200).astype(np.uint32),
        np.repeat(np.arange(6, dtype=np.uint32), 150),  # planted heavies
    ])
    rng.shuffle(second)
    batches = [(first, T0), (second, T0), (np.arange(8, dtype=np.uint32), T0 + 4)]
    wm_pool = _wm(SK_POOL)
    pool_out = _run(wm_pool, batches)
    assert wm_pool.get_counters()["sketch_promotions"] >= 1
    assert wm_pool.get_counters()["sketch_pool_spill"] == 0
    slab_out = _run(_wm(SK_SLAB), batches)
    a, b = _blocks(slab_out)[T0], _blocks(pool_out)[T0]
    stream = np.concatenate([first, second])
    assert a.n_updates == b.n_updates == len(stream)
    np.testing.assert_array_equal(a.hll, b.hll)  # bit-exact across promote
    assert int(np.sum(a.hist)) == int(np.sum(b.hist))
    true_distinct = len(np.unique(stream))
    assert abs(b.distinct() - true_distinct) / true_distinct < 0.15
    uniq, counts = np.unique(stream, return_counts=True)
    est = b.estimate(uniq * np.uint32(2654435761) + np.uint32(1),
                     uniq ^ np.uint32(0x9E3779B9))
    assert (est >= counts * 100).all()
    # the planted heavies dominate the promoted block's recovery
    heavy_rec = {t_["id_a"] for t_ in b.topk(6)}
    assert len(set(range(6)) & heavy_rec) >= 4


def test_lane_expansion_properties():
    """Direct per-lane pins of the promotion algebra the plane relies
    on: CMS tile-expansion preserves point-query estimates exactly;
    log-hist coarsen/expand round-trips mass and the quantile bin."""
    from deepflow_tpu.ops.cms import (
        cms_expand, cms_init, cms_query, cms_update,
    )
    from deepflow_tpu.ops.histogram import (
        loghist_coarsen_bin, loghist_expand,
    )

    rng = np.random.default_rng(62)
    hi = jnp.asarray(rng.integers(0, 1 << 32, 200, dtype=np.uint32))
    lo = jnp.asarray(rng.integers(0, 1 << 32, 200, dtype=np.uint32))
    w = jnp.ones(200, jnp.int32)
    valid = jnp.ones(200, bool)
    compact = cms_update(cms_init(3, 64), hi, lo, w, valid)
    wide = cms_expand(compact, 512)
    # every key hashed into the compact table reads the SAME estimate
    # out of the tiled wide table (congruent slots carry the count)
    np.testing.assert_array_equal(
        np.asarray(cms_query(compact, hi, lo)),
        np.asarray(cms_query(wide, hi, lo)),
    )
    # raw mass scales by exactly the tile factor
    assert int(jnp.sum(wide)) == int(jnp.sum(compact)) * (512 // 64)

    # hist: wide→compact bin mapping is exact integer division; expand
    # conserves mass and lands it inside the source coarse bin
    wide_bins = jnp.asarray(rng.integers(0, 64, 500, dtype=np.int32))
    coarse = loghist_coarsen_bin(wide_bins, 8)
    np.testing.assert_array_equal(np.asarray(coarse),
                                  np.asarray(wide_bins) // 8)
    compact_h = np.zeros((2, 8), np.int64)
    np.add.at(compact_h, (0, np.asarray(coarse)), 1)
    expanded = np.asarray(loghist_expand(jnp.asarray(compact_h), 64))
    assert expanded.shape == (2, 64)
    assert expanded.sum() == compact_h.sum()
    np.testing.assert_array_equal(
        expanded.reshape(2, 8, 8).sum(-1), compact_h
    )


# -- geometry validation --------------------------------------------------


@pytest.mark.parametrize("bad,match", [
    (dict(wide_slots=0), "wide_slots"),
    (dict(compact_slots=0), "compact_slots"),
    (dict(cms_factor=3), "power of two"),
    (dict(cms_factor=1024), "cannot promote the cms lane"),
    (dict(hist_factor=128), "cannot promote the hist lane"),
    (dict(topk_factor=256), "cannot promote the topk lane"),
    (dict(promote_fill=0.0), "promote_fill"),
    (dict(promote_fill=1.5), "promote_fill"),
])
def test_pool_geometry_rejected(bad, match):
    """SketchConfig must reject pool/ring geometries where promotion
    cannot fit the widest lane — at CONSTRUCTION, naming the lane, not
    as a shape error inside a jitted step."""
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(SK_SLAB, pool=dataclasses.replace(POOL, **bad))


def test_pool_rejects_unpackable_hll():
    with pytest.raises(ValueError, match="divisible by 4"):
        SketchConfig(num_groups=2, hll_precision=1, cms_depth=2,
                     cms_width=64, hist=LogHistSpec(bins=16, vmin=1.0,
                                                    gamma=1.3),
                     topk_rows=0, topk_cols=8, pool=PoolConfig())


def test_pool_requires_cms_saturation_lane():
    with pytest.raises(ValueError, match="cms_depth"):
        dataclasses.replace(SK_SLAB, cms_depth=0, pool=POOL)


# -- spill accounting -----------------------------------------------------


def test_pool_exhaustion_spills_counted_not_silent():
    """More concurrently-open windows than pool slots: the overflow
    window loses sketch coverage COUNTED (sketch_pool_spill), the exact
    tier keeps every row, and no block is contaminated."""
    tiny = dataclasses.replace(
        SK_SLAB, pool=dataclasses.replace(POOL, compact_slots=1,
                                          wide_slots=1))
    wm = _wm(tiny, delay=2)  # R = 4 ring slots, but only 2 pool slots
    ks = np.arange(30, dtype=np.uint32)
    flushed = _run(wm, [(ks, T0), (ks, T0 + 1), (ks, T0 + 2), (ks, T0 + 3)])
    c = wm.get_counters()
    assert c["sketch_pool_spill"] > 0
    # exact rows flushed for EVERY window regardless of sketch spill
    assert sorted(f.window_idx for f in flushed) == [T0, T0 + 1, T0 + 2,
                                                     T0 + 3]
    assert all(f.count == 30 for f in flushed)
    # windows that did hold a slot close with clean blocks
    for f in flushed:
        if f.sketches is not None:
            assert f.sketches.n_updates == 30


def test_pool_occupancy_counter_moves():
    wm = _wm(SK_POOL)
    list(wm.ingest(*_doc_batch(np.arange(20, dtype=np.uint32), T0)))
    assert wm.get_counters()["sketch_pool_occ"] >= 1


# -- checkpoint v6 --------------------------------------------------------


def _ckpt_roundtrip_single(tmp_path, batches_pre, batches_post):
    """Run pool wm over pre-batches, checkpoint, continue original AND
    restored over post-batches; → (original flushed, restored flushed)."""
    from deepflow_tpu.aggregator.checkpoint import (
        load_window_state, save_window_state,
    )

    wm = _wm(SK_POOL)
    out_a = []
    for keys, t in batches_pre:
        out_a.extend(wm.ingest(*_doc_batch(keys, t)))
    ckpt = tmp_path / "pool.ckpt"
    out_a.extend(save_window_state(wm, ckpt))
    wm2 = load_window_state(ckpt, TAG_SCHEMA, FLOW_METER)
    out_b = list(out_a)
    for keys, t in batches_post:
        out_a.extend(wm.ingest(*_doc_batch(keys, t)))
        out_b.extend(wm2.ingest(*_doc_batch(keys, t)))
    out_a.extend(wm.flush_all())
    out_b.extend(wm2.flush_all())
    return out_a, out_b


def _assert_flushed_bit_exact(got, want):
    assert [f.window_idx for f in got] == [f.window_idx for f in want]
    for a, b in zip(got, want):
        assert a.count == b.count
        np.testing.assert_array_equal(a.key_hi, b.key_hi)
        np.testing.assert_array_equal(a.meters, b.meters)
        if a.sketches is None:
            assert b.sketches is None
            continue
        assert a.sketches.n_updates == b.sketches.n_updates
        for lane in ("hll", "cms", "hist", "tk_votes", "tk_hi", "tk_lo",
                     "tk_ida", "tk_idb"):
            np.testing.assert_array_equal(
                np.asarray(getattr(a.sketches, lane)),
                np.asarray(getattr(b.sketches, lane)), err_msg=lane,
            )


def test_checkpoint_v6_mid_promotion_roundtrip_bit_exact(tmp_path):
    """Kill-mid-promotion: the checkpoint lands AFTER a window promoted
    compact→wide but BEFORE it closed. The restored manager must finish
    the window bit-exact vs the uninterrupted run — the wide arena,
    slot maps and saturation state all ride the v6 file."""
    rng = np.random.default_rng(63)
    pre = [(rng.integers(0, 2000, 600).astype(np.uint32), T0)]  # promotes
    post = [(rng.integers(0, 2000, 300).astype(np.uint32), T0),
            (np.arange(40, dtype=np.uint32), T0 + 1),
            (np.arange(40, dtype=np.uint32), T0 + 4)]
    wm_probe = _wm(SK_POOL)
    for keys, t in pre:
        list(wm_probe.ingest(*_doc_batch(keys, t)))
    wm_probe.settle()
    assert wm_probe.get_counters()["sketch_promotions"] >= 1, \
        "pre-batches must trip a promotion for this pin to bite"
    out_a, out_b = _ckpt_roundtrip_single(tmp_path, pre, post)
    _assert_flushed_bit_exact(out_b, out_a)


def test_checkpoint_v6_meta_records_pool(tmp_path):
    from deepflow_tpu.aggregator.checkpoint import (
        read_checkpoint_meta, save_window_state,
    )

    wm = _wm(SK_POOL)
    list(wm.ingest(*_doc_batch(np.arange(10, dtype=np.uint32), T0)))
    ckpt = tmp_path / "meta.ckpt"
    save_window_state(wm, ckpt)
    meta = read_checkpoint_meta(ckpt)
    assert meta["version"] >= 6
    assert meta["sketch"]["pool"] == POOL.meta()


def test_slab_file_into_pooled_manager_reinits_loudly(tmp_path, caplog):
    """The v5-compatibility contract: a file whose sketch meta carries
    no pool (v5 files and slab v6 files look identical here) restores
    into a pool-configured manager with the sketch tier re-initialized
    and a LOUD log — pooled arenas cannot be re-seated from slabs. The
    exact tier restores bit-exact regardless."""
    from deepflow_tpu.aggregator.checkpoint import (
        load_window_state, save_window_state,
    )

    wm = _wm(SK_SLAB)
    list(wm.ingest(*_doc_batch(np.arange(50, dtype=np.uint32), T0)))
    ckpt = tmp_path / "slab.ckpt"
    save_window_state(wm, ckpt)
    with caplog.at_level(logging.WARNING):
        wm2 = load_window_state(ckpt, TAG_SCHEMA, FLOW_METER,
                                sketch_config=SK_POOL)
    assert any("cannot be re-seated" in r.message for r in caplog.records)
    assert wm2.config.sketch.pool is not None
    # exact rows survived; the re-initialized pooled plane works
    flushed = _run(wm2, [(np.arange(50, dtype=np.uint32), T0 + 4)])
    assert sum(f.count for f in flushed) >= 50
    assert wm2.get_counters()["sketch_pool_spill"] == 0


def test_slab_checkpoint_still_roundtrips_bit_exact(tmp_path):
    """v5-shaped files (no pool) keep loading bit-exact — the pooled
    lanes synthesize zero-size, nothing shifts in the layout."""
    from deepflow_tpu.aggregator.checkpoint import (
        load_window_state, save_window_state,
    )

    rng = np.random.default_rng(64)
    wm = _wm(SK_SLAB)
    list(wm.ingest(*_doc_batch(rng.integers(0, 300, 200).astype(np.uint32),
                               T0)))
    ckpt = tmp_path / "slab2.ckpt"
    save_window_state(wm, ckpt)
    wm2 = load_window_state(ckpt, TAG_SCHEMA, FLOW_METER)
    out_a = _run(wm, [(np.arange(30, dtype=np.uint32), T0 + 4)])
    out_b = _run(wm2, [(np.arange(30, dtype=np.uint32), T0 + 4)])
    _assert_flushed_bit_exact(out_b, out_a)


# -- sharded twin ---------------------------------------------------------


def _sharded_cfg(pool):
    from deepflow_tpu.parallel.sharded import ShardedConfig

    return ShardedConfig(
        capacity_per_device=1 << 10, num_services=8, hll_precision=7,
        cms_depth=2, cms_width=256,
        hist=LogHistSpec(bins=32, vmin=1.0, gamma=1.3),
        topk_cols=64, sketch_pending=8, sketch_pool=pool,
    )


def _sharded_run(n_dev, pool, batches):
    from deepflow_tpu.parallel.mesh import make_mesh
    from deepflow_tpu.parallel.sharded import (
        ShardedPipeline, ShardedWindowManager,
    )

    wm = ShardedWindowManager(
        ShardedPipeline(make_mesh(n_dev), _sharded_cfg(pool)))
    for fb in batches:
        wm.ingest(fb.tags, fb.meters, fb.valid)
    wm.drain()
    return wm, {b.window: b for b in wm.pop_closed_sketches()}


SH_POOL = PoolConfig(compact_slots=3, wide_slots=1, cms_factor=4,
                     topk_factor=2, hist_factor=4, promote_fill=0.5)


def test_sharded_pool_matches_slab_and_single_device():
    """Sharded twin equivalence: pooled blocks merge across the mesh to
    the same order-independent truth as slab blocks (HLL bit-exact,
    hist mass conserved) and a 2-device pooled run equals the 1-device
    pooled run bit-exact on merge-closed lanes."""
    from deepflow_tpu.ingest.replay import SyntheticFlowGen

    gen = SyntheticFlowGen(num_tuples=300, seed=54)
    batches = [gen.flow_batch(128, t) for t in (T0, T0 + 1, T0 + 4)]
    _, slab = _sharded_run(1, None, batches)
    wm_p1, pool1 = _sharded_run(1, SH_POOL, batches)
    wm_p2, pool2 = _sharded_run(2, SH_POOL, batches)
    assert set(slab) == set(pool1) == set(pool2)
    assert wm_p1.get_counters()["sketch_pool_spill"] == 0
    assert wm_p2.get_counters()["sketch_pool_spill"] == 0
    for w, a in slab.items():
        b1, b2 = pool1[w], pool2[w]
        assert a.n_updates == b1.n_updates == b2.n_updates
        np.testing.assert_array_equal(a.hll, b1.hll)  # pool vs slab
        assert int(np.sum(a.hist)) == int(np.sum(b1.hist))
        # mesh-merge determinism of the pooled plane itself
        np.testing.assert_array_equal(b1.hll, b2.hll)
        np.testing.assert_array_equal(b1.cms, b2.cms)
        np.testing.assert_array_equal(b1.hist, b2.hist)


def test_sharded_checkpoint_v6_mid_promotion_roundtrip(tmp_path):
    """Sharded kill-mid-promotion: checkpoint after a promoting batch,
    restore into a FRESH manager, continue both on identical traffic —
    closed blocks and counters must match bit-exact."""
    from deepflow_tpu.aggregator.checkpoint import (
        restore_sharded_state, save_sharded_state,
    )
    from deepflow_tpu.ingest.replay import SyntheticFlowGen
    from deepflow_tpu.parallel.mesh import make_mesh
    from deepflow_tpu.parallel.sharded import (
        ShardedPipeline, ShardedWindowManager,
    )

    # few distinct tuples at high volume saturate the compact CMS row
    gen = SyntheticFlowGen(num_tuples=400, seed=57)
    pre = [gen.flow_batch(256, T0), gen.flow_batch(256, T0)]
    post = [gen.flow_batch(128, T0 + 1), gen.flow_batch(128, T0 + 4)]
    mk = lambda: ShardedWindowManager(
        ShardedPipeline(make_mesh(2), _sharded_cfg(SH_POOL)))
    wm = mk()
    for fb in pre:
        wm.ingest(fb.tags, fb.meters, fb.valid)
    wm.drain()
    assert wm.get_counters()["sketch_promotions"] >= 1, \
        "pre-batches must trip a promotion for this pin to bite"
    # blocks closed before the barrier already left the device state:
    # they belong to the pre-checkpoint output, not the comparison
    wm.pop_closed_sketches()
    ckpt = tmp_path / "sh_pool.ckpt"
    save_sharded_state(wm, ckpt)
    wm2 = mk()
    restore_sharded_state(wm2, ckpt)
    blocks = {}
    for m in (wm, wm2):
        for fb in post:
            m.ingest(fb.tags, fb.meters, fb.valid)
        m.drain()
        blocks[id(m)] = {b.window: b for b in m.pop_closed_sketches()}
    a, b = blocks[id(wm)], blocks[id(wm2)]
    assert set(a) == set(b) and len(a) >= 1
    for w in a:
        assert a[w].n_updates == b[w].n_updates
        for lane in ("hll", "cms", "hist", "tk_votes", "tk_hi", "tk_lo"):
            np.testing.assert_array_equal(
                np.asarray(getattr(a[w], lane)),
                np.asarray(getattr(b[w], lane)), err_msg=lane,
            )
    ca, cb = wm.get_counters(), wm2.get_counters()
    # device-truth lanes ride the checkpoint and must agree exactly
    for k in ("sketch_promotions", "sketch_pool_spill"):
        assert ca[k] == cb[k], k
    # the original also closed the pre-barrier window (emitted before
    # the save), so its host-cumulative close count leads by exactly it
    assert ca["sketch_blocks_closed"] == cb["sketch_blocks_closed"] + 1


# -- shared-sort ring fold (ISSUE 20 satellite) ---------------------------


def test_tier_ring_fold_shared_sort_bit_exact():
    """The cascade's ring fold with the dispatch-owned shared order
    (shared_sort=True, rank-merge against the canonical tier prefix)
    must be BIT-EXACT vs the full two-array keyed sort across fills,
    including sentinel-invalid ring rows and the empty ring."""
    from deepflow_tpu.aggregator.cascade import _ring_fold_impl
    from deepflow_tpu.aggregator.stash import stash_fold, stash_init
    from tests.test_merge_fold import TINY_METER, TINY_TAGS, _rand_acc

    sum_cols = tuple(int(i) for i in np.nonzero(TINY_METER.sum_mask)[0])
    max_cols = tuple(int(i) for i in np.nonzero(TINY_METER.max_mask)[0])
    rng = np.random.default_rng(65)
    for fill in (0, 1, 37, 128):
        tier = stash_init(256, TINY_TAGS, TINY_METER)
        seed = _rand_acc(rng, 192, 150, n_windows=4, n_keys=40)
        tier, _ = stash_fold(tier, seed, TINY_METER)  # canonical prefix
        acc = _rand_acc(rng, 128, fill, n_windows=4, n_keys=40)
        lanes = jnp.zeros((2,), jnp.uint32)
        a_state, _, a_lanes = _ring_fold_impl(
            tier, acc, lanes, sum_cols, max_cols, shared_sort=False)
        b_state, _, b_lanes = _ring_fold_impl(
            tier, acc, lanes, sum_cols, max_cols, shared_sort=True)
        for f in ("slot", "key_hi", "key_lo", "tags", "meters", "valid",
                  "dropped_overflow"):
            np.testing.assert_array_equal(
                np.asarray(getattr(a_state, f)),
                np.asarray(getattr(b_state, f)),
                err_msg=f"fill={fill} lane={f}",
            )
        np.testing.assert_array_equal(np.asarray(a_lanes),
                                      np.asarray(b_lanes))


def test_stash_canonicalize_restores_sorted_prefix():
    """Restore-time repair for pre-v6 tier stashes: after punching a
    hole into the live prefix (the old non-compacting flush), one
    canonicalize pass re-establishes the sorted positional prefix and
    preserves every live row bit-for-bit."""
    from deepflow_tpu.aggregator.stash import (
        stash_canonicalize, stash_fold, stash_init,
    )
    from deepflow_tpu.ops.segment import SENTINEL_SLOT
    from tests.test_merge_fold import TINY_METER, TINY_TAGS, _rand_acc

    rng = np.random.default_rng(66)
    st = stash_init(128, TINY_TAGS, TINY_METER)
    st, _ = stash_fold(st, _rand_acc(rng, 128, 100, n_windows=4,
                                     n_keys=30), TINY_METER)
    live_before = {
        (int(h), int(l), int(s))
        for h, l, s, v in zip(np.asarray(st.key_hi), np.asarray(st.key_lo),
                              np.asarray(st.slot), np.asarray(st.valid))
        if v
    }
    # punch holes mid-prefix (what an old range flush left behind)
    slot = np.asarray(st.slot).copy()
    valid = np.asarray(st.valid).copy()
    holes = [i for i in range(len(valid)) if valid[i]][1:6]
    slot[holes] = np.uint32(SENTINEL_SLOT)
    valid[holes] = False
    broken = dataclasses.replace(st, slot=jnp.asarray(slot),
                                 valid=jnp.asarray(valid))
    fixed = stash_canonicalize(broken)
    v = np.asarray(fixed.valid)
    n_live = int(v.sum())
    assert v[:n_live].all() and not v[n_live:].any()  # positional prefix
    keys = np.stack([np.asarray(fixed.slot)[:n_live],
                     np.asarray(fixed.key_hi)[:n_live],
                     np.asarray(fixed.key_lo)[:n_live]], axis=1)
    assert all(tuple(keys[i]) <= tuple(keys[i + 1])
               for i in range(n_live - 1))  # (slot,key)-ascending
    live_after = {
        (int(np.asarray(fixed.key_hi)[i]), int(np.asarray(fixed.key_lo)[i]),
         int(np.asarray(fixed.slot)[i]))
        for i in range(n_live)
    }
    expect = {k for k in live_before
              if k not in {(int(np.asarray(st.key_hi)[i]),
                            int(np.asarray(st.key_lo)[i]),
                            int(np.asarray(st.slot)[i])) for i in holes}}
    assert live_after == expect
