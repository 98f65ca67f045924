"""Multi-device tests on the 8-way virtual CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np

from deepflow_tpu.datamodel.batch import FlowBatch
from deepflow_tpu.ingest.replay import SyntheticFlowGen
from deepflow_tpu.ops.hll import hll_estimate, hll_init, hll_update
from deepflow_tpu.ops.hashing import fingerprint64
from deepflow_tpu.parallel.mesh import make_mesh
from deepflow_tpu.parallel.sharded import ShardedConfig, ShardedPipeline


def _batch_for(pipe, n_per_dev):
    gen = SyntheticFlowGen(num_tuples=500, seed=42)
    fb = gen.flow_batch(n_per_dev * pipe.n_devices, 1000)
    return fb


def test_mesh_shapes():
    mesh = make_mesh(8, n_hosts=2)
    assert mesh.devices.shape == (2, 4)
    assert mesh.axis_names == ("host", "chip")


def test_sharded_step_runs_and_counts_docs():
    mesh = make_mesh(8, n_hosts=2)
    cfg = ShardedConfig(capacity_per_device=1 << 10, num_services=64, hll_precision=8)
    pipe = ShardedPipeline(mesh, cfg)
    stash, sketches = pipe.init_state()

    fb = _batch_for(pipe, 128)
    acc = pipe.init_acc(4 * 128)
    stash, acc, sketches = pipe.step(stash, acc, 0, sketches, fb.tags, fb.meters, fb.valid)
    stash, acc, _fold_rows = pipe.fold(stash, acc)

    # every shard should now hold some valid stash rows
    valid = np.asarray(stash.valid)
    assert valid.shape[0] == 8
    assert (valid.sum(axis=1) > 0).all()
    # total stash docs ≤ 4 per input flow, > 0
    assert 0 < valid.sum() <= 4 * 128 * 8


def test_sharded_total_meters_match_input():
    """Sharding must not lose meter mass: the sum of packet_tx over all
    device stashes for edge docs equals the input sum (each flow emits
    its meter once per doc lane)."""
    from deepflow_tpu.datamodel.schema import FLOW_METER, TAG_SCHEMA

    mesh = make_mesh(8, n_hosts=1)
    cfg = ShardedConfig(capacity_per_device=1 << 12, num_services=64, hll_precision=8)
    pipe = ShardedPipeline(mesh, cfg)
    stash, sketches = pipe.init_state()

    fb = _batch_for(pipe, 64)
    in_pkt_tx = fb.meters[:, FLOW_METER.index("packet_tx")].sum()

    acc = pipe.init_acc(4 * 64)
    stash, acc, sketches = pipe.step(stash, acc, 0, sketches, fb.tags, fb.meters, fb.valid)
    stash, acc, _fold_rows = pipe.fold(stash, acc)

    valid = np.asarray(stash.valid)
    # stash payloads are column-major [D, M, S] / [D, T, S]
    meters = np.transpose(np.asarray(stash.meters), (0, 2, 1))
    tags = np.transpose(np.asarray(stash.tags), (0, 2, 1))
    code_col = TAG_SCHEMA.index("code_id")
    pkt_col = FLOW_METER.index("packet_tx")
    # edge docs with direction0 (lane 2) carry the unreversed meter exactly
    # once per flow → their packet_tx total equals the input total.
    from deepflow_tpu.datamodel.code import CodeId, Direction

    dir_col = TAG_SCHEMA.index("direction")
    total = 0.0
    for d in range(8):
        rows = valid[d]
        is_edge = np.isin(tags[d][:, code_col], (int(CodeId.EDGE_IP_PORT), int(CodeId.EDGE_MAC_IP_PORT)))
        is_c2s = tags[d][:, dir_col] == int(Direction.CLIENT_TO_SERVER)
        total += meters[d][rows & is_edge & is_c2s, pkt_col].sum()
    # flows with direction0 known: all in our generator draw with p=0.9
    gen_dir0 = fb.tags["direction0"] != 0
    expected = fb.meters[gen_dir0, pkt_col].sum()
    assert total == expected


def test_window_close_merges_hll_across_devices():
    mesh = make_mesh(8, n_hosts=2)
    cfg = ShardedConfig(capacity_per_device=1 << 10, num_services=16, hll_precision=12)
    pipe = ShardedPipeline(mesh, cfg)
    stash, sketches = pipe.init_state()

    # ~4000 distinct client ips across all shards, one service
    n = 8 * 512
    rng = np.random.default_rng(7)
    gen = SyntheticFlowGen(num_tuples=4000, seed=9)
    fb = gen.flow_batch(n, 2000)
    # pin all flows to one service key
    fb.tags["l3_epc_id1"][:] = 5
    fb.tags["server_port"][:] = 443

    acc = pipe.init_acc(4 * 512)
    stash, acc, sketches = pipe.step(stash, acc, 0, sketches, fb.tags, fb.meters, fb.valid)
    kept, global_view, pod_1m = pipe.window_close(sketches)

    # ISSUE 8: per-window state is authoritative — the view does NOT
    # reset the local planes (slots reset when their window closes
    # in-step); the first return is the planes unchanged
    np.testing.assert_array_equal(
        np.asarray(kept.hll), np.asarray(sketches.hll)
    )
    # global estimate ≈ distinct client ips
    svc = int((5 * 131 + 443) % 16)
    est_rows = np.asarray(jax.device_get(global_view.hll))
    # replicated across devices: every device's copy must agree
    for d in range(1, 8):
        np.testing.assert_array_equal(est_rows[0], est_rows[d])
    est = float(np.asarray(hll_estimate(jnp.asarray(est_rows[0])))[svc])
    true = len(np.unique(fb.tags["ip0_w3"]))
    assert abs(est - true) / true < 0.1
    # pod-wide 1m view exists and matches global (single window here)
    np.testing.assert_array_equal(np.asarray(pod_1m)[0], est_rows[0])


def _groupby_docs(doc_batches, meter_schema):
    """Reduce DocBatches by (timestamp, tag-row) with the schema's
    SUM/MAX lanes — the cross-shard merge that belongs to the query
    layer, used here to compare partial per-device docs to the oracle."""
    from collections import defaultdict

    sum_mask = meter_schema.sum_mask
    acc = {}
    for db in doc_batches:
        for i in range(db.size):
            if not db.valid[i]:
                continue
            key = (int(db.timestamp[i]), tuple(int(x) for x in db.tags[i]))
            m = db.meters[i].astype(np.float64)
            if key in acc:
                prev = acc[key]
                acc[key] = np.where(sum_mask, prev + m, np.maximum(prev, m))
            else:
                acc[key] = m
    return acc


def test_sharded_doc_flush_matches_single_device_oracle():
    """Flushed docs from the 8-device mesh, re-merged by key, must equal
    the single-device RollupPipeline's output on the same stream."""
    from deepflow_tpu.aggregator.pipeline import PipelineConfig, RollupPipeline
    from deepflow_tpu.aggregator.window import WindowConfig
    from deepflow_tpu.datamodel.schema import FLOW_METER
    from deepflow_tpu.parallel.sharded import ShardedWindowManager

    mesh = make_mesh(8, n_hosts=2)
    cfg = ShardedConfig(capacity_per_device=1 << 11, num_services=16, hll_precision=8)
    pipe = ShardedPipeline(mesh, cfg)
    swm = ShardedWindowManager(pipe)

    single = RollupPipeline(
        PipelineConfig(window=WindowConfig(capacity=1 << 14), batch_size=512)
    )

    gen = SyntheticFlowGen(num_tuples=300, seed=11)
    t0 = 5000
    sharded_docs, single_docs = [], []
    from deepflow_tpu.datamodel.batch import FlowBatch

    for t in (t0, t0, t0 + 1, t0 + 2, t0 + 8):
        fb = gen.flow_batch(512, t)
        sharded_docs += swm.ingest(fb.tags, fb.meters, fb.valid)
        single_docs += single.ingest(
            FlowBatch(tags=fb.tags, meters=fb.meters, valid=fb.valid)
        )
    sharded_docs += swm.drain()
    single_docs += single.drain()

    a = _groupby_docs(sharded_docs, FLOW_METER)
    b = _groupby_docs(single_docs, FLOW_METER)
    assert a.keys() == b.keys()
    assert len(a) > 0
    for k in a:
        np.testing.assert_allclose(a[k], b[k], rtol=1e-5, atol=1e-5)


def test_sharded_growing_batch_keeps_accumulated_rows():
    """Regression twin of test_window_manager_growing_batch_keeps_accumulated_rows
    for the sharded manager: a batch bigger than the per-device ring must
    fold pending rows before replacing it, on every device."""
    from deepflow_tpu.parallel.sharded import ShardedWindowManager

    mesh = make_mesh(8, n_hosts=2)
    cfg = ShardedConfig(
        capacity_per_device=1 << 10, num_services=16, hll_precision=8,
        accum_batches=2,
    )
    pipe = ShardedPipeline(mesh, cfg)
    swm = ShardedWindowManager(pipe)

    gen = SyntheticFlowGen(num_tuples=5000, seed=13)
    t0 = 7000
    fb_small = gen.flow_batch(8 * 8, t0)  # sizes ring at 2×32 rows/device
    fb_big = gen.flow_batch(8 * 64, t0)  # 256 rows/device > ring → re-init
    docs = []
    docs += swm.ingest(fb_small.tags, fb_small.meters, fb_small.valid)
    docs += swm.ingest(fb_big.tags, fb_big.meters, fb_big.valid)
    fb_tick = gen.flow_batch(8, t0 + 10)  # close window t0
    docs += swm.ingest(fb_tick.tags, fb_tick.meters, fb_tick.valid)

    # single-device oracle over the identical stream
    from deepflow_tpu.aggregator.pipeline import PipelineConfig, RollupPipeline
    from deepflow_tpu.aggregator.window import WindowConfig
    from deepflow_tpu.datamodel.schema import FLOW_METER

    single = RollupPipeline(
        PipelineConfig(window=WindowConfig(capacity=1 << 14), batch_size=512)
    )
    sdocs = []
    for fb in (fb_small, fb_big, fb_tick):
        sdocs += single.ingest(FlowBatch(tags=fb.tags, meters=fb.meters, valid=fb.valid))

    a = _groupby_docs(docs, FLOW_METER)
    b = _groupby_docs(sdocs, FLOW_METER)
    assert len(a) > 0 and a.keys() == b.keys()
    for k in a:
        np.testing.assert_allclose(a[k], b[k], rtol=1e-5, atol=1e-5)


def test_hll_sharded_equals_single_device():
    """pmax of per-shard HLL planes == HLL of the concatenated stream."""
    rng = np.random.default_rng(3)
    ids = rng.integers(0, 3000, size=(4096, 1), dtype=np.uint32)
    hi, lo = fingerprint64(jnp.asarray(ids))
    gid = jnp.zeros(4096, jnp.int32)
    ref = hll_update(hll_init(1, 10), gid, hi, lo, jnp.ones(4096, bool))

    merged = np.zeros_like(np.asarray(ref))
    for s in range(8):
        sl = slice(s * 512, (s + 1) * 512)
        part = hll_update(hll_init(1, 10), gid[sl], hi[sl], lo[sl], jnp.ones(512, bool))
        merged = np.maximum(merged, np.asarray(part))
    np.testing.assert_array_equal(merged, np.asarray(ref))


def test_sharded_prereduce_matches_single_device_oracle():
    """Same 8-device vs single-device equality with the batch-local
    pre-reduce on (ShardedConfig.batch_unique_cap)."""
    from deepflow_tpu.aggregator.pipeline import PipelineConfig, RollupPipeline
    from deepflow_tpu.aggregator.window import WindowConfig
    from deepflow_tpu.datamodel.batch import FlowBatch
    from deepflow_tpu.datamodel.schema import FLOW_METER
    from deepflow_tpu.parallel.sharded import ShardedWindowManager

    mesh = make_mesh(8, n_hosts=2)
    cfg = ShardedConfig(
        capacity_per_device=1 << 11, num_services=16, hll_precision=8,
        batch_unique_cap=256,  # 300 tuples / 8 devices → plenty of headroom
    )
    pipe = ShardedPipeline(mesh, cfg)
    swm = ShardedWindowManager(pipe)

    single = RollupPipeline(
        PipelineConfig(window=WindowConfig(capacity=1 << 14), batch_size=512)
    )

    gen = SyntheticFlowGen(num_tuples=300, seed=11)
    t0 = 5000
    sharded_docs, single_docs = [], []
    for t in (t0, t0, t0 + 1, t0 + 2, t0 + 8):
        fb = gen.flow_batch(512, t)
        sharded_docs += swm.ingest(fb.tags, fb.meters, fb.valid)
        single_docs += single.ingest(
            FlowBatch(tags=fb.tags, meters=fb.meters, valid=fb.valid)
        )
    sharded_docs += swm.drain()
    single_docs += single.drain()

    a = _groupby_docs(sharded_docs, FLOW_METER)
    b = _groupby_docs(single_docs, FLOW_METER)
    assert a.keys() == b.keys()
    assert len(a) > 0
    for k in a:
        np.testing.assert_allclose(a[k], b[k], rtol=1e-5, atol=1e-5)
