"""End-to-end conformance: jit pipeline vs NumPy/dict oracle.

Replays the same synthetic flow batches through L4Pipeline (fanout →
fingerprint → windowed stash on device) and oracle_l4_rollup (scalar
dicts, int64), asserting identical per-window key sets and exact meter
agreement.
"""

import numpy as np

from deepflow_tpu.aggregator.fanout import FanoutConfig
from deepflow_tpu.aggregator.pipeline import L4Pipeline, L4PipelineConfig
from deepflow_tpu.aggregator.window import WindowConfig
from deepflow_tpu.datamodel.schema import FLOW_METER, TAG_SCHEMA
from deepflow_tpu.ingest.replay import SyntheticFlowGen
from deepflow_tpu.oracle.numpy_oracle import oracle_l4_rollup

KEY_FIELDS = [f.name for f in TAG_SCHEMA.fields if f.key]
KEY_IDX = [TAG_SCHEMA.index(n) for n in KEY_FIELDS]


def _docbatch_to_dict(db):
    """{(window, key_tuple): meter int64 array}"""
    out = {}
    for i in range(db.size):
        key = (int(db.timestamp[i]),) + tuple(int(db.tags[i, j]) for j in KEY_IDX)
        assert key not in out, f"duplicate key emitted: {key}"
        out[key] = db.meters[i].astype(np.int64)
    return out


def _run_both(gen_kwargs, batches, config=FanoutConfig(), interval=1):
    gen = SyntheticFlowGen(**gen_kwargs)
    pipe = L4Pipeline(
        L4PipelineConfig(
            fanout=config,
            window=WindowConfig(interval=interval, delay=2, capacity=1 << 12),
            batch_size=512,
        )
    )
    all_records = []
    emitted = {}
    for t, size in batches:
        recs = gen.records(size, t)
        all_records.extend(recs)
        from deepflow_tpu.datamodel.batch import FlowBatch

        for db in pipe.ingest(FlowBatch.from_records(recs)):
            emitted.update(_docbatch_to_dict(db))
    for db in pipe.drain():
        emitted.update(_docbatch_to_dict(db))

    oracle = oracle_l4_rollup(all_records, config, interval=interval)
    # device DocBatch timestamps are window *start seconds*; oracle windows
    # are indices — normalize to start seconds.
    oracle_keys = {
        (d.window * interval,) + tuple(d.tag[k] for k in KEY_FIELDS): d for d in oracle.values()
    }
    return emitted, oracle_keys


def _compare(emitted, oracle_keys):
    assert set(emitted.keys()) == set(oracle_keys.keys()), (
        f"key sets differ: only-device={len(set(emitted) - set(oracle_keys))} "
        f"only-oracle={len(set(oracle_keys) - set(emitted))}"
    )
    for key, dev_meter in emitted.items():
        ref = oracle_keys[key].meter
        for i, f in enumerate(FLOW_METER.fields):
            assert dev_meter[i] == ref[f.name], (
                f"meter mismatch at {f.name}: device={dev_meter[i]} oracle={ref[f.name]} key={key}"
            )


def test_single_window_small():
    emitted, oracle = _run_both(
        {"num_tuples": 50, "seed": 1}, batches=[(1000, 100), (1000, 100), (1004, 1)]
    )
    assert len(oracle) > 0
    _compare(emitted, oracle)


def test_multi_window_replay():
    batches = [(t, 200) for t in range(2000, 2006)] + [(2010, 1)]
    emitted, oracle = _run_both({"num_tuples": 300, "seed": 2}, batches)
    windows = {k[0] for k in oracle}
    assert len(windows) >= 6
    _compare(emitted, oracle)


def test_direction_mix_and_inactive():
    emitted, oracle = _run_both(
        {"num_tuples": 80, "seed": 3, "p_both_dirs": 0.4, "p_one_dir": 0.3},
        batches=[(3000, 300), (3003, 1)],
    )
    _compare(emitted, oracle)


def test_inactive_ip_aggregation_config():
    cfg = FanoutConfig(inactive_ip_aggregation=True)
    emitted, oracle = _run_both(
        {"num_tuples": 60, "seed": 4}, batches=[(4000, 200), (4003, 1)], config=cfg
    )
    _compare(emitted, oracle)


def test_minute_granularity():
    batches = [(t, 100) for t in (5000, 5030, 5059, 5061, 5125)]
    emitted, oracle = _run_both({"num_tuples": 40, "seed": 5}, batches, interval=60)
    _compare(emitted, oracle)


def test_l4_both_inactive_record_dropped():
    # collector.rs:489-493: both hosts inactive + inactive_ip_aggregation
    # → whole record dropped, including edge docs
    from deepflow_tpu.datamodel.batch import FlowBatch
    from deepflow_tpu.datamodel.code import Direction, SignalSource

    cfg = FanoutConfig(inactive_ip_aggregation=True)
    rec = {
        "timestamp": 1_700_000_000,
        "signal_source": int(SignalSource.PACKET),
        "ip0_w3": 1,
        "ip1_w3": 2,
        "protocol": 6,
        "server_port": 80,
        "direction0": int(Direction.CLIENT_TO_SERVER),
        "direction1": int(Direction.SERVER_TO_CLIENT),
        "is_active_host0": 0,
        "is_active_host1": 0,
        "is_active_service": 1,
        "meter": {"packet_tx": 7},
    }
    pipe = L4Pipeline(
        L4PipelineConfig(
            fanout=cfg, window=WindowConfig(interval=1, delay=2, capacity=256), batch_size=64
        )
    )
    out = pipe.ingest(FlowBatch.from_records([rec])) + pipe.drain()
    assert all(db.size == 0 for db in out)
    assert oracle_l4_rollup([rec], cfg) == {}


def test_conformance_forced_pallas(monkeypatch):
    """The whole device pipeline stays oracle-exact with the Pallas
    suffix-scan reduce forced on (CPU runs it in interpret mode).
    Integer meters must be bit-exact; the suite's meters are integral
    so _compare's equality check IS the bit-exactness check."""
    import jax

    monkeypatch.setenv("DEEPFLOW_SEGREDUCE", "pallas")
    jax.clear_caches()  # path selection happens at trace time
    try:
        emitted, oracle = _run_both(
            {"num_tuples": 50, "seed": 1},
            batches=[(1000, 100), (1000, 100), (1004, 1)],
        )
        assert len(oracle) > 0
        _compare(emitted, oracle)
    finally:
        monkeypatch.delenv("DEEPFLOW_SEGREDUCE")
        jax.clear_caches()


def test_batch_unique_cap_prereduce_exact():
    """The batch-local pre-reduce (fanout-after-reduce) must
    be EXACT: same fold output as the plain step, because identical raw
    tag rows land identical doc rows per lane and the lane meter
    transforms are column permutations (sum/max commute)."""
    import jax.numpy as jnp

    from deepflow_tpu.aggregator.fanout import FanoutConfig
    from deepflow_tpu.aggregator.pipeline import make_ingest_step
    from deepflow_tpu.aggregator.stash import accum_init, stash_init
    from deepflow_tpu.datamodel.schema import FLOW_METER, TAG_SCHEMA

    gen = SyntheticFlowGen(num_tuples=37, seed=3)  # heavy dup factor
    batch = 512
    fb = gen.flow_batch(batch, 1_700_000_000)
    tags = {k: jnp.asarray(v) for k, v in fb.tags.items()}
    meters = jnp.asarray(fb.meters)
    valid = jnp.asarray(fb.valid)

    def run(cap):
        append, fold = make_ingest_step(FanoutConfig(), interval=1,
                                        batch_unique_cap=cap)
        n_doc = 4 * (cap if cap else batch)
        state = stash_init(1 << 11, TAG_SCHEMA, FLOW_METER)
        acc = accum_init(2 * n_doc, TAG_SCHEMA, FLOW_METER)
        state, acc = append(state, acc, jnp.int32(0), tags, meters, valid)
        state, acc = append(state, acc, jnp.int32(n_doc), tags, meters, valid)
        state, acc = fold(state, acc)
        return state

    plain = run(None)
    reduced = run(256)  # 37 tuples → plenty of cap headroom

    # identical live segments: same keys, same slots, same reduced meters
    np.testing.assert_array_equal(np.asarray(plain.valid), np.asarray(reduced.valid))
    m = np.asarray(plain.valid)
    for field in ("slot", "key_hi", "key_lo"):
        np.testing.assert_array_equal(
            np.asarray(getattr(plain, field))[m], np.asarray(getattr(reduced, field))[m])
    np.testing.assert_array_equal(np.asarray(plain.tags)[:, m], np.asarray(reduced.tags)[:, m])
    np.testing.assert_allclose(
        np.asarray(plain.meters)[:, m], np.asarray(reduced.meters)[:, m], rtol=0, atol=0)
    assert int(reduced.dropped_overflow) == 0

    # cap overflow is shed + counted, not silently merged
    capped = run(16)  # 37 uniques > 16
    assert int(capped.dropped_overflow) > 0


def test_rollup_pipeline_with_prereduce_matches_plain():
    """RollupPipeline with PipelineConfig.batch_unique_cap produces the
    same flushed docs as the plain pipeline (production-path twin of the
    step-level exactness test)."""
    from deepflow_tpu.aggregator.pipeline import L4Pipeline, PipelineConfig
    from deepflow_tpu.aggregator.window import WindowConfig
    from deepflow_tpu.datamodel.batch import FlowBatch

    gen = SyntheticFlowGen(num_tuples=64, seed=9)

    gen_records = {t: gen.records(256, t) for t in (9000, 9001, 9004)}

    def run(cap):
        pipe = L4Pipeline(PipelineConfig(
            window=WindowConfig(capacity=1 << 12), batch_size=512,
            batch_unique_cap=cap,
        ))
        rows = {}
        for t in (9000, 9000, 9001, 9004):
            for db in pipe.ingest(FlowBatch.from_records(gen_records[t])):
                rows.update(_docbatch_to_dict(db))
        for db in pipe.drain():
            rows.update(_docbatch_to_dict(db))
        return rows, pipe.counters

    a, _ = run(None)
    b, counters = run(128)
    assert a.keys() == b.keys() and len(a) > 0
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])
    assert counters["prereduce_dropped"] == 0
