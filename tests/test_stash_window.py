import jax.numpy as jnp
import numpy as np

from deepflow_tpu.datamodel.schema import MergeOp, MeterField, MeterSchema, TagField, TagSchema
from deepflow_tpu.aggregator.stash import stash_flush, stash_init, stash_merge
from deepflow_tpu.aggregator.window import WindowConfig, WindowManager

TINY_METER = MeterSchema(
    "tiny",
    (
        MeterField("a", MergeOp.SUM),
        MeterField("b", MergeOp.SUM),
        MeterField("mx", MergeOp.MAX),
    ),
)
TINY_TAGS = TagSchema((TagField("k1"), TagField("k2")))


def _mkbatch(rows):
    """rows: list of (slot, hi, lo, (k1,k2), (a,b,mx))"""
    n = len(rows)
    slot = jnp.asarray(np.array([r[0] for r in rows], dtype=np.uint32))
    hi = jnp.asarray(np.array([r[1] for r in rows], dtype=np.uint32))
    lo = jnp.asarray(np.array([r[2] for r in rows], dtype=np.uint32))
    tags = jnp.asarray(np.array([r[3] for r in rows], dtype=np.uint32).T)
    meters = jnp.asarray(np.array([r[4] for r in rows], dtype=np.float32).T)
    valid = jnp.ones((n,), dtype=bool)
    return slot, hi, lo, tags, meters, valid


def test_stash_merge_accumulates_across_batches():
    st = stash_init(8, TINY_TAGS, TINY_METER)
    b1 = _mkbatch([(1, 10, 0, (7, 8), (1, 2, 5)), (1, 11, 0, (9, 9), (10, 0, 1))])
    st = stash_merge(st, *b1, TINY_METER)
    b2 = _mkbatch([(1, 10, 0, (7, 8), (4, 4, 2))])
    st = stash_merge(st, *b2, TINY_METER)

    st, out = stash_flush(st, 1)
    assert int(out["count"]) == 2
    mask = np.asarray(out["mask"])
    meters = np.asarray(out["meters"]).T[mask]
    his = np.asarray(out["key_hi"])[mask]
    row = {int(h): m for h, m in zip(his, meters)}
    np.testing.assert_array_equal(row[10], [5, 6, 5])  # sums + max
    np.testing.assert_array_equal(row[11], [10, 0, 1])
    # flushed rows are gone
    st, out2 = stash_flush(st, 1)
    assert int(out2["count"]) == 0


def test_stash_overflow_drops_newest_window():
    st = stash_init(4, TINY_TAGS, TINY_METER)
    # window 1: two keys; window 2: four keys → 6 segments > capacity 4
    rows = [(1, i, 0, (i, 0), (1, 0, 0)) for i in (1, 2)]
    rows += [(2, i, 0, (i, 0), (1, 0, 0)) for i in (1, 2, 3, 4)]
    st = stash_merge(st, *_mkbatch(rows), TINY_METER)
    assert int(st.dropped_overflow) == 2
    # older window fully retained
    st, out = stash_flush(st, 1)
    assert int(out["count"]) == 2


def test_window_manager_flushes_after_delay():
    wm = WindowManager(WindowConfig(interval=1, delay=2, capacity=16), TINY_TAGS, TINY_METER)

    def batch(ts_list, key_list):
        n = len(ts_list)
        ts = np.array(ts_list, dtype=np.uint32)
        hi = np.array(key_list, dtype=np.uint32)
        lo = np.zeros(n, dtype=np.uint32)
        tags = np.stack([hi, hi], axis=0).astype(np.uint32)
        meters = np.ones((3, n), dtype=np.float32)
        return (
            jnp.asarray(ts),
            jnp.asarray(hi),
            jnp.asarray(lo),
            jnp.asarray(tags),
            jnp.asarray(meters),
            jnp.ones(n, dtype=bool),
        )

    # t=100,101 → nothing closes yet (delay 2)
    assert wm.ingest(*batch([100, 100, 101], [1, 1, 2])) == []
    # t=103 → window 100 closes (103-2=101 > 100)
    flushed = wm.ingest(*batch([103], [3]))
    assert [f.window_idx for f in flushed] == [100]
    f = flushed[0]
    assert f.count == 1  # key 1 merged twice in window 100
    assert int(f.key_hi[0]) == 1
    np.testing.assert_array_equal(f.meters[0], [2, 2, 1])

    # late arrival for window 100 is dropped
    assert wm.ingest(*batch([100], [9])) == []
    assert wm.drop_before_window == 1

    # drain
    rest = wm.flush_all()
    assert [f.window_idx for f in rest] == [101, 103]
    assert wm.counters["occupancy"] == 0


def test_window_manager_growing_batch_keeps_accumulated_rows():
    """Regression: a batch larger than the accumulator ring re-initializes
    it; pending rows must be folded into the stash first, not dropped."""
    wm = WindowManager(
        WindowConfig(interval=1, delay=2, capacity=64, accum_batches=2),
        TINY_TAGS,
        TINY_METER,
    )

    def batch(n, ts, key0):
        return (
            jnp.full((n,), ts, dtype=jnp.uint32),
            jnp.asarray(np.arange(key0, key0 + n, dtype=np.uint32)),
            jnp.zeros(n, dtype=jnp.uint32),
            jnp.zeros((2, n), dtype=jnp.uint32),
            jnp.ones((3, n), dtype=jnp.float32),
            jnp.ones(n, dtype=bool),
        )

    wm.ingest(*batch(2, 50, 0))  # ring sized 2×2=4, fill=2
    wm.ingest(*batch(8, 50, 100))  # bigger than ring → re-init path
    flushed = wm.ingest(*batch(1, 60, 999))  # close window 50
    assert sum(f.count for f in flushed) == 10  # 2 + 8, nothing lost


def test_window_manager_multi_window_batch():
    wm = WindowManager(WindowConfig(interval=1, delay=1, capacity=32), TINY_TAGS, TINY_METER)
    ts = [10, 11, 12, 13, 14]
    n = len(ts)
    b = (
        jnp.asarray(np.array(ts, dtype=np.uint32)),
        jnp.asarray(np.arange(n, dtype=np.uint32)),
        jnp.zeros(n, dtype=jnp.uint32),
        jnp.zeros((2, n), dtype=jnp.uint32),
        jnp.ones((3, n), dtype=jnp.float32),
        jnp.ones(n, dtype=bool),
    )
    flushed = wm.ingest(*b)
    # t_max=14, delay=1 → windows 10..12 close
    assert [f.window_idx for f in flushed] == [10, 11, 12]
    assert all(f.count == 1 for f in flushed)


# ---------------------------------------------------------------------------
# PR 29: the fold's output loop runs a trip count, not a shape


import pytest

from deepflow_tpu.aggregator import stash as stash_mod
from deepflow_tpu.aggregator import window as window_mod
from deepflow_tpu.aggregator.stash import accum_init, stash_fold_counted
from deepflow_tpu.ops import segment

_BLOCK = 8


@pytest.fixture
def small_blocks(monkeypatch):
    """OUT_BLOCK_ROWS = 8, so a 64-row stash is 8 blocks. The module's
    jitted programs read the constant when they trace: drop what they
    hold before, and after, so no other test meets an 8-row program."""
    jitted = (stash_mod.collector_fold_counted, stash_mod.collector_merge_fold,
              stash_mod.collector_fold, window_mod._raw_append_step)
    for f in jitted:
        f.clear_cache()
    monkeypatch.setattr(segment, "OUT_BLOCK_ROWS", _BLOCK)
    yield _BLOCK
    for f in jitted:
        f.clear_cache()


def _ring(cap, keys, slot=5):
    """An accumulator of `cap` rows holding one row a key (twice the
    first): [A] lanes, sentinel behind."""
    acc = accum_init(cap, TINY_TAGS, TINY_METER)
    k = np.asarray([keys[0]] + list(keys), np.uint32) if len(keys) else np.zeros(0, np.uint32)
    n = k.size
    sl = np.full(cap, 0xFFFFFFFF, np.uint32)
    sl[:n] = slot
    hi = np.zeros(cap, np.uint32)
    hi[:n] = k
    tags = np.zeros((2, cap), np.uint32)
    tags[:, :n] = np.stack([k, k + 1])
    meters = np.zeros((3, cap), np.float32)
    meters[:, :n] = 1.0
    return type(acc)(slot=jnp.asarray(sl), key_hi=jnp.asarray(hi), key_lo=acc.key_lo,
                     tags=jnp.asarray(tags), meters=jnp.asarray(meters))


@pytest.mark.parametrize("live", [10, 32])
def test_fold_returns_its_trip_count(small_blocks, live):
    cap = 64
    st = stash_init(cap, TINY_TAGS, TINY_METER)
    st, _, lanes = stash_fold_counted(st, _ring(48, range(live)), TINY_METER)
    assert int(np.asarray(st.valid).sum()) == live
    assert [int(x) for x in lanes] == [live + 1, -(-live // small_blocks)]
    assert segment.out_blocks_total(cap) == cap // small_blocks


def test_fold_is_one_program_for_every_live_count(small_blocks):
    """10 live segments, then cap/2, then more than the stash holds: the
    same compiled fold, and each returns ceil(min(num_seg, cap) / B)."""
    cap = 64
    fold = stash_mod.collector_fold_counted
    assert fold._cache_size() == 0
    st = stash_init(cap, TINY_TAGS, TINY_METER)
    trips = []
    for keys in (range(10), range(100, 122), range(200, 240)):
        st, _, lanes = stash_fold_counted(st, _ring(48, keys), TINY_METER)
        trips.append(int(lanes[1]))
    # 10, then 10 + 22 = cap / 2, then 72 segments of which 64 are kept
    assert trips == [2, 4, 8]
    assert int(st.dropped_overflow) == 8 and np.asarray(st.valid).all()
    assert fold._cache_size() == 1


def test_fold_blocks_lane_and_host_sums(small_blocks):
    """The trip count rides the counter block (lane CB_FOLD_BLOCKS, block
    version 8) and `_process_block` sums it, and the stash's block count,
    once a processed block."""
    cap = 64
    wm = WindowManager(
        WindowConfig(interval=1, delay=2, capacity=cap, accum_batches=2),
        TINY_TAGS, TINY_METER,
    )
    total = cap // small_blocks

    def batch(ts, keys):
        k = np.asarray(keys, np.uint32)
        n = k.size
        return (jnp.full((n,), ts, jnp.uint32), jnp.asarray(k), jnp.zeros(n, jnp.uint32),
                jnp.asarray(np.stack([k, k])), jnp.ones((3, n), jnp.float32),
                jnp.ones(n, bool))

    seen = []
    # two batches fill the ring with nothing folded; the third does not
    # fit behind them, so the ring folds first (15 segments: 2 blocks)
    # and its block carries that; the fourth's block reads the same fold
    # and then advances, which folds to 35 segments (5 blocks) before
    # window 100 is flushed; the fifth's block carries those
    for ts, keys in ((100, range(0, 10)), (100, range(5, 15)), (100, range(15, 25)),
                     (103, range(25, 35)), (103, range(35, 45))):
        wm.ingest(*batch(ts, keys))
        c = wm.get_counters()
        seen.append((c["fold_blocks_run_sum"], c["fold_blocks_total_sum"]))
    assert seen == [(0, total), (0, 2 * total), (2, 3 * total), (4, 4 * total),
                    (9, 5 * total)]
    assert c["stash_evictions"] == 0
    assert window_mod.COUNTER_BLOCK_VERSION == 8
    assert window_mod.CB_FIELDS[window_mod.CB_FOLD_BLOCKS] == "fold_blocks"
    assert wm._fold_lanes_dev.shape == (2,)


def test_fused_step_is_one_program_for_every_distinct_flow_count(small_blocks):
    """The step's pre-reduce is the same group-by (cap = batch_unique_cap,
    here 8 blocks of 8): batches of 3 and of 40 distinct flows run the one
    compiled step, and their rows come out whole."""
    from deepflow_tpu.aggregator.pipeline import L4Pipeline, PipelineConfig
    from deepflow_tpu.datamodel.batch import FlowBatch
    from deepflow_tpu.ingest.replay import SyntheticFlowGen

    pipe = L4Pipeline(PipelineConfig(
        window=WindowConfig(capacity=1 << 10), batch_size=128, batch_unique_cap=64,
    ))
    t0 = 1_700_000_000
    docs = []
    for i, tuples in enumerate((3, 40, 3, 40)):
        gen = SyntheticFlowGen(num_tuples=tuples, seed=5 + i)
        docs += pipe.ingest(FlowBatch.from_records(gen.records(128, t0 + i)))
    docs += pipe.drain()
    c = pipe.get_counters()
    assert pipe._step._cache_size() == 1 and c["jit_retraces"] == 0
    assert c["prereduce_shed"] == 0 and c["stash_evictions"] == 0
    assert sum(len(d.to_dicts()) for d in docs) == c["flushed_doc"] > 0
    assert c["fold_blocks_total_sum"] == 4 * ((1 << 10) // small_blocks)


def test_live_block_share_layer_file_reads_the_two_sums():
    """`chipbench/layers/fold.live_block_share.json` (the benchmark's
    reader of the two sums) against planes made by hand; from a program
    without the counters it reads nothing, not 0."""
    import importlib.util
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(root, "chipbench", "layers.py")
    mod_spec = importlib.util.spec_from_file_location("chipbench_layers", path)
    layers = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(layers)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # 23 when this one came; PR 31 appended one, PR 33 the sketch cell's
    # three, PR 34 the close's host half's three, PR 36 the four-chip
    # cell's five, PR 37 the close's host passes' two, PR 38 the host's
    # time by owner's nine, PR 39 the four-chip cell's device-merged share
    assert len(bench["per_layer"]) == 47
    entry = bench["per_layer"][22]
    layer = layers.load_layer("fold.live_block_share")
    assert {k: layer[k] for k in entry} == entry and "workloads" not in entry
    assert (entry["layer"], entry["moves"], entry["better"]) == ("fold", "records_per_s", "lower")
    planes = {"counters": {"pipeline.fold_blocks_run_sum": 40 * 5,
                           "pipeline.fold_blocks_total_sum": 40 * 128}}
    assert layers.read_metric(layer, planes) == pytest.approx(100 * 5 / 128)
    for parent in ({}, {"counters": {}},
                   {"counters": {"pipeline.stash_live_rows_sum": 9,
                                 "pipeline.fold_blocks_total_sum": 0}}):
        assert layers.read_metric(layer, parent) is None
