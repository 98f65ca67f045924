"""stash_flush_range conformance: the fused batched drain must be
bit-exact versus the sequential per-window `stash_flush` oracle — same
rows, same order, same counters — on both the single-device and sharded
paths (ISSUE 2 acceptance)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepflow_tpu.aggregator.stash import (
    stash_flush,
    stash_flush_range,
    stash_init,
    stash_merge,
    unpack_flush_rows,
)
from deepflow_tpu.aggregator.window import WindowConfig, WindowManager
from deepflow_tpu.datamodel.schema import (
    MergeOp,
    MeterField,
    MeterSchema,
    TagField,
    TagSchema,
)

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


def _demo_state(capacity=32):
    """Windows 3, 5, 6, 9 occupied (4 and 7-8 empty gaps), float meters
    with non-trivial bit patterns."""
    st = stash_init(capacity, TINY_TAGS, TINY_METER)
    rows = []
    for w, nkeys in ((3, 4), (5, 2), (6, 5), (9, 3)):
        for k in range(nkeys):
            rows.append((w, 100 * w + k, k, (k, w), (1.5 * k + 0.1, w, k * 0.25)))
    # duplicate keys to exercise the merge reduction
    rows += [(5, 500, 0, (0, 5), (2.25, 1.0, 9.5)), (3, 301, 1, (1, 3), (0.5, 0.5, 0.5))]
    return stash_merge(st, *_mkbatch(rows), TINY_METER)


def _clone(state):
    return jax.tree.map(jnp.array, state)


def _oracle_rows(state, lo, hi):
    """Sequential ascending per-window stash_flush loop → (state, rows)
    where rows mirror the packed layout: (win, hi, lo, tags, meters)."""
    slots = np.asarray(state.slot)
    valid = np.asarray(state.valid)
    occupied = sorted(
        int(w) for w in np.unique(slots[valid]) if lo <= int(w) < hi
    ) if valid.any() else []
    win_l, hi_l, lo_l, tag_l, met_l = [], [], [], [], []
    for w in occupied:
        state, out = stash_flush(state, np.uint32(w))
        mask = np.asarray(out["mask"])
        n = int(mask.sum())
        win_l.append(np.full(n, w, np.uint32))
        hi_l.append(np.asarray(out["key_hi"])[mask])
        lo_l.append(np.asarray(out["key_lo"])[mask])
        tag_l.append(np.asarray(out["tags"]).T[mask])
        met_l.append(np.asarray(out["meters"]).T[mask])
    cat = lambda parts, width: (
        np.concatenate(parts) if parts else np.zeros((0,) + width, np.uint32)
    )
    return state, (
        cat(win_l, ()),
        cat(hi_l, ()),
        cat(lo_l, ()),
        cat(tag_l, (TINY_TAGS.num_fields,)),
        np.concatenate(met_l) if met_l else np.zeros((0, 3), np.float32),
    )


def _range_rows(state, lo, hi):
    new_state, packed, total = stash_flush_range(state, np.uint32(lo), np.uint32(hi))
    rows = np.asarray(packed[: int(total)])
    return new_state, unpack_flush_rows(rows, TINY_TAGS.num_fields)


def _assert_rows_equal(a, b):
    for x, y in zip(a, b):
        # float meters compared on exact bits (bit-exact acceptance)
        if x.dtype == np.float32:
            np.testing.assert_array_equal(x.view(np.uint32), y.view(np.uint32))
        else:
            np.testing.assert_array_equal(x, y)


def test_flush_range_bit_exact_vs_per_window_oracle():
    st = _demo_state()
    o_state, o_rows = _oracle_rows(_clone(st), 0, 8)
    r_state, r_rows = _range_rows(_clone(st), 0, 8)
    assert len(r_rows[0]) > 0
    _assert_rows_equal(o_rows, r_rows)
    # windows ≥ hi stay put; flushed slots reclaimed identically
    for leaf in ("slot", "valid", "key_hi", "key_lo"):
        np.testing.assert_array_equal(
            np.asarray(getattr(o_state, leaf)), np.asarray(getattr(r_state, leaf))
        )
    # drop/overflow counters preserved
    assert int(o_state.dropped_overflow) == int(r_state.dropped_overflow)


def test_flush_range_empty_span_and_empty_windows_shift_silently():
    st = _demo_state()
    # [4, 5): window 4 is an empty gap → zero rows, state untouched
    new_state, packed, total = stash_flush_range(_clone(st), np.uint32(4), np.uint32(5))
    assert int(total) == 0
    np.testing.assert_array_equal(np.asarray(new_state.valid), np.asarray(st.valid))
    # [0, 10): gaps at 4, 7, 8 contribute no rows but windows 3,5,6,9 all flush
    _, rows = _range_rows(_clone(st), 0, 10)
    assert sorted(set(rows[0].tolist())) == [3, 5, 6, 9]


def test_flush_range_preserves_overflow_counter():
    st = stash_init(4, TINY_TAGS, TINY_METER)
    rows = [(1, i, 0, (i, 0), (1, 0, 0)) for i in (1, 2)]
    rows += [(2, i, 0, (i, 0), (1, 0, 0)) for i in (1, 2, 3, 4)]
    st = stash_merge(st, *_mkbatch(rows), TINY_METER)
    assert int(st.dropped_overflow) == 2
    new_state, packed, total = stash_flush_range(st, np.uint32(0), np.uint32(2))
    assert int(total) == 2  # older window fully retained despite overflow
    assert int(new_state.dropped_overflow) == 2


def test_sharded_flush_range_matches_per_window_loop():
    """Same bit-exactness on the mesh path: pipe.flush_range vs the
    pipe.flush_window per-window oracle, per device."""
    from deepflow_tpu.datamodel.schema import TAG_SCHEMA
    from deepflow_tpu.ingest.replay import SyntheticFlowGen
    from deepflow_tpu.parallel.mesh import make_mesh
    from deepflow_tpu.parallel.sharded import ShardedConfig, ShardedPipeline

    mesh = make_mesh(8, n_hosts=2)
    cfg = ShardedConfig(capacity_per_device=1 << 10, num_services=16, hll_precision=8)
    pipe = ShardedPipeline(mesh, cfg)
    stash, sketches = pipe.init_state()
    gen = SyntheticFlowGen(num_tuples=400, seed=21)
    acc = pipe.init_acc(4 * 64)
    for i, t in enumerate((9000, 9001, 9003)):
        fb = gen.flow_batch(8 * 64, t)
        stash, acc, sketches = pipe.step(
            stash, acc, i * 4 * 64, sketches, fb.tags, fb.meters, fb.valid
        )
    stash, acc, _fold_rows = pipe.fold(stash, acc)

    lo, hi = 9000, 9003
    T = TAG_SCHEMA.num_fields

    # oracle: ascending per-window flush_window loop
    o_stash = jax.tree.map(jnp.array, stash)
    o_rows = {d: [] for d in range(8)}
    for w in range(lo, hi):
        o_stash, out = pipe.flush_window(o_stash, np.uint32(w))
        mask = np.asarray(out["mask"])
        for d in range(8):
            m = mask[d]
            if m.any():
                o_rows[d].append(
                    (
                        np.full(int(m.sum()), w, np.uint32),
                        np.asarray(out["key_hi"])[d][m],
                        np.asarray(out["key_lo"])[d][m],
                        np.asarray(out["tags"])[d].T[m],
                        np.asarray(out["meters"])[d].T[m],
                    )
                )

    r_stash, packed, totals = pipe.flush_range(
        jax.tree.map(jnp.array, stash), lo, hi
    )
    totals_np = np.asarray(totals)
    assert int(totals_np.sum()) > 0
    for d in range(8):
        got = unpack_flush_rows(np.asarray(packed[d, : int(totals_np[d])]), T)
        want = [
            np.concatenate([part[i] for part in o_rows[d]])
            for i in range(5)
        ] if o_rows[d] else [np.zeros(0)] * 5
        _assert_rows_equal(tuple(want), got)
    # residual state identical
    for leaf in ("slot", "valid"):
        np.testing.assert_array_equal(
            np.asarray(getattr(o_stash, leaf)), np.asarray(getattr(r_stash, leaf))
        )


def _batch(ts_list, key_list):
    n = len(ts_list)
    ts = np.array(ts_list, dtype=np.uint32)
    hi = np.array(key_list, dtype=np.uint32)
    tags = np.stack([hi, hi], axis=0).astype(np.uint32)
    meters = np.ones((3, n), dtype=np.float32)
    return (
        jnp.asarray(ts),
        jnp.asarray(hi),
        jnp.zeros(n, dtype=jnp.uint32),
        jnp.asarray(tags),
        jnp.asarray(meters),
        jnp.ones(n, dtype=bool),
    )


def test_async_drain_same_output_one_call_later():
    """async_drain double-buffers the flush: identical windows/rows as
    the synchronous mode, returned one ingest call later; flush_all
    settles everything."""
    sync = WindowManager(
        WindowConfig(interval=1, delay=2, capacity=64), TINY_TAGS, TINY_METER
    )
    asy = WindowManager(
        WindowConfig(interval=1, delay=2, capacity=64, async_drain=True),
        TINY_TAGS,
        TINY_METER,
    )
    batches = [
        ([100, 100, 101], [1, 1, 2]),
        ([103], [3]),
        ([104, 105], [4, 5]),
        ([110], [6]),
    ]
    got_s, got_a = [], []
    for ts, keys in batches:
        got_s += sync.ingest(*_batch(ts, keys))
        got_a += asy.ingest(*_batch(ts, keys))
    # async trails: the window closed by the last batch is still pending
    assert len(got_a) < len(got_s)
    got_s += sync.flush_all()
    got_a += asy.flush_all()

    def key(fs):
        return [
            (f.window_idx, f.count, f.key_hi.tolist(), f.meters.tolist())
            for f in fs
        ]

    assert key(got_a) == key(got_s)
    assert sync.drop_before_window == asy.drop_before_window
    assert sync.total_docs_in == asy.total_docs_in
    assert sync.total_flushed == asy.total_flushed


# ---------------------------------------------------------------------------
# PR 34: a flushed window's meters are a strided view of the fetched matrix
# (as its tags always were). The consumers index rows or columns, so they
# read the same from it as from a contiguous copy.


def _flushed_docbatch_and_contiguous_twin():
    from deepflow_tpu.aggregator.pipeline import L4Pipeline, PipelineConfig
    from deepflow_tpu.datamodel.batch import FlowBatch
    from deepflow_tpu.ingest.replay import SyntheticFlowGen

    pipe = L4Pipeline(PipelineConfig(batch_size=512))
    gen = SyntheticFlowGen(num_tuples=25, seed=3)
    docs = pipe.ingest(FlowBatch.from_records(gen.records(200, 1_700_000_000)))
    docs += pipe.drain()
    db = max(docs, key=lambda d: d.size)
    assert db.size > 8
    # as the drain handed it on: views of one matrix, its row stride
    assert db.meters.strides[0] == db.tags.strides[0] > 4 * db.meters.shape[1]
    assert not db.meters.flags.c_contiguous
    addr = lambda a: a.__array_interface__["data"][0]
    assert addr(db.meters) - addr(db.tags) == 4 * db.tags.shape[1]  # one row of one matrix
    twin = dataclasses.replace(
        db, tags=np.ascontiguousarray(db.tags), meters=np.ascontiguousarray(db.meters))
    assert twin.meters.flags.c_contiguous
    return pipe, db, twin


@pytest.mark.parametrize("consumer", ["codec", "store"])
def test_consumers_read_strided_meters_as_a_contiguous_copy(consumer):
    pipe, db, twin = _flushed_docbatch_and_contiguous_twin()
    if consumer == "codec":
        from deepflow_tpu.ingest.codec import DocumentDecoder, encode_docbatch

        wire, wire_twin = (encode_docbatch(d, flags=int(pipe.flags)) for d in (db, twin))
        assert wire == wire_twin and len(wire) == db.size
        (got,), (want,) = (list(DocumentDecoder().decode(w).values())
                           for w in (wire, wire_twin))
        for a, b in ((got.meters, want.meters), (got.tags, want.tags),
                     (got.timestamp, want.timestamp)):
            np.testing.assert_array_equal(a.view(np.uint32), b.view(np.uint32))
        # what came off the wire is what the window held
        np.testing.assert_array_equal(
            np.sort(got.to_docbatch().meter("byte_tx")), np.sort(db.meter("byte_tx")))
    else:
        from deepflow_tpu.storage.store import ColumnarStore, ColumnSpec, TableSchema

        names = [f.name for f in db.meter_schema.fields]
        schema = TableSchema(
            "w", (ColumnSpec("time", "u4"), ColumnSpec("ip0_w3", "u4"))
            + tuple(ColumnSpec(n, "f4") for n in names), partition_s=3600)
        scans = []
        for d in (db, twin):
            store = ColumnarStore()
            store.create_table("db", schema)
            cols = {"time": d.timestamp, "ip0_w3": d.tag("ip0_w3"),
                    **{n: d.meter(n) for n in names}}
            assert store.insert("db", "w", cols) == d.size
            scans.append(store.scan("db", "w"))
        assert scans[0].keys() == scans[1].keys() >= {"time", "ip0_w3", *names}
        for name in scans[0]:
            np.testing.assert_array_equal(
                scans[0][name].view(np.uint32), scans[1][name].view(np.uint32))
            assert scans[0][name].flags.c_contiguous
        np.testing.assert_array_equal(
            scans[0]["byte_tx"].view(np.uint32),
            np.ascontiguousarray(db.meter("byte_tx")).view(np.uint32))
