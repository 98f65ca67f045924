"""ISSUE 3 pipeline self-telemetry: device counter block, stage spans,
and the dogfooded deepflow_system round trip (counters → store → SQL +
PromQL, bit-exact vs the host-side WindowManager counters)."""

from __future__ import annotations

import math

import numpy as np
import pytest

from deepflow_tpu.aggregator.pipeline import L4Pipeline, PipelineConfig
from deepflow_tpu.aggregator.window import (
    CB_LEN,
    CB_STASH_OCCUPANCY,
    CB_VERSION,
    COUNTER_BLOCK_VERSION,
    WindowConfig,
    WindowManager,
)
from deepflow_tpu.datamodel.batch import FlowBatch
from deepflow_tpu.ingest.replay import SyntheticFlowGen
from deepflow_tpu.integration.dfstats import (
    DEEPFLOW_SYSTEM_DB,
    DEEPFLOW_SYSTEM_TABLE,
    points_to_influx,
    system_metric_name,
    system_sink,
)
from deepflow_tpu.querier.engine import QueryEngine
from deepflow_tpu.querier.promql import query_instant
from deepflow_tpu.storage.store import ColumnarStore
from deepflow_tpu.utils.spans import (
    PIPELINE_SPAN_NAMES,
    SPAN_FLUSH_DRAIN,
    SPAN_INGEST_DISPATCH,
    SPAN_STATS_FETCH,
    SPAN_WINDOW_ADVANCE,
    SpanTracer,
)
from deepflow_tpu.utils.stats import StatsCollector, StatsPoint

T0 = 1_700_000_000


def _ingest_some(pipe, n_batches=6, batch=128, seed=3):
    gen = SyntheticFlowGen(num_tuples=200, seed=seed)
    for i in range(n_batches):
        pipe.ingest(FlowBatch.from_records(gen.records(batch, T0 + i)))
    return pipe


# ---------------------------------------------------------------------------
# (1) device counter plane


def test_counter_block_versioned_and_coherent():
    pipe = _ingest_some(
        L4Pipeline(PipelineConfig(window=WindowConfig(capacity=1 << 12),
                                  batch_size=256))
    )
    c = pipe.get_counters()
    # block lanes made it to the host mirror
    assert c["doc_in"] > 0
    assert c["stash_occupancy"] >= 0 and c["stash_evictions"] == 0
    assert c["excess_word_hits"] == 0  # synthetic tags honor the widths
    assert c["window_advances"] > 0
    # the legacy live probes agree with the cached lanes once settled:
    # evictions only move at folds, which run before dispatch
    live = pipe.counters
    assert live["drop_overflow"] == c["stash_evictions"]


def test_snapshot_lanes_ride_the_counter_block():
    """ISSUE 10 (CB v6): snapshot_reads/snapshot_bytes ride the
    EXISTING per-batch fetch — after a snapshot, the next dispatched
    batch's counter block mirrors the host accounting exactly, and the
    snapshot itself shows up in the transfer accounting (2 fetches)."""
    pipe = _ingest_some(
        L4Pipeline(PipelineConfig(
            window=WindowConfig(capacity=1 << 12, min_snapshot_interval=0.0),
            batch_size=256,
        )),
        n_batches=2,
    )
    c0 = pipe.get_counters()
    assert c0["snapshot_reads"] == 0 and c0["device_snapshot_reads"] == 0
    f0 = c0["host_fetches"]
    snap = pipe.snapshot_open()
    assert snap.windows and all(w.partial for w in snap.windows)
    c1 = pipe.get_counters()
    assert c1["snapshot_reads"] == 1 and c1["snapshot_bytes"] > 0
    assert c1["host_fetches"] - f0 <= 2  # the 2-fetch pull-path read
    # device plane still carries the pre-snapshot lanes until the next
    # dispatch ships the rebuilt [reads, bytes] vector
    assert c1["device_snapshot_reads"] == 0
    gen = SyntheticFlowGen(num_tuples=200, seed=3)
    pipe.ingest(FlowBatch.from_records(gen.records(64, T0 + 10)))
    c2 = pipe.get_counters()
    assert c2["device_snapshot_reads"] == c2["snapshot_reads"] == 1
    assert c2["device_snapshot_bytes"] == c2["snapshot_bytes"] > 0


def test_counter_block_rejects_version_drift():
    import jax.numpy as jnp

    wm = WindowManager(WindowConfig(capacity=64))
    bad = jnp.zeros((CB_LEN,), jnp.uint32)  # version lane = 0
    with pytest.raises(ValueError, match="version"):
        wm._process_stats(bad)


def test_counter_block_layout_constants():
    from deepflow_tpu.aggregator.window import (
        CB_FEEDER_SHED,
        CB_FIELDS,
        CB_RING_FILL,
    )

    from deepflow_tpu.aggregator.window import (
        CB_CASCADE_ROWS,
        CB_CASCADE_SHED,
        CB_FOLD_BLOCKS,
        CB_FOLD_ROWS,
        CB_SKETCH_ROWS,
        CB_SKETCH_SHED,
        CB_SKETCH_POOL_OCC,
        CB_SKETCH_POOL_SPILL,
        CB_SKETCH_PROMOTIONS,
        CB_SNAPSHOT_BYTES,
        CB_SNAPSHOT_READS,
    )

    # layout drift between the device builder and the host parser must
    # fail here, not silently mis-slice (v2 appended the feeder_shed
    # lane, ISSUE 4; v3 appended fold_rows, ISSUE 5; v4 appended the
    # sketch_rows/sketch_shed plane lanes, ISSUE 8; v5 appended the
    # rollup cascade's cascade_rows/cascade_shed lanes, ISSUE 9; v6
    # appended the live read plane's snapshot_reads/snapshot_bytes
    # lanes, ISSUE 10; v7 appended the pooled sketch memory's
    # sketch_pool_spill/sketch_pool_occ/sketch_promotions lanes,
    # ISSUE 20; v8 appended fold_blocks, the trip count of the last
    # fold's output loop, PR 29)
    assert CB_VERSION == 0 and CB_LEN == 22
    assert COUNTER_BLOCK_VERSION == 8
    assert CB_STASH_OCCUPANCY == 7
    assert CB_FEEDER_SHED == 10
    assert CB_FOLD_ROWS == 11
    assert CB_SKETCH_ROWS == 12
    assert CB_SKETCH_SHED == 13
    assert CB_CASCADE_ROWS == 14
    assert CB_CASCADE_SHED == 15
    assert CB_SNAPSHOT_READS == 16
    assert CB_SNAPSHOT_BYTES == 17
    assert CB_SKETCH_POOL_SPILL == 18
    assert CB_SKETCH_POOL_OCC == 19
    assert CB_SKETCH_PROMOTIONS == 20
    assert CB_FOLD_BLOCKS == 21
    # the documented field-name table mirrors the index constants
    assert len(CB_FIELDS) == CB_LEN
    assert CB_FIELDS[CB_VERSION] == "version"
    assert CB_FIELDS[CB_STASH_OCCUPANCY] == "stash_occupancy"
    assert CB_FIELDS[CB_RING_FILL] == "ring_fill"
    assert CB_FIELDS[CB_FEEDER_SHED] == "feeder_shed"
    assert CB_FIELDS[CB_FOLD_ROWS] == "fold_rows"
    assert CB_FIELDS[CB_FOLD_BLOCKS] == "fold_blocks"
    assert CB_FIELDS[CB_SKETCH_ROWS] == "sketch_rows"
    assert CB_FIELDS[CB_SKETCH_SHED] == "sketch_shed"
    assert CB_FIELDS[CB_CASCADE_ROWS] == "cascade_rows"
    assert CB_FIELDS[CB_CASCADE_SHED] == "cascade_shed"
    assert CB_FIELDS[CB_SNAPSHOT_READS] == "snapshot_reads"
    assert CB_FIELDS[CB_SNAPSHOT_BYTES] == "snapshot_bytes"
    assert CB_FIELDS[CB_SKETCH_POOL_SPILL] == "sketch_pool_spill"
    assert CB_FIELDS[CB_SKETCH_POOL_OCC] == "sketch_pool_occ"
    assert CB_FIELDS[CB_SKETCH_PROMOTIONS] == "sketch_promotions"


# ---------------------------------------------------------------------------
# (2) host stage tracing


def test_spans_cover_pipeline_stages_and_checkpoint(tmp_path):
    from deepflow_tpu.aggregator.checkpoint import save_window_state
    from deepflow_tpu.querier.live import QueryResultCache

    pipe = _ingest_some(
        L4Pipeline(PipelineConfig(window=WindowConfig(capacity=1 << 12),
                                  batch_size=256))
    )
    save_window_state(pipe.wm, tmp_path / "ckpt.npz")
    # the live read plane's stages (ISSUE 10): snapshot_open emits
    # query.snapshot on the pipeline tracer; a result-cache lookup
    # emits query.cache on whatever tracer the cache carries
    pipe.snapshot_open()
    cache = QueryResultCache(max_entries=4, tracer=pipe.tracer)
    assert cache.lookup(("q", "db", "t"), token=1) is None
    summary = pipe.tracer.summary()
    for name in PIPELINE_SPAN_NAMES:
        assert name in summary, f"missing span {name}: {sorted(summary)}"
        assert summary[name]["count"] > 0
        assert summary[name]["total_us"] >= summary[name]["max_us"] >= 0
    # dispatch fires once per non-empty batch; advance strictly fewer
    assert summary[SPAN_INGEST_DISPATCH]["count"] == 6
    assert summary[SPAN_STATS_FETCH]["count"] >= 6
    assert summary[SPAN_WINDOW_ADVANCE]["count"] < 6
    assert summary[SPAN_FLUSH_DRAIN]["count"] >= 1


def test_spans_export_through_otlp_exporter_path():
    """Tracer spans drain through the EXISTING exporter seam: rows land
    on the l7_flow_log traces lane and OtlpExporter._row_to_span turns
    each into a well-formed OTel span."""
    from deepflow_tpu.server.exporters import CallbackExporter, OtlpExporter

    tracer = SpanTracer(service="unit.pipeline")
    with tracer.span(SPAN_INGEST_DISPATCH):
        pass
    with tracer.span(SPAN_FLUSH_DRAIN):
        pass

    seen = []
    exp = CallbackExporter(lambda table, rows: seen.append((table, rows)))
    n = tracer.export_otlp(exp)
    assert n == 2
    table, rows = seen[0]
    assert table == "l7_flow_log"
    assert {r["endpoint"] for r in rows} == {SPAN_INGEST_DISPATCH, SPAN_FLUSH_DRAIN}
    spans = [OtlpExporter._row_to_span(r) for r in rows]
    assert all(s.service == "unit.pipeline" for s in spans)
    assert all(len(s.trace_id) == 32 and len(s.span_id) == 16 for s in spans)
    # drained: a second export ships nothing
    assert tracer.export_otlp(exp) == 0


def test_jit_cache_monitor_counts_compile_then_retrace():
    import jax
    import jax.numpy as jnp

    from deepflow_tpu.utils.spans import JitCacheMonitor

    f = jax.jit(lambda x: x + 1)
    mon = JitCacheMonitor(f)
    f(jnp.ones(4))
    assert mon.get_counters() == {"jit_compiles": 1, "jit_retraces": 0}
    f(jnp.ones(4))  # same shape — cache hit
    assert mon.get_counters() == {"jit_compiles": 1, "jit_retraces": 0}
    f(jnp.ones(5))  # shape leak
    assert mon.get_counters() == {"jit_compiles": 1, "jit_retraces": 1}


# ---------------------------------------------------------------------------
# (3) dogfooding: deepflow_system round trip (the acceptance criterion)


def test_pipeline_counters_roundtrip_sql_and_promql():
    pipe = _ingest_some(
        L4Pipeline(PipelineConfig(window=WindowConfig(capacity=1 << 12),
                                  batch_size=256))
    )
    expected = pipe.get_counters()
    assert expected["doc_in"] > 0 and expected["host_fetches"] > 0

    store = ColumnarStore()
    col = StatsCollector(interval_s=999)
    col.register("tpu_pipeline", pipe, kind="L4Pipeline", interval="1s")
    col.register("tpu_pipeline_spans", pipe.tracer, kind="L4Pipeline")
    col.add_sink(system_sink(store))
    col.tick(now=float(T0 + 100))

    # -- SQL engine over deepflow_system.deepflow_system ---------------
    eng = QueryEngine(store)
    for field in ("doc_in", "flushed_doc", "drop_before_window",
                  "stash_occupancy", "host_fetches", "bytes_fetched",
                  "snapshot_reads", "snapshot_bytes"):
        metric = system_metric_name("tpu_pipeline", field)
        res = eng.execute(
            "SELECT value FROM deepflow_system.deepflow_system "
            f"WHERE metric = '{metric}'"
        )
        assert res.rows == 1, (field, res.rows)
        assert float(res.values["value"][0]) == float(expected[field]), field

    # span aggregates dogfood through the same table
    res = eng.execute(
        "SELECT value FROM deepflow_system.deepflow_system WHERE metric = "
        f"'{system_metric_name('tpu_pipeline_spans', 'ingest.dispatch.count')}'"
    )
    assert res.rows == 1 and float(res.values["value"][0]) == 6.0

    # -- PromQL over the same rows -------------------------------------
    for field in ("doc_in", "window_advances", "bytes_uploaded"):
        out = query_instant(
            store,
            system_metric_name("tpu_pipeline", field) + '{kind="L4Pipeline"}',
            T0 + 101,
            db=DEEPFLOW_SYSTEM_DB,
            table=DEEPFLOW_SYSTEM_TABLE,
        )
        assert len(out) == 1, field
        assert out[0]["labels"]["interval"] == "1s"
        assert out[0]["value"] == float(expected[field]), field


def test_system_sink_skips_nonfinite_and_nonnumeric():
    store = ColumnarStore()
    sink = system_sink(store)
    sink(
        [
            StatsPoint(float(T0), "m", (), {
                "ok": 3, "bad_nan": float("nan"), "bad_inf": float("inf"),
                "name": "not-a-number",
            })
        ]
    )
    rows = store.scan(DEEPFLOW_SYSTEM_DB, DEEPFLOW_SYSTEM_TABLE)
    assert list(rows["metric"]) == ["m_ok"]
    assert rows["value"][0] == 3.0


# ---------------------------------------------------------------------------
# satellites: influx typing/escaping + collector source-error policy


def test_points_to_influx_int_typing_and_nonfinite_skip():
    text = points_to_influx(
        [
            StatsPoint(float(T0), "mod", (("a", "x=y\\z, w"),), {
                "n": 7,
                "flag": True,
                "ratio": 0.5,
                "nan": float("nan"),
                "inf": float("-inf"),
            })
        ]
    )
    assert text == (
        f"mod,a=x\\=y\\\\z\\,\\ w n=7i,flag=1i,ratio=0.5 {T0}000000000"
    )
    from deepflow_tpu.integration.formats import parse_influx_lines

    points, errors = parse_influx_lines(text)
    assert errors == 0
    assert points[0].tags == {"a": "x=y\\z, w"}
    assert points[0].fields == {"n": 7.0, "flag": 1.0, "ratio": 0.5}
    assert all(math.isfinite(v) for v in points[0].fields.values())


def test_points_to_influx_numpy_scalars_keep_int_typing():
    text = points_to_influx(
        [StatsPoint(float(T0), "m", (), {"i": np.int64(9), "f": np.float32(2.0)})]
    )
    assert "i=9i" in text and "f=2.0" in text


def test_stats_collector_backs_off_and_reprobes_broken_sources():
    """ISSUE 6: a source that keeps failing enters capped exponential
    backoff (sampled at 1, 2, 4, … tick spacing) instead of being
    dropped forever; when it heals, reporting resumes and the recovery
    is counted once."""
    col = StatsCollector(interval_s=999)

    calls = {"n": 0}
    state = {"fail": True}

    def flaky():
        calls["n"] += 1
        if state["fail"]:
            raise RuntimeError("boom")
        return {"x": 1}

    col.register("bad", flaky)
    col.register("good", lambda: {"x": 1})

    for _ in range(StatsCollector.MAX_SOURCE_FAILURES):
        pts = col.tick(now=float(T0))
        # the healthy source keeps reporting throughout
        assert [p.module for p in pts] == ["good"]
    assert col.n_source_errors == StatsCollector.MAX_SOURCE_FAILURES
    # backoff: the next tick skips the broken source (cooldown=1)...
    col.tick(now=float(T0 + 1))
    assert calls["n"] == StatsCollector.MAX_SOURCE_FAILURES
    # ...but the one after re-probes it — NOT dropped permanently
    col.tick(now=float(T0 + 2))
    assert calls["n"] == StatsCollector.MAX_SOURCE_FAILURES + 1
    assert col.n_source_errors == StatsCollector.MAX_SOURCE_FAILURES + 1
    # the spacing grows (cooldown=2 now) and is capped
    col.tick(now=float(T0 + 3))
    assert calls["n"] == StatsCollector.MAX_SOURCE_FAILURES + 1

    # heal the source: burn through the remaining cooldown, then the
    # re-probe succeeds, reporting resumes, recovery counted once
    state["fail"] = False
    for i in range(4):
        pts = col.tick(now=float(T0 + 4 + i))
        if sorted(p.module for p in pts) == ["bad", "good"]:
            break
    else:
        raise AssertionError("backed-off source never re-probed")
    assert col.n_source_recoveries == 1
    # healthy again: sampled every tick from here on
    pts = col.tick(now=float(T0 + 10))
    assert sorted(p.module for p in pts) == ["bad", "good"]
    assert col.n_source_recoveries == 1


def test_stats_collector_survives_broken_sink():
    """A raising sink callback must not kill the tick (the collector
    thread would die silently) — contained and counted."""
    col = StatsCollector(interval_s=999)
    col.register("m", lambda: {"x": 1})
    col.add_sink(lambda pts: (_ for _ in ()).throw(RuntimeError("sink boom")))
    got = []
    col.add_sink(got.extend)
    pts = col.tick(now=float(T0))
    assert [p.module for p in pts] == ["m"]
    assert col.n_sink_errors == 1
    assert got  # the healthy sink still received the points


def test_stats_collector_transient_failure_recovers():
    col = StatsCollector(interval_s=999)
    state = {"fail": True}

    def flaky():
        if state["fail"]:
            raise RuntimeError("transient")
        return {"x": 2}

    col.register("flaky", flaky)
    col.tick(now=float(T0))  # one failure
    state["fail"] = False
    pts = col.tick(now=float(T0 + 1))  # recovers — failure streak resets
    assert [p.module for p in pts] == ["flaky"]
    assert col.n_source_errors == 1
    state["fail"] = True
    for _ in range(StatsCollector.MAX_SOURCE_FAILURES - 1):
        col.tick(now=float(T0 + 2))
    # streak restarted after recovery: still registered
    assert [p.module for p in col.tick(now=float(T0 + 3))] == []


# ---------------------------------------------------------------------------
# sharded twin: counters + spans + telemetry snapshot shape


def test_sharded_manager_telemetry_snapshot():
    from deepflow_tpu.ops.histogram import LogHistSpec
    from deepflow_tpu.parallel.mesh import make_mesh
    from deepflow_tpu.parallel.sharded import (
        ShardedConfig,
        ShardedPipeline,
        ShardedWindowManager,
    )

    mesh = make_mesh(2)
    cfg = ShardedConfig(
        capacity_per_device=1 << 10, num_services=16, hll_precision=6,
        hist=LogHistSpec(bins=64, vmin=1.0, gamma=1.3),
    )
    wm = ShardedWindowManager(ShardedPipeline(mesh, cfg))
    gen = SyntheticFlowGen(num_tuples=100, seed=9)
    for t in (T0, T0 + 1, T0 + 5):
        fb = gen.flow_batch(64, t)
        wm.ingest(fb.tags, fb.meters, fb.valid)
    wm.drain()  # shutdown path must keep the advance-span parity below
    snap = wm.telemetry()
    import json

    json.dumps(snap)  # must be JSON-able as-is (bench snapshot contract)
    assert snap["counters"]["flow_in"] > 0  # pre-fanout flow rows
    assert snap["counters"]["host_fetches"] > 0
    assert snap["counters"]["bytes_uploaded"] > 0
    assert snap["spans"][SPAN_INGEST_DISPATCH]["count"] == 3
    assert SPAN_FLUSH_DRAIN in snap["spans"]
    # ONE window.advance span per advance (the close-before/fold-after
    # split must not double-count) — stage attribution comparable with
    # the single-chip path
    assert (
        snap["spans"][SPAN_WINDOW_ADVANCE]["count"]
        == snap["counters"]["window_advances"]
    )


def test_system_table_labels_not_truncated():
    """Variable-width metric/labels columns: a long packed label string
    must round-trip unclipped (a fixed U<n> would cut it mid-escape and
    PromQL selectors would silently match nothing)."""
    store = ColumnarStore()
    sink = system_sink(store)
    long_val = "v" * 600 + ",x=y"  # > the old U512 clip, with escapables
    sink([StatsPoint(float(T0), "m", (("big", long_val),), {"ok": 1})])
    out = query_instant(
        store, "m_ok", T0 + 1,
        db=DEEPFLOW_SYSTEM_DB, table=DEEPFLOW_SYSTEM_TABLE,
    )
    assert len(out) == 1
    assert out[0]["labels"]["big"] == long_val
    assert out[0]["value"] == 1.0


def test_concurrent_sample_pulls_race_tick_thread():
    """ISSUE 18 satellite: the fleet sink makes pull-path `sample()` a
    SECOND consumer of the same counter faces the tick thread reads.
    Hammer both concurrently over healthy, flapping, and broken
    sources: no exception escapes, per-source failure/recovery
    bookkeeping stays consistent (the per-source lock — unlocked
    check-then-act would double-count recoveries or lose failure
    counts), backoff still advances ONLY on ticks, and a healthy
    source's fields are never dropped from a tick snapshot."""
    import threading

    col = StatsCollector()
    calls = {"healthy": 0, "flaky": 0}
    flaky_fail = {"on": False}

    def healthy():
        calls["healthy"] += 1
        return {"v": calls["healthy"]}

    def flaky():
        calls["flaky"] += 1
        if flaky_fail["on"]:
            raise RuntimeError("flap")
        return {"v": 1}

    def broken():
        raise RuntimeError("always")

    col.register("healthy", healthy)
    flaky_src = col.register("flaky", flaky)
    broken_src = col.register("broken", broken)

    stop = threading.Event()
    errors: list[BaseException] = []

    def puller():
        # the fleet exporter's consumption shape: bare sample() pulls
        while not stop.is_set():
            try:
                pts = col.sample(1000.0)
                mods = [p.module for p in pts]
                assert "healthy" in mods
            except BaseException as e:  # pragma: no cover
                errors.append(e)
                return

    threads = [threading.Thread(target=puller) for _ in range(4)]
    for t in threads:
        t.start()
    try:
        for i in range(60):
            # flap the flaky source on and off while ticks race pulls
            flaky_fail["on"] = (i // 10) % 2 == 1
            pts = col.tick(1000.0 + i)
            assert any(p.module == "healthy" for p in pts)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10)
    assert not errors, errors

    # bookkeeping consistency under the race: counts are sane (no
    # negative/garbled state), the broken source sits in backoff with
    # a bounded cooldown, and the flaky source ended recovered
    assert col.n_source_errors >= 3  # broken alone guarantees this
    assert 0 <= broken_src.cooldown <= col.MAX_BACKOFF_TICKS
    assert broken_src.failures >= col.MAX_SOURCE_FAILURES
    assert broken_src.suppressed
    flaky_fail["on"] = False
    for i in range(col.MAX_BACKOFF_TICKS + 1):
        col.tick(2000.0 + i)
    assert flaky_src.failures == 0 and not flaky_src.suppressed
    # recoveries never exceed the number of suppression entries — a
    # double-counted recovery is exactly what the per-source lock
    # prevents
    assert col.n_source_recoveries <= col.n_source_errors
