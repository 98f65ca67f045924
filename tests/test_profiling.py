"""ISSUE 12 device profiling plane: the HBM ledger (per-plane bytes,
reconciled against jax.live_arrays), the XLA step census, the span
latency distributions (log-hist quantiles → deepflow_system → alert
rules), and the lifecycle/threading satellites."""

from __future__ import annotations

import threading
import time

import jax
import numpy as np
import pytest

from deepflow_tpu.aggregator.cascade import CascadeConfig
from deepflow_tpu.aggregator.pipeline import L4Pipeline, PipelineConfig
from deepflow_tpu.aggregator.sketchplane import SketchConfig
from deepflow_tpu.aggregator.window import WindowConfig
from deepflow_tpu.datamodel.batch import FlowBatch
from deepflow_tpu.ingest.replay import SyntheticFlowGen
from deepflow_tpu.ops.histogram import LogHistSpec
from deepflow_tpu.profiling import (
    DeviceMemoryLedger,
    StepCostCensus,
    default_census,
    default_ledger,
    plane_bytes,
    profile_tick_sink,
)
from deepflow_tpu.utils.spans import (
    SPAN_INGEST_DISPATCH,
    SpanHistSpec,
    SpanTracer,
    loghist_quantiles_np,
)

T0 = 1_700_000_000

_SK = SketchConfig(
    num_groups=4, hll_precision=7, cms_depth=2, cms_width=256,
    hist=LogHistSpec(bins=32, vmin=1.0, gamma=1.3),
    topk_rows=2, topk_cols=64, pending=8,
)


def _mk_pipe(*, sketch=True, cascade=True, capacity=1 << 10, **wkw):
    return L4Pipeline(PipelineConfig(
        window=WindowConfig(
            capacity=capacity,
            sketch=_SK if sketch else None,
            cascade=CascadeConfig(intervals=(60,), capacity=capacity)
            if cascade else None,
            **wkw,
        ),
        batch_size=256,
    ))


def _ingest(pipe, n=4, batch=128, seed=3, t0=T0, stride=1):
    gen = SyntheticFlowGen(num_tuples=150, seed=seed)
    for i in range(n):
        pipe.ingest(FlowBatch.from_records(gen.records(batch, t0 + i * stride)))
    return pipe


def _owned_leaves(planes: dict) -> dict[int, object]:
    """id → leaf device array over every plane (the ownership set the
    ledger claims to account)."""
    out = {}
    for tree in planes.values():
        for leaf in jax.tree_util.tree_leaves(tree):
            if hasattr(leaf, "nbytes") and hasattr(leaf, "dtype"):
                out[id(leaf)] = leaf
    return out


# ---------------------------------------------------------------------------
# (1) DeviceMemoryLedger — reconciliation vs jax.live_arrays


def test_ledger_reconciles_with_live_arrays_single_chip():
    """THE acceptance pin: Σ per-plane ledger bytes == the summed bytes
    of exactly the pipeline-owned device buffers, every one of which is
    present in jax.live_arrays() — sketch plane AND cascade enabled."""
    pipe = _ingest(_mk_pipe(), n=4, stride=30)  # crosses a minute: tiers live
    planes = pipe.wm.device_planes()
    owned = _owned_leaves(planes)
    assert owned, "no device planes enumerated"

    live = {id(a) for a in jax.live_arrays()}
    missing = [i for i in owned if i not in live]
    assert not missing, f"{len(missing)} owned buffers absent from live_arrays"

    ledger_total = sum(plane_bytes(tree)[0] for tree in planes.values())
    live_total = sum(int(a.nbytes) for a in owned.values())
    assert ledger_total == live_total
    # the canonical planes all report, and the sketch slabs dominate a
    # small stash (the plane the disaggregation ROADMAP item will shrink)
    per = {name: plane_bytes(tree)[0] for name, tree in planes.items()}
    for name in ("stash", "accumulator", "sketch", "cascade"):
        assert per[name] > 0, per


def test_ledger_reconciles_with_live_arrays_sharded():
    from deepflow_tpu.parallel.mesh import make_mesh
    from deepflow_tpu.parallel.sharded import (
        ShardedConfig,
        ShardedPipeline,
        ShardedWindowManager,
    )

    for n_dev in (1, 2):
        mesh = make_mesh(n_dev)
        cfg = ShardedConfig(
            capacity_per_device=1 << 10, num_services=16, hll_precision=6,
            hist=LogHistSpec(bins=64, vmin=1.0, gamma=1.3),
            cascade=(60,), cascade_capacity=1 << 10,
        )
        wm = ShardedWindowManager(ShardedPipeline(mesh, cfg))
        gen = SyntheticFlowGen(num_tuples=150, seed=7)
        for i, t in enumerate((T0, T0 + 1, T0 + 70)):
            fb = gen.flow_batch(64 * n_dev, t)
            wm.ingest(fb.tags, fb.meters, fb.valid)
        planes = wm.device_planes()
        owned = _owned_leaves(planes)
        live = {id(a) for a in jax.live_arrays()}
        assert all(i in live for i in owned), n_dev
        ledger_total = sum(plane_bytes(tree)[0] for tree in planes.values())
        assert ledger_total == sum(int(a.nbytes) for a in owned.values())
        # per-device attribution: the ledger row divides by the mesh size
        led = DeviceMemoryLedger()
        led.register("swm", wm, devices=n_dev)
        rows = {r["plane"]: r for r in led.snapshot()}
        assert rows["stash"]["devices"] == n_dev
        assert rows["stash"]["bytes_per_device"] == rows["stash"]["bytes"] // n_dev
        wm.close()


def test_ledger_lifecycle_construction_growth_close():
    """Satellite: plane bytes appear on pipeline construction, grow
    when sketch/cascade are enabled, and the registration leaves the
    ledger on close() — and on plain GC (weakref, the r13 tier-registry
    stance)."""
    led = DeviceMemoryLedger()

    plain = _mk_pipe(sketch=False, cascade=False)
    led.register("plain", plain.wm, interval="1s")
    rows = led.snapshot()
    assert rows, "no rows at construction"
    plain_total = sum(r["bytes"] for r in rows)
    assert plain_total > 0  # the stash exists before any batch
    assert not any(r["plane"] == "sketch" for r in rows)

    rich = _mk_pipe(sketch=True, cascade=True)
    led.register("rich", rich.wm, interval="1s")
    rows = led.snapshot()
    by_mod = {}
    for r in rows:
        by_mod.setdefault(r["module"], 0)
        by_mod[r["module"]] += r["bytes"]
    assert by_mod["rich"] > by_mod["plain"]  # sketch+cascade slabs grew it
    assert any(r["module"] == "rich" and r["plane"] == "sketch" and r["bytes"] > 0
               for r in rows)

    # ingest grows the accumulator plane (sized on first batch) and the
    # watermark follows
    _ingest(rich, n=2)
    rows2 = {(r["module"], r["plane"]): r for r in led.snapshot()}
    acc = rows2[("rich", "accumulator")]
    assert acc["bytes"] > 0 and acc["bytes_hwm"] >= acc["bytes"]

    # close() deregisters eagerly from the DEFAULT ledger (the managers
    # register there at construction)
    assert any(s.owner() is rich.wm for s in default_ledger._sources)
    rich.close()
    assert not any(s.owner() is rich.wm for s in default_ledger._sources)

    # plain GC: the weakly-held source vanishes from snapshots
    del plain
    import gc

    gc.collect()
    mods = {r["module"] for r in led.snapshot()}
    assert "plain" not in mods


def test_ledger_transient_checkpoint_scratch(tmp_path):
    from deepflow_tpu.aggregator.checkpoint import save_window_state

    pipe = _ingest(_mk_pipe(sketch=False, cascade=False), n=2)
    save_window_state(pipe.wm, tmp_path / "ck.npz")
    rows = {r["plane"]: r for r in default_ledger.snapshot()}
    ck = rows["checkpoint_scratch"]
    assert ck["bytes"] == 0 and ck["bytes_hwm"] > 0  # transient: HWM only


# ---------------------------------------------------------------------------
# (2) StepCostCensus


def test_census_per_bucket_entries_and_analysis(monkeypatch):
    # fresh census: the default is process-wide and other tests'
    # same-service pipelines would pollute the per-bucket assertions
    import deepflow_tpu.profiling.census as census_mod

    census = StepCostCensus()
    monkeypatch.setattr(census_mod, "default_census", census)
    pipe = L4Pipeline(PipelineConfig(
        window=WindowConfig(capacity=1 << 10),
        batch_size=256, bucket_sizes=(64, 256),
    ))
    gen = SyntheticFlowGen(num_tuples=150, seed=11)
    pipe.ingest(FlowBatch.from_records(gen.records(48, T0)))     # bucket 64
    pipe.ingest(FlowBatch.from_records(gen.records(200, T0 + 1)))  # bucket 256
    pipe.ingest(FlowBatch.from_records(gen.records(40, T0 + 2)))  # reuse 64
    svc = pipe._census_service
    rows = [r for r in census.snapshot() if r["service"] == svc]
    assert {r["bucket"] for r in rows} == {64, 256}
    for r in rows:
        assert r["compiles"] == 1, r  # one compile per bucket, ever
        assert r["compile_wall_s"] > 0
    # the pull-path analysis: flops + bytes accessed + peak memory per
    # (callable, bucket) — cached after the first pull
    rows = [r for r in census.snapshot(analyze=True) if r["service"] == svc]
    for r in rows:
        assert r.get("flops", 0) > 0, r
        assert r.get("bytes_accessed", 0) > 0, r
        assert "argument_size_in_bytes" in r, r
    # bigger bucket → strictly more flops (the attribution is real)
    by_bucket = {r["bucket"]: r for r in rows}
    assert by_bucket[256]["flops"] > by_bucket[64]["flops"]
    # embedded in the bench telemetry shape (absence-tolerant consumers)
    tel = pipe.telemetry()
    assert tel["profile"]["hbm_bytes"]["stash"] > 0
    assert {r["bucket"] for r in tel["profile"]["census"]} == {64, 256}


def test_census_survives_collected_callable():
    census = StepCostCensus()

    import jax.numpy as jnp

    fn = jax.jit(lambda x: x * 2)
    x = jnp.ones((8,), jnp.float32)
    census.observe("svc", "step", 8, fn, (x,))
    census.note_compile("svc", "step", 8, 0.5)
    del fn
    import gc

    gc.collect()
    rows = census.snapshot(analyze=True)
    assert rows[0]["analysis_error"] == "callable collected"
    assert rows[0]["compile_wall_s"] == 0.5  # shapes + wall time survive


# ---------------------------------------------------------------------------
# (3) span latency distributions


def test_span_hist_quantiles_match_exact_percentiles():
    tr = SpanTracer(hist_spec=SpanHistSpec(bins=512, vmin=1.0, gamma=1.02))
    rng = np.random.default_rng(0)
    durs = rng.lognormal(mean=6.0, sigma=1.0, size=4000)  # ~400µs median
    for d in durs:
        tr.record("stage.x", int(d))
    qv = tr.quantiles("stage.x", (0.5, 0.99))
    exact = np.percentile(np.floor(durs).astype(int), [50, 99])
    # the log-hist guarantees (gamma-1)/(gamma+1) ≈ 1% relative error
    assert abs(qv[0] - exact[0]) / exact[0] < 0.05
    assert abs(qv[1] - exact[1]) / exact[1] < 0.05
    # Countable face carries the p-lanes; summary carries them for bench
    c = tr.get_counters()
    assert c["stage.x.p50_us"] == pytest.approx(qv[0], rel=1e-3)  # 0.1µs rounding
    assert "p99_us" in tr.summary()["stage.x"]
    assert tr.quantiles("never.ran") is None


def test_span_tracer_threaded_stress():
    """Satellite: record() under concurrent feeder-pump + query threads
    — every aggregate (count, total, histogram mass) must equal the
    exact per-thread sums; a racy read-modify-write loses updates."""
    tr = SpanTracer(ring_size=64)
    N_THREADS, N_REC = 8, 2000
    durs = [(t * 37 + 13) % 5000 + 1 for t in range(N_THREADS)]

    stop = threading.Event()

    def reader():
        while not stop.is_set():
            tr.get_counters()
            tr.summary()
            tr.quantiles("hot")

    def writer(d):
        for _ in range(N_REC):
            tr.record("hot", d)

    readers = [threading.Thread(target=reader) for _ in range(2)]
    writers = [threading.Thread(target=writer, args=(d,)) for d in durs]
    for t in readers + writers:
        t.start()
    for t in writers:
        t.join()
    stop.set()
    for t in readers:
        t.join()
    c = tr.get_counters()
    assert c["hot.count"] == N_THREADS * N_REC
    assert c["hot.total_us"] == sum(d * N_REC for d in durs)
    with tr._lock:
        assert int(tr._agg["hot"].hist.sum()) == N_THREADS * N_REC


def test_loghist_quantiles_np_empty_and_point_mass():
    spec = SpanHistSpec(bins=64, vmin=1.0, gamma=1.3)
    assert (loghist_quantiles_np(np.zeros(64, np.int64), spec,
                                 (0.5, 0.99)) == 0).all()
    h = np.zeros(64, np.int64)
    h[spec.bin(100.0)] = 50
    qv = loghist_quantiles_np(h, spec, (0.1, 0.5, 0.99))
    assert np.all(qv == qv[0])  # point mass: every quantile = that bin
    assert abs(qv[0] - 100.0) / 100.0 < spec.gamma  # inside the bin's span


# ---------------------------------------------------------------------------
# (4) dogfood: SQL + PromQL answers, and the end-to-end alert pin


def _dogfood(pipe):
    """Run one collector tick over the pipeline + a PRIVATE ledger
    (the process-wide default accumulates every other test's live
    pipelines — the metric names are identical either way) into a
    fresh store's deepflow_system table; returns (store, collector)."""
    from deepflow_tpu.integration.dfstats import system_sink
    from deepflow_tpu.storage.store import ColumnarStore
    from deepflow_tpu.utils.stats import StatsCollector

    store = ColumnarStore()
    led = DeviceMemoryLedger()
    led.register("wm", pipe.wm)
    col = StatsCollector()
    col.register("tpu_pipeline_spans", pipe.tracer)
    # the collector holds countables WEAKLY — the caller must keep the
    # ledger alive (the returned handle) or its rows silently stop
    col.register("tpu_hbm", led)
    col.add_sink(system_sink(store))
    return store, col, led


def test_hbm_and_span_quantiles_answer_via_sql_and_promql():
    """Acceptance pin: `ingest.dispatch` p99 AND `tpu_hbm_sketch_bytes`
    are answerable via BOTH engines from deepflow_system."""
    from deepflow_tpu.querier.engine import QueryEngine
    from deepflow_tpu.querier.promql import query_instant

    pipe = _ingest(_mk_pipe(), n=3)
    store, col, _led = _dogfood(pipe)
    col.tick(now=T0 + 10)

    # SQL
    engine = QueryEngine(store)
    r = engine.execute(
        "SELECT value FROM deepflow_system.deepflow_system "
        "WHERE metric = 'tpu_hbm_sketch_bytes'"
    )
    assert r.rows and float(r.values["value"][0]) > 0
    expected = plane_bytes(pipe.wm.device_planes()["sketch"])[0]
    assert float(r.values["value"][0]) == float(expected)

    r = engine.execute(
        "SELECT value FROM deepflow_system.deepflow_system "
        "WHERE metric = 'tpu_pipeline_spans_ingest_dispatch_p99_us'"
    )
    assert r.rows
    p99_sql = float(r.values["value"][0])
    assert p99_sql == pytest.approx(
        float(pipe.tracer.quantiles(SPAN_INGEST_DISPATCH, (0.99,))[0]), rel=0.01
    )

    # PromQL
    rows = query_instant(store, "tpu_hbm_sketch_bytes", T0 + 10,
                         db="deepflow_system", table="deepflow_system")
    assert rows and rows[0]["value"] == float(expected)
    rows = query_instant(store, "tpu_pipeline_spans_ingest_dispatch_p99_us",
                         T0 + 10, db="deepflow_system",
                         table="deepflow_system")
    assert rows and rows[0]["value"] == p99_sql > 0


def test_span_latency_alert_fires_end_to_end():
    """Acceptance pin: an alert rule on a span-latency quantile fires
    through the r15 engine when the profiling tick lands — the
    ProfileSnapshot event (published at each sample tick) triggers the
    evaluation, not a poll."""
    from deepflow_tpu.querier.alerts import AlertEngine, AlertRule
    from deepflow_tpu.querier.events import ProfileSnapshot, QueryEventBus
    from deepflow_tpu.querier.live import LiveRegistry

    pipe = _ingest(_mk_pipe(sketch=False, cascade=False), n=3)
    store, col, _led = _dogfood(pipe)
    bus = QueryEventBus(name="prof")
    col.add_sink(profile_tick_sink(bus))

    eng = AlertEngine(store, live=LiveRegistry(), bus=bus, name="prof",
                      log_sink=False)
    fired = []
    eng.add_sink(lambda ev: fired.append(ev), name="test")
    eng.add_rule(AlertRule(
        name="slow_dispatch",
        query="tpu_pipeline_spans_ingest_dispatch_p99_us",
        comparator=">", threshold=0.0, for_s=0,
    ))
    assert eng.state("slow_dispatch") == "inactive"
    # the tick writes the quantile rows AND publishes ProfileSnapshot —
    # the engine evaluates on that event (no evaluate_rule/tick calls)
    col.tick(now=T0 + 10)
    assert eng.state("slow_dispatch") == "firing"
    assert fired and fired[0]["rule"] == "slow_dispatch"
    assert fired[0]["value"] > 0
    ev_counts = bus.get_counters()
    assert ev_counts["events_published"] >= 1
    # the event itself carried the ledger's snapshot clock
    bus.publish(ProfileSnapshot("deepflow_system", "deepflow_system", 999))
    eng.close()


def test_profile_tick_sink_is_tick_only():
    """The ProfileSnapshot publisher fires per collector TICK, never on
    pull-path sample() reads (dashboard pulls must not publish)."""
    from deepflow_tpu.querier.events import ProfileSnapshot, QueryEventBus
    from deepflow_tpu.utils.stats import StatsCollector

    got = []
    bus = QueryEventBus(name="tick_only")
    bus.subscribe(lambda evs: got.extend(
        e for e in evs if isinstance(e, ProfileSnapshot)), name="t")
    col = StatsCollector()
    col.register("m", lambda: {"x": 1})
    col.add_sink(profile_tick_sink(bus))
    col.sample()
    assert not got
    col.tick()
    assert len(got) == 1
    col.tick()
    assert len(got) == 2 and got[1].seq > got[0].seq


# ---------------------------------------------------------------------------
# (5) REST surface (the Server composition pin lives in
# tests/test_rest_monitor_issu.py's fixture style)


def test_rest_profile_device_endpoint(tmp_path):
    import json
    import urllib.request

    from deepflow_tpu.server.main import Server
    from deepflow_tpu.utils.config import load_config

    pipe = _ingest(_mk_pipe(sketch=True, cascade=False), n=2)
    cfg, _ = load_config({
        "receiver": {"tcp_port": 0, "udp_port": 0},
        "ingester": {"n_decoders": 1, "prefer_native": False},
        "storage": {"root": str(tmp_path / "store")},
    })
    srv = Server(cfg).start()
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{srv.rest.port}/v1/profile/device?analyze=0"
        ) as r:
            out = json.loads(r.read())
        assert r.status == 200
        planes = {row["plane"] for row in out["hbm"]}
        assert "stash" in planes and "sketch" in planes
        assert out["hbm_totals"]["sketch_bytes"] > 0
        assert isinstance(out["census"], list)
        svc_rows = [c for c in out["census"]
                    if c["service"] == pipe._census_service]
        # analyze=0 computes nothing NEW (earlier pulls' cached analyses
        # may legitimately ride along) — compiles/wall are always there
        assert svc_rows and all(c["compiles"] >= 1 for c in svc_rows)
    finally:
        srv.stop()


def test_ledger_pending_flush_plane_under_async_drain():
    """Review fix pin: the async-drain double buffers (deferred stats
    vector + dispatched-but-unfetched flush handles) are enumerated
    device planes — steady async operation holds real HBM between
    ingest calls and the ledger must see it."""
    pipe = _mk_pipe(sketch=False, cascade=False, async_drain=True)
    gen = SyntheticFlowGen(num_tuples=150, seed=13)
    # an advancing batch leaves a dispatched flush + deferred stats
    # held until the NEXT ingest call
    pipe.ingest(FlowBatch.from_records(gen.records(128, T0)))
    pipe.ingest(FlowBatch.from_records(gen.records(128, T0 + 10)))
    planes = pipe.wm.device_planes()
    assert plane_bytes(planes["pending_flush"])[0] > 0
    # the reconciliation invariant holds with the holds included
    owned = _owned_leaves(planes)
    live = {id(a) for a in jax.live_arrays()}
    assert all(i in live for i in owned)
    assert sum(plane_bytes(t)[0] for t in planes.values()) == sum(
        int(a.nbytes) for a in owned.values()
    )
    # settled: the holds drain and the plane empties
    pipe.wm.settle()
    assert plane_bytes(pipe.wm.device_planes()["pending_flush"])[0] == 0
    pipe.close()


def test_census_service_keys_are_per_pipeline_instance():
    """Review fix pin: two concurrently-live pipelines of the same
    class/interval (different configs — different fused-step
    signatures) must not alias in the census: each gets its own
    service key, shapes, and analysis."""
    a = _mk_pipe(sketch=False, cascade=False)
    b = _mk_pipe(sketch=True, cascade=False)
    assert a._census_service != b._census_service
    gen = SyntheticFlowGen(num_tuples=100, seed=17)
    a.ingest(FlowBatch.from_records(gen.records(128, T0)))
    b.ingest(FlowBatch.from_records(gen.records(128, T0)))
    rows_a = a.profile_snapshot()["census"]
    rows_b = b.profile_snapshot()["census"]
    assert rows_a and rows_b
    assert all(r["service"] == a._census_service for r in rows_a)
    assert all(r["service"] == b._census_service for r in rows_b)
    a.close(), b.close()
