#!/usr/bin/env python
"""All five BASELINE.json configs + a measured CPU baseline, one JSON
line each (BASELINE.md:22-39; r3 verdict item #4). Run from repo root:

    python bench_all.py [--cpu] [--quick]

Configs:
  1 flow_metrics 1s rollup   — synthetic accumulated-flow replay, 10k
    5-tuples, amortized append/fold cadence (the bench.py number), plus
    the MEASURED CPU-oracle baseline on the identical stream; this
    config's vs line is device_rate / cpu_oracle_rate.
  2 L7 RED + t-digest        — request replay through the L7 path, RED
    meters + p50/p99 from the latency log-histogram t-digest.
  3 HLL cardinality          — 1M true client cardinality through the
    HLL plane; reports measured relative error (<1% required).
  4 CMS heavy hitters        — top-K endpoints by bytes via count-min,
    reports top-10 recall vs exact.
  5 pod-wide 1m rollup       — 64-agent firehose over the mesh pipeline
    with collective sketch merges (8-device CPU mesh when multichip
    hardware is absent; on the single TPU it degrades to a 1-device
    mesh, still through shard_map).

Output: one {"metric", "value", "unit", "vs_baseline"} JSON line per
config; also writes PERF_ALL.json with the full detail. The provenance
line names the device (platform, kind, count) the parent ran on, and
the exit code is non-zero if any config failed.

One process holds a chip at a time. config1-5 run on the device in THIS
process, which therefore holds the chip; config6-19 each start a child
(`bench/*.py`), and a child that needs the chip cannot be started from a
parent that has touched JAX. Those children name the CPU platform
themselves at import and say so in their records (`"device"`): their
numbers are CPU counts, never chip numbers. What survives of this
layout is the benchmark PR's call (ROADMAP S0).
"""

from __future__ import annotations

import argparse
import json
import sys
import time

if "--cpu" in sys.argv:
    import os

    os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np

NORTH_STAR = 50e6

results = []


def emit(metric, value, unit, vs_baseline, **detail):
    line = {"metric": metric, "value": round(float(value), 4), "unit": unit,
            "vs_baseline": round(float(vs_baseline), 4)}
    print(json.dumps(line), flush=True)
    results.append({**line, **detail})


def _telemetry(obj):
    """Counter-block + span-summary snapshot for the aggregate JSON
    (ISSUE 3). None — never a crash — when the pipeline predates
    telemetry or the run died before the manager existed."""
    try:
        return obj.telemetry()
    except Exception:
        return None


def config1(quick: bool):
    import jax
    import jax.numpy as jnp

    from deepflow_tpu.aggregator.fanout import FANOUT_LANES, FanoutConfig
    from deepflow_tpu.aggregator.pipeline import make_ingest_step
    from deepflow_tpu.aggregator.stash import accum_init, stash_init
    from deepflow_tpu.datamodel.schema import FLOW_METER, TAG_SCHEMA
    from deepflow_tpu.ingest.replay import SyntheticFlowGen

    BATCH = 1 << 12 if quick else 1 << 20
    # cap must exceed per-batch uniques or the run sheds keys: 4096
    # draws from 10k tuples → ~3.3k uniques (quick); full batches hit
    # all ~10k+ (×2 windows) → 32k cap
    CAPU = 1 << 12 if quick else 1 << 15
    CAP = 1 << 16
    K = 2
    CYCLES = 2 if quick else 8

    gen = SyntheticFlowGen(num_tuples=10_000, seed=0)
    fb = gen.flow_batch(BATCH, 1_700_000_000)
    tags = {k: jnp.asarray(v) for k, v in fb.tags.items()}
    meters = jnp.asarray(fb.meters)
    valid = jnp.asarray(fb.valid)

    append_fn, fold_fn = make_ingest_step(
        FanoutConfig(), interval=1, batch_unique_cap=CAPU
    )
    append = jax.jit(append_fn, donate_argnums=(0, 1))
    fold = jax.jit(fold_fn, donate_argnums=(0, 1))
    stride = FANOUT_LANES * CAPU
    state = stash_init(CAP, TAG_SCHEMA, FLOW_METER)
    acc = accum_init(K * stride, TAG_SCHEMA, FLOW_METER)

    def cycle(state, acc):
        for k in range(K):
            state, acc = append(state, acc, jnp.int32(k * stride), tags, meters, valid)
        return fold(state, acc)

    # chained cycles, synced by one tiny host fetch
    state, acc = cycle(state, acc)
    _ = np.asarray(state.slot[:1])
    t0 = time.perf_counter(); _ = np.asarray(state.slot[:1])
    fetch_base = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(CYCLES):
        state, acc = cycle(state, acc)
    _ = np.asarray(state.slot[:1])
    dev_rate = BATCH * K * CYCLES / (time.perf_counter() - t0 - fetch_base)

    # CPU oracle baseline on the identical stream shape (the reference
    # publishes no numbers — BASELINE.md mandates measuring our own)
    from deepflow_tpu.oracle.numpy_oracle import oracle_l4_rollup

    n_oracle = min(BATCH, 4096)
    records = gen.records(n_oracle, 1_700_000_000)
    t0 = time.perf_counter()
    oracle_l4_rollup(records, config=FanoutConfig())
    cpu_rate = n_oracle / (time.perf_counter() - t0)

    emit("c1_flow_metrics_1s_rollup", dev_rate, "records/s", dev_rate / cpu_rate,
         cpu_oracle_rate=cpu_rate, north_star_frac=dev_rate / NORTH_STAR)


def config2(quick: bool):
    import jax
    import jax.numpy as jnp

    from deepflow_tpu.aggregator.fanout import FANOUT_LANES, FanoutConfig
    from deepflow_tpu.aggregator.pipeline import make_ingest_step
    from deepflow_tpu.aggregator.stash import accum_init, stash_init
    from deepflow_tpu.datamodel.schema import APP_METER, TAG_SCHEMA
    from deepflow_tpu.ops.histogram import LogHistSpec, loghist_update
    from deepflow_tpu.ops.tdigest import tdigest_from_loghist, tdigest_quantile

    BATCH = 1 << 12 if quick else 1 << 18
    CAPU = 1 << 11 if quick else 1 << 12  # ≥ 64 svc × 16 endpoint uniques
    total = 1 << 17 if quick else 1 << 21  # ~2M requests
    spec = LogHistSpec(bins=512, vmin=1.0, gamma=1.04)

    from deepflow_tpu.ingest.replay import SyntheticAppGen

    gen = SyntheticAppGen(num_services=64, endpoints_per_service=16, seed=1)
    draw = gen._draw(BATCH)
    fb = gen.app_batch(BATCH, 1_700_000_000, draw=draw)
    tags = {k: jnp.asarray(v) for k, v in fb.tags.items()}
    meters = jnp.asarray(fb.meters)
    valid = jnp.asarray(fb.valid)
    # the generator's true service id — NOT a port residue (a port-mod
    # binning can leave bins empty and record 0.0 percentiles)
    svc_id = jnp.asarray(draw[0].astype(np.int32))

    append_fn, fold_fn = make_ingest_step(
        FanoutConfig(), interval=1, app=True, batch_unique_cap=CAPU
    )
    append = jax.jit(append_fn, donate_argnums=(0, 1))
    fold = jax.jit(fold_fn, donate_argnums=(0, 1))
    doc_rows = FANOUT_LANES * CAPU
    K = 2
    state = stash_init(1 << 16, TAG_SCHEMA, APP_METER)
    acc = accum_init(K * doc_rows, TAG_SCHEMA, APP_METER)

    m_idx = APP_METER.index
    hist = jnp.zeros((64, spec.bins), jnp.int32)

    @jax.jit
    def upd_hist(hist, svc, meters, valid):
        rrt = meters[:, m_idx("rrt_sum")] / jnp.maximum(meters[:, m_idx("rrt_count")], 1.0)
        return loghist_update(hist, svc, rrt, valid & (meters[:, m_idx("rrt_count")] > 0), spec)

    # warm, then one true host-fetch sync (PERF.md §6)
    state, acc = append(state, acc, jnp.int32(0), tags, meters, valid)
    state, acc = fold(state, acc)
    hist = upd_hist(hist, svc_id, meters, valid)
    _ = np.asarray(state.slot[:1])
    t0 = time.perf_counter(); _ = np.asarray(state.slot[:1])
    fetch_base = time.perf_counter() - t0

    iters = max(1, total // BATCH)
    t0 = time.perf_counter()
    k = 0
    for i in range(iters):
        state, acc = append(state, acc, jnp.int32(k * doc_rows), tags, meters, valid)
        hist = upd_hist(hist, svc_id, meters, valid)
        k += 1
        if k == K:
            state, acc = fold(state, acc)
            k = 0
    _ = np.asarray(state.slot[:1])
    rate = BATCH * iters / (time.perf_counter() - t0 - fetch_base)

    # pooled distribution over ALL services (merge = histogram sum),
    # plus one per-service row as a spot check
    pooled = hist.sum(axis=0, keepdims=True)
    means, weights = tdigest_from_loghist(pooled, spec)
    p50, p99 = np.asarray(
        tdigest_quantile(means[0], weights[0], jnp.asarray([0.5, 0.99]))
    )
    svc0 = tdigest_from_loghist(hist[:1], spec)
    s_p50, s_p99 = np.asarray(
        tdigest_quantile(svc0[0][0], svc0[1][0], jnp.asarray([0.5, 0.99]))
    )
    # an empty-sketch regression must never be recordable again
    assert float(p99) > 0.0, "c2 pooled histogram is empty"
    assert float(s_p99) > 0.0, "c2 service-0 histogram is empty"
    emit("c2_l7_red_tdigest", rate, "requests/s", rate / NORTH_STAR,
         p50_us=float(p50), p99_us=float(p99),
         svc0_p50_us=float(s_p50), svc0_p99_us=float(s_p99))


def config3(quick: bool):
    import jax
    import jax.numpy as jnp

    from deepflow_tpu.ops.hashing import fingerprint64
    from deepflow_tpu.ops.hll import hll_estimate, hll_init, hll_update

    true_card = 1 << 17 if quick else 1_000_000
    BATCH = 1 << 16
    precision = 14
    rng = np.random.default_rng(2)
    state = hll_init(1, precision)
    upd = jax.jit(hll_update, donate_argnums=(0,))
    gid = jnp.zeros(BATCH, jnp.int32)
    v = jnp.ones(BATCH, bool)

    # stream 4x the cardinality in repeats (clients recur across windows)
    total = true_card * 4
    ids = rng.integers(0, true_card, total).astype(np.uint32)
    ids[:true_card] = np.arange(true_card, dtype=np.uint32)  # all present
    t0 = time.perf_counter()
    seen = 0
    for off in range(0, total, BATCH):
        chunk = ids[off : off + BATCH]
        if len(chunk) < BATCH:
            chunk = np.pad(chunk, (0, BATCH - len(chunk)))
        hi, lo = fingerprint64(jnp.asarray(chunk[:, None]))
        state = upd(state, gid, hi, lo, v)
        seen += len(chunk)
    est = float(np.asarray(hll_estimate(state))[0])
    dt = time.perf_counter() - t0
    rel_err = abs(est - true_card) / true_card
    emit("c3_hll_rel_err_at_1M", rel_err, "fraction", 1.0 if rel_err < 0.01 else 0.0,
         estimate=est, true_cardinality=true_card, update_rate=seen / dt)


def config4(quick: bool):
    import jax
    import jax.numpy as jnp

    from deepflow_tpu.ops.cms import cms_init, cms_query, cms_update
    from deepflow_tpu.ops.hashing import fingerprint64

    n_endpoints = 1 << 14  # 16-way tag group-by space
    BATCH = 1 << 16
    iters = 4 if quick else 16
    rng = np.random.default_rng(3)
    # zipf-ish endpoint popularity
    weights = 1.0 / np.arange(1, n_endpoints + 1) ** 1.2
    weights /= weights.sum()
    state = cms_init(depth=4, width=1 << 14)
    upd = jax.jit(cms_update, donate_argnums=(0,))
    truth = np.zeros(n_endpoints, np.int64)
    t0 = time.perf_counter()
    for _ in range(iters):
        eps = rng.choice(n_endpoints, BATCH, p=weights).astype(np.uint32)
        byte_w = rng.integers(100, 1500, BATCH).astype(np.int32)
        np.add.at(truth, eps, byte_w)
        hi, lo = fingerprint64(jnp.asarray(eps[:, None]))
        state = upd(state, hi, lo, jnp.asarray(byte_w), jnp.ones(BATCH, bool))
    jax.block_until_ready(state)
    rate = BATCH * iters / (time.perf_counter() - t0)

    all_ids = np.arange(n_endpoints, dtype=np.uint32)
    hi, lo = fingerprint64(jnp.asarray(all_ids[:, None]))
    est = np.asarray(cms_query(state, hi, lo))
    top_true = set(np.argsort(truth)[-10:].tolist())
    top_est = set(np.argsort(est)[-10:].tolist())
    recall = len(top_true & top_est) / 10.0
    emit("c4_cms_topk_endpoints", rate, "spans/s", recall, top10_recall=recall)


def config5(quick: bool):
    import jax

    from deepflow_tpu.ingest.replay import SyntheticFlowGen
    from deepflow_tpu.ops.histogram import LogHistSpec
    from deepflow_tpu.parallel.mesh import make_mesh
    from deepflow_tpu.parallel.sharded import (
        ShardedConfig,
        ShardedPipeline,
        ShardedWindowManager,
    )

    n_dev = len(jax.devices())
    mesh = make_mesh(n_dev, n_hosts=2 if n_dev % 2 == 0 and n_dev > 1 else 1)
    cfg = ShardedConfig(
        capacity_per_device=1 << 12,
        num_services=256,
        hll_precision=10,
        hist=LogHistSpec(bins=256, vmin=1.0, gamma=1.08),
        # ≥ E[uniques] of 32k draws from 10k tuples (~9.6k) so the run
        # sheds nothing
        batch_unique_cap=None if quick else 1 << 14,
    )
    pipe = ShardedPipeline(mesh, cfg)
    wm = ShardedWindowManager(pipe)

    per_dev = 1 << 10 if quick else 1 << 15
    batch = per_dev * n_dev  # "64-agent firehose" sharded over the mesh
    gen = SyntheticFlowGen(num_tuples=10_000, seed=4)
    t0s = 1_700_000_000
    # warm ALL the compile paths (step, window_close, fold, flush) —
    # the first advancing window pays them; timing must not
    for wt in (t0s, t0s + 60, t0s + 61, t0s + 65):
        fb = gen.flow_batch(batch, wt)
        wm.ingest(fb.tags, fb.meters, fb.valid)
    iters = 4 if quick else 12
    # pre-generate outside the timed loop — synthetic data creation is
    # not part of the pipeline under test
    batches = [gen.flow_batch(batch, t0s + 70 + i) for i in range(iters)]
    _ = np.asarray(wm.sketches.hll.ravel()[:1])  # host-fetch sync
    t0 = time.perf_counter(); _ = np.asarray(wm.sketches.hll.ravel()[:1])
    fetch_base = time.perf_counter() - t0
    t0 = time.perf_counter()
    docs = 0
    for fb in batches:
        docs += sum(d.size for d in wm.ingest(fb.tags, fb.meters, fb.valid))
    _ = np.asarray(wm.sketches.hll.ravel()[:1])
    rate = batch * iters / (time.perf_counter() - t0 - fetch_base)

    # mesh scaling rows (1/2/4/8 virtual CPU devices, collective close
    # timed separately) — the r4 verdict's c5 fix: the headline above is
    # single-chip steady ingest; the mesh statement is this curve, run
    # in the same environment dryrun_multichip validates.
    scaling = []
    if not quick:
        import subprocess

        try:
            out = subprocess.run(
                [sys.executable, "bench/mesh_scaling.py"],
                capture_output=True, text=True, timeout=900,
                # fold-mode A/B at two device counts keeps the run inside
                # the timeout; the standalone tool defaults to the full
                # 1/2/4/8 × full/merge matrix
                env={**__import__("os").environ, "MESH_PER_DEV": str(1 << 13),
                     "MESH_ITERS": "8", "MESH_DEVICES": "1,4",
                     "MESH_FOLD_MODES": "full,merge"},
            )
            rec = json.loads(out.stdout.strip().splitlines()[-1])
            scaling = rec["rows"]
            if rec.get("partial"):  # mesh_scaling's partial-JSON convention
                scaling = scaling + [{"error": rec.get("error", "partial run")}]
        except Exception as e:
            scaling = [{"error": repr(e)}]
    emit("c5_pod_1m_rollup_mesh", rate, "records/s", rate / NORTH_STAR,
         n_devices=n_dev, flushed_docs=docs, mesh_scaling=scaling,
         telemetry=_telemetry(wm))


def config6(quick: bool):
    """Feeder runtime (ISSUE 4): wire-to-window rate through multi-queue
    fan-in + bucket coalescing + the K-batch counter ring. Runs
    bench/feeder_probe.py in a clean CPU subprocess (the probe pins
    JAX_PLATFORMS=cpu; on-chip columns pending, PERF.md §14) and
    re-emits its record; the vs line is host-fetches-per-batch — the
    lever this subsystem exists to push below 1."""
    import os
    import subprocess

    env = {**os.environ, "FEEDER_ITERS": "16" if quick else "48"}
    out = subprocess.run(
        [sys.executable, "bench/feeder_probe.py"],
        capture_output=True, text=True, timeout=900, env=env,
    )
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    emit("c6_feeder_wire_to_window", rec["rec_s"], "records/s",
         rec["fetches_per_batch"], **{
             k: rec[k] for k in (
                 "batches", "host_fetches", "stats_ring", "buckets",
                 "jit_retraces", "jit_compiles", "shed_records", "pad_rows",
             )
         }, telemetry=rec.get("telemetry"),
         feeder_telemetry=rec.get("feeder_telemetry"))


def config7(quick: bool):
    """Fold stage A/B (ISSUE 5): full-sort fold vs incremental
    merge-fold via bench/foldbench.py (chained-sync §7a recipe, real
    TAG_SCHEMA × FLOW_METER payload widths). The vs line is the
    full/merge speedup at the largest shape run; the span-bounded
    advance variant rides in the detail rows. Quick mode trims to one
    small shape; the full on-chip grid is the foldbench default
    (PERF.md §15)."""
    import os
    import subprocess

    shapes = (
        "65536:8192" if quick
        else "65536:8192,65536:65536,262144:8192,262144:65536"
    )
    env = {**os.environ, "FOLDBENCH_SHAPES": shapes,
           "FOLDBENCH_ITERS": "2" if quick else "4"}
    out = subprocess.run(
        [sys.executable, "bench/foldbench.py"],
        capture_output=True, text=True, timeout=900, env=env,
    )
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    rows = rec["rows"]
    if not rows:
        emit("c7_fold_full_vs_merge", 0, "error", 0,
             error=rec.get("error", "no rows"))
        return
    last = rows[-1]
    emit("c7_fold_full_vs_merge", last["merge_ms"], "ms/fold",
         last["speedup_full_vs_merge"], rows=rows,
         partial=rec.get("partial", False), error=rec.get("error"))


def config8(quick: bool):
    """Journal overhead A/B (ISSUE 6): the config6 feeder workload run
    journal-off vs journal-on (vs journal-on+fsync) via
    bench/journal_probe.py — the vs line is the buffered-journal
    overhead in percent (the crash-safety tax on steady-state ingest;
    protocol + committed numbers in PERF.md §16)."""
    import os
    import subprocess

    env = {**os.environ, "JOURNAL_ITERS": "16" if quick else "48"}
    out = subprocess.run(
        [sys.executable, "bench/journal_probe.py"],
        capture_output=True, text=True, timeout=1200, env=env,
    )
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    if rec.get("partial"):
        emit("c8_journal_overhead", 0, "error", 0, error=rec.get("error"))
        return
    emit("c8_journal_overhead", rec["journal_on"]["rec_s"], "records/s",
         rec["overhead_pct"],
         overhead_fsync_pct=rec["overhead_fsync_pct"],
         journal_off=rec["journal_off"], journal_on=rec["journal_on"],
         journal_on_fsync=rec["journal_on_fsync"], buckets=rec["buckets"])


def config9(quick: bool):
    """Sketch tier A/B (ISSUE 8): exact-only vs +sketch-plane vs +top-K
    through the windowed raw-doc path under Zipf+scan traffic, via
    bench/sketchbench.py (protocol + committed numbers: PERF.md §17;
    the pooled-memory run is §28 / SKETCHBENCH_r02.json). The vs line
    is the top-K variant's heavy-hitter recall at the largest shape
    run; cardinality error, the exact tier's shed coverage and the
    ISSUE 20 pooled-memory density (`density_vs_slab` on the "pool"
    row, from live HBM ledger bytes) ride the detail rows. Quick mode
    trims to one small shape; the acceptance grid (1M-row batches,
    ≥1M distinct keys, K=128, Zipf s=1.1) is the standalone default."""
    import os
    import subprocess

    env = {**os.environ}
    if quick:
        env.update(SKETCHBENCH_SHAPES="65536:8192", SKETCHBENCH_BATCHES="2",
                   SKETCHBENCH_KEYS=str(1 << 18))
    out = subprocess.run(
        [sys.executable, "bench/sketchbench.py"],
        capture_output=True, text=True, timeout=3600, env=env,
    )
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    rows = rec["rows"]
    if not rows:
        emit("c9_sketch_tier", 0, "error", 0, error=rec.get("error", "no rows"))
        return
    topk_rows = [r for r in rows if r["variant"] == "topk"]
    last = topk_rows[-1] if topk_rows else rows[-1]
    pool_rows = [r for r in rows if r["variant"] == "pool"]
    emit("c9_sketch_tier", last["rec_s"], "records/s",
         last.get("topk_recall", 0.0), rows=rows,
         cardinality_error=last.get("cardinality_error"),
         exact_coverage=last.get("exact_coverage"),
         pool_density_vs_slab=(
             pool_rows[-1].get("density_vs_slab") if pool_rows else None),
         pool_topk_recall=(
             pool_rows[-1].get("topk_recall") if pool_rows else None),
         n_keys=rec["n_keys"], zipf_s=rec["zipf_s"], k_top=rec["k_top"],
         partial=rec.get("partial", False), error=rec.get("error"))


def config10(quick: bool):
    """Rollup cascade A/B (ISSUE 9): double-ingest vs cascade on the
    §14 feeder-shaped dual-granularity workload via
    bench/cascadebench.py (protocol + committed numbers: PERF.md §18,
    CASCADEBENCH_r01.json). The vs line is the cascade/double ingest
    speedup (acceptance ≥1.5× on the CPU grid); the long-range query
    A/B (1h span at 1s replay vs tier-selected 1m) rides the detail."""
    import os
    import subprocess

    env = {**os.environ}
    if quick:
        env.update(CASCADEBENCH_BATCHES="32", CASCADEBENCH_REPS="1")
    out = subprocess.run(
        [sys.executable, "bench/cascadebench.py"],
        capture_output=True, text=True, timeout=1800, env=env,
    )
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    if rec.get("partial"):
        emit("c10_rollup_cascade", 0, "error", 0, error=rec.get("error"))
        return
    ing, q = rec["ingest"], rec["query"]
    emit("c10_rollup_cascade", ing["cascade"]["rec_s"], "records/s",
         ing["speedup_cascade_vs_double"],
         double=ing["double"], cascade=ing["cascade"],
         query_rows_ratio=q["rows_ratio"],
         query_speedup=q["speedup_tier_vs_replay"],
         batch=rec["batch"], n_batches=rec["n_batches"],
         tuples=rec["tuples"])


def config11(quick: bool):
    """Live read plane (ISSUE 10): snapshot overhead on the §14 feeder
    workload + cached vs uncached repeated-query latency via
    bench/livebench.py (protocol: PERF.md §19). The vs line is the
    result-cache speedup on the repeated dashboard query; the snapshot
    ingest overhead rides the detail."""
    import os
    import subprocess

    env = {**os.environ}
    if quick:
        env.update(LIVEBENCH_ITERS="16", LIVEBENCH_QUERY_REPS="20")
    out = subprocess.run(
        [sys.executable, "bench/livebench.py"],
        capture_output=True, text=True, timeout=1800, env=env,
    )
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    if rec.get("partial"):
        emit("c11_live_read", 0, "error", 0, error=rec.get("error"))
        return
    q = rec["query"]
    emit("c11_live_read", q["cached_ms"], "ms/query",
         q["speedup_cached"],
         uncached_ms=q["uncached_ms"], series=q["series"],
         cache=q["cache"], ingest=rec["ingest"],
         snap_every=rec["snap_every"], iters=rec["iters"])


def config12(quick: bool):
    """Push query plane (ISSUE 11): dashboard-storm fan-out
    amplification + flush→watcher invalidation latency via
    bench/pushbench.py (protocol: PERF.md §20, committed numbers:
    PUSHBENCH_r01.json). The vs line is the amplification at the
    largest watcher count (acceptance ≥100× from ONE evaluation per
    event, results pinned bit-exact vs a fresh pull); evals/sec and
    the publish→delivery latency ride the detail rows."""
    import os
    import subprocess

    env = {**os.environ}
    if quick:
        env.update(PUSHBENCH_WATCHERS="1,100", PUSHBENCH_EVENTS="8",
                   PUSHBENCH_FLOWS="128")
    out = subprocess.run(
        [sys.executable, "bench/pushbench.py"],
        capture_output=True, text=True, timeout=1800, env=env,
    )
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    if rec.get("partial"):
        emit("c12_push_plane", 0, "error", 0, error=rec.get("error"))
        return
    rows = rec["rows"]
    last = rows[-1]
    assert last["pinned_bit_exact"], "push-delivered result diverged from pull"
    emit("c12_push_plane", last["deliveries_per_s"], "deliveries/s",
         last["amplification"],
         evals_per_s=last["evals_per_s"],
         publish_to_last_watcher_ms=last["publish_to_last_watcher_ms"],
         watchers=last["watchers"], rows=rows, events=rec["events"],
         flows=rec["flows"])


def config13(quick: bool):
    """Device profiling plane (ISSUE 12): always-on ledger + census +
    span-quantile overhead on the §14 feeder workload via
    bench/profbench.py (protocol: PERF.md §21, committed numbers:
    PROFBENCH_r01.json). The vs line is the overhead percent under an
    aggressive every-4-pumps profiling consumer (acceptance <2% with
    fetch parity — parity itself is CI-gated deterministically); the
    profile pull latencies and per-bucket census rows ride the
    detail."""
    import os
    import subprocess

    env = {**os.environ}
    if quick:
        env.update(PROFBENCH_ITERS="16")
    out = subprocess.run(
        [sys.executable, "bench/profbench.py"],
        capture_output=True, text=True, timeout=1800, env=env,
    )
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    if rec.get("partial"):
        emit("c13_device_profiling", 0, "error", 0, error=rec.get("error"))
        return
    emit("c13_device_profiling", rec["profiled"]["rec_s"], "records/s",
         rec["overhead_pct"],
         fetch_parity=rec["fetch_parity"], pull=rec["pull"],
         hbm_bytes=rec["hbm_bytes"], census=rec["census"],
         span_p99_us=rec["span_p99_us"],
         passive=rec["passive"], iters=rec["iters"])


def config14(quick: bool):
    """Window lineage tracing + freshness plane (ISSUE 13): passive vs
    traced A/B on the §14 feeder workload via bench/tracebench.py
    (protocol: PERF.md §22, committed numbers: TRACEBENCH_r01.json).
    The vs line is the overhead percent with the full lineage stack +
    an every-4-pumps consumer (fetch parity itself is CI-gated
    deterministically); span-row volume and the trace pull latencies
    ride the detail, on-chip columns reserved."""
    import os
    import subprocess

    env = {**os.environ}
    if quick:
        env.update(TRACEBENCH_ITERS="16")
    out = subprocess.run(
        [sys.executable, "bench/tracebench.py"],
        capture_output=True, text=True, timeout=1800, env=env,
    )
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    if rec.get("partial"):
        emit("c14_window_lineage", 0, "error", 0, error=rec.get("error"))
        return
    emit("c14_window_lineage", rec["traced"]["rec_s"], "records/s",
         rec["overhead_pct"],
         fetch_parity=rec["fetch_parity"],
         span_rows_per_window=rec["traced"]["span_rows_per_window"],
         span_rows_per_1k_records=rec["traced"]["span_rows_per_1k_records"],
         pull_ms_live_assemble=rec["traced"]["pull_ms_live_assemble"],
         pull_ms_store_query=rec["traced"]["pull_ms_store_query"],
         passive=rec["passive"], iters=rec["iters"])


def config15(quick: bool):
    """Multi-host mesh scale-out (ISSUE 14): N-process jax.distributed
    deployments via bench/mesh_scaling.py MESH_PROCS — each host one
    shard group, key-hash-routed agents, fully-local data path — the
    aggregate rec/s statement the pod-scale ROADMAP item demanded
    (protocol: PERF.md §23, committed numbers: MESHBENCH_r01.json;
    acceptance: ≥1.7× aggregate at 2 processes)."""
    import os
    import subprocess

    env = {**os.environ, "MESH_PROCS": "1,2" if quick else "1,2,4"}
    if quick:
        env["MESHBENCH_ITERS"] = "16"
    out = subprocess.run(
        [sys.executable, "bench/mesh_scaling.py"],
        capture_output=True, text=True, timeout=1800, env=env,
    )
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    if rec.get("partial"):
        emit("c15_multihost_mesh", 0, "error", 0, error=rec.get("error"))
        return
    rows = rec["proc_rows"]
    last = rows[-1]
    emit("c15_multihost_mesh", last["aggregate_rec_s"], "records/s",
         last.get("scale_vs_1proc", 0),
         n_processes=last["n_processes"],
         per_host_rec_s=last["per_host_rec_s"],
         init_s_max=last["init_s_max"], rows=rows)


def config16(quick: bool):
    """Rebalance-pause protocol (ISSUE 15): bench/mesh_scaling.py
    MESH_REBALANCE=1 — the shard-group handover pause (quiesce →
    manifest checkpoint → restore on the new owner) decomposed by
    phase, plus recovery-to-steady rate, swept over group state size
    (protocol + committed CPU numbers: PERF.md §24). The headline value
    is the largest-state row's pause; vs_baseline is post/pre steady
    rate — 1.0 means the flip left no lingering cost."""
    import os
    import subprocess

    env = {**os.environ, "MESH_REBALANCE": "1"}
    if quick:
        env["MESH_REBALANCE_PRELOADS"] = "8"
        env["MESHBENCH_ITERS"] = "8"
    out = subprocess.run(
        [sys.executable, "bench/mesh_scaling.py"],
        capture_output=True, text=True, timeout=1800, env=env,
    )
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    rows = rec.get("rebalance_rows", [])
    if rec.get("partial") or not rows:
        emit("c16_rebalance_pause", 0, "error", 0, error=rec.get("error"))
        return
    last = rows[-1]
    emit("c16_rebalance_pause", last["pause_ms"], "ms",
         last["post_rec_s"] / max(last["pre_rec_s"], 1e-9),
         ckpt_bytes=last["ckpt_bytes"], recovery_ms=last["recovery_ms"],
         first_pump_ms=last["first_pump_ms"], rows=rows)


def config17(quick: bool):
    """One-pass shared sort (ISSUE 17): bench/sortbench.py A/Bs the
    multi-sort oracle vs the shared-sort rewrite through the +top-K
    windowed ingest at the §17 shapes, with census-attributed
    sorts/dispatch and a bit-parity digest embedded (protocol +
    committed CPU numbers: PERF.md §25, SORTBENCH_r01.json; acceptance:
    ≥1.2× on the +topk shape with bit_parity true). The headline value
    is the last shape's one-pass rate; vs_baseline is its speedup over
    the multi-sort oracle on the same stream."""
    import os
    import subprocess

    env = {**os.environ}
    if quick:
        env["SORTBENCH_SHAPES"] = "65536:8192"
        env["SORTBENCH_BATCHES"] = "2"
    out = subprocess.run(
        [sys.executable, "bench/sortbench.py"],
        capture_output=True, text=True, timeout=3600, env=env,
    )
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    ones = [r for r in rec.get("rows", []) if r["mode"] == "onepass"]
    if rec.get("partial") or not ones:
        emit("c17_one_pass_sort", 0, "error", 0, error=rec.get("error"))
        return
    last = ones[-1]
    emit("c17_one_pass_sort", last["rec_s"], "records/s",
         last["speedup_vs_multisort"],
         batch=last["batch"], stash=last["stash"],
         bit_parity=last["bit_parity"],
         sorts_per_dispatch=rec["sorts_per_dispatch"], rows=rec["rows"])


def config18(quick: bool):
    """Fleet telemetry plane (ISSUE 18): bench/fleetbench.py A/Bs the
    §14 feeder workload passive vs with the full fleet export loop
    (collector tick → frame build/encode → TCP ship → aggregator merge)
    and sweeps the merged-read cost over hosts and over per-host sample
    volume (protocol: PERF.md §26; acceptance: ingest overhead within
    noise — fetch parity is CI-gated — and aggregator cost O(hosts),
    not O(samples)). The vs line is the ingest overhead percent."""
    import os
    import subprocess

    env = {**os.environ}
    if quick:
        env.update(FLEETBENCH_ITERS="16", FLEETBENCH_HOSTS="2,4")
    out = subprocess.run(
        [sys.executable, "bench/fleetbench.py"],
        capture_output=True, text=True, timeout=1800, env=env,
    )
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    if rec.get("partial"):
        emit("c18_fleet_plane", 0, "error", 0, error=rec.get("error"))
        return
    emit("c18_fleet_plane", rec["fleet"]["rec_s"], "records/s",
         rec["overhead_pct"],
         frame_bytes_avg=rec["fleet"]["frame_bytes_avg"],
         hosts_rows=rec["hosts_rows"],
         per_host_ms_ratio=rec["per_host_ms_ratio"],
         samples_ratio=rec["samples_ratio"],
         frame_bytes_ratio=rec["frame_bytes_ratio"],
         merge_ms_ratio=rec["merge_ms_ratio"],
         passive=rec["passive"], iters=rec["iters"])


def config19(quick: bool):
    """Wire delivery plane (ISSUE 19): bench/wirebench.py fans merged
    eval envelopes from H socketed host publishers through the
    FleetSubscriptionRouter to W wire clients over a watchers × rules ×
    hosts grid (protocol: PERF.md §27; acceptance: publish→all-watchers
    latency FLAT in W — ONE upstream eval per event batch per query,
    fan-out is W bounded-queue appends — with per-host rows pinned
    bit-exact vs each host's own evaluation). The headline value is the
    largest cell's deliveries/s; the vs line is the worst
    max-W-over-W=1 latency ratio (1.0 == perfectly flat)."""
    import os
    import subprocess

    env = {**os.environ}
    if quick:
        env.update(WIREBENCH_EVENTS="8", WIREBENCH_WATCHERS="1,10",
                   WIREBENCH_HOSTS="1", WIREBENCH_RULES="0")
    out = subprocess.run(
        [sys.executable, "bench/wirebench.py"],
        capture_output=True, text=True, timeout=1800, env=env,
    )
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    if rec.get("partial"):
        emit("c19_wire_fanout", 0, "error", 0, error=rec.get("error"))
        return
    big = max(rec["rows"], key=lambda r: r["watchers"] * r["hosts"])
    emit("c19_wire_fanout", big["deliveries_per_s"], "deliveries/s",
         max(rec["latency_ratio_wmax_over_w1"].values()),
         latency_ratio_wmax_over_w1=rec["latency_ratio_wmax_over_w1"],
         publish_to_all_watchers_ms_mean=big[
             "publish_to_all_watchers_ms_mean"],
         pinned_bit_exact=all(r["pinned_bit_exact"] for r in rec["rows"]),
         drops=sum(r["drops"] for r in rec["rows"]),
         upstream_subs=max(r["upstream_subs"] for r in rec["rows"]),
         rows=rec["rows"])


def main() -> int:
    from deepflow_tpu.utils.compile_cache import enable_compile_cache
    from deepflow_tpu.utils.provenance import bench_provenance, device_identity

    p = argparse.ArgumentParser()
    p.add_argument("--cpu", action="store_true")
    p.add_argument("--quick", action="store_true")
    args = p.parse_args()
    # provenance first (ISSUE 18 satellite): every bench JSON names the
    # commit, platform, and DEEPFLOW_* knob set it measured
    enable_compile_cache()
    prov = bench_provenance()
    prov["device"] = device_identity()
    print(json.dumps({"provenance": prov}), flush=True)
    failed = []
    for fn in (config1, config2, config3, config4, config5, config6, config7,
               config8, config9, config10, config11, config12, config13,
               config14, config15, config16, config17, config18,
               config19):
        try:
            fn(args.quick)
        except Exception as e:  # one config must not kill the others
            emit(fn.__name__ + "_error", 0, "error", 0, error=repr(e))
            failed.append(fn.__name__)
    # quick/CPU smoke runs must never clobber the committed full-run
    # record the docs cite
    out = "PERF_ALL.json" if not (args.quick or args.cpu) else "PERF_ALL_QUICK.json"
    with open(out, "w") as f:
        json.dump({"provenance": prov, "results": results}, f, indent=1)
    # configs that caught their own child's failure emit unit "error"
    failed += [r["metric"] for r in results
               if r["unit"] == "error" and not r["metric"].endswith("_error")]
    if failed:
        print(f"bench_all: FAILED configs: {failed}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
