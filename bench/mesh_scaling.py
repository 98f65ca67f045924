#!/usr/bin/env python
"""Mesh-scaling rows for BASELINE config 5 — the r4 verdict's demand
that c5 be a *mesh* statement, not a fetch-latency measurement.

Three recipes in one tool:

**MESH_PROCS=N1,N2,... (ISSUE 14)** — the multi-HOST recipe: for each
N, spawn N clean-env subprocesses (the dryrun_multichip pattern), each
one host of an N-process `jax.distributed` deployment
(MeshTopology.distributed, one shard group per process, fully-local
data path) running the §14 feeder-shaped workload — frames → queues →
FeederRuntime → ShardedFeedSink → windowed drains — against ITS group
only (key-hash routing already steered the agents there; the routing
itself is CI-pinned in tests/test_mesh_multiproc.py). Reports per-host
and AGGREGATE rec/s per process count plus the distributed bring-up
wall. Emits {"proc_rows": [...]} alongside (or instead of) the device
rows; MESHBENCH_r01.json holds the committed snapshot.

**MESH_REBALANCE=1 (ISSUE 15)** — the rebalance-pause protocol row
(PERF.md §24): a feeder-shaped shard group on the OLD owner's
standalone topology view is preloaded to a given state size and timed
at steady state, then handed over — `GroupRebalancer.release` (quiesce
→ manifest checkpoint → journal rotate) and `adopt`
(restore_sharded_state into a fresh manager under the NEW owner's
view) — with the pause decomposed into release/build/restore, the
first post-adopt pump (the cold manager's compile) reported
separately, and the per-step cadence walked until it re-enters 1.5× of
the pre-handover steady step (recovery-to-steady). One row per
MESH_REBALANCE_PRELOADS entry (state size sweep). Emits
{"rebalance_rows": [...]}.

**Default (device) recipe** — the single-process virtual CPU mesh at
1/2/4/8 devices (XLA_FLAGS=--xla_force_host_platform_device_count=8,
the same environment dryrun_multichip validates), at a FIXED
per-device batch (weak scaling, the pod-firehose shape), timing:

  * steady ingest cycles (step + amortized fold) — chained, no host
    round trip inside the loop; one measured fetch latency is
    subtracted from the window (PERF.md §7a recipe);
  * the *windowed* cadence — timestamps advance so every iteration
    closes a window through the fused `flush_range` batched drain
    (one totals fetch + one packed row-block fetch per advance,
    ISSUE 2) — the end-to-end rate the product ships through;
  * the collective window close (psum/pmax sketch merges over
    chip/host axes) separately, since that is the mesh-specific cost.

Prints one JSON line: {"rows": [{n_devices, ingest_rec_s,
windowed_rec_s, drain_ms, close_ms, ...}, ...]}. On any failure it
prints {"rows": [...partial...], "partial": true, "error": ...} and
exits 0 (bench.py convention — the harness always gets parseable
output). bench_all.py config5 shells out to this and embeds the rows
in PERF_ALL's c5 detail.
"""

from __future__ import annotations

import json
import os
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"  # this tool measures the CPU mesh only
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from deepflow_tpu.utils.provenance import device_identity  # noqa: E402

from deepflow_tpu.ingest.replay import SyntheticFlowGen  # noqa: E402
from deepflow_tpu.ops.histogram import LogHistSpec  # noqa: E402
from deepflow_tpu.parallel.mesh import make_mesh  # noqa: E402
from deepflow_tpu.parallel.sharded import (  # noqa: E402
    ShardedConfig,
    ShardedPipeline,
    ShardedWindowManager,
)


def _sync(wm):
    """Fetch ONE sketch element — the chained-sync fence (PERF.md §7a)."""
    return np.asarray(wm.sketches.hll.ravel()[:1])


def run(n_dev: int, per_dev: int, iters: int, fold_mode: str = "full") -> dict:
    mesh = make_mesh(n_dev, n_hosts=2 if n_dev >= 2 else 1)
    cfg = ShardedConfig(
        capacity_per_device=1 << 12,
        num_services=256,
        hll_precision=10,
        hist=LogHistSpec(bins=256, vmin=1.0, gamma=1.08),
        batch_unique_cap=1 << 13,
        fold_mode=fold_mode,
    )
    pipe = ShardedPipeline(mesh, cfg)
    wm = ShardedWindowManager(pipe)
    batch = per_dev * n_dev
    gen = SyntheticFlowGen(num_tuples=10_000, seed=4)
    t0s = 1_700_000_000

    # warm every compile path (step, fold, window_close, flush_range)
    for wt in (t0s, t0s + 60, t0s + 61, t0s + 65):
        fb = gen.flow_batch(batch, wt)
        wm.ingest(fb.tags, fb.meters, fb.valid)

    # one measured fetch to subtract from every chained window (§7a)
    _sync(wm)
    t0 = time.perf_counter()
    _sync(wm)
    fetch_base = time.perf_counter() - t0

    # steady ingest (one window, no closes inside the timed loop)
    batches = [gen.flow_batch(batch, t0s + 70) for _ in range(iters)]
    _sync(wm)
    t0 = time.perf_counter()
    for fb in batches:
        wm.ingest(fb.tags, fb.meters, fb.valid)
    _sync(wm)
    ingest_s = max(time.perf_counter() - t0 - fetch_base, 1e-9)
    ingest_rate = batch * iters / ingest_s

    # windowed cadence: every iteration advances time by one interval,
    # closing one window through the fused batched drain (flush_range)
    wbatches = [gen.flow_batch(batch, t0s + 80 + i) for i in range(iters)]
    _sync(wm)
    t0 = time.perf_counter()
    docs = 0
    for fb in wbatches:
        docs += sum(d.size for d in wm.ingest(fb.tags, fb.meters, fb.valid))
    _sync(wm)
    windowed_s = max(time.perf_counter() - t0 - fetch_base, 1e-9)
    windowed_rate = batch * iters / windowed_s
    # per-advance drain overhead = windowed minus steady, per iteration
    drain_ms = max(windowed_s - ingest_s, 0.0) / iters * 1e3

    # collective close alone: psum/pmax merges over the mesh axes
    t0 = time.perf_counter()
    closes = 4
    for _ in range(closes):
        wm.sketches, _gv, _pod = pipe.window_close(wm.sketches)
    _sync(wm)
    close_ms = (time.perf_counter() - t0 - fetch_base) / closes * 1e3

    row = {
        "n_devices": n_dev,
        "fold_mode": fold_mode,
        "per_device_batch": per_dev,
        "ingest_rec_s": round(ingest_rate, 1),
        "windowed_rec_s": round(windowed_rate, 1),
        "windowed_docs": docs,
        "drain_ms": round(drain_ms, 3),
        "close_ms": round(close_ms, 3),
        "fetch_base_ms": round(fetch_base * 1e3, 3),
    }
    try:  # stage attribution snapshot (ISSUE 3); tolerate its absence
        row["telemetry"] = wm.telemetry()
    except Exception as e:
        row["telemetry"] = None
        row["telemetry_error"] = repr(e)
    return row


# ---------------------------------------------------------------------------
# multi-process recipe (ISSUE 14)


def _proc_body(spec: dict) -> None:
    """One host of an N-process deployment (subprocess entry): real
    `jax.distributed` bring-up at N>1, one shard group, the §14
    feeder-shaped workload against it, one JSON result file."""
    import time as _time

    from deepflow_tpu.feeder import FeederConfig, encode_flowbatch_frames
    from deepflow_tpu.ingest.queues import PyOverwriteQueue
    from deepflow_tpu.parallel.topology import MeshTopology
    from deepflow_tpu.parallel.sharded import ShardedWindowManager

    nproc = spec["num_processes"]
    pid = spec["process_id"]
    t_init = _time.perf_counter()
    if nproc > 1:
        topo = MeshTopology.distributed(
            spec["coordinator"], nproc, pid,
            n_groups=nproc, devices_per_group=1,
        )
    else:
        topo = MeshTopology.single(n_groups=1, devices_per_group=1)
    init_s = _time.perf_counter() - t_init
    group = topo.owned_groups()[0]

    cfg = ShardedConfig(
        capacity_per_device=1 << 13,
        num_services=64,
        hll_precision=8,
        hist=LogHistSpec(bins=128, vmin=1.0, gamma=1.1),
    )
    wm = ShardedWindowManager(ShardedPipeline(topo, cfg, shard_group=group))
    queues = [PyOverwriteQueue(1 << 12) for _ in range(2)]
    buckets = (512, 1024, 2048)
    feeder = wm.make_feeder(
        queues, buckets, FeederConfig(frames_per_queue=16)
    )

    iters = spec["iters"]
    t0s = 1_700_000_000
    gen = SyntheticFlowGen(num_tuples=2000, seed=100 + pid)
    # pre-encode every step's frames (the probe times fan-in + decode +
    # coalesce + dispatch + windowed drains, not the generator); time
    # advances every 4 steps so windows close through the fused drain
    sizes = [buckets[i % len(buckets)] - (31 * i) % 128 for i in range(iters)]
    steps = [
        encode_flowbatch_frames(
            gen.flow_batch(n, t0s + 10 + i // 4),
            agent_id=pid * 64 + i, max_rows_per_frame=512,
        )
        for i, n in enumerate(sizes)
    ]
    # warm every bucket's compile path
    for b in buckets:
        for fr in encode_flowbatch_frames(
            gen.flow_batch(b, t0s), max_rows_per_frame=512
        ):
            queues[0].put(fr)
        feeder.pump()

    f0 = feeder.get_counters()
    docs = 0
    start = _time.perf_counter()
    for i, frames in enumerate(steps):
        for j, fr in enumerate(frames):
            queues[j % len(queues)].put(fr)
        docs += sum(d.size for d in feeder.pump())
    docs += sum(d.size for d in wm.drain())
    elapsed = _time.perf_counter() - start
    f1 = feeder.get_counters()
    records = f1["records_out"] - f0["records_out"]
    res = {
        "process_id": pid,
        "records": int(records),
        "elapsed_s": round(elapsed, 4),
        "rec_s": round(records / max(elapsed, 1e-9), 1),
        "init_s": round(init_s, 3),
        "flushed_docs": int(docs),
        "host_fetches": wm.get_counters()["host_fetches"],
    }
    from pathlib import Path

    from deepflow_tpu.parallel.hostproc import exit_after_barrier

    Path(spec["out"]).write_text(json.dumps(res))
    # shared done-file exit barrier (parallel/hostproc.py): process 0
    # hosts the coordination service and must outlive its peers; skip
    # the atexit shutdown barrier (results are already durable)
    exit_after_barrier(Path(spec["out"]).parent, pid, nproc)


def _spawn_proc_row(nproc: int, iters: int) -> dict:
    """Spawn nproc clean-env hosts, aggregate their rates."""
    import subprocess
    import tempfile
    from pathlib import Path

    from deepflow_tpu.parallel.topology import free_coordinator_port

    from deepflow_tpu.parallel.hostproc import clean_cpu_env

    d = Path(tempfile.mkdtemp(prefix=f"meshprocs{nproc}-"))
    coord = f"127.0.0.1:{free_coordinator_port()}"
    here = os.path.abspath(__file__)
    procs = []
    for pid in range(nproc):
        spec = {
            "num_processes": nproc, "process_id": pid,
            "coordinator": coord, "iters": iters,
            "out": str(d / f"res.p{pid}.json"),
        }
        procs.append(subprocess.Popen(
            [sys.executable, here, "--mesh-proc", json.dumps(spec)],
            env=clean_cpu_env(1), stdout=subprocess.DEVNULL,
            stderr=subprocess.PIPE, text=True,
        ))
    per_host = []
    try:
        for pid, p in enumerate(procs):
            try:
                _out, err = p.communicate(timeout=900)
            except subprocess.TimeoutExpired:
                p.kill()
                _out, err = p.communicate()
                raise RuntimeError(
                    f"mesh proc {pid}/{nproc} timed out:\n" + err[-2000:]
                )
            if p.returncode != 0:
                raise RuntimeError(
                    f"mesh proc {pid}/{nproc} rc={p.returncode}:\n"
                    + err[-2000:]
                )
            per_host.append(
                json.loads((d / f"res.p{pid}.json").read_text())
            )
    except Exception:
        # never leak live jax.distributed children (a wedged process 0
        # would also keep the coordinator port bound for the next row)
        for p in procs:
            if p.poll() is None:
                p.kill()
        raise
    agg = round(sum(r["rec_s"] for r in per_host), 1)
    return {
        "n_processes": nproc,
        "aggregate_rec_s": agg,
        "per_host_rec_s": [r["rec_s"] for r in per_host],
        "records": sum(r["records"] for r in per_host),
        "init_s_max": max(r["init_s"] for r in per_host),
        "host_fetches": [r["host_fetches"] for r in per_host],
    }


def run_procs(proc_counts: list[int], iters: int,
              rows: list[dict] | None = None) -> list[dict]:
    """Appends each completed row into `rows` AS IT LANDS, so a later
    process count's failure still leaves the finished rows for the
    partial record (the bench.py contract)."""
    rows = [] if rows is None else rows
    base = None
    for n in proc_counts:
        row = _spawn_proc_row(n, iters)
        if base is None and row["n_processes"] == 1:
            base = row["aggregate_rec_s"]
        if base:
            row["scale_vs_1proc"] = round(row["aggregate_rec_s"] / base, 2)
        rows.append(row)
    return rows


# ---------------------------------------------------------------------------
# rebalance-pause recipe (ISSUE 15)


def _rebalance_row(preload_steps: int, iters: int) -> dict:
    """One pause measurement at one state size, in a scratch dir that
    is removed afterward (the large-preload checkpoints are exactly
    the rows the state sweep makes big — repeated runs must not
    accumulate them in /tmp)."""
    import shutil
    import tempfile
    from pathlib import Path

    d = Path(tempfile.mkdtemp(prefix="meshreb-"))
    try:
        return _rebalance_row_in(preload_steps, iters, d)
    finally:
        shutil.rmtree(d, ignore_errors=True)


def _rebalance_row_in(preload_steps: int, iters: int, d) -> dict:
    """One pause measurement at one state size. Both topology views
    live in THIS process (MeshTopology.standalone — the protocol is
    control-plane only, so the pause does not depend on which process
    hosts which half), which keeps the row a protocol cost, not a
    process-spawn cost."""

    from deepflow_tpu.aggregator.checkpoint import save_sharded_state
    from deepflow_tpu.feeder import FeederConfig, encode_flowbatch_frames
    from deepflow_tpu.ingest.queues import PyOverwriteQueue
    from deepflow_tpu.parallel.rebalance import GroupRebalancer
    from deepflow_tpu.parallel.topology import MeshTopology

    group, old_pid, new_pid = 1, 1, 0
    cfg = ShardedConfig(
        capacity_per_device=1 << 13,
        num_services=64,
        hll_precision=8,
        hist=LogHistSpec(bins=128, vmin=1.0, gamma=1.1),
    )
    buckets = (512, 1024, 2048)
    t0s = 1_700_000_000
    gen = SyntheticFlowGen(num_tuples=2000, seed=41)
    ckpt = d / "handover.ckpt"

    def build(pid, topology=None):
        topo = topology if topology is not None else MeshTopology.standalone(
            pid, 2, n_groups=2, devices_per_group=1
        )
        wm = ShardedWindowManager(
            ShardedPipeline(topo, cfg, shard_group=group)
        )
        queues = [PyOverwriteQueue(1 << 12)]
        jdir = d / f"p{pid}"
        jdir.mkdir(exist_ok=True)
        feeder = wm.make_feeder(
            queues, buckets, FeederConfig(frames_per_queue=16),
            journal_dir=jdir,
        )
        return topo, wm, queues, feeder

    def step(queues, feeder, i):
        n = buckets[i % len(buckets)] - (31 * i) % 128
        for fr in encode_flowbatch_frames(
            gen.flow_batch(n, t0s + 10 + i // 4),
            agent_id=i, max_rows_per_frame=512,
        ):
            queues[0].put(fr)
        feeder.pump()
        return n

    old_topo, wm_old, queues_old, feeder_old = build(old_pid)
    # warm compiles, then preload to the target state size
    records = 0
    for i in range(preload_steps):
        records += step(queues_old, feeder_old, i)
    # steady cadence before the handover
    t0 = time.perf_counter()
    pre_records = sum(
        step(queues_old, feeder_old, preload_steps + i)
        for i in range(iters)
    )
    pre_s = time.perf_counter() - t0
    pre_step_s = pre_s / iters
    records += pre_records
    # the group state the checkpoint actually captures: everything fed
    # BEFORE the handover (recovery/post traffic is measurement-only)
    records_at_handover = records

    # -- the pause: release on the old owner ... -------------------------
    reb_old = GroupRebalancer(old_topo)
    plan = reb_old.plan(group, new_pid)
    t_pause = time.perf_counter()
    reb_old.release(
        plan, feeder=feeder_old,
        save=lambda extra: save_sharded_state(
            wm_old, ckpt, extra_meta=extra
        ),
    )
    release_ms = (time.perf_counter() - t_pause) * 1e3
    # -- ... adopt on the new owner --------------------------------------
    reb_new = GroupRebalancer(
        MeshTopology.standalone(new_pid, 2, n_groups=2, devices_per_group=1)
    )
    plan2 = reb_new.plan(group, new_pid)
    reb_new.claim(plan2)
    t1 = time.perf_counter()
    _topo, wm_new, queues_new, feeder_new = build(
        new_pid, topology=plan2.topology
    )
    build_ms = (time.perf_counter() - t1) * 1e3
    t1 = time.perf_counter()
    reb_new.adopt(plan2, swm=wm_new, ckpt_path=str(ckpt))
    restore_ms = (time.perf_counter() - t1) * 1e3
    pause_ms = (time.perf_counter() - t_pause) * 1e3

    # recovery: the first pump pays the fresh manager's compiles; walk
    # the cadence until a step lands back inside 1.5× the pre-handover
    # steady step
    t1 = time.perf_counter()
    records += step(queues_new, feeder_new, preload_steps + iters)
    first_pump_ms = (time.perf_counter() - t1) * 1e3
    recovery_steps = 1
    t_rec = time.perf_counter()
    for i in range(1, 4 * iters):
        t1 = time.perf_counter()
        records += step(queues_new, feeder_new, preload_steps + iters + i)
        recovery_steps += 1
        if time.perf_counter() - t1 <= 1.5 * pre_step_s:
            break
    recovery_ms = first_pump_ms + (time.perf_counter() - t_rec) * 1e3
    t0 = time.perf_counter()
    post_records = sum(
        step(queues_new, feeder_new, preload_steps + 5 * iters + i)
        for i in range(iters)
    )
    post_s = time.perf_counter() - t0
    return {
        "preload_steps": preload_steps,
        "records_at_handover": int(records_at_handover),
        "ckpt_bytes": int(os.path.getsize(ckpt)),
        "pause_ms": round(pause_ms, 2),
        "release_ms": round(release_ms, 2),
        "build_ms": round(build_ms, 2),
        "restore_ms": round(restore_ms, 2),
        "first_pump_ms": round(first_pump_ms, 2),
        "recovery_ms": round(recovery_ms, 2),
        "recovery_steps": recovery_steps,
        "pre_rec_s": round(pre_records / max(pre_s, 1e-9), 1),
        "post_rec_s": round(post_records / max(post_s, 1e-9), 1),
    }


def run_rebalance(preloads: list[int], iters: int,
                  rows: list[dict] | None = None) -> list[dict]:
    rows = [] if rows is None else rows
    for p in preloads:
        rows.append(_rebalance_row(p, iters))
    return rows


def main():
    reb_env = os.environ.get("MESH_REBALANCE", "")
    if reb_env:
        preloads = [
            int(p) for p in os.environ.get(
                "MESH_REBALANCE_PRELOADS", "8,32"
            ).split(",") if p
        ]
        iters = int(os.environ.get("MESHBENCH_ITERS", 24))
        rows = []
        try:
            run_rebalance(preloads, iters, rows)
            print(json.dumps({"rebalance_rows": rows,
                              "device": device_identity()}), flush=True)
        except Exception as e:  # parseable partial, never a traceback
            print(
                json.dumps({
                    "rebalance_rows": rows, "partial": True,
                    "error": repr(e),
                }),
                flush=True,
            )
        return
    proc_env = os.environ.get("MESH_PROCS", "")
    if proc_env:
        proc_counts = [int(p) for p in proc_env.split(",") if p]
        iters = int(os.environ.get("MESHBENCH_ITERS", 48))
        rows = []
        try:
            run_procs(proc_counts, iters, rows)
            print(json.dumps({"proc_rows": rows,
                              "device": device_identity()}), flush=True)
        except Exception as e:  # parseable partial, never a traceback
            print(
                json.dumps(
                    {"proc_rows": rows, "partial": True, "error": repr(e)}
                ),
                flush=True,
            )
        return
    per_dev = int(os.environ.get("MESH_PER_DEV", 1 << 13))
    iters = int(os.environ.get("MESH_ITERS", 8))
    # fold-mode A/B (ISSUE 5): the windowed cadence's drain_ms is what
    # the incremental merge-fold attacks — emit before/after rows
    modes = [
        m for m in os.environ.get("MESH_FOLD_MODES", "full,merge").split(",") if m
    ]
    devices = [
        int(d) for d in os.environ.get("MESH_DEVICES", "1,2,4,8").split(",") if d
    ]
    rows = []
    try:
        for mode in modes:
            for n in devices:
                rows.append(run(n, per_dev, iters, fold_mode=mode))
        print(json.dumps({"rows": rows, "device": device_identity()}),
              flush=True)
    except Exception as e:  # parseable partial record, never a traceback
        print(
            json.dumps({"rows": rows, "partial": True, "error": repr(e)}),
            flush=True,
        )


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--mesh-proc":
        _proc_body(json.loads(sys.argv[2]))
    else:
        main()
