#!/usr/bin/env python
"""Benchmark: flow-records/sec/chip through the L4 rollup hot path.

Measures the steady-state ingest cycle on the attached accelerator,
replaying the BASELINE config-1 workload shape: synthetic
accumulated-flow batches over 10k unique 5-tuples at 1s windows.

The cycle is the production cadence (aggregator/pipeline.py): per batch
one `append` (batch-local groupby pre-reduce → fanout → packed-word
fingerprint → accumulator write), and every ACCUM_BATCHES batches one
`fold` (the amortized sort+segment reduce of [stash + accumulator]
rows). The pre-reduce (PERF.md §7) collapses each batch to its unique
raw keys BEFORE the 4-lane doc fanout — exact for any workload, and the
reason fold rows stop scaling with the dup factor. Reported records/sec
includes the full amortized cost of aggregation, not just the append.

Timing is taken around `jax.block_until_ready` on the chained state.

The program runs on the accelerator JAX finds and FAILS where there is
none: on the CPU platform, or on any backend, compile or runtime error,
it prints a record with value 0 and an `error`, and exits non-zero. A
number from a CPU run must never be read as a chip number, so every
record names the device it ran on.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline",
"device": {"platform", "kind", "count"}}.
vs_baseline is against the north-star target of 50M records/sec/chip
(BASELINE.json; the reference publishes no absolute numbers — SURVEY §6).
"""

from __future__ import annotations

import json
import os
import sys
import time

TARGET = 50e6  # records/sec/chip north star

# The fold sorts CAPACITY + ACCUM_BATCHES×4×UNIQUE_CAP rows
# (262k here); the appends sort BATCH raw rows. UNIQUE_CAP bounds
# per-batch unique keys (3x headroom over the 10k-tuple workload);
# overflow is shed and counted, never silent.
BATCH = int(os.environ.get("BENCH_BATCH", 1 << 21))  # flows per step
CAPACITY = int(os.environ.get("BENCH_CAPACITY", 1 << 16))  # stash segments
ACCUM_BATCHES = int(os.environ.get("BENCH_ACCUM_BATCHES", 2))
UNIQUE_CAP = int(os.environ.get("BENCH_UNIQUE_CAP", 1 << 15))
WARMUP_CYCLES = 1
CYCLES = int(os.environ.get("BENCH_CYCLES", 8))


def _record(value: float, **extra) -> str:
    return json.dumps(
        {
            "metric": "flow_records_per_sec_per_chip",
            "value": round(value, 1),
            "unit": "records/s",
            "vs_baseline": round(value / TARGET, 4),
            **extra,
        }
    )


def _run() -> float:
    import jax
    import jax.numpy as jnp

    from deepflow_tpu.aggregator.fanout import FANOUT_LANES, FanoutConfig
    from deepflow_tpu.aggregator.pipeline import make_ingest_step
    from deepflow_tpu.aggregator.stash import accum_init, stash_init
    from deepflow_tpu.datamodel.schema import FLOW_METER, TAG_SCHEMA
    from deepflow_tpu.ingest.replay import SyntheticFlowGen

    gen = SyntheticFlowGen(num_tuples=10_000, seed=0)
    fb = gen.flow_batch(BATCH, 1_700_000_000)
    tags = {k: jnp.asarray(v) for k, v in fb.tags.items()}
    meters = jnp.asarray(fb.meters)
    valid = jnp.asarray(fb.valid)

    append_fn, fold_fn = make_ingest_step(
        FanoutConfig(), interval=1, batch_unique_cap=UNIQUE_CAP or None
    )
    append = jax.jit(append_fn, donate_argnums=(0, 1))
    fold = jax.jit(fold_fn, donate_argnums=(0, 1))

    stride = FANOUT_LANES * (UNIQUE_CAP or BATCH)
    state = stash_init(CAPACITY, TAG_SCHEMA, FLOW_METER)
    acc = accum_init(ACCUM_BATCHES * stride, TAG_SCHEMA, FLOW_METER)

    def cycle(state, acc):
        for k in range(ACCUM_BATCHES):
            state, acc = append(state, acc, jnp.int32(k * stride), tags, meters, valid)
        return fold(state, acc)

    for _ in range(WARMUP_CYCLES):
        state, acc = cycle(state, acc)
    jax.block_until_ready(state)  # compile + warmup done

    t0 = time.perf_counter()
    for _ in range(CYCLES):
        state, acc = cycle(state, acc)
    jax.block_until_ready(state)
    dt = time.perf_counter() - t0

    return BATCH * ACCUM_BATCHES * CYCLES / dt


def main() -> int:
    device = None
    try:
        from deepflow_tpu.utils.compile_cache import enable_compile_cache
        from deepflow_tpu.utils.provenance import device_identity

        enable_compile_cache()
        device = device_identity()
        if device["platform"] == "cpu":
            raise RuntimeError(
                "bench.py measures the accelerator and JAX found only the "
                "CPU platform; run it on the chip"
            )
        rate = _run()
    except Exception as e:  # no accelerator, compile or runtime failure
        print(_record(0.0, error=f"{type(e).__name__}: {e}", device=device))
        return 1
    print(_record(rate, device=device))
    return 0


if __name__ == "__main__":
    sys.exit(main())
