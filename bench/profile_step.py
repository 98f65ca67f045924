#!/usr/bin/env python
"""Step-time breakdown for the L4 rollup hot path (feeds PERF.md).

Times each stage of the ingest step in isolation on the attached chip:
dispatch overhead, fanout, fingerprint, batch-local sort+reduce, and the
full stash fold, across batch sizes. Run from repo root:

    python bench/profile_step.py [--cpu]
"""

from __future__ import annotations

import argparse
import sys
import time

import numpy as np

if "--cpu" in sys.argv:
    import os

    os.environ["JAX_PLATFORMS"] = "cpu"

import jax
import jax.numpy as jnp

sys.path.insert(0, ".")

from deepflow_tpu.aggregator.fanout import FANOUT_LANES, FanoutConfig, fanout_l4
from deepflow_tpu.aggregator.pipeline import _KEY_COLS, _doc_fingerprint, make_ingest_step
from deepflow_tpu.aggregator.stash import accum_init, stash_init
from deepflow_tpu.datamodel.schema import FLOW_METER, TAG_SCHEMA
from deepflow_tpu.ingest.replay import SyntheticFlowGen
from deepflow_tpu.ops.hashing import fingerprint64_t
from deepflow_tpu.ops.segment import groupby_reduce


def timeit(fn, *args, iters=20, warmup=3, donate=None):
    jfn = jax.jit(fn, donate_argnums=donate) if donate else jax.jit(fn)
    out = None
    for _ in range(warmup):
        out = jfn(*args)
        if donate:
            args = (out,) + args[1:]
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = jfn(*args)
        if donate:
            args = (out,) + args[1:]
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--cpu", action="store_true")
    p.add_argument("--batches", type=int, nargs="*", default=[1 << 14, 1 << 16, 1 << 18])
    args = p.parse_args()

    print(f"platform={jax.devices()[0].platform} device={jax.devices()[0]}")
    sum_cols = np.nonzero(FLOW_METER.sum_mask)[0].astype(np.int32)
    max_cols = np.nonzero(FLOW_METER.max_mask)[0].astype(np.int32)

    for batch in args.batches:
        gen = SyntheticFlowGen(num_tuples=10_000, seed=0)
        fb = gen.flow_batch(batch, 1_700_000_000)
        tags = {k: jnp.asarray(v) for k, v in fb.tags.items()}
        meters = jnp.asarray(fb.meters)
        valid = jnp.asarray(fb.valid)
        capacity = 1 << 16

        res = {}

        # 0. dispatch floor: trivial donated state update
        state0 = jnp.zeros((capacity,), jnp.float32)
        res["dispatch_floor"] = timeit(lambda s: s + 1.0, state0, donate=(0,))

        # 1. fanout alone
        fo = FanoutConfig()
        res["fanout"] = timeit(lambda t, m, v: fanout_l4(t, m, v, fo), tags, meters, valid)

        # 2. fingerprint alone (on fanned-out tags)
        doc_tags, doc_meters, ts, doc_valid = jax.jit(
            lambda t, m, v: fanout_l4(t, m, v, fo)
        )(tags, meters, valid)
        jax.block_until_ready(doc_tags)
        key_cols = jnp.asarray(_KEY_COLS)

        def fp_raw(dt):
            # legacy raw-column fold: key row select + 32-column murmur
            km = jnp.take(dt, key_cols, axis=0)
            return fingerprint64_t(km)

        res["fingerprint_raw"] = timeit(fp_raw, doc_tags)
        # production path since r6: packed key words (PERF.md §9d)
        res["fingerprint_packed"] = timeit(_doc_fingerprint, doc_tags)

        # 3. batch-local sort+reduce ([4N] rows)
        hi, lo = jax.jit(_doc_fingerprint)(doc_tags)
        window = (ts // jnp.uint32(1)).astype(jnp.uint32)

        def local_reduce(w, h, l, dt, dm, dv):
            return groupby_reduce(w, h, l, dt, jnp.transpose(dm), dv,
                                  sum_cols, max_cols)

        res["local_sort_reduce_4N"] = timeit(
            local_reduce, window, hi, lo, doc_tags, doc_meters, doc_valid
        )

        # 3b. sort only, key lanes only ([4N])
        def sort_only(w, h, l):
            iota = jnp.arange(w.shape[0], dtype=jnp.int32)
            return jax.lax.sort((w, h, l, iota), num_keys=3)

        res["sort_keys_4N"] = timeit(sort_only, window, hi, lo)

        # 3c. sort at fold size ([4N + capacity])
        wq = jnp.concatenate([window, jnp.zeros((capacity,), jnp.uint32)])
        hq = jnp.concatenate([hi, jnp.zeros((capacity,), jnp.uint32)])
        lq = jnp.concatenate([lo, jnp.zeros((capacity,), jnp.uint32)])
        res["sort_keys_4N+cap"] = timeit(sort_only, wq, hq, lq)

        # 4. production cadence: append per batch + fold every
        # accum_batches (aggregator/pipeline.make_ingest_step).
        accum_batches = 8
        append_fn, fold_fn = make_ingest_step(FanoutConfig(), interval=1)
        append_j = jax.jit(append_fn, donate_argnums=(0, 1))
        fold_j = jax.jit(fold_fn, donate_argnums=(0, 1))
        doc_rows = FANOUT_LANES * batch
        state = stash_init(capacity, TAG_SCHEMA, FLOW_METER)
        acc = accum_init(accum_batches * doc_rows, TAG_SCHEMA, FLOW_METER)

        # warm both compiles
        state, acc = append_j(state, acc, jnp.int32(0), tags, meters, valid)
        state, acc = fold_j(state, acc)
        jax.block_until_ready(acc.slot)

        # append timed over a full ring of iterations so dispatch overlap
        # matches the cycle loop below (a single synced sample would
        # overstate it and could push fold_amortized negative)
        t0 = time.perf_counter()
        for k in range(accum_batches):
            state, acc = append_j(
                state, acc, jnp.int32(k * doc_rows), tags, meters, valid
            )
        jax.block_until_ready(acc.slot)
        res["append"] = (time.perf_counter() - t0) / accum_batches
        state, acc = fold_j(state, acc)  # reset ring for the cycle loop

        iters = 5
        t0 = time.perf_counter()
        for _ in range(iters):
            for k in range(accum_batches):
                state, acc = append_j(
                    state, acc, jnp.int32(k * doc_rows), tags, meters, valid
                )
            state, acc = fold_j(state, acc)
        jax.block_until_ready(acc.slot)
        cyc = (time.perf_counter() - t0) / iters
        res["fold_amortized"] = cyc / accum_batches - res["append"]
        res["cycle_per_batch"] = cyc / accum_batches

        print(f"\nbatch={batch} ({doc_rows} doc rows, capacity={capacity}):")
        for k, v in res.items():
            print(f"  {k:24s} {v * 1e3:8.3f} ms")
        print(
            f"  -> amortized rate: {batch / res['cycle_per_batch'] / 1e6:.2f} M flows/s"
        )


if __name__ == "__main__":
    main()
