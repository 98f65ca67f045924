#!/usr/bin/env python
"""Round-5 append-stage bisection — run ON CHIP before any rewrite.

The r4 verdict: the append's per-raw-row linear work (~45 ns/record at
BATCH=2M) is the design floor. This harness bisects the append into
cumulative prefixes of the real pipeline graph and times each with the
chained-sync method (PERF.md §6: carry a scalar through K iterations,
one host fetch at the end), so successive deltas attribute time to:

  A  stack 25 tag cols + fingerprint64_t + slot
  B  + lax.sort((slot, hi, lo, iota))
  C  + head flags / segment-id cumsum
  D  + meter row-gather [N, 62] via perm
  E  + full-width segment_sum (num_segments=CAPU)
  F  + full-width segment_max
  G  = full batch_prereduce (adds head positions + tag gathers)
  H  = full append (prereduce + fanout + key fingerprint + accum write)

r6 variants (the levers that replaced D/E/F and part of A):
  2  A with the fingerprint folding dict columns directly (no stack)
  3  A with the PACKED-word fingerprint (datamodel/code.py plans)
  q  C + standalone row-gather (D) + the Pallas suffix reduce (the
     shipped shape; the r6 in-kernel gather, stage p, was refused by
     the chip's compiler and deleted in PR 22)

G/H always time the CURRENT production graph (packed fingerprint and,
on TPU / forced pallas, the Pallas reduce); compare 3 vs A on-chip.

Usage: python bench/microbench_r5.py [--batch 2097152] [--capu 32768]
                                     [--stages abcdefgh23q]
Copy results into PERF.md.
"""

from __future__ import annotations

import argparse
import sys
import time
from functools import partial

sys.path.insert(0, ".")

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from deepflow_tpu.aggregator.fanout import FANOUT_LANES, FanoutConfig
from deepflow_tpu.aggregator.pipeline import batch_prereduce, make_ingest_step
from deepflow_tpu.aggregator.stash import accum_init, stash_init
from deepflow_tpu.datamodel.schema import FLOW_METER, TAG_SCHEMA
from deepflow_tpu.ingest.replay import SyntheticFlowGen
from deepflow_tpu.ops.hashing import fingerprint64_t

SUM_COLS = np.nonzero(FLOW_METER.sum_mask)[0].astype(np.int32)
MAX_COLS = np.nonzero(FLOW_METER.max_mask)[0].astype(np.int32)


def _prep(tags, c):
    """Mix the carry into one tag column (bijective per iteration — the
    unique-key structure is preserved) and stack columns like the real
    pre-reduce does."""
    tags = dict(tags)
    tags["ip0_w3"] = tags["ip0_w3"] ^ c
    names = sorted(tags)
    tags_t = jnp.stack([jnp.asarray(tags[k], jnp.uint32) for k in names])
    slot = jnp.asarray(tags["timestamp"], jnp.uint32)
    return tags_t, slot


def stage_a(c, tags, meters, valid):
    tags_t, slot = _prep(tags, c)
    hi, lo = fingerprint64_t(tags_t)
    return c ^ hi[0] ^ lo[0] ^ slot[0]


def _sorted(c, tags, valid):
    tags_t, slot = _prep(tags, c)
    hi, lo = fingerprint64_t(tags_t)
    n = slot.shape[0]
    slot = jnp.where(valid, slot, jnp.uint32(0xFFFFFFFF))
    hi = jnp.where(valid, hi, jnp.uint32(0xFFFFFFFF))
    lo = jnp.where(valid, lo, jnp.uint32(0xFFFFFFFF))
    iota = jnp.arange(n, dtype=jnp.int32)
    return lax.sort((slot, hi, lo, iota), num_keys=3), tags_t


def stage_b(c, tags, meters, valid):
    (s_slot, s_hi, s_lo, perm), _ = _sorted(c, tags, valid)
    return c ^ s_hi[0] ^ s_lo[0] ^ jnp.uint32(perm[0])


def _segids(sorted_lanes):
    s_slot, s_hi, s_lo, perm = sorted_lanes
    n = s_slot.shape[0]
    head = jnp.concatenate(
        [jnp.ones((1,), bool),
         (s_slot[1:] != s_slot[:-1]) | (s_hi[1:] != s_hi[:-1]) | (s_lo[1:] != s_lo[:-1])]
    )
    live = s_slot != jnp.uint32(0xFFFFFFFF)
    seg_id = jnp.cumsum(head.astype(jnp.int32)) - 1
    seg_id = jnp.where(live, seg_id, n)
    num_seg = jnp.sum((head & live).astype(jnp.int32))
    return seg_id, num_seg


def stage_c(c, tags, meters, valid):
    lanes, _ = _sorted(c, tags, valid)
    seg_id, num_seg = _segids(lanes)
    return c ^ jnp.uint32(num_seg) ^ jnp.uint32(seg_id[-1])


def stage_d(c, tags, meters, valid):
    lanes, _ = _sorted(c, tags, valid)
    seg_id, num_seg = _segids(lanes)
    rows = jnp.take(meters, lanes[3], axis=0)  # [N, M]
    return c ^ jnp.uint32(num_seg) ^ rows[0, 0].astype(jnp.uint32)


def _stage_ef(c, tags, meters, valid, capu, with_max):
    lanes, _ = _sorted(c, tags, valid)
    seg_id, num_seg = _segids(lanes)
    rows = jnp.take(meters, lanes[3], axis=0)
    ps = jax.ops.segment_sum(rows, seg_id, num_segments=capu, indices_are_sorted=True)
    out = c ^ ps[0, 0].astype(jnp.uint32)
    if with_max:
        pm = jax.ops.segment_max(rows, seg_id, num_segments=capu, indices_are_sorted=True)
        out = out ^ pm[0, 0].astype(jnp.uint32)
    return out ^ jnp.uint32(num_seg)


def stage_v1(c, tags, meters, valid, capu):
    """Like F but segment_max over ONLY the 9 max-semantic lanes,
    gathered as a separate narrow [N, 9] matrix."""
    lanes, tags_t = _sorted(c, tags, valid)
    seg_id, num_seg = _segids(lanes)
    rows = jnp.take(meters, lanes[3], axis=0)
    ps = jax.ops.segment_sum(rows, seg_id, num_segments=capu, indices_are_sorted=True)
    max_rows = jnp.take(meters[:, MAX_COLS], lanes[3], axis=0)  # [N, 9]
    pm = jax.ops.segment_max(max_rows, seg_id, num_segments=capu, indices_are_sorted=True)
    return c ^ ps[0, 0].astype(jnp.uint32) ^ pm[0, 0].astype(jnp.uint32) ^ jnp.uint32(num_seg)


def stage_v2(c, tags, meters, valid):
    """Like A but fingerprint folds the dict columns directly — no
    [T, N] stack materialization."""
    from deepflow_tpu.ops.hashing import SEED_HI, SEED_LO, _fold

    tags = dict(tags)
    tags["ip0_w3"] = tags["ip0_w3"] ^ c
    names = sorted(tags)
    cols = [jnp.asarray(tags[k], jnp.uint32) for k in names]
    hi = _fold(cols, SEED_HI, jnp)
    lo = _fold(cols, SEED_LO, jnp)
    slot = jnp.asarray(tags["timestamp"], jnp.uint32)
    return c ^ hi[0] ^ lo[0] ^ slot[0]


def stage_v3(c, tags, meters, valid):
    """Like A but with the r6 packed-word fingerprint: bin-packed u32
    key words built once, both seeds fold ~23 words instead of 37."""
    from deepflow_tpu.datamodel.code import RAW_TAG_PACK, pack_tag_words
    from deepflow_tpu.ops.hashing import fingerprint64_words

    tags = dict(tags)
    tags["ip0_w3"] = tags["ip0_w3"] ^ c
    hi, lo = fingerprint64_words(pack_tag_words(tags, RAW_TAG_PACK, jnp))
    slot = jnp.asarray(tags["timestamp"], jnp.uint32)
    return c ^ hi[0] ^ lo[0] ^ slot[0]


def _stage_pallas(c, tags, meters, valid, capu):
    """C + D's row gather + the Pallas suffix reduce."""
    from deepflow_tpu.ops.segreduce_pallas import sorted_segment_sum_max

    lanes, _ = _sorted(c, tags, valid)
    seg_id, num_seg = _segids(lanes)
    first_pos = jnp.searchsorted(seg_id, jnp.arange(capu, dtype=jnp.int32))
    rows = jnp.take(meters, lanes[3], axis=0)
    ps, pm = sorted_segment_sum_max(rows, seg_id, capu, first_pos)
    return (c ^ ps[0, 0].astype(jnp.uint32) ^ pm[0, 0].astype(jnp.uint32)
            ^ jnp.uint32(num_seg))


def stage_g(c, tags, meters, valid, capu):
    tags = dict(tags)
    tags["ip0_w3"] = tags["ip0_w3"] ^ c
    r_tags, r_meters, r_valid, dropped = batch_prereduce(
        tags, meters, valid, 1, capu, SUM_COLS, MAX_COLS
    )
    return (c ^ r_tags["ip0_w3"][0] ^ r_meters[0, 0].astype(jnp.uint32)
            ^ jnp.uint32(dropped))


def chained(name, fn, iters=6):
    c = jnp.uint32(1)
    t0 = time.perf_counter()
    c = fn(c)
    _ = np.asarray(c)
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter(); _ = np.asarray(c)
    fetch = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(iters):
        c = fn(c)
    _ = np.asarray(c)
    ms = (time.perf_counter() - t0 - fetch) / iters * 1e3
    print(f"{name:44s} compile {compile_s:6.1f}s  steady {ms:9.2f} ms", flush=True)
    return ms


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=1 << 21)
    ap.add_argument("--capu", type=int, default=1 << 15)
    ap.add_argument("--iters", type=int, default=6)
    ap.add_argument("--stages", default="abcdefgh")
    args = ap.parse_args()
    N, CAPU = args.batch, args.capu

    gen = SyntheticFlowGen(num_tuples=10_000, seed=0)
    fb = gen.flow_batch(N, 1_700_000_000)
    tags = {k: jnp.asarray(v) for k, v in fb.tags.items()}
    meters = jnp.asarray(fb.meters)
    valid = jnp.asarray(fb.valid)
    print(f"batch={N} capu={CAPU} device={jax.devices()[0]}", flush=True)

    res = {}
    # jit wrappers hoisted ONCE — a fresh jit(lambda) per call would
    # recompile every iteration and time compiles, not kernels
    jit_a = jax.jit(stage_a)
    jit_b = jax.jit(stage_b)
    jit_c = jax.jit(stage_c)
    jit_d = jax.jit(stage_d)
    jit_e = jax.jit(partial(_stage_ef, capu=CAPU, with_max=False))
    jit_f = jax.jit(partial(_stage_ef, capu=CAPU, with_max=True))
    jit_g = jax.jit(partial(stage_g, capu=CAPU))
    jit_v1 = jax.jit(partial(stage_v1, capu=CAPU))
    jit_v2 = jax.jit(stage_v2)
    jit_v3 = jax.jit(stage_v3)
    jit_q = jax.jit(partial(_stage_pallas, capu=CAPU))
    stages = {
        "1": ("V1 narrow segment_max", lambda c: jit_v1(c, tags, meters, valid)),
        "2": ("V2 destacked fingerprint", lambda c: jit_v2(c, tags, meters, valid)),
        "3": ("V3 packed-word fingerprint", lambda c: jit_v3(c, tags, meters, valid)),
        "q": ("Q row-gather + pallas reduce", lambda c: jit_q(c, tags, meters, valid)),
        "a": ("A stack+fingerprint", lambda c: jit_a(c, tags, meters, valid)),
        "b": ("B +sort4", lambda c: jit_b(c, tags, meters, valid)),
        "c": ("C +segids", lambda c: jit_c(c, tags, meters, valid)),
        "d": ("D +meter row-gather", lambda c: jit_d(c, tags, meters, valid)),
        "e": ("E +segment_sum", lambda c: jit_e(c, tags, meters, valid)),
        "f": ("F +segment_max", lambda c: jit_f(c, tags, meters, valid)),
        "g": ("G full batch_prereduce", lambda c: jit_g(c, tags, meters, valid)),
    }
    for key, (name, fn) in stages.items():
        if key in args.stages:
            res[key] = chained(name, fn, args.iters)

    if "h" in args.stages:
        append_fn, _ = make_ingest_step(FanoutConfig(), interval=1, batch_unique_cap=CAPU)
        append = jax.jit(append_fn, donate_argnums=(0, 1))
        stride = FANOUT_LANES * CAPU
        state = stash_init(1 << 16, TAG_SCHEMA, FLOW_METER)
        acc = accum_init(2 * stride, TAG_SCHEMA, FLOW_METER)

        t0 = time.perf_counter()
        state, acc = append(state, acc, jnp.int32(0), tags, meters, valid)
        _ = np.asarray(state.dropped_overflow)
        compile_s = time.perf_counter() - t0
        t0 = time.perf_counter(); _ = np.asarray(state.dropped_overflow)
        fetch = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(args.iters):
            state, acc = append(state, acc, jnp.int32(0), tags, meters, valid)
        _ = np.asarray(state.dropped_overflow)
        ms = (time.perf_counter() - t0 - fetch) / args.iters * 1e3
        print(f"{'H full append':44s} compile {compile_s:6.1f}s  steady {ms:9.2f} ms", flush=True)
        res["h"] = ms

    order = [k for k in "abcdefgh" if k in res]
    print("\ndeltas:")
    prev = 0.0
    for k in order:
        print(f"  {k}: {res[k] - prev:+8.2f} ms  (cum {res[k]:8.2f})")
        prev = res[k]
    if "h" in res:
        print(f"\nns/record at H: {res['h'] * 1e6 / N:.1f}")


if __name__ == "__main__":
    main()
