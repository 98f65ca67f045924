#!/usr/bin/env python
"""Primitive throughput on the attached chip: sort vs scatter vs gather.

Decides the stash architecture (sort/segment vs hash/scatter). Every
iteration is data-dependent on the previous one (the
measured op consumes a carry scalar), and the loop ends with a device_get
so async dispatch cannot hide execution. Run from repo root:

    python bench/microbench_kernels.py [--cpu]
"""

from __future__ import annotations

import sys
import time

if "--cpu" in sys.argv:
    import os

    os.environ["JAX_PLATFORMS"] = "cpu"

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def timeit(make_fn, iters=10, warmup=2):
    """make_fn() -> (fn, args). fn(carry, *args) -> new u32 carry scalar,
    chained so iteration i depends on i-1."""
    fn, args = make_fn()
    jfn = jax.jit(fn)
    carry = jnp.uint32(0)
    for _ in range(warmup):
        carry = jfn(carry, *args)
    _ = jax.device_get(carry)
    t0 = time.perf_counter()
    for _ in range(iters):
        carry = jfn(carry, *args)
    _ = jax.device_get(carry)
    return (time.perf_counter() - t0) / iters


def main():
    print(f"platform={jax.devices()[0].platform}", flush=True)
    rng = np.random.default_rng(0)

    def report(name, n, t):
        print(f"{name:22s} n={n:>8}: {t*1e3:8.3f} ms  ({n/t/1e6:8.1f} M rows/s)", flush=True)

    for n in (1 << 17, 1 << 19, 1 << 21):
        a = jnp.asarray(rng.integers(0, 2**32, n, dtype=np.uint32))
        b = jnp.asarray(rng.integers(0, 2**32, n, dtype=np.uint32))
        c = jnp.asarray(rng.integers(0, 2**32, n, dtype=np.uint32))

        def mk_sort3():
            def f(carry, a, b, c):
                iota = jnp.arange(a.shape[0], dtype=jnp.int32)
                o = lax.sort((a ^ carry, b, c, iota), num_keys=3)
                return o[0][0] ^ jnp.uint32(o[3][0])

            return f, (a, b, c)

        report("sort3+iota", n, timeit(mk_sort3))

        def mk_sort1():
            def f(carry, a):
                iota = jnp.arange(a.shape[0], dtype=jnp.int32)
                o = lax.sort((a ^ carry, iota), num_keys=1)
                return o[0][0] ^ jnp.uint32(o[1][0])

            return f, (a,)

        report("sort1+iota", n, timeit(mk_sort1))

    S = 1 << 16
    for r in (1 << 16, 1 << 18, 1 << 20):
        idx = jnp.asarray(rng.integers(0, S, r, dtype=np.int32))
        vals = jnp.asarray(rng.random((r, 36), dtype=np.float32))
        sid = jnp.sort(idx)

        def mk_scatter_add():
            def f(carry, ix, v):
                tbl = jnp.zeros((S, 36), jnp.float32) + carry.astype(jnp.float32)
                tbl = tbl.at[ix].add(v)
                return tbl[0, 0].astype(jnp.uint32)

            return f, (idx, vals)

        report("scatter_add 36c", r, timeit(mk_scatter_add))

        def mk_gather40():
            tbl = jnp.asarray(rng.integers(0, 2**32, (S, 40), dtype=np.uint32))

            def f(carry, tb, ix):
                g = jnp.take(tb + carry, ix, axis=0)
                return g[0, 0]

            return f, (tbl, idx)

        report("gather 40c", r, timeit(mk_gather40))

        def mk_segsum():
            def f(carry, v, s):
                out = jax.ops.segment_sum(v + carry.astype(jnp.float32), s, num_segments=S)
                return out[0, 0].astype(jnp.uint32)

            return f, (vals, sid)

        report("segsum 36c sorted", r, timeit(mk_segsum))

        def mk_segscan():
            def f(carry, v, s):
                v = v + carry.astype(jnp.float32)
                n_ = v.shape[0]
                d = 1
                while d < n_:
                    same = jnp.concatenate([jnp.zeros((d,), bool), s[d:] == s[:-d]])
                    shifted = jnp.concatenate(
                        [jnp.zeros((d, v.shape[1]), v.dtype), v[:-d]]
                    )
                    v = v + jnp.where(same[:, None], shifted, 0)
                    d *= 2
                return v[0, 0].astype(jnp.uint32)

            return f, (vals, sid)

        report("segscan-shift 36c", r, timeit(mk_segscan))

        def mk_fingerprint():
            # column-major [C, r], the layout the pipeline actually
            # fingerprints (fingerprint64_t over key rows of [T, 4N])
            from deepflow_tpu.ops.hashing import fingerprint64_t

            tmat = jnp.asarray(rng.integers(0, 2**32, (30, r), dtype=np.uint32))

            def f(carry, tm):
                hi, lo = fingerprint64_t(tm + carry)
                return hi[0] ^ lo[0]

            return f, (tmat,)

        report("fingerprint_t 30c", r, timeit(mk_fingerprint))


if __name__ == "__main__":
    sys.path.insert(0, ".")
    main()
