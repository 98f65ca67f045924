"""HyperLogLog — per-group cardinality on device.

The reference aggregates exactly (no sketches anywhere in server/ or
agent/ — SURVEY §0); HLL is this framework's addition for per-service
distinct counts (BASELINE config 3). Design for TPU:

  * state is a dense `[num_groups, m]` int32 register plane
    (m = 2^precision). Updates are one `scatter-max`; merges are
    elementwise `max`, so cross-chip merge is a single `pmax` over the
    mesh axis — no host round-trip.
  * rho (leading-zero rank) is computed from the hash's hi lane via
    floor(log2): exact, because only the top set bit matters.

precision=14 → 16384 registers/group → ~0.81% standard error, meeting the
<1% north-star bound.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp


def hll_init(num_groups: int, precision: int = 14) -> jnp.ndarray:
    return jnp.zeros((num_groups, 1 << precision), dtype=jnp.int32)


def _clz32(x: jnp.ndarray) -> jnp.ndarray:
    """Count leading zeros of u32, exactly, via branchless binary search
    (float log2 rounds up near powers of two, which would bias rho low)."""
    x = x.astype(jnp.uint32)
    zero_in = x == 0
    n = jnp.zeros(x.shape, dtype=jnp.int32)
    for s in (16, 8, 4, 2, 1):
        has_s_zeros = x < jnp.uint32(1 << (32 - s))
        n = jnp.where(has_s_zeros, n + s, n)
        x = jnp.where(has_s_zeros, x << jnp.uint32(s), x)
    return jnp.where(zero_in, jnp.int32(32), n)


@partial(jax.jit, donate_argnums=(0,))
def hll_update(state: jnp.ndarray, group_ids, hash_hi, hash_lo, valid) -> jnp.ndarray:
    """Scatter-max one batch of observations.

    group_ids: [N] i32 (rows in state); hash_hi/lo: [N] u32 fingerprint of
    the *distinct-counted entity* (e.g. client ip); valid: [N] bool.
    """
    m = state.shape[1]
    p = int(m).bit_length() - 1
    reg = (hash_lo & jnp.uint32(m - 1)).astype(jnp.int32)
    rho = (_clz32(hash_hi) + 1).astype(jnp.int32)  # 1..33
    gid = jnp.where(valid, group_ids, state.shape[0])  # OOB rows dropped
    return state.at[gid, reg].max(rho, mode="drop")


# rho is the leading-zero rank of a 32-bit hash word plus one, whatever
# the precision: a register holds 0 (never hit) … HLL_Q + 1.
HLL_Q = 32


def _sigma(x: jnp.ndarray) -> jnp.ndarray:
    """Ertl's sigma(x) = x + sum_k x^(2^k) 2^(k-1), elementwise: the
    share of empty registers' contribution; sigma(1) is infinite."""
    one = x == 1.0
    x = jnp.where(one, 0.0, x)

    def body(c):
        x, y, z, _ = c
        x = x * x
        return x, y + y, z + x * y, z

    _, _, z, _ = jax.lax.while_loop(
        lambda c: jnp.any(c[2] != c[3]), body, (x, jnp.ones_like(x), x, x - 1.0)
    )
    return jnp.where(one, jnp.inf, z)


def _tau(x: jnp.ndarray) -> jnp.ndarray:
    """Ertl's tau(x), elementwise: the share of saturated registers'
    contribution; tau(0) = tau(1) = 0."""
    flat = (x == 0.0) | (x == 1.0)
    x = jnp.where(flat, 0.25, x)

    def body(c):
        x, y, z, _ = c
        x = jnp.sqrt(x)
        y = 0.5 * y
        return x, y, z - (1.0 - x) * (1.0 - x) * y, z

    _, _, z, _ = jax.lax.while_loop(
        lambda c: jnp.any(c[2] != c[3]), body,
        (x, jnp.ones_like(x), 1.0 - x, 2.0 - x),
    )
    return jnp.where(flat, 0.0, z / 3.0)


@jax.jit
def hll_estimate(state: jnp.ndarray) -> jnp.ndarray:
    """[num_groups] cardinality estimates: Ertl's improved raw estimator
    (O. Ertl, "New cardinality estimation algorithms for HyperLogLog
    sketches", arXiv:1702.01284, Algorithm 6). It reads the histogram
    `C[k]` of register values and needs no empirical table and no switch
    between estimators, so it has no bias bump where the classic
    estimator left linear counting at 2.5 m (up to +2.5% in the mean
    near n = 41,000 at p = 14). An empty row reads 0."""
    m = state.shape[1]
    q = HLL_Q
    c = [jnp.sum((state == k).astype(jnp.float32), axis=1) for k in range(q + 2)]
    z = m * _tau(1.0 - c[q + 1] / m)
    for k in range(q, 0, -1):
        z = 0.5 * (z + c[k])
    z = z + m * _sigma(c[0] / m)
    return (m * m / (2.0 * math.log(2.0))) / z


def hll_merge(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Register-wise max — associative/commutative, safe under psum-style
    tree merges (`lax.pmax` over a mesh axis does this in-network)."""
    return jnp.maximum(a, b)


def hll_estimate_np(state) -> "np.ndarray":
    """Host-side estimate over a fetched register plane (np in/out) —
    the same estimator as `hll_estimate` in float64, for query paths
    that must not touch the device (sketchplane.WindowSketchBlock)."""
    import numpy as np

    state = np.asarray(state)
    m = state.shape[1]
    q = HLL_Q
    # C[k]: registers equal to k, k = 0 … q + 1, a row
    c = np.stack(
        [np.count_nonzero(state == k, axis=1) for k in range(q + 2)], axis=1
    ).astype(np.float64)
    z = m * _tau_np(1.0 - c[:, q + 1] / m)
    for k in range(q, 0, -1):
        z = 0.5 * (z + c[:, k])
    with np.errstate(divide="ignore"):
        z = z + m * _sigma_np(c[:, 0] / m)
        return (m * m / (2.0 * math.log(2.0))) / z


def _sigma_np(x):
    import numpy as np

    one = x == 1.0
    x = np.where(one, 0.0, x)
    y, z = np.ones_like(x), x.copy()
    while True:
        x = x * x
        z_new = z + x * y
        y = y + y
        if np.array_equal(z_new, z):
            return np.where(one, np.inf, z)
        z = z_new


def _tau_np(x):
    import numpy as np

    flat = (x == 0.0) | (x == 1.0)
    x = np.where(flat, 0.25, x)
    y, z = np.ones_like(x), 1.0 - x
    while True:
        x = np.sqrt(x)
        y = 0.5 * y
        z_new = z - (1.0 - x) ** 2 * y
        if np.array_equal(z_new, z):
            return np.where(flat, 0.0, z / 3.0)
        z = z_new


clz32 = _clz32  # per-register rank helper, shared with the window plane


# ---------------------------------------------------------------------------
# pooled sub-sketch form (ISSUE 20). A compact pool slot keeps the FULL
# m registers — rho is 1..33, so int8 holds a register exactly and the
# compact HLL is bit-identical to the wide plane (promotion is a cast,
# merge stays register max). Density comes from the 4× narrower dtype;
# the packed-u32 form below is the wire/pending-block layout (4
# registers per word, little-endian byte order).


def hll_pack_registers(regs, xp=jnp):
    """[..., m] i8/i32 registers → [..., m//4] u32 words (4 per word,
    byte 0 = register 0). m must be divisible by 4 (precision ≥ 2)."""
    r = xp.asarray(regs).astype(xp.uint32) & xp.uint32(0xFF)
    b = r.reshape(r.shape[:-1] + (r.shape[-1] // 4, 4))
    return (
        b[..., 0]
        | (b[..., 1] << xp.uint32(8))
        | (b[..., 2] << xp.uint32(16))
        | (b[..., 3] << xp.uint32(24))
    )


def hll_unpack_registers_np(words, m: int):
    """Host inverse of `hll_pack_registers`: [..., m//4] u32 → [..., m]
    i32 registers (values 0..33 — no sign handling needed)."""
    import numpy as np

    w = np.asarray(words, dtype=np.uint32)
    out = np.empty(w.shape[:-1] + (m,), dtype=np.int32)
    b = out.reshape(w.shape[:-1] + (m // 4, 4))
    b[..., 0] = w & np.uint32(0xFF)
    b[..., 1] = (w >> np.uint32(8)) & np.uint32(0xFF)
    b[..., 2] = (w >> np.uint32(16)) & np.uint32(0xFF)
    b[..., 3] = (w >> np.uint32(24)) & np.uint32(0xFF)
    return out
