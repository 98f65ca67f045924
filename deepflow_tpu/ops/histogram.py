"""Log-binned latency histograms (DDSketch-style) — the streaming
quantile path.

Per-group `[num_groups, bins]` int32 planes with geometric bin edges:
bin(v) = floor(log_gamma(v / vmin)). Updates are one scatter-add, merges
are elementwise add (`psum`-able), and quantile queries are a cumsum +
threshold search at flush time. Guaranteed relative quantile error is
(gamma-1)/(gamma+1); the default covers 1µs..~17min at ≤2% error with
1024 bins. At window close the plane can also be compressed into t-digest
centroids (ops/tdigest.py) for compact export.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class LogHistSpec:
    bins: int = 1024
    vmin: float = 1.0  # values at/below vmin land in bin 0
    gamma: float = 1.02

    @property
    def vmax(self) -> float:
        return self.vmin * self.gamma ** (self.bins - 1)

    def rel_error(self) -> float:
        return (self.gamma - 1.0) / (self.gamma + 1.0)


def loghist_init(num_groups: int, spec: LogHistSpec) -> jnp.ndarray:
    return jnp.zeros((num_groups, spec.bins), dtype=jnp.int32)


def loghist_edges(spec: LogHistSpec) -> np.ndarray:
    """[bins - 1] f32: the lower edges of bins 1 … bins-1, vmin·gamma^k
    computed in float64 and rounded once to float32. The table IS the
    binning rule: a host reference that holds the same table bins every
    value as the device does."""
    k = np.arange(1, spec.bins, dtype=np.float64)
    return (spec.vmin * np.power(spec.gamma, k)).astype(np.float32)


def loghist_bin(values: jnp.ndarray, spec: LogHistSpec) -> jnp.ndarray:
    """[N] f32 values → [N] i32 bin ids: the number of edges at or
    below the value (bin(v) = floor(log_gamma(v / vmin)) cut to
    0 … bins-1, decided by float32 comparisons against `loghist_edges`
    and by no platform's `log`: on the TPU a float32 log is some 30 ulp
    off, which moved a value near an edge into the neighbouring bin and
    made a closed block differ from the same records binned on a host,
    PR 33)."""
    v = values.astype(jnp.float32)
    edges = jnp.asarray(loghist_edges(spec))
    return jnp.sum(v[..., None] >= edges, axis=-1, dtype=jnp.int32)


@partial(jax.jit, static_argnames=("spec",), donate_argnums=(0,))
def loghist_update(state: jnp.ndarray, group_ids, values, valid, spec: LogHistSpec) -> jnp.ndarray:
    b = loghist_bin(values, spec)
    gid = jnp.where(valid, group_ids, state.shape[0])  # OOB → dropped
    return state.at[gid, b].add(1, mode="drop")


@partial(jax.jit, static_argnames=("spec", "qs"))
def loghist_quantiles(state: jnp.ndarray, spec: LogHistSpec, qs: tuple[float, ...]) -> jnp.ndarray:
    """[num_groups, len(qs)] quantile estimates (geometric bin centers)."""
    counts = state.astype(jnp.float32)
    cum = jnp.cumsum(counts, axis=1)
    total = cum[:, -1:]
    centers = spec.vmin * jnp.power(
        jnp.float32(spec.gamma), jnp.arange(spec.bins, dtype=jnp.float32) + 0.5
    )
    out = []
    for q in qs:
        target = q * total  # rank threshold per group
        idx = jnp.sum((cum < target).astype(jnp.int32), axis=1)
        idx = jnp.clip(idx, 0, spec.bins - 1)
        est = centers[idx]
        out.append(jnp.where(total[:, 0] > 0, est, 0.0))
    return jnp.stack(out, axis=1)


def loghist_merge(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return a + b


# ---------------------------------------------------------------------------
# pooled sub-sketch form (ISSUE 20): a compact pool slot keeps
# bins//factor geometric bins — equivalent to the same spec with
# gamma^factor, so the compact relative-error bound widens to
# (gamma^f - 1)/(gamma^f + 1). The compact bin derives from the ALREADY
# computed wide bin by integer division (exact — no second float
# binning that could drift off by one), and expansion re-centers each
# compact bin at the middle wide bin it covers.


def loghist_coarsen_bin(wide_bin, factor: int, xp=jnp):
    """[N] wide bin ids → compact bin ids (factor wide bins per compact
    bin). Exact integer correspondence with `loghist_bin` at the wide
    spec."""
    return xp.asarray(wide_bin) // factor


def loghist_expand(compact, bins: int, xp=jnp):
    """[..., bins//factor] compact counts → [..., bins], each compact
    bin's mass placed at the central wide bin it covers (matches the
    geometric-center estimate `loghist_quantiles`/tdigest read)."""
    bc = compact.shape[-1]
    factor = bins // bc
    assert factor * bc == bins, (bc, bins)
    out = xp.zeros(compact.shape[:-1] + (bins,), dtype=compact.dtype)
    centers = xp.arange(bc) * factor + factor // 2
    if xp is jnp:
        return out.at[..., centers].set(compact)
    out[..., centers] = compact
    return out
