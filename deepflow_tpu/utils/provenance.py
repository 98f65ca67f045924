"""The device a result was measured on."""

from __future__ import annotations


def device_identity() -> dict:
    """The device a result was measured on, as JAX reports it —
    bench.py's record carries it, so a CPU count is never read as a chip
    number."""
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}
