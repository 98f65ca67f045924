"""Bench provenance stamp (ISSUE 18 satellite).

Every bench JSON embeds the exact config it measured: git SHA (+dirty
flag), platform identity, and a snapshot of the `DEEPFLOW_*` env knobs
(plus the JAX platform pin) — so a PERF.md column is attributable to a
commit and a knob set instead of "whatever the box had that day".
"""

from __future__ import annotations


def bench_provenance() -> dict:
    import os
    import platform
    import subprocess
    import time

    sha = None
    dirty = None
    here = os.path.dirname(os.path.abspath(__file__))
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            timeout=10, cwd=here,
        ).stdout.strip() or None
        dirty = bool(subprocess.run(
            ["git", "status", "--porcelain"], capture_output=True,
            text=True, timeout=10, cwd=here,
        ).stdout.strip())
    except Exception:
        pass  # benches must run from an exported tree too
    out = {
        "git_sha": sha,
        "git_dirty": dirty,
        "time": int(time.time()),
        "platform": {
            "python": platform.python_version(),
            "system": platform.system(),
            "release": platform.release(),
            "machine": platform.machine(),
            "cpus": os.cpu_count(),
        },
        # the knob snapshot: every DEEPFLOW_* flag (shared-sort, fused
        # sketch, merge-scatter, …) plus the backend pin — the flip
        # decisions PERF.md tracks hinge on exactly these
        "env": {
            k: v for k, v in sorted(os.environ.items())
            if k.startswith("DEEPFLOW_") or k == "JAX_PLATFORMS"
        },
    }
    try:
        import jax
        import jaxlib

        out["platform"]["jax"] = jax.__version__
        out["platform"]["jaxlib"] = jaxlib.__version__
    except Exception:
        pass
    return out


def device_identity() -> dict:
    """The device a result was measured on, as JAX reports it — every
    bench record carries it, so a CPU count is never read as a chip
    number."""
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}
