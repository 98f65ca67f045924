"""Process-lifecycle helpers for multi-host CPU runs (ISSUE 14). For
the CPU mesh tests only: these children name the CPU platform
and never take a chip (one process holds a chip at a time, so a child
that needed it could not be started from a parent that has touched JAX).

Used by tests/mesh_harness.py, the driver that spawns real N-process
`jax.distributed` deployments. Its two tricky pieces live here:

* **clean_cpu_env** — the dryrun_multichip stance: name the CPU
  platform BEFORE any jax import in the child and pin the virtual
  device count.
* **the done-file exit barrier** — process 0 hosts the coordination
  service, so it must outlive every peer's useful work (exiting early
  FATALs them via error polling), while NO process may enter the
  jax.distributed atexit shutdown barrier once a peer has died (it
  wedges on the missing heartbeat). Each host therefore writes its
  results durably, marks done, waits for its peers' marks, and
  `os._exit`s — skipping atexit entirely.
"""

from __future__ import annotations

import os
import sys
import time
from pathlib import Path


def clean_cpu_env(device_count: int = 1) -> dict:
    """Subprocess environment with `device_count` virtual CPU devices,
    whatever platform the parent's JAX is bound to."""
    env = dict(os.environ)
    env.pop("JAX_PLATFORM_NAME", None)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [
        f for f in env.get("XLA_FLAGS", "").split()
        if "host_platform_device_count" not in f
    ]
    flags.append(f"--xla_force_host_platform_device_count={device_count}")
    env["XLA_FLAGS"] = " ".join(flags)
    return env


def mark_done(workdir, process_id: int) -> None:
    """Durably mark this host's work complete (a dying host marks
    BEFORE os._exit so peers stop waiting on it)."""
    (Path(workdir) / f"done.p{process_id}").write_text("1")


def await_peers(workdir, process_id: int, num_processes: int,
                timeout_s: float = 120.0) -> bool:
    """Block until every peer has marked done (or timeout). Returns
    True when all marks were seen."""
    others = [
        Path(workdir) / f"done.p{q}"
        for q in range(num_processes) if q != process_id
    ]
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if all(o.exists() for o in others):
            return True
        time.sleep(0.05)
    return False


def exit_after_barrier(workdir, process_id: int, num_processes: int,
                       *, rc: int = 0, timeout_s: float = 120.0) -> None:
    """mark done → wait for peers → os._exit(rc), skipping the
    jax.distributed atexit shutdown barrier (see module docstring)."""
    mark_done(workdir, process_id)
    if num_processes > 1:
        await_peers(workdir, process_id, num_processes,
                    timeout_s=timeout_s)
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)
