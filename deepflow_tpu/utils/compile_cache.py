"""Where JAX's persistent compilation cache lives.

One whole-program compile of the device step costs minutes, so every
launcher (chip_smoke.py, bench.py, Server.start) keeps the
cache on. The directory is part of the cache key: it must not move.

  * `JAX_COMPILATION_CACHE_DIR` set → JAX reads it itself; nothing is
    set in code.
  * otherwise → `<checkout>/.jax_cache` (git-ignored). Never a temp
    name, a pid or a time: a directory that moves never hits.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def enable_compile_cache() -> str:
    """Turn the persistent cache on at the fixed place; returns its
    directory. Idempotent; call before the first compile."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
