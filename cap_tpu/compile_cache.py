"""Persistent XLA compilation cache for cap_tpu programs.

The engines are shape-static by design (pow-2 bucket padding, fixed
chunk shapes), so across processes the same programs recompile from
scratch — on TPU a cold compile of the full mixed pipeline costs tens
of seconds. Enabling JAX's persistent compilation cache makes every
compile after the first process-lifetime one a disk hit.

Call :func:`enable` before the first jit execution (bench.py, the
tools, the fleet worker, chip_smoke.py and tests/conftest.py do).

Where the cache lives:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; this module
  sets no directory.
- otherwise: ``<checkout>/.jax_cache`` — a fixed path (the path is part
  of the cache key, so it must never move between runs), listed in
  ``.gitignore`` and ``.chiprunignore``.
"""

from __future__ import annotations

import os

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")
_enabled = False


def enable() -> str:
    """Idempotently enable the persistent compilation cache; returns
    its directory."""
    global _enabled
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    path = env or DEFAULT_DIR
    if _enabled:
        return path
    import jax

    if not env:
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _enabled = True
    return path
