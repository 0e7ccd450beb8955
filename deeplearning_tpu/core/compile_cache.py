"""Library-wide persistent XLA compile cache.

Every entry point (training CLIs, experiment loader, serving engine,
perf tools) calls ``enable_compile_cache()`` so a given step function is
compiled at most once per machine, not once per process: the cold
ViT-B/16 train-step compile is the longest single set-up cost any tool
pays, and a serialized executable makes every later invocation load it
from disk instead.

Where the cache lives:
- ``JAX_COMPILATION_CACHE_DIR`` set: JAX has already read it; this
  module leaves ``jax_compilation_cache_dir`` alone.
- otherwise: the fixed ``<checkout>/.jax_cache`` (the path is part of
  the cache key's environment, so it must not move between runs).

``DLTPU_COMPILE_CACHE=0`` (or ``off``/``none``/``false``) disables it.
"""

from __future__ import annotations

import os
from typing import Optional

_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_enabled_dir: Optional[str] = None


def enable_compile_cache() -> Optional[str]:
    """Turn JAX's persistent compilation cache on. Idempotent. Returns
    the active cache dir, or None when disabled by the environment."""
    global _enabled_dir
    if os.environ.get("DLTPU_COMPILE_CACHE", "").lower() in (
            "0", "off", "none", "false"):
        return None
    if _enabled_dir is not None:
        return _enabled_dir
    import jax
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = _DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # cache even sub-second compiles: CPU smoke runs benefit too, and
    # the min-entry-size floor would otherwise skip small executables
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _enabled_dir = cache_dir
    return _enabled_dir


def active_cache_dir() -> Optional[str]:
    """The directory enabled by ``enable_compile_cache``, if any."""
    return _enabled_dir
