"""Where this checkout keeps JAX's persistent compilation cache.

One rule for every entry point (``chip_smoke.py``, ``launch/train.py``,
``launch/serve.py``, ``benchmarks/run.py``): when
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
set here; otherwise the cache lives at ``<checkout>/.jax_cache``. The
path is fixed because it is part of the cache's key — a directory that
moves never hits.

It also counts the persistent cache's hits and misses into the
process's metrics registry (``compile/cache_hits``,
``compile/cache_misses``), from the events ``jax.monitoring`` records
on each compile; the listener is registered once per process.
"""

from __future__ import annotations

import os
import pathlib

import jax

from repro.obs import get_registry

__all__ = ["enable_compile_cache"]

_CHECKOUT = pathlib.Path(__file__).resolve().parents[2]
_COUNTED = {"/jax/compilation_cache/cache_hits": "compile/cache_hits",
            "/jax/compilation_cache/cache_misses": "compile/cache_misses"}
_listening = False


def _count_compiles(event: str, **_) -> None:
    name = _COUNTED.get(event)
    if name is not None:
        get_registry().counter(name).inc()


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    global _listening
    if not _listening:
        jax.monitoring.register_event_listener(_count_compiles)
        _listening = True
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(_CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
