"""Persistent XLA compile cache: always on, placed from outside or at one
fixed path.

The TVM argument (PAPERS.md) applied to this stack: the traced step is an
ahead-of-time compilation artifact, yet without a cache every process
restart re-pays the full XLA compile — minutes for the big train steps. JAX
ships a persistent on-disk compilation cache; this module decides where it
lives, once, at ``paddle_tpu`` import:

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads it itself and the package
  sets no directory in code. That is how a scheduler, a test run or the
  chip tool places the cache.
* unset — ``<checkout>/.jax_cache``, derived from the package's own
  location and listed in ``.gitignore``. The path is part of the cache's
  key, so it is never a temporary, per-process or dated name: a directory
  that moves never hits.

JAX's own thresholds decide what is worth writing (compiles of a second or
more): the chip's compiles are far above them, and the small CPU
executables of the tests stay out of the directory.

Observability: a ``compile_cache/hit`` / ``compile_cache/miss`` counter
pair in :mod:`paddle_tpu.monitor`, fed by JAX's own monitoring events — so
a bench JSON ``metrics`` section from a warm process shows the hits
directly. Pair with ``tools/warmup.py`` (AOT ``lower().compile()`` of a
named model) to prime the cache before the real job.
"""

from __future__ import annotations

import os

from .monitor import metrics as _mx

__all__ = ["setup_compile_cache", "compile_cache_dir"]

# Registered at import so the counters exist (value 0) before the first
# compile — tools/dump_metrics --selftest asserts their presence.
_m_hit = _mx.counter("compile_cache/hit",
                     help="XLA executables loaded from the persistent "
                          "compile cache")
_m_miss = _mx.counter("compile_cache/miss",
                      help="XLA compiles that went to the compiler and were "
                           "written to the persistent cache")

_configured = False

_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"

_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def compile_cache_dir() -> str:
    """Where the cache lives: ``JAX_COMPILATION_CACHE_DIR``, else the fixed
    ``.jax_cache`` beside the package. The tuned-kernel and calibration
    tables sit in the same directory (tune.table_path,
    monitor.numerics.table_path)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or _DEFAULT_DIR


def _on_event(event: str, **kwargs) -> None:
    if event == _HIT_EVENT:
        _m_hit.inc()
    elif event == _MISS_EVENT:
        _m_miss.inc()


def setup_compile_cache() -> None:
    """Place the cache (see the module docstring) and hook the hit/miss
    counters. Idempotent; called at ``paddle_tpu`` import, before anything
    can compile."""
    global _configured
    if _configured:
        return
    import jax
    from jax import monitoring

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", _DEFAULT_DIR)
    monitoring.register_event_listener(_on_event)
    _configured = True
