"""JAX's persistent compilation cache, placed from outside, and compile-time
accounting.

``enable_compile_cache()`` is called by the entry points (``repro.launch``
and ``chip_smoke.py``) before their first compile.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
set here; otherwise the cache lives at ``<checkout>/.jax_cache``, a fixed
path, since the path is part of what a later run must find again.

``compile_totals()`` reports the seconds of JAX's backend-compile timer
and the number of persistent-cache hits, since the first call.  The timer
wraps the persistent-cache lookup (``compiler.compile_or_get_cached``), so
a hit counts its read time and a warm cache shows as fewer seconds.
"""
from __future__ import annotations

import os
from typing import Tuple

import jax
from jax import monitoring

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_totals = {"compile_s": 0.0, "cache_hits": 0}
_listening = False


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR


def _on_duration(event: str, duration: float, **_) -> None:
    if event == _BACKEND_COMPILE:
        _totals["compile_s"] += duration


def _on_event(event: str, **_) -> None:
    if event == _CACHE_HIT:
        _totals["cache_hits"] += 1


def compile_totals() -> Tuple[float, int]:
    """(backend compile seconds, persistent-cache hits) since the first
    call of this function in the process."""
    global _listening
    if not _listening:
        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_event)
        _listening = True
    return _totals["compile_s"], _totals["cache_hits"]
