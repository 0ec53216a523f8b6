"""JAX's persistent compilation cache, switched on by entry-point scripts.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it on its own and
:func:`enable` leaves it alone. Otherwise the cache goes to ``.jax_cache/``
at the root of the checkout: a fixed path, so a later run in the same
checkout finds what an earlier one compiled. Importing the library never
turns the cache on; scripts call :func:`enable` before their first compile.

:class:`CompileStats` counts compiles and cache hits and misses from JAX's
own monitoring events, for scripts that report them.
"""
from __future__ import annotations

import os
from collections import Counter
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on for every program this process
    compiles; returns the cache directory."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    # keep programs that compile in under a second too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir


class CompileStats:
    """Backend compiles (count and milliseconds, cache reads included)
    and persistent-cache hits and misses, counted from construction on.
    JAX keeps the listeners for the life of the process."""

    FIELDS = ("compiles", "compile_ms", "cache_hits", "cache_misses")

    def __init__(self):
        self._counts: Counter = Counter()
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event: str, **_):
        if event in ("/jax/compilation_cache/cache_hits",
                     "/jax/compilation_cache/cache_misses"):
            self._counts[event.rsplit("/", 1)[1]] += 1

    def _on_duration(self, event: str, secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self._counts["compiles"] += 1
            self._counts["compile_ms"] += round(secs * 1e3)

    def snapshot(self) -> dict:
        return {k: self._counts[k] for k in self.FIELDS}
