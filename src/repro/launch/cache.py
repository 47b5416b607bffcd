"""Compilation: the persistent cache, and a counter of backend compiles.

JAX reads ``$JAX_COMPILATION_CACHE_DIR`` itself; when it is set, that
directory is the cache and nothing here overrides it.  Otherwise the cache
lives at the fixed ``<repo>/.jax_cache``, never at a temporary or per-run
path, so a later run of the same checkout finds what an earlier one
compiled.
"""

from __future__ import annotations

import collections
import os
import pathlib

import jax

REPO_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory (see the
    module docstring) and return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)


# emitted once per executable JAX builds or loads from the persistent cache
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """Counts backend compilations while active (``with CompileCounter()
    as c``): ``c.count`` in all, ``c.by_name`` per jitted function name.
    A warmed-up steady state counts zero."""

    def __init__(self):
        self.by_name: collections.Counter = collections.Counter()

    @property
    def count(self) -> int:
        return sum(self.by_name.values())

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.by_name[kw.get("fun_name", "?")] += 1

    def __enter__(self) -> "CompileCounter":
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_event)
