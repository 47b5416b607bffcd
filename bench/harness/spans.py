"""Host spans and counters that the benchmark's own files record.

A span is recorded twice: in memory (name, start, end, attributes) on the
host's ``perf_counter`` clock, for the per-layer readers, and as a
``jax.profiler.TraceAnnotation``, so that it shares the device trace's
clock and the trace reduction can say what the host did in each idle gap.
"""

from __future__ import annotations

import contextlib
import time
from collections import Counter
from typing import Dict, List, Optional, Tuple

import jax


class Recorder:
    def __init__(self):
        self.spans: List[Tuple[str, float, float, Dict]] = []
        self.counters: Counter = Counter()

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            try:
                yield attrs
            finally:
                self.spans.append((name, t0, time.perf_counter(), attrs))

    def named(self, name: str) -> List[Tuple[str, float, float, Dict]]:
        return [s for s in self.spans if s[0] == name]

    def total(self, name: str) -> float:
        return sum(t1 - t0 for _, t0, t1, _ in self.named(name))


class Compiles:
    """Counts backend compilations while active, like the program's own
    counter: one JAX monitoring event per executable built or loaded."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        self.names: Counter = Counter()

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == self.EVENT:
            self.count += 1
            self.names[kw.get("fun_name", "?")] += 1

    def __enter__(self) -> "Compiles":
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on)


def patch(obj, attr: str, wrap) -> Optional[callable]:
    """Replace ``obj.attr`` by ``wrap(original)``; returns the undo."""
    orig = getattr(obj, attr)
    setattr(obj, attr, wrap(orig))
    return lambda: setattr(obj, attr, orig)
