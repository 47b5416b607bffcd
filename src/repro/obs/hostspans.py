"""Host-clock spans: what the host did, on the device trace's clock.

The :class:`~repro.obs.Tracer` stamps the simulated fabric in virtual µs.
On a chip the question is another one: while the device sat idle, what was
the host doing?  :class:`HostSpans` answers it.  A span is recorded twice:
in memory as ``(name, t0, t1, attrs)`` on ``time.perf_counter``, and as a
``jax.profiler.TraceAnnotation``, so that a profiler trace taken around the
run holds it on the same clock as the device's operations.

Attach one with ``Fabric.attach_spans(rec)`` (``None`` detaches).  The
serving peers then open spans where their work happens, each carrying the
request id ``rid``; the event loop adds the events it ran to the counter
``fabric.events``.  With nothing attached every site is one attribute check
that returns the shared :data:`NULL_SPAN`: no recorder, no span, no clock
read.  Any object with the same interface (``span``, ``counters``,
``spans``) may be attached instead of a :class:`HostSpans`.
"""

from __future__ import annotations

import time
from collections import Counter
from contextlib import contextmanager, nullcontext
from typing import Dict, List, Tuple

# what every span site returns while nothing is attached
NULL_SPAN = nullcontext()

Span = Tuple[str, float, float, Dict]


class HostSpans:
    """Spans and counters on the host clock (``time.perf_counter``)."""

    def __init__(self) -> None:
        from jax.profiler import TraceAnnotation
        self._annotate = TraceAnnotation
        self.spans: List[Span] = []
        self.counters: Counter = Counter()

    @contextmanager
    def span(self, name: str, **attrs):
        """Record ``(name, t0, t1, attrs)`` around the block; a span closes
        before the span that encloses it, so self time is its duration less
        that of the spans inside it."""
        t0 = time.perf_counter()
        with self._annotate(name):
            try:
                yield attrs
            finally:
                self.spans.append((name, t0, time.perf_counter(), attrs))

    def named(self, name: str) -> List[Span]:
        return [s for s in self.spans if s[0] == name]

    def total(self, name: str) -> float:
        """Seconds in the spans called ``name``."""
        return sum(t1 - t0 for _, t0, t1, _ in self.named(name))


def host_span(fabric, name: str, **attrs):
    """``fabric.spans.span(name, **attrs)`` when spans are attached, else
    the shared :data:`NULL_SPAN`."""
    rec = fabric.spans
    return rec.span(name, **attrs) if rec is not None else NULL_SPAN


def host_count(fabric, name: str, n: int) -> None:
    """Add ``n`` to the counter ``name`` when spans are attached."""
    rec = fabric.spans
    if rec is not None:
        rec.counters[name] += n
