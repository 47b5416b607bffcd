"""repro.obs: zero-cost-when-off tracing, metrics and leak auditing.

Two clocks:

* virtual time (the simulated fabric's µs): attach a :class:`Tracer` to a
  fabric (``Tracer(fabric)``; existing and future engines are wired either
  way) to record per-WR lifecycle spans, ctrl-plane instants, gauges and
  tagged observation windows; export with :func:`export_chrome_trace`
  (Perfetto) and :meth:`Tracer.finalize` (flat metrics dict for
  ``BENCH_*.json``).  :class:`HealthMonitor` and :class:`FlightRecorder`
  work on the same clock; :class:`Histogram` and :class:`MetricRegistry`
  are arithmetic on either.
* the host clock (``time.perf_counter``): attach :class:`HostSpans` with
  ``fabric.attach_spans(rec)`` to record what the host did per request
  (prefill, staging, decode steps, sampling) and the events the loop ran,
  as ``jax.profiler.TraceAnnotation``s on the device trace's clock.

With nothing attached every hook is a single guarded attribute check.
"""

from .audit import assert_clean, format_audit
from .export import build_trace_events, export_chrome_trace
from .health import HealthMonitor, PairHealth
from .hostspans import NULL_SPAN, HostSpans, host_count, host_span
from .metrics import Histogram, MetricRegistry, rank_percentile
from .recorder import FlightRecorder
from .tracer import Tracer, Window, WrSpan, traced_phase, traced_window

__all__ = [
    "Tracer", "WrSpan", "Window", "traced_phase", "traced_window",
    "Histogram", "MetricRegistry", "rank_percentile",
    "HealthMonitor", "PairHealth", "FlightRecorder",
    "HostSpans", "host_span", "host_count", "NULL_SPAN",
    "build_trace_events", "export_chrome_trace",
    "assert_clean", "format_audit",
]
