"""repro.obs — telemetry: structured tracing + metrics.

Two small, orthogonal pieces (see docs/observability.md for the catalog):

  * ``Tracer``          — process-local structured event log (spans /
    instants / counters on a monotonic clock) with a Chrome-trace /
    Perfetto JSON exporter; enabled spans are mirrored into the JAX
    profiler's trace.  Thread-safe; a DISABLED tracer is a cheap
    no-op (singleton null span, zero events, zero state growth) so the
    serving hot loop can stay instrumented unconditionally.
  * ``MetricsRegistry`` — named counters / gauges / histograms with a
    ``snapshot()`` dict contract.  Always on (plain dict arithmetic);
    this is where ``engine.stats()`` percentiles and the
    ``BENCH_*.json`` artifacts come from.

The module-level default tracer (``get_tracer()``) is DISABLED; every
instrumented constructor accepts ``tracer=`` and falls back to it, so code
is traceable without plumbing until a driver (``train.py --trace`` /
``RLConfig.trace_path``) creates an enabled tracer and threads it through.
"""
from repro.obs.metrics import MetricsRegistry
from repro.obs.tracer import NULL_SPAN, Tracer

_DEFAULT = Tracer(enabled=False)


def get_tracer() -> Tracer:
    """The process-local default tracer (disabled unless a driver enables
    it).  Instrumented code uses this when no tracer is injected."""
    return _DEFAULT


__all__ = ["Tracer", "MetricsRegistry", "NULL_SPAN", "get_tracer"]
