"""repro.obs -- unified telemetry: metrics registry + span tracer.

Dependency-free (stdlib only; a span also opens a profiler ``TraceMe``
once JAX is imported, see ``obs/trace.py``) so every layer of the repo
can import it without cycles: ``core``, ``store``, ``serve`` and the
benchmarks all write into the process-default :func:`registry` and
:func:`tracer`, and one snapshot sees the whole system (DESIGN.md
Sec. 12).

    from repro import obs

    obs.registry().counter("repro_encode_flushes_total").inc()
    with obs.span("encode.flush", attrs={"streams": 8}, histogram=h):
        ...                             # synchronous code only
    text = obs.to_prometheus()          # Prometheus exposition
    doc = obs.to_json()                 # JSON snapshot (metrics + spans)

``set_enabled(False)`` short-circuits every metric write (and span
recording via ``tracer().enabled``) -- the metrics-off arm of the
overhead bench ``benchmarks/bench_obs_overhead.py``.
"""
from .metrics import (                                        # noqa: F401
    Counter, Gauge, Histogram, MetricsRegistry,
    DEFAULT_LATENCY_BUCKETS, registry, set_enabled,
)
from .trace import Span, SpanTracer, tracer, span, event      # noqa: F401
from .export import (                                         # noqa: F401
    to_prometheus, to_json, parse_prometheus, selfcheck,
    histogram_quantile, quantile, quantile_from_parsed,
    SloSpec, SloResult, evaluate_slos,
)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "DEFAULT_LATENCY_BUCKETS", "registry", "set_enabled",
    "Span", "SpanTracer", "tracer", "span", "event",
    "to_prometheus", "to_json", "parse_prometheus", "selfcheck",
    "histogram_quantile", "quantile", "quantile_from_parsed",
    "SloSpec", "SloResult", "evaluate_slos",
]
