"""Observability for the kernel serving path: tracing, metrics, profiling.

Four pieces, one import surface:

* :mod:`repro.obs.timer` — the single wall-clock code path
  (:func:`now_s` / :func:`now_us` / :class:`Stopwatch`), enforced by the
  ``timer-discipline`` lint rule;
* :mod:`repro.obs.trace` — per-request :class:`Span` trees with fan-in
  links, a bounded ring and a JSONL exporter; phase spans
  (:func:`~repro.obs.trace.phase`) inside each request and launch, on the
  profiler's clock when :func:`~repro.obs.trace.annotate` is on;
* :mod:`repro.obs.metrics` — :class:`MetricsRegistry` of counters,
  gauges and streaming histograms; :class:`CounterDict` is the
  backward-compatible view the frozen ``KernelService.stats`` contract
  is served from;
* :mod:`repro.obs.profile` — :class:`LaunchProfiler` pairing each
  launch's static preflight plan with its measured wall time.
"""
from repro.obs.metrics import (
    Counter,
    CounterDict,
    Gauge,
    Histogram,
    MetricsRegistry,
)
from repro.obs.profile import (
    LaunchProfiler,
    LaunchRecord,
    active,
    install,
    profiled,
)
from repro.obs.timer import Stopwatch, now_s, now_us
from repro.obs.trace import Span, Tracer

__all__ = [
    "Counter",
    "CounterDict",
    "Gauge",
    "Histogram",
    "LaunchProfiler",
    "LaunchRecord",
    "MetricsRegistry",
    "Span",
    "Stopwatch",
    "Tracer",
    "active",
    "install",
    "now_s",
    "now_us",
    "profiled",
]
