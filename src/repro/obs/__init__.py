"""Observability subsystem: metrics registry, span tracer, run reporter.

Dependency-free (stdlib only), so every layer of the repo — core protocol,
stream executor, layout engine, trainer, serving engine, kernels — can import
``repro.obs`` without cycles.  See DESIGN.md §13 for the stable metric-name
catalog and the span hierarchy.

Module-level conveniences operate on the process-wide defaults::

    from repro import obs

    obs.counter("odb_protocol_rounds_total").inc()
    with obs.span("train/step", step=3):
        ...
    obs.instant("dgap/closure", event="join_all_finished")

The default registry is *enabled* (counters are cheap; `metrics.json` and
the trainer log line always have data); the default tracer is *disabled*
until ``--telemetry DIR`` (or a test) switches it on via
:func:`enable_telemetry`.
"""

from __future__ import annotations

from repro.obs.metrics import (
    DEFAULT_BUCKETS,
    DEFAULT_MAX_LABEL_CHILDREN,
    DROPPED_SERIES,
    NULL,
    Counter,
    CrossProcessAggregator,
    Gauge,
    Histogram,
    MetricsRegistry,
    NullMetric,
    default_registry,
)
from repro.obs.report import (
    ROUND_DURATION_BUCKETS,
    RoundTimeline,
    RunReporter,
    enable_telemetry,
)
from repro.obs.scrape import ScrapeServer, start_scrape_server
from repro.obs.trace import NULL_SPAN, Span, SpanTracer, default_tracer, name_os_thread

__all__ = [
    "DEFAULT_BUCKETS",
    "DEFAULT_MAX_LABEL_CHILDREN",
    "DROPPED_SERIES",
    "NULL",
    "NULL_SPAN",
    "ROUND_DURATION_BUCKETS",
    "Counter",
    "CrossProcessAggregator",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "NullMetric",
    "RoundTimeline",
    "RunReporter",
    "ScrapeServer",
    "Span",
    "SpanTracer",
    "counter",
    "default_registry",
    "default_tracer",
    "enable_telemetry",
    "gauge",
    "histogram",
    "instant",
    "name_os_thread",
    "span",
    "start_scrape_server",
]


def counter(name: str, help: str = "", unit: str = "", **labels):
    """Counter from the default registry (NULL sink when disabled)."""
    return default_registry().counter(name, help=help, unit=unit, **labels)


def gauge(name: str, help: str = "", unit: str = "", **labels):
    """Gauge from the default registry (NULL sink when disabled)."""
    return default_registry().gauge(name, help=help, unit=unit, **labels)


def histogram(name: str, buckets=DEFAULT_BUCKETS, help: str = "", unit: str = "", **labels):
    """Histogram from the default registry (NULL sink when disabled)."""
    return default_registry().histogram(
        name, buckets=buckets, help=help, unit=unit, **labels
    )


def span(name: str, cat: str = "", **args):
    """Span context manager on the default tracer (NULL_SPAN when disabled)."""
    return default_tracer().span(name, cat=cat, **args)


def instant(name: str, cat: str = "", **args) -> None:
    """Instant event on the default tracer (no-op when disabled)."""
    default_tracer().instant(name, cat=cat, **args)
