"""Unified telemetry for the repro stack.

Three small, dependency-free pieces:

- :mod:`repro.obs.metrics` — process-wide registry of labeled counters,
  gauges, and histograms with Prometheus text exposition and a
  one-global-read disabled path (off by default).
- :mod:`repro.obs.trace` — structured spans with thread attribution,
  exported as Chrome/Perfetto trace-event JSON (off by default) and, while
  a JAX profiler session collects, as profiler events on the clock of the
  device ops.
- :func:`configure_logging` — one-call console logging for the
  ``repro.*`` logger namespace used across the package.

Serving code declares metrics at import time and instruments hot paths
unconditionally; until ``metrics.enable()`` / ``trace.start()`` is
called, every hook is a single module-global read.  See
``docs/architecture.md`` §13 for the metric catalog and span taxonomy.
"""
from __future__ import annotations

import logging

from repro.obs import metrics, trace
from repro.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    LabelCardinalityError,
    MetricsRegistry,
    REGISTRY,
    lint_exposition,
)
from repro.obs.trace import TraceCollector, instant, span, validate_trace

__all__ = [
    "metrics",
    "trace",
    "Counter",
    "Gauge",
    "Histogram",
    "LabelCardinalityError",
    "MetricsRegistry",
    "REGISTRY",
    "TraceCollector",
    "span",
    "instant",
    "lint_exposition",
    "validate_trace",
    "configure_logging",
]


def configure_logging(level: int = logging.INFO,
                      stream=None, force: bool = False) -> logging.Logger:
    """Attach a console handler to the ``repro`` logger namespace.

    Idempotent: if the ``repro`` logger already has handlers (or a
    handler is installed on the root logger) it only adjusts the level,
    unless ``force=True``.  Scoped to the ``repro`` logger rather than
    the root so embedding applications keep control of their own logging.
    """
    log = logging.getLogger("repro")
    log.setLevel(level)
    has_root = logging.getLogger().handlers
    if force or (not log.handlers and not has_root):
        handler = logging.StreamHandler(stream)
        handler.setFormatter(logging.Formatter("[%(name)s] %(message)s"))
        log.addHandler(handler)
        log.propagate = False
    return log
