"""repro.obs — tracing, metrics, and perf-trajectory observability.

The measurement layer under every other subsystem: a contextvar-scoped
span tracer (``trace``), process-local counters/gauges/histograms
(``metrics``), and markdown/JSON reporting (``report``).  One master
switch governs all recording::

    import repro.obs as obs

    obs.enable()                     # or REPRO_OBS=1 in the environment
    repro.sort.sort(x)
    print(obs.report.render_markdown())
    obs.disable()

Disabled (the default) the whole layer is a flag check per call site — no
spans, no events, no metric writes, bit-identical outputs.  Spans also reach
the JAX profiler trace whenever one is being recorded, switch or not.
See README "Observability" for the span and metric catalog.
"""
from __future__ import annotations

from repro.obs import metrics, report, trace  # noqa: F401
from repro.obs.metrics import (  # noqa: F401
    counter, gauge, histogram, snapshot)
from repro.obs.trace import (  # noqa: F401
    Span, enable, disable, enabled, events, record_event, spans, tracing)

__all__ = [
    "trace", "metrics", "report",
    "enable", "disable", "enabled", "tracing",
    "span", "Span", "spans", "events", "record_event",
    "counter", "gauge", "histogram", "snapshot",
    "clear",
]

# ``obs.span("name", ...)`` opens a span; ``obs.trace`` stays the module so
# call sites can do ``from repro.obs import trace`` and ``trace.trace(...)``
span = trace.trace


def clear() -> None:
    """Reset every recorded span, event, and metric (the enabled flag is
    left as-is)."""
    trace.clear()
    metrics.reset()
