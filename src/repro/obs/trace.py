"""Contextvar-scoped span tracer with a zero-overhead disabled mode.

The paper's whole argument is an accounting argument: ADS-IMC wins because
it *counts* data movement per sort stage (Tables I/II).  This module is the
software stack's counting instrument — a lightweight span tracer every hot
path threads through:

    from repro.obs import trace

    with trace.trace("samplesort.phase2", bytes=nbytes) as sp:
        out = exchange(...)
        sp.fence(out)          # blocks only while the autotune loop is armed

Design contract (enforced by tests/test_obs.py):

  * **One clock with the device.**  While a JAX profiler trace is being
    recorded (jaxlib's own ``TraceMe.is_enabled()``, see
    ``profiler_active``), every span also enters a
    ``jax.profiler.TraceAnnotation`` of its name, its attributes as the
    annotation's arguments.  So program spans sit on the calling thread's
    line of the trace, beside JAX's ``PjitFunction(...)`` dispatch events
    and on the same clock as the device's operations.  The outermost span
    of a call draws a per-process call sequence number (``call``) that its
    children carry too, so one call's spans share an identifier.
  * **Zero overhead when off.**  With no profiler trace active and
    recording disabled, ``trace(...)`` makes two checks before any
    allocation and returns a shared no-op singleton; nothing is recorded,
    no span objects are built, and traced functions return bit-identical
    outputs.  ``REPRO_OBS`` (:func:`enable`) governs only the in-memory
    records: span records, events, counters, gauges and histograms.
  * **Spans never block.**  :meth:`Span.fence` waits for the device only
    while the closed-loop autotuner is armed (``REPRO_AUTOTUNE=1``), whose
    ``planner.cost_model_error`` signal is the one reader of the fenced
    device time (``device_ms``).  Otherwise it returns its argument
    untouched, so a span never adds a host sync to what it measures.
    Under a trace (inside ``jax.jit``/``shard_map``) it never blocks.
  * **Nested.**  The active span stack lives in a contextvar, so spans nest
    per thread/async context and each finished record carries its depth and
    parent name.

Events (``record_event``) are the structured, non-timing side of the same
log: the planner appends one ``plan_decision`` event per cache miss with the
full candidate cost table, and the engine appends ``cost_observation``
events pairing predicted with measured ns (armed autotuner only) — the raw
series behind the ``cost_model_error`` metric.
"""
from __future__ import annotations

import contextlib
import contextvars
import itertools
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation

__all__ = [
    "enable", "disable", "enabled", "tracing", "trace", "Span",
    "profiler_active", "record_event", "events", "spans", "clear",
    "to_json",
]

# THE recording flag: span records, events and metrics; every entry point
# checks it (and, for spans, the profiler) before allocating anything
_ENABLED = bool(os.environ.get("REPRO_OBS"))

_LOCK = threading.Lock()
_SPANS: List[Dict[str, Any]] = []          # finished spans, completion order
_EVENTS: List[Dict[str, Any]] = []         # structured events, append order
_STACK: contextvars.ContextVar[Tuple["Span", ...]] = contextvars.ContextVar(
    "repro_obs_span_stack", default=())
_CALLS = itertools.count(1)                # call sequence of outermost spans

# ``profiler_active()`` is true while a JAX profiler trace is being
# recorded: jaxlib's own ``TraceMe.is_enabled`` (TraceAnnotation subclasses
# TraceMe), bound directly so a span site pays one builtin call
profiler_active = TraceAnnotation.is_enabled


def enabled() -> bool:
    return _ENABLED


def enable() -> None:
    global _ENABLED
    _ENABLED = True


def disable() -> None:
    global _ENABLED
    _ENABLED = False


@contextlib.contextmanager
def tracing(on: bool = True):
    """Scoped enable/disable (tests, one-off profiled sections)::

        with trace.tracing():
            repro.sort.sort(x)
        print(trace.spans())
    """
    global _ENABLED
    prev = _ENABLED
    _ENABLED = bool(on)
    try:
        yield
    finally:
        _ENABLED = prev


def clear() -> None:
    """Drop every recorded span and event (the stack is left alone)."""
    with _LOCK:
        _SPANS.clear()
        _EVENTS.clear()


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

def _concrete(value: Any) -> bool:
    """True iff no leaf of ``value`` is a jax tracer (safe to block on)."""
    import jax
    return not any(isinstance(leaf, jax.core.Tracer)
                   for leaf in jax.tree_util.tree_leaves(value))


class Span:
    """One timed region: a record when recording is enabled, an annotation
    in the profiler trace while one is active."""

    __slots__ = ("name", "attrs", "depth", "parent", "call", "_t0",
                 "_record", "_ann", "wall_ms", "device_ms")

    def __init__(self, name: str, attrs: Dict[str, Any]):
        self.name = name
        self.attrs = attrs
        self.depth = 0
        self.parent: Optional[str] = None
        self.call = 0
        self._t0 = 0.0
        self._record = _ENABLED
        self._ann: Optional[TraceAnnotation] = None
        self.wall_ms: Optional[float] = None
        self.device_ms: Optional[float] = None

    def set(self, **attrs) -> "Span":
        """Attach attributes discovered mid-span (bucket counts, plans)."""
        self.attrs.update(attrs)
        if self._ann is not None:
            self._ann.set_metadata(**attrs)
        return self

    def fence(self, value):
        """With the autotune loop armed, block until ``value`` is
        device-complete and record the span's device time; otherwise (and
        on tracers, inside jit) do nothing.  Returns ``value`` unchanged
        either way, so call sites can fence their return."""
        from repro.core import tuning
        if tuning.autotune_armed() and _concrete(value):
            import jax
            jax.block_until_ready(value)
            self.device_ms = (time.perf_counter() - self._t0) * 1e3
        return value

    def __enter__(self) -> "Span":
        stack = _STACK.get()
        self.depth = len(stack)
        self.parent = stack[-1].name if stack else None
        self.call = stack[0].call if stack else next(_CALLS)
        _STACK.set(stack + (self,))
        if profiler_active():
            self._ann = TraceAnnotation(self.name, call=self.call,
                                        **self.attrs)
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.wall_ms = (time.perf_counter() - self._t0) * 1e3
        if self._ann is not None:
            self._ann.__exit__(*exc)
            self._ann = None
        stack = _STACK.get()
        if stack and stack[-1] is self:
            _STACK.set(stack[:-1])
        if not self._record:
            return
        with _LOCK:
            _SPANS.append({
                "name": self.name, "parent": self.parent,
                "depth": self.depth, "call": self.call,
                "wall_ms": self.wall_ms, "device_ms": self.device_ms,
                "attrs": dict(self.attrs),
            })


class _NoopSpan:
    """The shared disabled-mode span: every method is a no-op and
    ``trace(...)`` hands out this one instance — no per-call allocation."""

    __slots__ = ()
    name = None
    wall_ms = None
    device_ms = None
    attrs: Dict[str, Any] = {}

    def set(self, **attrs) -> "_NoopSpan":
        return self

    def fence(self, value):
        return value

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc) -> None:
        pass


_NOOP = _NoopSpan()


def trace(name: str, **attrs):
    """Open a span (use as a context manager).  With recording disabled
    and no profiler trace active -> the shared no-op singleton; nothing
    is allocated or recorded."""
    if not _ENABLED and not profiler_active():
        return _NOOP
    return Span(name, attrs)


def spans() -> List[Dict[str, Any]]:
    """Finished span records (completion order — children before parents)."""
    with _LOCK:
        return list(_SPANS)


# ---------------------------------------------------------------------------
# structured events
# ---------------------------------------------------------------------------

def record_event(kind: str, **fields) -> None:
    """Append one structured event (no-op when disabled)."""
    if not _ENABLED:
        return
    with _LOCK:
        _EVENTS.append({"kind": kind, **fields})


def events(kind: Optional[str] = None) -> List[Dict[str, Any]]:
    with _LOCK:
        evs = list(_EVENTS)
    if kind is not None:
        evs = [e for e in evs if e.get("kind") == kind]
    return evs


# ---------------------------------------------------------------------------
# export
# ---------------------------------------------------------------------------

def _jsonable(v):
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    if isinstance(v, dict):
        return {str(k): _jsonable(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_jsonable(x) for x in v]
    try:                                    # numpy scalars
        return v.item()
    except (AttributeError, ValueError):
        return repr(v)


def to_json(indent: Optional[int] = None) -> str:
    return json.dumps({"spans": _jsonable(spans()),
                       "events": _jsonable(events())}, indent=indent)
