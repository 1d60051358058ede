"""Structured trace spans: request/wave IDs propagated end to end.

A request is minted a ``trace_id`` at ``FrontDoor.submit``; the wave it
dispatches in runs inside a ``span()`` whose context is thread-local, so
everything the wave touches on that thread — ``GeStoreService.serve_wave``,
the store scan/gather/materialize stages, ``core/segments.py`` reads —
can attach its timings and failure events to the active trace without
any plumbing through intermediate signatures.

``StageTimer`` times one *leaf* of a coarse stage, e.g.
``StageTimer(trace, "gather", "copy")``: it adds its seconds to
``trace["gather"]`` and ``trace["gather.copy"]`` (the additive contract
``FrontDoor.stats()`` aggregates), to the enclosing span, and to the
process-wide registry histogram ``stage.gather.copy``. While it is open
it holds a ``jax.profiler.TraceAnnotation("gestore.gather.copy")``, so a
profiler trace shows the leaf on the same clock as the device's work. A
coarse stage is the sum of its leaves, and the leaves tile its body; a
leaf never encloses another leaf, and only the thread that drives the
device opens them (a trace names each idle gap after the host event
that overlaps it most, and an enclosing annotation would take them all).
Work handed to another thread shows on the driving thread as the leaf
that waits for it; such threads stay on the host clock (spans, registry
histograms). ``jax.profiler`` is imported on the first leaf, so importing
this package does not import JAX; without JAX the annotation is a no-op.

Span lifecycle: ``span(name, ...)`` pushes onto the calling thread's
stack (nesting gives ``parent`` links), and on exit records one
``kind="span"`` event — name, trace id, parent id, duration, per-stage
seconds, caller fields — into the flight recorder plus a duration sample
into the ``span.<name>`` registry histogram. IDs are process-monotonic
(``<prefix>-<n>``), deterministic under a single thread, unique across
threads.
"""
from __future__ import annotations

import threading
import time

from .metrics import REGISTRY

_id_lock = threading.Lock()
_id_next = 0

_tls = threading.local()


def new_trace_id(prefix: str = "req") -> str:
    """Mint a process-unique id, e.g. ``req-000017`` / ``wave-000018``."""
    global _id_next
    with _id_lock:
        _id_next += 1
        n = _id_next
    return f"{prefix}-{n:06d}"


class Span:
    """One live span on a thread's stack (use the ``span()`` context
    manager; this class is the handle it yields)."""

    __slots__ = ("name", "trace_id", "parent_id", "fields", "stages", "_t0",
                 "duration_s")

    def __init__(self, name: str, trace_id: str, parent_id: str | None,
                 fields: dict):
        self.name = name
        self.trace_id = trace_id
        self.parent_id = parent_id
        self.fields = fields
        self.stages: dict[str, float] = {}
        self.duration_s = 0.0

    def add_stage(self, stage: str, seconds: float) -> None:
        self.stages[stage] = self.stages.get(stage, 0.0) + seconds


def current_span() -> Span | None:
    """The innermost active span on this thread, or None."""
    stack = getattr(_tls, "stack", None)
    return stack[-1] if stack else None


def current_trace_id() -> str | None:
    """The active trace id on this thread (None outside any span)."""
    s = current_span()
    return s.trace_id if s is not None else None


class span:
    """Context manager opening a span on the calling thread.

    Args:
      name: span name (becomes the ``span.<name>`` histogram).
      trace_id: propagate an existing id (e.g. the one minted at submit);
        None inherits the enclosing span's id, or mints a fresh one at
        the root.
      **fields: structured payload copied into the recorded event.
    """

    __slots__ = ("_name", "_trace_id", "_fields", "_span", "_t0")

    def __init__(self, name: str, *, trace_id: str | None = None, **fields):
        self._name = name
        self._trace_id = trace_id
        self._fields = fields

    def __enter__(self) -> Span:
        parent = current_span()
        tid = self._trace_id
        if tid is None:
            tid = parent.trace_id if parent is not None else new_trace_id()
        s = Span(self._name, tid,
                 parent.trace_id if parent is not None else None,
                 self._fields)
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        stack.append(s)
        self._span = s
        self._t0 = time.perf_counter()
        return s

    def __exit__(self, exc_type, exc, tb):
        s = self._span
        s.duration_s = time.perf_counter() - self._t0
        _tls.stack.pop()
        REGISTRY.histogram(f"span.{s.name}").record(s.duration_s)
        from .recorder import RECORDER
        RECORDER.record(
            "span", name=s.name, trace=s.trace_id, parent=s.parent_id,
            duration_s=s.duration_s,
            **({"stages": dict(s.stages)} if s.stages else {}),
            **({"error": repr(exc)} if exc is not None else {}),
            **s.fields)
        return False


class _NoAnnotation:
    """Stand-in for ``TraceAnnotation`` where JAX is not installed."""

    def __init__(self, name: str):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_annotation = None


def _trace_annotation(name: str):
    """A ``jax.profiler.TraceAnnotation`` named ``name`` (no keyword
    arguments, so the trace event's name is exactly ``name``)."""
    global _annotation
    if _annotation is None:
        try:
            from jax.profiler import TraceAnnotation
        except ImportError:
            TraceAnnotation = _NoAnnotation
        _annotation = TraceAnnotation
    return _annotation(name)


class StageTimer:
    """Time one leaf of a coarse stage (see the module docstring).

    ``StageTimer(trace, stage)`` is a leaf with no sub-name: its key is
    ``stage``. ``StageTimer(trace, stage, leaf)`` adds its wall seconds to
    ``trace[stage]`` and to ``trace["<stage>.<leaf>"]`` (no-op when trace
    is None; additive, so one trace dict can span a whole wave), to both
    keys of the enclosing span (if any), and to the process-wide
    ``stage.<key>`` histogram; while open it holds the profiler
    annotation ``gestore.<key>``."""

    __slots__ = ("_trace", "_stage", "_key", "_ann", "_t0")

    def __init__(self, trace: dict | None, stage: str,
                 leaf: str | None = None):
        self._trace, self._stage = trace, stage
        self._key = stage if leaf is None else f"{stage}.{leaf}"

    def __enter__(self):
        self._ann = _trace_annotation("gestore." + self._key)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        keys = ((self._stage,) if self._key == self._stage
                else (self._stage, self._key))
        if self._trace is not None:
            for k in keys:
                self._trace[k] = self._trace.get(k, 0.0) + dt
        s = current_span()
        if s is not None:
            for k in keys:
                s.add_stage(k, dt)
        REGISTRY.histogram(f"stage.{self._key}").record(dt)
        return False
