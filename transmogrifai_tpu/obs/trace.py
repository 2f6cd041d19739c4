"""Hierarchical span tracing — one timeline across train/sweep/serve.

Every plane of the system kept its own siloed profiler (``PlanProfiler``,
``IngestProfiler``, ``ServingMetrics``, elastic counters) — good ledgers,
but none of them answers "what happened, in what order, and why" when a
sweep shrinks its mesh mid-rung or a guarded swap rolls back.  This module
is the shared timeline: a process-wide :class:`Tracer` collects
:class:`Span` records (name, category, parent, wall interval, attributes)
from lightweight hooks threaded through ``OpWorkflow.train/refresh``, the
execution plan, the streaming driver, the sweep work queue, and the
serving batch path.

Design constraints, in priority order:

* **Off-path-free when disabled.**  Tracing is opt-in
  (:func:`start_trace`); every hook starts with a single module-global
  ``None`` check, so the disabled cost per hook is one attribute load +
  branch (gated <1% of train wall by the OBS_SMOKE bench contract).
* **Thread-correct.**  The span stack is thread-local; code that fans out
  to worker threads (the plan's host-stage pool, the serving dispatch
  thread) passes the parent span explicitly — the same discipline the
  ``MetricsCollector`` install already follows.
* **Bounded.**  A tracer retains at most ``max_spans`` finished spans
  (drops count in ``dropped``) so a runaway loop cannot OOM the process
  it was meant to observe.

Sinks live in sibling modules: Chrome-trace export (``obs/export.py``),
the flight-recorder event ring (``obs/flight.py``), Prometheus text
exposition (``obs/prometheus.py``).
"""
from __future__ import annotations

import contextlib
import itertools
import threading
import time
import uuid
from typing import Any, Dict, List, Optional

__all__ = ["Span", "Tracer", "start_trace", "stop_trace", "install_tracer",
           "current_tracer", "tracing", "span", "current_span",
           "begin_span", "end_span", "phases", "new_trace_id",
           "set_global_attrs", "global_attrs"]

#: attrs stamped onto EVERY span this process opens — the pod runtime
#: sets {"process": process_index} here so the coordinator can merge the
#: per-process span trees and still attribute each span to its host
_GLOBAL_ATTRS: Dict[str, Any] = {}


def set_global_attrs(**attrs: Any) -> None:
    """Merge process-wide span attributes (e.g. the pod process index).
    Only consulted while a tracer is armed — the disabled hook path stays
    a single None check."""
    _GLOBAL_ATTRS.update(attrs)


def global_attrs() -> Dict[str, Any]:
    return dict(_GLOBAL_ATTRS)


def new_trace_id() -> str:
    return uuid.uuid4().hex[:16]


class Span:
    """One timed node of the span tree (finished spans are immutable by
    convention; ``attrs`` may be enriched until :func:`end_span`)."""

    __slots__ = ("name", "cat", "trace_id", "span_id", "parent_id",
                 "t0_unix", "t0", "dur_s", "attrs", "thread")

    def __init__(self, name: str, cat: str, trace_id: str, span_id: int,
                 parent_id: Optional[int], attrs: Dict[str, Any]):
        self.name = name
        self.cat = cat
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.t0_unix = time.time()
        self.t0 = time.perf_counter()
        self.dur_s: Optional[float] = None
        self.attrs = attrs
        self.thread = threading.current_thread().name

    def to_json(self) -> Dict[str, Any]:
        return {"name": self.name, "cat": self.cat,
                "traceId": self.trace_id, "spanId": self.span_id,
                "parentId": self.parent_id,
                "t0": round(self.t0_unix, 6),
                "durSecs": (None if self.dur_s is None
                            else round(self.dur_s, 6)),
                "thread": self.thread, "attrs": dict(self.attrs)}


class Tracer:
    """Collects one run's span tree; thread-safe."""

    def __init__(self, label: str = "", trace_id: Optional[str] = None,
                 max_spans: int = 100_000):
        self.label = label
        self.trace_id = trace_id or new_trace_id()
        self.max_spans = int(max_spans)
        self.started_at = time.time()
        self.spans: List[Span] = []
        self.dropped = 0
        self._ids = itertools.count(1)
        self._lock = threading.Lock()
        #: FlightRecorder installed alongside this tracer (start_trace
        #: wires one by default so span ids link events to the tree)
        self.flight = None
        #: perf_counter minus time.time(), sampled once: puts an interval
        #: that arrives on the wall clock (JAX's compile events) on the
        #: clock every other span is on
        self.unix_to_perf = time.perf_counter() - time.time()
        #: the JAX monitoring listener start_trace registered for this
        #: tracer (stop_trace takes exactly this one out again)
        self.compile_listener = None

    def begin(self, name: str, cat: str, parent_id: Optional[int],
              attrs: Dict[str, Any]) -> Span:
        return Span(name, cat, self.trace_id, next(self._ids),
                    parent_id, attrs)

    def end(self, sp: Span) -> None:
        sp.dur_s = time.perf_counter() - sp.t0
        self._store(sp)

    def _store(self, sp: Span) -> None:
        with self._lock:
            if len(self.spans) < self.max_spans:
                self.spans.append(sp)
            else:
                self.dropped += 1

    def record(self, name: str, cat: str, parent_id: Optional[int],
               t0_unix: float, t1_unix: float,
               attrs: Dict[str, Any]) -> Span:
        """A finished span whose interval is known only after the fact
        (``time.time()`` seconds): stored like any other, with its start
        moved onto the ``perf_counter`` clock."""
        sp = self.begin(name, cat, parent_id, attrs)
        sp.t0_unix = t0_unix
        sp.t0 = t0_unix + self.unix_to_perf
        sp.dur_s = max(t1_unix - t0_unix, 0.0)
        self._store(sp)
        return sp

    def snapshot(self) -> List[Span]:
        with self._lock:
            return list(self.spans)

    def __len__(self) -> int:
        with self._lock:
            return len(self.spans)


#: the installed tracer; None = tracing disabled (every hook's fast path)
_TRACER: Optional[Tracer] = None

_local = threading.local()


def current_tracer() -> Optional[Tracer]:
    return _TRACER


def current_span() -> Optional[Span]:
    stack = getattr(_local, "stack", None)
    return stack[-1] if stack else None


def install_tracer(tracer: Optional[Tracer]) -> Optional[Tracer]:
    """Install ``tracer`` process-wide (None disables tracing)."""
    global _TRACER
    _TRACER = tracer
    return tracer


#: JAX monitoring events recorded as spans while a tracer is armed: every
#: program a traced run builds shows as ``jit.trace:<fun_name>``,
#: ``jit.lower:<fun_name>`` and ``jit.compile:<fun_name>`` (the last is
#: raised for a load from the persistent cache too)
_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jit.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit.lower",
    "/jax/core/compile/backend_compile_duration": "jit.compile",
}


def _compile_listener(tracer: Tracer):
    """The time-span listener of ``tracer``: JAX calls it on the thread
    that compiled, when the event ends, so the span current there is the
    parent."""
    def on_time_span(event: str, start_time: float, end_time: float,
                     **kwargs) -> None:
        kind = _COMPILE_EVENTS.get(event)
        if kind is None:
            return
        parent = current_span()
        tracer.record(f"{kind}:{kwargs.get('fun_name', '?')}", "compile",
                      parent.span_id if parent is not None else None,
                      start_time, end_time, dict(_GLOBAL_ATTRS))
    return on_time_span


def start_trace(label: str = "", max_spans: int = 100_000,
                flight_capacity: int = 4096,
                capture_hlo: bool = True) -> Tracer:
    """Arm tracing process-wide: installs a fresh :class:`Tracer`, a
    linked :class:`~transmogrifai_tpu.obs.flight.FlightRecorder` (span-id
    causality links come for free), a listener that records every program
    JAX traces, lowers and compiles as a ``jit.*`` span, and — unless
    ``capture_hlo=False`` — the compiled-program feature hook
    (``obs/hlo.py``) so device stages record their HLO op mix / FLOPs /
    bytes-accessed."""
    import jax.monitoring

    from . import flight as _flight
    from . import hlo as _hlo

    tracer = Tracer(label=label, max_spans=max_spans)
    tracer.flight = _flight.FlightRecorder(capacity=flight_capacity,
                                           trace_id=tracer.trace_id)
    _flight.install_recorder(tracer.flight)
    if capture_hlo:
        _hlo.arm()
    tracer.compile_listener = _compile_listener(tracer)
    jax.monitoring.register_event_time_span_listener(
        tracer.compile_listener)
    install_tracer(tracer)
    return tracer


def stop_trace() -> Optional[Tracer]:
    """Disarm tracing; returns the tracer that was active (its spans and
    flight recorder stay readable/exportable after stop)."""
    from . import flight as _flight
    from . import hlo as _hlo

    tracer = _TRACER
    install_tracer(None)
    _flight.install_recorder(None)
    _hlo.disarm()
    if tracer is not None and tracer.compile_listener is not None:
        import jax.monitoring

        # this one listener only: clear_event_listeners() would take
        # every other meter in the process (the benchmark's) with it
        jax.monitoring.unregister_event_time_span_listener(
            tracer.compile_listener)
        tracer.compile_listener = None
    return tracer


@contextlib.contextmanager
def tracing(label: str = "", **kwargs):
    """``with tracing() as tracer:`` — start/stop_trace as a scope."""
    tracer = start_trace(label, **kwargs)
    try:
        yield tracer
    finally:
        stop_trace()


def begin_span(name: str, cat: str = "run",
               parent: Optional[Span] = None, **attrs) -> Optional[Span]:
    """Open a span (explicit form for hot paths / cross-thread parents).

    Returns None when tracing is disabled — callers hand the result
    straight back to :func:`end_span`, which no-ops on None, so the
    disabled path stays two cheap calls with no allocation."""
    t = _TRACER
    if t is None:
        return None
    if parent is None:
        parent = current_span()
    if _GLOBAL_ATTRS:
        attrs = {**_GLOBAL_ATTRS, **attrs}
    sp = t.begin(name, cat, parent.span_id if parent is not None else None,
                 attrs)
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    stack.append(sp)
    return sp


def end_span(sp: Optional[Span], **attrs) -> None:
    """Close a span opened by :func:`begin_span` (None = no-op).  Extra
    ``attrs`` merge in at close (e.g. retry counts known only at exit)."""
    if sp is None:
        return
    if attrs:
        sp.attrs.update(attrs)
    stack = getattr(_local, "stack", None)
    if stack:
        try:
            stack.remove(sp)
        except ValueError:  # closed from a different thread: fine
            pass
    t = _TRACER
    if t is not None and t.trace_id == sp.trace_id:
        t.end(sp)


@contextlib.contextmanager
def span(name: str, cat: str = "run", parent: Optional[Span] = None,
         **attrs):
    """Context-manager span; yields the Span (or None when disabled)."""
    sp = begin_span(name, cat, parent=parent, **attrs)
    try:
        yield sp
    finally:
        end_span(sp)


class phases:
    """Consecutive spans that tile one long function: the first opens with
    the object, ``to(name)`` closes the open one and opens the next, and
    leaving the ``with`` block closes the last, on a ``return`` or an
    exception too.  For a body too long to indent under one ``with span``
    a phase (``GBTGridGroup.run``, the tree estimators' ``fit_raw``).
    Disabled cost: the ``None`` check of ``begin_span`` / ``end_span`` a
    call, and no ``Span``."""

    __slots__ = ("cat", "_open")

    def __init__(self, first: str, cat: str = "run", **attrs):
        self.cat = cat
        self._open = begin_span(first, cat, **attrs)

    def to(self, name: str, **attrs) -> None:
        end_span(self._open)
        self._open = begin_span(name, self.cat, **attrs)

    def __enter__(self) -> "phases":
        return self

    def __exit__(self, *exc) -> None:
        end_span(self._open)
        self._open = None
