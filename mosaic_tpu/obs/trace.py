"""Hierarchical tracing: spans over the telemetry event spine.

A *span* is one named, timed unit of work (`serve.dispatch`, one durable
stream segment, a snapshot write) with identity — ``trace_id`` shared by
every span of one logical operation, ``span_id`` unique per span,
``parent_id`` linking child to parent. Spans ride the existing
`runtime/telemetry.py` pipeline: ending a span records one
``event="span"`` dict (so capture scopes, bench trails, and exporters
see spans and flat events in ONE totally-ordered stream), and every
*other* event recorded while a span is active on the thread is stamped
with the span's ids — a retry, an escalation, a watchdog stall, or a
degradation is thereby causally attached to the stage it happened in.

Context propagation is explicit, mirroring the runtime's existing
cross-thread idioms (``telemetry.current_sinks``/``adopt_sinks``,
``faults.current_plans``/``adopt_plans``):

- the active span stack is thread-local; nesting on one thread needs no
  ceremony (``with span("outer"): with span("inner"): ...``);
- :func:`current_context` returns the innermost active
  :class:`SpanContext`; a worker thread calls :func:`adopt_context`
  with it and its spans/events join the caller's trace — one serve
  request submitted on thread A and dispatched by the batcher thread is
  ONE trace (`tests/test_serve.py` pins the connectivity);
- a *detached* span (:func:`start_span` ``detached=True``) gets ids and
  a parent from the ambient context but does NOT occupy the caller's
  stack — the shape for request-lifetime roots that begin on the submit
  thread and end on the dispatch thread (`serve/admission.py`).

One clock with the profiler: every non-detached span also enters a
``jax.profiler.TraceAnnotation`` named ``mosaic.<span name>`` on its own
thread, carrying ``t`` (the span's monotonic start in nanoseconds, the
value it records as ``start_mono``) and ``span_id``. With no profiler
session that is a no-op inside TraceMe; with one the span lands on
``/host:CPU`` on the clock the device planes use, and every such
annotation is an anchor: ``offset = event.start_ns - t`` places any
telemetry event (``ts_mono``/``start_mono``/``seconds``) on the device's
axis. Detached spans enter none (TraceMe is per-thread).

Ids are 128-bit (trace) / 64-bit (span) random hex, Dapper-style, from a
per-process generator seeded by the OS (a ``getrandom`` call per id cost
5.9 µs on the chip's host, a fifth of a span: PERF.md, PR 24).
Everything here is stdlib-only and imports nothing above
``runtime/telemetry.py``, so any layer may use it; a span looks jax up
only where some other module has imported it already (the one import is
in :func:`device_intervals`, which reads a profiler trace).
"""

from __future__ import annotations

import dataclasses
import os
import random
import sys
import threading
import time

from ..runtime import telemetry as _telemetry

_LOCAL = threading.local()


@dataclasses.dataclass(frozen=True)
class SpanContext:
    """The propagatable identity of a span: what a child needs to link
    to it, and nothing else (safe to serialize — the durable stream
    stores one in its snapshot sidecars so a resume joins the
    interrupted run's trace)."""

    trace_id: str
    span_id: str

    def as_dict(self) -> dict:
        return {"trace_id": self.trace_id, "span_id": self.span_id}

    @classmethod
    def from_dict(cls, d: dict | None) -> "SpanContext | None":
        if not d or not d.get("trace_id") or not d.get("span_id"):
            return None
        return cls(str(d["trace_id"]), str(d["span_id"]))


#: ids need to be unique, not unguessable: one generator a process,
#: seeded from the OS (again in a forked child, which would otherwise
#: repeat its parent's ids); ``getrandbits`` is one C call under the GIL
_IDS = random.Random(os.urandom(32))
if hasattr(os, "register_at_fork"):
    os.register_at_fork(after_in_child=lambda: _IDS.seed(os.urandom(32)))


def _new_trace_id() -> str:
    return f"{_IDS.getrandbits(128):032x}"


def _new_span_id() -> str:
    return f"{_IDS.getrandbits(64):016x}"


def _stack() -> list:
    stack = getattr(_LOCAL, "stack", None)
    if stack is None:
        stack = _LOCAL.stack = []
    return stack


class Span:
    """One in-flight span. Prefer the :func:`span` context manager; use
    :func:`start_span`/:meth:`end` directly when begin and end live on
    different threads (request lifecycles)."""

    __slots__ = (
        "name", "context", "parent_id", "attrs",
        "_t0", "_start_mono", "_stack", "_ended", "_ann", "_thread",
    )

    def __init__(
        self, name: str, context: SpanContext, parent_id: str | None,
        attrs: dict, stack: list | None,
    ):
        self.name = name
        self.context = context
        self.parent_id = parent_id
        self.attrs = attrs
        self._t0 = time.perf_counter()
        self._start_mono = round(time.monotonic(), 6)
        self._stack = stack
        self._ended = False
        self._ann = None
        self._thread = 0
        if stack is not None:
            self._annotate()

    def _annotate(self) -> None:
        """Enter the profiler annotation (non-detached spans only), if a
        profiler session is on. No profiler without jax, so jax is never
        imported from here."""
        jax = sys.modules.get("jax")
        if jax is None:
            return
        annotation = jax.profiler.TraceAnnotation
        if not annotation.is_enabled():
            return
        self._thread = threading.get_ident()
        self._ann = annotation(
            "mosaic." + self.name,
            t=int(round(self._start_mono * 1e9)),
            span_id=self.context.span_id,
        )
        self._ann.__enter__()

    def _release(self) -> bool:
        """Leave the stack; False if already ended."""
        if self._ended:
            return False
        self._ended = True
        if self._stack is not None and self in self._stack:
            self._stack.remove(self)
        return True

    def _close_annotation(self) -> None:
        # TraceMe is per-thread: an annotation ended from another thread
        # is dropped, never closed on the wrong thread's timeline
        if self._ann is not None:
            if self._thread == threading.get_ident():
                self._ann.__exit__(None, None, None)
            self._ann = None

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc_type is not None:  # stamped, and the exception goes on
            self.attrs["error"] = exc_type.__name__
        self.end()
        return False

    def set(self, **attrs) -> "Span":
        """Attach/overwrite attributes (recorded at end)."""
        self.attrs.update(attrs)
        return self

    def elapsed(self) -> float:
        """Seconds since the span began, on the clock its ``seconds``
        will be read from — for an event recorded inside the span that
        states the span's own duration (`recheck_narrow`)."""
        return max(time.perf_counter() - self._t0, 0.0)

    def end(self, **attrs) -> dict | None:
        """Record the span event and release it (idempotent — a request
        span may race completion against shutdown shedding; the first
        end wins). Safe to call from a thread other than the starter:
        only the starter's stack is touched, via the shared list. The
        annotation closes after the event is recorded, so what recording
        costs lies inside the span that pays it."""
        if not self._release():
            return None
        self.attrs.update(attrs)
        try:
            return _telemetry.record(
                "span",
                name=self.name,
                trace_id=self.context.trace_id,
                span_id=self.context.span_id,
                parent_id=self.parent_id,
                seconds=round(max(time.perf_counter() - self._t0, 0.0), 6),
                start_mono=self._start_mono,
                **self.attrs,
            )
        finally:
            self._close_annotation()

    def reparent(self, parent: SpanContext | None) -> "Span":
        """Join ``parent``'s trace as its child — for a span that learns
        what it belongs to only at its end (the batcher's wait for the
        request that ends it). None leaves the span as it is."""
        if parent is not None:
            self.context = SpanContext(parent.trace_id, self.context.span_id)
            self.parent_id = parent.span_id
        return self

    def drop(self) -> None:
        """Release the span WITHOUT an event: its annotation still shows
        in a profiler session, the telemetry stream stays quiet (the
        batcher's idle ticks)."""
        if self._release():
            self._close_annotation()


def start_span(
    name: str,
    *,
    parent: SpanContext | None = None,
    detached: bool = False,
    **attrs,
) -> Span:
    """Begin a span; the caller owns calling :meth:`Span.end`.

    ``parent`` overrides the ambient context (the innermost active span
    on this thread, else an :func:`adopt_context` adoption); with
    neither, the span roots a NEW trace. ``detached=True`` keeps the
    span off this thread's stack: it gets identity and parentage but
    does not become the ambient parent of subsequent sibling spans —
    request-lifetime roots use this so two requests submitted back to
    back from one thread do not nest.
    """
    if parent is None:
        parent = current_context()
    trace_id = parent.trace_id if parent is not None else _new_trace_id()
    ctx = SpanContext(trace_id, _new_span_id())
    stack = None if detached else _stack()
    sp = Span(
        name, ctx,
        parent.span_id if parent is not None else None,
        dict(attrs), stack,
    )
    if stack is not None:
        stack.append(sp)
    return sp


def span(
    name: str, *, parent: SpanContext | None = None, **attrs
) -> Span:
    """Span a block: ``with span("serve.dispatch", bucket=b) as sp: ...``.

    On an exception the span is stamped ``error=<type name>`` (matching
    ``telemetry.timed``) and the exception goes on; the span event is
    recorded either way. (The span is its own context manager: a
    generator-based one cost a fifth of a span on the serve path.)
    """
    return start_span(name, parent=parent, **attrs)


def current_context() -> SpanContext | None:
    """The innermost active span's context on this thread — else the
    context this thread :func:`adopt_context`-ed, else None. Hand it to
    a worker thread (or persist it) to keep one logical operation one
    trace."""
    stack = getattr(_LOCAL, "stack", None)
    if stack:
        return stack[-1].context
    return getattr(_LOCAL, "base", None)


def adopt_context(context: SpanContext | None) -> None:
    """Make ``context`` (a :func:`current_context` result from another
    thread, or a :class:`SpanContext` restored from a snapshot) this
    thread's ambient parent. Spans started here join that trace;
    events recorded here are stamped with it. ``None`` clears the
    adoption."""
    _LOCAL.base = context


def device_intervals(xplane_path: str) -> list:
    """The reader's side of the one clock: the chip's module runs in a
    profiler trace (``.xplane.pb``, gzipped or not) as ``(start, end)``
    seconds on THIS program's monotonic clock, in order — what
    `obs.timeline.attribute(device_intervals=)` takes as class
    ``device`` (`tools/stall_report.py --xplane`).

    Every ``mosaic.*`` annotation is an anchor: its start on the trace's
    clock less the ``t`` it carries is the offset between the two clocks;
    the median over the trace is taken. A trace with no such annotation
    (taken by another program, or outside any span) cannot be placed and
    gives an empty list."""
    import gzip
    import statistics

    from jax.profiler import ProfileData

    if xplane_path.endswith(".gz"):
        with gzip.open(xplane_path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(xplane_path)
    offsets, runs = [], []
    for plane in data.planes:
        for line in plane.lines:
            if plane.name.startswith("/device:"):
                if line.name == "XLA Modules":
                    runs += [
                        (float(ev.start_ns), float(ev.start_ns + ev.duration_ns))
                        for ev in line.events
                    ]
                continue
            for ev in line.events:
                if ev.name.startswith("mosaic."):
                    t = dict(ev.stats).get("t")
                    if t is not None:
                        offsets.append(float(ev.start_ns) - int(t))
    if not offsets:
        return []
    offset = statistics.median(offsets)
    return sorted(((a - offset) / 1e9, (b - offset) / 1e9) for a, b in runs)


class _Tracer:
    """The `runtime/telemetry.py` provider: stamps events, carries
    contexts across threads (``telemetry.current_trace``/
    ``adopt_trace`` delegate here so runtime modules never import
    obs)."""

    def ids(self) -> dict | None:
        ctx = current_context()
        if ctx is None:
            return None
        return {"trace_id": ctx.trace_id, "span_id": ctx.span_id}

    def current(self):
        return current_context()

    def adopt(self, context) -> None:
        adopt_context(context)

    def start_span(self, name: str, **kw):
        return start_span(name, **kw)


_TRACER = _Tracer()
_telemetry.register_tracer(_TRACER)
