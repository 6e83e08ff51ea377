"""Structured resilience telemetry.

Every escalation attempt, transient retry, fault injection, and
degradation emits one flat event dict here. Events always go to the
`mosaic_tpu.runtime` logger — but only when that logger is actually
enabled (see :func:`record`); tests and services additionally subscribe
with :func:`capture` to assert on (or export) the exact trail — the
acceptance contract is that resilience is *visible*, never silent.

Observability hooks (`mosaic_tpu/obs/`): this module stays the ONE
event spine, and the obs subsystem layers on top of it through two
registration points rather than a parallel pipeline:

- :func:`register_tracer` — the tracer stamps every event with the
  active ``trace_id``/``span_id`` (explicit fields win), and
  :func:`current_trace`/:func:`adopt_trace` let worker threads carry
  the caller's span context the same way :func:`current_sinks`/
  :func:`adopt_sinks` carry capture scopes;
- :func:`add_observer` — process-wide event observers (the obs metrics
  bridge) see every event after the thread-local sinks do.

Both are no-ops until ``mosaic_tpu.obs`` is imported, so the runtime
layer never depends on the observability layer.
"""

from __future__ import annotations

import contextlib
import itertools
import logging
import math
import os
import threading
import time

_LOCAL = threading.local()

#: process-wide event sequence — ``itertools.count`` increments under the
#: GIL, so concurrent recorders (watchdog workers, stream threads) still
#: get unique, strictly increasing numbers
_SEQ = itertools.count()

#: per-process incarnation id, minted once at import:
#: ``<start-unix-seconds:8 hex>-<pid>-<random:6 hex>`` — the identity
#: that stitches a fleet story back together. Every JSONL trail, flight-
#: recorder dump, snapshot sidecar, and ProgramStore sidecar is stamped
#: with it, so `tools/fleet_report.py` can merge the trails of a restart
#: storm (N child processes, N incarnations) into one logical timeline.
#: The leading hex timestamp makes incarnations of one host sort in
#: start order; the random suffix disambiguates pid reuse.
INCARNATION = (
    f"{int(time.time()):08x}-{os.getpid()}-{os.urandom(3).hex()}"
)


def incarnation() -> str:
    """This process's :data:`INCARNATION` id (stable for the process
    lifetime; a forked/relaunched process mints its own)."""
    return INCARNATION


def incarnation_event() -> dict:
    """One ``event="incarnation"`` meta dict anchoring this process to
    the wall clock: ``ts_mono`` and ``ts_epoch`` are sampled together,
    so a fleet reader can place any of this trail's monotonic stamps on
    the shared wall-clock axis (``ts_epoch + (e.ts_mono - ts_mono)``)
    — monotonic clocks are per-process and never comparable directly."""
    return {
        "event": "incarnation",
        "incarnation": INCARNATION,
        "pid": os.getpid(),
        "ts_mono": round(time.monotonic(), 6),
        "ts_epoch": round(time.time(), 6),
    }

#: the runtime event logger, resolved ONCE — ``utils.get_logger`` force-
#: installs a handler at INFO, which made every record() format and emit
#: a log line even with no sinks and no one reading; record() now guards
#: with ``isEnabledFor`` so an app must opt in (configure the logger or
#: call ``utils.get_logger``) before events cost any formatting
_LOGGER = logging.getLogger("mosaic_tpu.runtime")

#: registered by ``mosaic_tpu.obs.trace`` — an object with
#: ``ids() -> dict | None``, ``current() -> context | None``,
#: ``adopt(context) -> None`` and ``start_span(name, **kw) -> span``;
#: None until the obs subsystem is imported
_TRACER = None

#: process-wide event observers (``fn(evt) -> None``) — the obs metrics
#: bridge registers here; observers must be cheap and non-raising
_OBSERVERS: list = []


def _sinks() -> list:
    sinks = getattr(_LOCAL, "sinks", None)
    if sinks is None:
        sinks = _LOCAL.sinks = []
    return sinks


def current_sinks() -> list:
    """This thread's live sink list — hand it to :func:`adopt_sinks` on
    a worker thread so events recorded there still reach the caller's
    :func:`capture` scopes (the watchdog does this; list appends are
    GIL-atomic, so sharing is safe)."""
    return _sinks()


def adopt_sinks(sinks: list) -> None:
    """Make ``sinks`` (a :func:`current_sinks` result from another
    thread) this thread's sink list."""
    _LOCAL.sinks = sinks


def register_tracer(tracer) -> None:
    """Install the span-context provider (``mosaic_tpu.obs.trace`` calls
    this at import). ``tracer.ids()`` returns ``{"trace_id": ...,
    "span_id": ...}`` when a span is active on the calling thread."""
    global _TRACER
    _TRACER = tracer


def current_trace():
    """The calling thread's active span context (opaque; hand it to
    :func:`adopt_trace` on a worker), or None when no tracer is
    registered or no span is active."""
    return None if _TRACER is None else _TRACER.current()


def adopt_trace(context) -> None:
    """Adopt a :func:`current_trace` result on this thread so events
    recorded here attach to the caller's span (no-op without a
    tracer or with ``context=None``)."""
    if _TRACER is not None and context is not None:
        _TRACER.adopt(context)


def start_span(name: str, **kw):
    """``obs.trace.start_span`` for runtime modules, which never import
    obs; the caller owns ``.end()``. None without a tracer."""
    return None if _TRACER is None else _TRACER.start_span(name, **kw)


def add_observer(fn) -> None:
    """Register a process-wide event observer (``fn(evt)``); every
    :func:`record` call reaches it after the thread-local sinks."""
    if fn not in _OBSERVERS:
        _OBSERVERS.append(fn)


def remove_observer(fn) -> None:
    """Unregister an :func:`add_observer` observer (idempotent)."""
    if fn in _OBSERVERS:
        _OBSERVERS.remove(fn)


def record(event: str, **fields) -> dict:
    """Emit one structured event: ``{"event": event, "seq": n,
    "ts_mono": t, **fields}``.

    Fields must be plain JSON-able scalars/dicts so trails can be dumped
    into bench lines verbatim. ``seq`` is a per-process strictly
    increasing sequence number and ``ts_mono`` a monotonic-clock stamp:
    fault/recovery event streams are thereby TOTALLY ordered — tests
    assert ordering (a retry precedes its degradation; a snapshot save
    precedes the resume that reads it) instead of guessing from list
    position across capture scopes.

    When a tracer is registered (``mosaic_tpu.obs``) and a span is
    active on this thread, the event is stamped with ``trace_id``/
    ``span_id`` — explicitly passed fields win, so span-end events
    carry their own ids untouched.

    Hot-path cost contract: with no sinks, no observers, and the
    ``mosaic_tpu.runtime`` logger disabled, record() performs NO string
    formatting and emits nothing (pinned by tests/test_obs.py).
    """
    evt = {
        "event": event,
        "seq": next(_SEQ),
        "ts_mono": round(time.monotonic(), 6),
        **fields,
    }
    if _TRACER is not None and "trace_id" not in evt:
        ids = _TRACER.ids()
        if ids is not None:
            evt.update(ids)
    for sink in _sinks():
        sink.append(evt)
    for obs in _OBSERVERS:
        obs(evt)
    if _LOGGER.isEnabledFor(logging.INFO):
        _LOGGER.info("%s %s", event, fields)
    return evt


@contextlib.contextmanager
def timed(event: str, **fields):
    """Record ``event`` with a measured ``seconds`` field around the block.

    The streaming pipeline's per-stage accounting contract: every stage
    (ring build, compile, join loop, generator loop, narrow recheck)
    emits exactly one event whose ``seconds`` is non-negative wall time —
    benches embed the captured trail verbatim in their JSON artifacts.

    A block that raises still records its event — stamped with
    ``error=<exception type name>`` (and the exception re-raises), so a
    failed stage is distinguishable from a fast success in any trail.
    """
    t0 = time.perf_counter()
    err: str | None = None
    try:
        yield
    except BaseException as e:  # noqa: BLE001 — stamped and re-raised
        err = type(e).__name__
        raise
    finally:
        extra = {} if err is None else {"error": err}
        record(
            event,
            seconds=round(max(time.perf_counter() - t0, 0.0), 6),
            **fields,
            **extra,
        )


def summarize(
    events, event: str | None = None, key: str = "seconds"
) -> dict:
    """Percentile summary of one numeric field over recorded events.

    ``{"count", "p50", "p90", "p99", "mean", "max", "sum"}`` over
    ``e[key]`` for every event dict in ``events`` carrying the field
    (restricted to ``e["event"] == event`` when given); all values 0.0
    when nothing matches. The ONE percentile implementation every reader
    shares (request latencies, stage timings) — a p99 computed two
    different ad-hoc ways is two different metrics.

    Percentiles are explicit nearest-rank (``ceil(q*n) - 1`` on the
    sorted sample): the q-th percentile is the smallest value with at
    least ``q*n`` samples at or below it. The previous
    ``int(round(q*(n-1)))`` spelling rode Python's banker's rounding,
    which drifts ranks for small n (n=4 p50 returned the 3rd value, not
    the 2nd) — exact-rank tests in tests/test_obs.py pin the definition.
    """
    vals = [
        float(e[key])
        for e in events
        if key in e and (event is None or e.get("event") == event)
    ]
    if not vals:
        return {
            "count": 0, "p50": 0.0, "p90": 0.0, "p99": 0.0,
            "mean": 0.0, "max": 0.0, "sum": 0.0,
        }
    vals.sort()
    n = len(vals)

    def pct(q: float) -> float:
        # nearest-rank: smallest index covering ceil(q*n) samples
        return vals[min(n - 1, max(0, math.ceil(q * n) - 1))]

    return {
        "count": n,
        "p50": round(pct(0.50), 6),
        "p90": round(pct(0.90), 6),
        "p99": round(pct(0.99), 6),
        "mean": round(sum(vals) / n, 6),
        "max": round(vals[-1], 6),
        "sum": round(sum(vals), 6),
    }


@contextlib.contextmanager
def capture():
    """Collect every resilience event emitted in the block (thread-local).

    >>> with telemetry.capture() as events:
    ...     pip_join(...)
    >>> [e for e in events if e["event"] == "capacity_overflow"]
    """
    events: list[dict] = []
    _sinks().append(events)
    try:
        yield events
    finally:
        # detach by IDENTITY: list.remove compares by equality, and a
        # nested capture sees the same event dicts as its enclosing one
        # (both sinks receive every append) — equality-based removal
        # would detach the OUTER scope and leak the inner
        s = _sinks()
        for i in range(len(s) - 1, -1, -1):
            if s[i] is events:
                del s[i]
                break
