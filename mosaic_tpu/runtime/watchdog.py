"""Monotonic-deadline watchdog around blocking device operations.

A hung scan dispatch, ``block_until_ready`` or snapshot D2H on a sick
device blocks the caller forever — the one failure mode the retry layer
cannot see, because no exception ever surfaces. :func:`guard` runs the
blocking callable on a daemon worker thread and waits against a
monotonic deadline; when the deadline fires it raises a typed
:class:`StalledDeviceError` (a :class:`TransientDeviceError` subclass,
so :func:`runtime.retry.call_with_retry` classifies and retries it like
any dropped connection). The abandoned worker finishes or dies with the
process — its result is discarded either way.

Deadlines resolve per site, most specific first:

1. ``MOSAIC_WATCHDOG_<SITE>`` — site name uppercased, dots/dashes to
   underscores (``stream.scan_step`` -> ``MOSAIC_WATCHDOG_STREAM_SCAN_STEP``),
   seconds; ``0`` disables the watchdog for that site;
2. ``MOSAIC_WATCHDOG_S`` — process-wide default, seconds;
3. the call's ``default_s`` argument (``None`` = no deadline).

With no deadline resolved and no stall injection active the callable
runs inline on the caller's thread — the production fast path pays one
env lookup and one thread-local read, no thread hop.

Fault-plan interplay: :func:`guard` consults the caller thread's fault
plans BEFORE dispatching (``faults.maybe_fail`` for transient errors and
``faults.planned_stall`` for simulated stalls), because plans are
thread-local and would be invisible from the worker. An injected stall
sleeps on the worker so the deadline genuinely fires mid-block, exactly
like a real hang.
"""

from __future__ import annotations

import os
import threading
import time

from . import faults, telemetry
from .errors import StalledDeviceError


def _env_seconds(name: str) -> float | None:
    raw = os.environ.get(name)
    if raw is None:
        return None
    try:
        return float(raw)
    except ValueError:
        return None


def env_name(site: str) -> str:
    """The per-site deadline env var for ``site``."""
    safe = "".join(
        c if c.isalnum() else "_" for c in site.upper()
    )
    return f"MOSAIC_WATCHDOG_{safe}"


def deadline_for(site: str, default_s: float | None = None) -> float | None:
    """Resolve the watchdog deadline for ``site`` in seconds.

    Per-site env beats the process-wide ``MOSAIC_WATCHDOG_S`` beats
    ``default_s``; a resolved value <= 0 disables the watchdog (None).
    """
    v = _env_seconds(env_name(site))
    if v is None:
        v = _env_seconds("MOSAIC_WATCHDOG_S")
    if v is None:
        v = default_s
    if v is None or v <= 0:
        return None
    return float(v)


def _handoff(site: str, leg: str):
    """One leg of the hand-off to the worker thread and back as a detached
    span (it starts on one thread and ends on the other), or None.
    Tracing never fails a guarded call or its wake-up: neither this nor
    :func:`_end` raises."""
    try:
        return telemetry.start_span(
            "dispatch.guard.handoff", detached=True, site=site, leg=leg
        )
    except Exception:  # lint: broad-except-ok (observability must not turn into a missed wake-up: the leg goes unrecorded)
        return None


def _end(span) -> None:
    if span is not None:
        try:
            span.end()
        except Exception:  # lint: broad-except-ok (as in _handoff: the dispatch's answer outranks its span)
            pass


def guard(site: str, fn, *args, default_s: float | None = None, **kwargs):
    """Run blocking ``fn(*args, **kwargs)`` under the site's deadline.

    Raises :class:`StalledDeviceError` when the deadline fires while
    ``fn`` is still blocked; returns ``fn``'s value (or re-raises its
    exception on the caller thread) otherwise. Fault hooks
    (``maybe_fail`` + planned stalls) are evaluated on the CALLER thread
    — plans are thread-local — then the stall is simulated on the
    worker so the deadline mechanism is exercised for real.
    """
    faults.maybe_fail(site)
    stall_s = faults.planned_stall(site)
    deadline = deadline_for(site, default_s)
    if deadline is None and not stall_s:
        return fn(*args, **kwargs)

    done = threading.Event()
    box: dict = {}
    sinks = telemetry.current_sinks()  # capture scopes span the worker
    trace = telemetry.current_trace()  # the active span does too

    def work():
        try:
            telemetry.adopt_sinks(sinks)
            telemetry.adopt_trace(trace)
            _end(out)
            if stall_s:
                time.sleep(stall_s)
            box["value"] = fn(*args, **kwargs)
        except BaseException as e:  # noqa: BLE001 — re-raised on caller
            box["error"] = e
        finally:
            box["back"] = _handoff(site, "back")
            done.set()

    t0 = time.monotonic()
    worker = threading.Thread(  # lint: thread-context-adoption-ok (plans stay caller-side: maybe_fail/planned_stall run pre-dispatch, and adopting in the worker would double-count nested sites against exact injection budgets)
        target=work, name=f"mosaic-watchdog:{site}", daemon=True
    )
    out = _handoff(site, "out")
    worker.start()
    if done.wait(timeout=deadline):
        _end(box["back"])
    else:
        elapsed = time.monotonic() - t0
        telemetry.record(
            "watchdog_stall", site=site,
            deadline_s=round(float(deadline), 3),
            elapsed_s=round(elapsed, 3),
        )
        raise StalledDeviceError(
            f"{site}: blocking device operation exceeded its "
            f"{deadline:.3f}s watchdog deadline "
            f"(set {env_name(site)} to tune)",
            site=site, deadline_s=float(deadline), elapsed_s=elapsed,
        )
    if "error" in box:
        raise box["error"]
    return box["value"]
