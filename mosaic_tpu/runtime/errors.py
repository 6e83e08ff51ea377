"""Typed error taxonomy for the runtime resilience layer.

Every failure a device join path can hit maps to one of three classes —
capacity (the bounded-shape contract overflowed), transient (the device
runtime hiccuped and the same call may succeed), and
degraded (the device path was abandoned and the f64 host oracle answered
instead). API boundaries raise these instead of returning raw ``-2``
sentinel rows or letting bare ``Exception``\\ s escape.
"""

from __future__ import annotations

import numpy as np


class MosaicRuntimeError(RuntimeError):
    """Base of every typed runtime-resilience error."""


class CapacityOverflow(MosaicRuntimeError):
    """A bounded-capacity device path overflowed and escalation could not
    (or was not allowed to) grow the caps to an exact answer.

    Carries the escalation trail so callers/telemetry can see every
    attempted cap set; ``overflow_count`` is the number of rows whose
    answer was still unknown at the last attempt.
    """

    def __init__(
        self,
        message: str,
        *,
        stage: str = "",
        caps: dict | None = None,
        attempts: int = 0,
        overflow_count: int = 0,
    ):
        super().__init__(message)
        self.stage = stage
        self.caps = dict(caps or {})
        self.attempts = attempts
        self.overflow_count = overflow_count


class TransientDeviceError(MosaicRuntimeError):
    """A device-runtime failure that may succeed on retry (the class
    fault injection raises synthetically)."""

    def __init__(self, message: str, *, site: str = ""):
        super().__init__(message)
        self.site = site


class StalledDeviceError(TransientDeviceError):
    """A blocking device operation exceeded its watchdog deadline.

    Raised by `runtime/watchdog.py` instead of letting a dispatch,
    ``block_until_ready`` or snapshot D2H hang forever. Subclassing
    :class:`TransientDeviceError` puts a stall on the same retry path as
    a dropped connection: bounded retry, then degradation or a typed
    failure — never a silent hang. ``elapsed_s`` is how long the operation had been
    blocked when the deadline fired.
    """

    def __init__(
        self, message: str, *, site: str = "", deadline_s: float = 0.0,
        elapsed_s: float = 0.0,
    ):
        super().__init__(message, site=site)
        self.deadline_s = deadline_s
        self.elapsed_s = elapsed_s


class Overloaded(MosaicRuntimeError):
    """The serving engine refused (or abandoned) a request under load.

    Raised by `mosaic_tpu/serve/admission.py` instead of queueing without
    bound: either the bounded request queue is full at admission
    (``reason="queue_full"``), the request's deadline expired before its
    results could be delivered (``reason="deadline"``), or the engine
    shut down with the request still queued (``reason="shutdown"``).
    Typed so callers can distinguish load shedding — retry later,
    against another replica — from a wrong answer, which this never is.
    """

    def __init__(
        self,
        message: str,
        *,
        reason: str = "",
        queue_depth: int = 0,
        capacity: int = 0,
        deadline_s: float = 0.0,
        elapsed_s: float = 0.0,
    ):
        super().__init__(message)
        self.reason = reason
        self.queue_depth = queue_depth
        self.capacity = capacity
        self.deadline_s = deadline_s
        self.elapsed_s = elapsed_s


class RasterDecodeError(MosaicRuntimeError, ValueError):
    """The native GeoTIFF engine rejected a file.

    Raised by :func:`mosaic_tpu.raster.read_raster` whenever
    ``mg_tiff_read`` returns a nonzero rc — the rc is mapped to the
    decoder's failure taxonomy (``native/src/tiff.cpp``) and carried
    alongside the path, so callers can distinguish "not a TIFF" from
    "unsupported layout" from plain IO failure. A decode failure is a
    property of the bytes on disk, never transient: it is excluded from
    the retry path by construction (``is_transient`` returns False).
    Also a ``ValueError`` because the decode path raised plain
    ``ValueError`` before the typed taxonomy existed — callers catching
    that keep working.
    """

    def __init__(self, message: str, *, path: str = "", rc: int = 0):
        super().__init__(message)
        self.path = path
        self.rc = rc


class RetryExhausted(MosaicRuntimeError):
    """The bounded transient-retry budget ran out without a success.

    ``last`` is the final underlying exception; ``attempts`` how many
    tries were made.
    """

    def __init__(self, message: str, *, attempts: int = 0, last=None):
        super().__init__(message)
        self.attempts = attempts
        self.last = last


class EpochLogCorrupt(MosaicRuntimeError):
    """A delta-log record INSIDE the valid prefix failed validation
    (unreadable sidecar, payload checksum mismatch, missing epoch in the
    sequence) while LATER records are intact.

    A corrupt *tail* is the expected kill-mid-write residue and is
    silently truncated (``epoch_log_truncated`` telemetry); corruption
    with valid successors means the bytes rotted or the directory was
    spliced — replay refuses rather than reconstruct a wrong index.
    """

    def __init__(self, message: str, *, log_dir: str = "", epoch: int = -1):
        super().__init__(message)
        self.log_dir = log_dir
        self.epoch = epoch


class EpochFingerprintMismatch(MosaicRuntimeError):
    """An epoch identity failed to line up: a delta record's ``prev``
    hash does not chain from its predecessor, a compacted snapshot's
    sealed prefix fingerprint disagrees with the surviving records, or a
    durable-stream resume presented an index from a DIFFERENT epoch than
    the snapshot was taken under. All are refusals — continuing would
    mix chip tables from two epochs into one answer.
    """

    def __init__(
        self, message: str, *, expected: str = "", actual: str = "",
        epoch: int = -1,
    ):
        super().__init__(message)
        self.expected = expected
        self.actual = actual
        self.epoch = epoch


#: substrings that mark an exception as transient — what a local PJRT
#: client can actually recover from by calling again (status
#: ``UNAVAILABLE`` / ``DEADLINE_EXCEEDED`` and connection-level drops);
#: matched case-insensitively against ``repr(exc)``. Deliberately NOT
#: here: ``INTERNAL``, ``RESOURCE_EXHAUSTED``, ``INVALID_ARGUMENT`` — XLA
#: and Mosaic report deterministic compile refusals under those, and a
#: retried-then-degraded compile failure is a host answer passed off as
#: a device run.
_TRANSIENT_MARKERS = (
    "unavailable",
    "deadline exceeded",
    "deadline_exceeded",
    "socket closed",
    "connection reset",
    "connection refused",
    "broken pipe",
)


def is_transient(exc: BaseException) -> bool:
    """Should this exception be retried?  `TransientDeviceError` always;
    other exceptions only when their text carries a known transient
    marker (programming errors like ValueError/TypeError never are)."""
    if isinstance(exc, TransientDeviceError):
        return True
    if isinstance(
        exc, (ValueError, TypeError, KeyError, AttributeError,
              RasterDecodeError)
    ):
        return False
    text = repr(exc).lower()
    return any(m in text for m in _TRANSIENT_MARKERS)


class DegradedResult(np.ndarray):
    """An ndarray view flagging a graceful-degradation result.

    Returned (instead of a plain array) when the device path failed past
    its retry budget and the f64 host oracle answered instead: values are
    exact, but the call did not run on the fast path. Behaves exactly
    like its base array everywhere else, so existing callers keep
    working; resilience-aware callers check ``getattr(r, "degraded",
    False)``.
    """

    degraded: bool = True

    @classmethod
    def wrap(
        cls, value, *, reason: str = "", attempts: int = 0,
        detail: dict | None = None,
    ) -> "DegradedResult":
        out = np.asarray(value).view(cls)
        out.reason = reason
        out.attempts = attempts
        out.detail = dict(detail or {})
        return out

    def __array_finalize__(self, obj):
        if obj is None:
            return
        self.reason = getattr(obj, "reason", "")
        self.attempts = getattr(obj, "attempts", 0)
        self.detail = getattr(obj, "detail", {})
