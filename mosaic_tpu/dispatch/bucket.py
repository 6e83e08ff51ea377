"""Shape bucketing: the bounded-compile-cache contract of the dispatch
core.

XLA specializes one executable per input shape, so dispatching raw
request shapes would compile an unbounded program population (and a cold
compile on the latency path is a multi-second p99 spike — the one thing
an online engine must never do). Every device dispatch therefore runs at
a shape drawn from a small fixed ladder: a request (or coalesced
micro-batch) of ``n`` rows is padded up to ``bucket_for(n)``, and
:meth:`DispatchCore.warmup` precompiles every (bucket, index, mesh)
program before traffic arrives. After warmup the dispatch path can only
replay cached executables — the serve tests pin "zero new compile
signatures after warmup" over randomized request sizes.

Pad rows duplicate the batch's first row: they flow through the probe
like any other point (no special-casing in the kernel, no risk of a
reserved coordinate colliding with real data) and are sliced off before
scatter-back, so they can never reach a caller. Caps sized at the full
bucket make tier overflow structurally impossible — a padded dispatch
is exact by construction, never escalates, and therefore never changes
its compile signature at runtime.

Compile accounting is two-layered: :func:`dispatch_signature` is the
deterministic cache key the core counts (signatures after warmup ==
buckets touched), and :func:`backend_compiles` reads a process-wide
XLA compile counter (best effort, via jax's monitoring events) so the
bench can report REAL compiles, not just intended ones.
"""

from __future__ import annotations

import dataclasses

import numpy as np

#: default ladder bounds: 64 covers single interactive requests, 64k is
#: one comfortable device micro-batch (the batcher's max coalesced size
#: must not exceed the top bucket)
DEFAULT_MIN_BUCKET = 64
DEFAULT_MAX_BUCKET = 65536


@dataclasses.dataclass(frozen=True)
class BucketLadder:
    """Geometric pad-to-bucket ladder (powers of ``growth`` from
    ``min_bucket`` to ``max_bucket`` inclusive)."""

    min_bucket: int = DEFAULT_MIN_BUCKET
    max_bucket: int = DEFAULT_MAX_BUCKET
    growth: int = 2

    def __post_init__(self):
        if self.min_bucket < 1 or self.max_bucket < self.min_bucket:
            raise ValueError(
                f"invalid ladder bounds [{self.min_bucket}, "
                f"{self.max_bucket}]"
            )
        if self.growth < 2:
            raise ValueError(f"growth must be >= 2, got {self.growth}")

    @property
    def buckets(self) -> tuple:
        out = []
        b = self.min_bucket
        while b < self.max_bucket:
            out.append(b)
            b *= self.growth
        out.append(self.max_bucket)
        return tuple(out)

    def bucket_for(self, n: int) -> int:
        """Smallest bucket >= ``n`` (raises for n > max_bucket: the
        batcher sizes its coalescing window so this cannot happen for
        admitted traffic)."""
        if n > self.max_bucket:
            raise ValueError(
                f"request of {n} rows exceeds the top bucket "
                f"{self.max_bucket} — raise max_bucket or split upstream"
            )
        b = self.min_bucket
        while b < n:
            b *= self.growth
        return min(b, self.max_bucket)

    def pad(self, points: np.ndarray) -> tuple[np.ndarray, int]:
        """(padded (B, 2) f64 copy, original n). Pad rows repeat row 0
        (inert: results past ``n`` are sliced off before scatter-back)."""
        pts = np.asarray(points, dtype=np.float64)
        n = int(pts.shape[0])
        b = self.bucket_for(max(n, 1))
        if n == b:
            return pts, n
        out = np.empty((b, 2), dtype=np.float64)
        out[:n] = pts
        out[n:] = pts[0] if n else 0.0
        return out, n


def mesh_key(mesh) -> "tuple | None":
    """Deterministic identity of a mesh for cache keys: axis names,
    axis sizes, and the flat device-id tuple. ``None`` stays ``None``
    (single-device dispatch)."""
    if mesh is None:
        return None
    return (
        tuple(mesh.axis_names),
        tuple(mesh.devices.shape),
        tuple(int(d.id) for d in mesh.devices.flat),
    )


def dispatch_signature(
    bucket: int, index, *, writeback: str,
    found_cap: int | None, heavy_cap: int | None,
    probe: str = "scatter", convex_cap: int | None = None,
    mesh=None,
) -> tuple:
    """The deterministic compile-cache key of one dispatch: the full
    static-argument set of the jitted join plus the padded shape, the
    index identity, and the placement (``(bucket, index, mesh)``). Two
    dispatches with equal signatures replay the same executable; the
    core asserts the signature set stops growing after
    :meth:`DispatchCore.warmup`."""
    return (
        int(bucket), id(index), writeback, found_cap, heavy_cap,
        probe, convex_cap, mesh_key(mesh),
    )


_METER = {"installed": False, "count": 0, "cache_hits": 0}


def _install_meter() -> None:
    if _METER["installed"]:
        return
    _METER["installed"] = True
    try:
        from jax._src import monitoring

        def _on_duration(name: str, dur: float, **kw) -> None:
            if name.endswith("backend_compile_duration"):
                _METER["count"] += 1

        def _on_event(name: str, **kw) -> None:
            if name.endswith("compilation_cache/cache_hits"):
                _METER["cache_hits"] += 1

        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_event)
        _METER["available"] = True
    except Exception:  # lint: broad-except-ok (xla monitoring listener is optional; meter reports unavailable)
        _METER["available"] = False


def backend_compiles() -> int | None:
    """Process-wide XLA backend-compile count since the meter was first
    read (monotonic; diff two reads to scope a region). ``None`` when
    this jax build exposes no monitoring hook — callers fall back to
    signature counting, which upper-bounds real compiles.

    jax times the compile stage around its persistent-cache lookup, so
    a program loaded from a warm `jax_compilation_cache_dir` fires the
    same duration event as one XLA compiled; those loads are subtracted
    here (and counted by :func:`compile_cache_hits`) — this number is
    what the backend actually compiled."""
    _install_meter()
    if not _METER.get("available"):
        return None
    return _METER["count"] - _METER["cache_hits"]


def compile_cache_hits() -> int | None:
    """Programs served from JAX's persistent compilation cache instead
    of being compiled (0 when no cache directory is configured)."""
    _install_meter()
    return _METER["cache_hits"] if _METER.get("available") else None
