"""The request lifecycle: submit -> future -> padded, shape-bucketed
device dispatch with the full runtime resilience stack wired in.

One :class:`ServeEngine` owns a resident
:class:`~mosaic_tpu.sql.join.ChipIndex` and turns concurrent
point-in-polygon requests into micro-batched dispatches on the unified
dispatch core (`mosaic_tpu/dispatch` — the same executable cache
`pip_join` and the stream/raster frontends use, so a server and a batch
job in one process share compiles). The pipeline per batch:

    admit (quarantine + backpressure, `serve/admission.py`)
      -> coalesce (max-batch / max-wait window, `serve/batcher.py`)
      -> pad to bucket + execute through `dispatch.DispatchCore` under
         the ``serve.dispatch`` watchdog/fault site, transient retry,
         host-oracle degradation (`dispatch.guarded_call` owns the
         composition; the engine only names the site and the deadline)
      -> scatter back per request, shedding only deadline-expired ones

- `runtime/faults.py` sites ``serve.admit`` / ``serve.batch`` /
  ``serve.dispatch`` make every failure mode injectable from tests.
- every stage emits ``serve_stage`` `telemetry.timed` events; per-request
  latency lands in ``serve_request`` events (`telemetry.summarize` turns
  them into the bench's p50/p99).

Compile discipline is the core's: caps fixed at the full (per-shard)
bucket, one signature per `(bucket, index, mesh)`, :meth:`warmup`
precompiling every rung. After warmup the signature set is frozen — a
dispatch introducing a new signature emits a ``serve_compile`` event and
counts in ``metrics()["cold_compiles"]`` (the serve tests pin this at
zero). Pass ``mesh=`` (or set ``MOSAIC_MESH``) to place every dispatch
data-parallel over a device mesh with the index replicated —
bit-identical results at any device count.
"""

from __future__ import annotations

import threading
from concurrent.futures import Future

import numpy as np

from ..dispatch import (
    BucketLadder,
    DispatchCore,
    backend_compiles,
    resolve_program_store,
)
from ..obs import trace as _trace
from ..runtime import telemetry as _telemetry
from ..runtime.errors import DegradedResult
from ..tune.resolve import resolve_knobs
from .admission import AdmissionController
from .batcher import MicroBatcher

import jax.numpy as jnp


class _MixedOut:
    """Result view for a mixed-kind batch: per-request answer segments
    keyed by their ``(start, stop)`` row interval in the concatenated
    batch. Requests are never split across batches, so the batcher's
    scatter-back slices ``out[off : off + req.n]`` land exactly on these
    keys — each request reads its own wire shape ((n,) int32 for PIP,
    (n, 2k) f64 for KNN) with no common dtype forced on the batch.

    ``degraded`` is batch-level and conservative: if ANY segment fell
    back to the host oracle, every request in the batch is flagged
    (values are exact either way — degradation changes provenance, not
    answers)."""

    def __init__(self, segments, *, degraded=False, reason=None, attempts=0):
        self._segments = segments
        self.degraded = bool(degraded)
        self.reason = reason
        self.attempts = attempts

    def __getitem__(self, sl: slice):
        seg = self._segments.get((sl.start, sl.stop))
        if seg is None:
            raise KeyError(
                f"no batch segment at rows [{sl.start}, {sl.stop})"
            )
        return seg


class ServeEngine:
    """Online serving engine over a resident chip index.

    >>> engine = ServeEngine(index, h3, 9, bounds=bbox)
    >>> engine.warmup()
    >>> fut = engine.submit(points)          # (n, 2) -> Future
    >>> rows = fut.result(timeout=1.0)       # (n,) int32, -1 = no match
    """

    def __init__(
        self,
        index,
        index_system,
        resolution: int,
        *,
        ladder: BucketLadder | None = None,
        max_batch_rows: int | None = None,
        max_wait_s: float = 0.002,
        queue_capacity: int = 256,
        default_deadline_s: float | None = 1.0,
        bounds: tuple | None = None,
        park_point: np.ndarray | None = None,
        writeback: str | None = None,
        cell_dtype=None,
        watchdog_grace_s: float = 0.5,
        probe: str | None = None,
        mesh=None,
        profile=None,
        program_store=None,
        knn=None,
        knn_lane: str | None = None,
    ):
        self.index = index
        self.index_system = index_system
        self.resolution = index_system.resolution_arg(resolution)
        # profile-consumed knobs resolve HERE, at the host entry point,
        # with the one documented precedence: explicit arg > env knob >
        # TuningProfile > built-in default (mosaic_tpu/tune/resolve.py)
        knobs = resolve_knobs(
            "serve_engine", profile,
            explicit={
                "probe": probe, "writeback": writeback,
                "bucket_min": None, "bucket_max": None,
                "knn_lane": knn_lane,
            },
            defaults={
                "probe": "scatter", "writeback": "scatter",
                "bucket_min": None, "bucket_max": None,
                "knn_lane": None,
            },
        )
        probe, writeback = knobs["probe"], knobs["writeback"]
        if ladder is None and (knobs["bucket_min"] or knobs["bucket_max"]):
            ladder = BucketLadder(
                min_bucket=int(knobs["bucket_min"] or 64),
                max_bucket=int(knobs["bucket_max"] or 65536),
            )
        self.ladder = ladder or BucketLadder()
        self.writeback = writeback
        self.cell_dtype = cell_dtype
        self.watchdog_grace_s = float(watchdog_grace_s)
        # a hot_swap rebinds (ladder, core, index) as one unit; the lock
        # only guards the rebind and the dispatch-side snapshot of the
        # pair, never the dispatch itself
        self._swap_lock = threading.Lock()
        # the core owns probe resolution (force-lane env folds
        # once, so the compile-cache signature stays honest), caps,
        # signature accounting, the guarded execute path, and (when a
        # store is bound — explicit arg or MOSAIC_PROGRAM_STORE) the
        # AOT program persistence that makes warmup a load, not a
        # compile storm
        self.program_store = resolve_program_store(program_store)
        self.core = DispatchCore(
            index, index_system, resolution, ladder=self.ladder,
            writeback=writeback, probe=probe,
            cell_dtype=cell_dtype, mesh=mesh,
            on_cold_compile=self._on_cold_compile,
            program_store=self.program_store,
        )
        self.probe = self.core.probe
        self.mesh = self.core.mesh
        # optional KNN frontend riding the same queue/batcher: a
        # KNNIndex builds a fresh frontend sharing the engine's mesh,
        # program store, and cold-compile tripwire; an existing
        # KNNFrontend is adopted as-is (tests pre-warm one)
        self.knn_lane = knobs["knn_lane"]
        self.knn = self._build_knn(knn, self.knn_lane)

        self.admission = AdmissionController(
            capacity=queue_capacity,
            default_deadline_s=default_deadline_s,
            bounds=bounds,
            park_point=park_point,
            find_park=self._derive_park,
        )
        self.batcher = MicroBatcher(
            self.admission,
            self._dispatch,
            max_batch_rows=(
                min(self.ladder.max_bucket, 16384)
                if max_batch_rows is None
                else int(max_batch_rows)
            ),
            max_wait_s=max_wait_s,
        )
        if self.batcher.max_batch_rows > self.ladder.max_bucket:
            raise ValueError(
                f"max_batch_rows {self.batcher.max_batch_rows} exceeds the "
                f"top bucket {self.ladder.max_bucket}"
            )
        self._closed = False
        # PIP dispatches and the rows they were padded to (batcher thread
        # only)
        self._dispatches = 0
        self._padded_rows = 0
        self.batcher.start()

    # ----------------------------------------------------------- public

    def submit(self, points, *, deadline_s: float | None = None):
        """Enqueue one request; returns its ``concurrent.futures.Future``
        resolving to the (n,) int32 matches (:class:`Overloaded` when
        shed). Raises :class:`Overloaded` at admission when the queue is
        full."""
        if self._closed:
            raise RuntimeError("engine is closed")
        pts = np.asarray(points, dtype=np.float64)
        if pts.ndim != 2 or pts.shape[1] != 2:
            raise ValueError(f"expected (n, 2) points, got {pts.shape}")
        if pts.shape[0] > self.ladder.max_bucket:
            raise ValueError(
                f"request of {pts.shape[0]} rows exceeds the top bucket "
                f"{self.ladder.max_bucket} — split it upstream"
            )
        return self.admission.admit(pts, deadline_s=deadline_s).future

    def join(self, points, *, deadline_s: float | None = None, timeout=None):
        """Synchronous convenience wrapper: submit and wait."""
        return self.submit(points, deadline_s=deadline_s).result(timeout)

    def submit_knn(self, points, k: int, *, deadline_s: float | None = None):
        """Enqueue one k-nearest-neighbour request; returns a Future
        resolving to a :class:`~mosaic_tpu.knn.frontend.KNNAnswer` with
        (n, k) ``ids``/``distance`` arrays (:class:`Overloaded` when
        shed). KNN requests ride the SAME admission queue, deadline
        budget, micro-batch window, and shed taxonomy as PIP traffic —
        the dispatch splits a mixed batch by ``Request.kind`` and each
        family keeps its exact answers. Quarantined (non-finite /
        out-of-bounds) rows answer ``ids=-1, distance=inf``."""
        if self._closed:
            raise RuntimeError("engine is closed")
        if self.knn is None:
            raise RuntimeError(
                "engine has no KNN frontend — pass knn= at construction "
                "or hot_swap(knn=...)"
            )
        k = int(k)
        if k < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        pts = np.asarray(points, dtype=np.float64)
        if pts.ndim != 2 or pts.shape[1] != 2:
            raise ValueError(f"expected (n, 2) points, got {pts.shape}")
        if pts.shape[0] > self.ladder.max_bucket:
            raise ValueError(
                f"request of {pts.shape[0]} rows exceeds the top bucket "
                f"{self.ladder.max_bucket} — split it upstream"
            )
        req = self.admission.admit(pts, deadline_s=deadline_s, kind="knn", k=k)
        return _decode_knn_future(req, k)

    def join_knn(
        self, points, k: int, *, deadline_s: float | None = None, timeout=None
    ):
        """Synchronous convenience wrapper: submit_knn and wait."""
        return self.submit_knn(points, k, deadline_s=deadline_s).result(
            timeout
        )

    def warmup(self) -> dict:
        """Precompile every ladder bucket against the resident index.

        Runs the exact dispatch path (cell assignment + jitted probe) on
        an inert full-bucket batch per rung, so the first real request
        at any admitted shape replays a cached executable. Returns
        ``{"buckets": ..., "seconds": ..., "signatures": ...}``; after
        this, any dispatch that still introduces a new compile signature
        is counted in ``metrics()["cold_compiles"]`` (and emits a
        ``serve_compile`` event) — the bounded-compile contract's
        tripwire."""
        t0 = backend_compiles()
        total = 0.0
        with _telemetry.capture() as events, _trace.span(
            "serve.warmup", buckets=len(self.ladder.buckets)
        ):
            for b in self.ladder.buckets:
                pts = np.zeros((b, 2), dtype=np.float64)
                with _telemetry.timed(
                    "serve_stage", stage="warmup", bucket=b
                ):
                    self.core.execute_padded(pts)
            if self.knn is not None:
                knn_stats = self.knn.warmup()
        total = sum(
            e["seconds"]
            for e in events
            if e.get("stage") == "warmup" and "seconds" in e
        )
        self.core.freeze()
        t1 = backend_compiles()
        out = {
            "buckets": len(self.ladder.buckets),
            "seconds": round(total, 4),
            "signatures": len(self.core.signatures),
        }
        if t0 is not None and t1 is not None:
            out["backend_compiles"] = t1 - t0
        if self.program_store is not None:
            out["aot"] = dict(self.core.aot_stats)
        if self.knn is not None:
            out["knn"] = knn_stats
        _telemetry.record("serve_warmup", **out)
        return out

    def hot_swap(
        self,
        index=None,
        *,
        profile=None,
        resolution: int | None = None,
        probe: str | None = None,
        writeback: str | None = None,
        ladder: BucketLadder | None = None,
        knn=None,
        knn_lane: str | None = None,
    ) -> dict:
        """Swap in a new index and/or `TuningProfile` without dropping
        the engine: a NEW dispatch core is built off to the side, its
        ladder rungs precompiled and its signature set frozen
        (`DispatchCore.warmup`), and only then is ``(ladder, core,
        index)`` rebound as one unit — requests in flight finish on the
        old core, requests after the swap replay cached executables.
        Zero cold compiles after the swap is enforced by the existing
        ``freeze()`` tripwire: any post-swap dispatch that still compiles
        counts in ``metrics()["cold_compiles"]``.

        Knob precedence matches the constructor (explicit > env > profile
        > default), with the engine's CURRENT settings as the defaults —
        a profile-less ``hot_swap(index)`` swaps the index and keeps the
        tuning. Returns the new core's warmup stats."""
        index = self.index if index is None else index
        knobs = resolve_knobs(
            "serve_engine.hot_swap", profile,
            explicit={
                "resolution": resolution,
                "probe": probe, "writeback": writeback,
                "bucket_min": None, "bucket_max": None,
            },
            defaults={
                "resolution": self.resolution,
                "probe": self.core.probe, "writeback": self.writeback,
                "bucket_min": None, "bucket_max": None,
            },
        )
        new_resolution = self.index_system.resolution_arg(knobs["resolution"])
        if ladder is None:
            if knobs["bucket_min"] or knobs["bucket_max"]:
                ladder = BucketLadder(
                    min_bucket=int(knobs["bucket_min"] or 64),
                    max_bucket=int(knobs["bucket_max"] or 65536),
                )
            else:
                ladder = self.ladder
        with _trace.span(
            "serve.hot_swap", buckets=len(ladder.buckets),
            profiled=profile is not None,
        ), _telemetry.timed("serve_stage", stage="hot_swap"):
            core = DispatchCore(
                index, self.index_system, new_resolution, ladder=ladder,
                writeback=knobs["writeback"],
                probe=knobs["probe"], cell_dtype=self.cell_dtype,
                mesh=self.mesh, on_cold_compile=self._on_cold_compile,
                program_store=self.program_store,
            )
            stats = core.warmup()  # precompiles every rung, then freezes
            # a new KNN index swaps the same way: frontend built and
            # warmed off to the side, rebound atomically with the core
            # (in-flight mixed batches already hold their snapshot)
            new_knn = self.knn
            if knn is not None:
                new_knn = self._build_knn(
                    knn, knn_lane or self.knn_lane
                )
                stats["knn"] = new_knn.warmup()
            with self._swap_lock:
                self.index = index
                self.resolution = new_resolution
                self.ladder = ladder
                self.core = core
                self.knn = new_knn
                self.writeback = knobs["writeback"]
                self.probe = core.probe
                # keep the coalescing window inside the new ladder's span
                self.batcher.max_batch_rows = min(
                    self.batcher.max_batch_rows, ladder.max_bucket
                )
        _telemetry.record("serve_swap", **stats)
        return stats

    def metrics(self) -> dict:
        """Cumulative counters of this engine: outcomes, and the batching
        and dispatch counted where they happen (what each answers for an
        operator: docs/ARCHITECTURE.md, "Operator counters")."""
        a, b = self.admission.metrics, self.batcher.metrics
        out = dict(a)
        out.update(b)
        out["shed"] = a["shed_queue_full"] + b["shed_deadline"]
        out["quarantined"] = a["quarantined_rows"]
        out["queue_depth"] = self.admission.depth()
        out["compile_signatures"] = len(self.core.signatures)
        out["cold_compiles"] = self.core.cold_compiles
        out["compacted"] = self.core.compacted(self.core.ladder.max_bucket)
        out["tier2_compacted"] = self.core.tier2_compacted(
            self.core.ladder.max_bucket
        )
        out["dispatches"] = self._dispatches
        out["padded_rows"] = self._padded_rows
        out["h2d_bytes"] = self.core.transfer_bytes["h2d"]
        out["d2h_bytes"] = self.core.transfer_bytes["d2h"]
        if self.knn is not None:
            out.update(self.knn.metrics())
            out["cold_compiles"] += self.knn.cold_compiles
        out["occupancy_mean"] = round(
            b["occupancy_sum"] / b["batches"], 4
        ) if b["batches"] else 0.0
        return out

    def close(self, timeout: float = 5.0) -> None:
        """Stop the batcher; queued requests are shed
        (``reason="shutdown"``)."""
        if not self._closed:
            self._closed = True
            self.batcher.stop(timeout)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # --------------------------------------------------------- dispatch

    def _build_knn(self, knn, lane):
        """Wrap a KNNIndex in a frontend sharing the engine's mesh,
        program store, and cold-compile tripwire; pass a ready-made
        frontend through unchanged; None stays None."""
        if knn is None:
            return None
        from ..knn.frontend import KNNFrontend

        if isinstance(knn, KNNFrontend):
            return knn
        return KNNFrontend(
            knn,
            lane=lane or "ring",
            mesh=self.mesh,
            program_store=self.program_store,
            on_cold_compile=self._on_cold_compile,
        )

    def _dispatch(self, points: np.ndarray, deadline_hint=None, reqs=None):
        """Batcher callback: pad, dispatch with resilience, unpad.
        Returns ``(results, occupancy)`` — a plain (n,) array for a
        uniform PIP batch, a :class:`_MixedOut` segment view when the
        batch carries KNN requests."""
        # snapshot the swap unit so a concurrent hot_swap can never pad
        # with one ladder and execute on the other core
        with self._swap_lock:
            ladder, core, knn = self.ladder, self.core, self.knn
        if reqs is not None and any(r.kind == "knn" for r in reqs):
            return self._dispatch_mixed(
                ladder, core, knn, points, deadline_hint, reqs
            )
        padded, n = self._pad(ladder, points)
        bucket = padded.shape[0]
        with _trace.span(
            "serve.dispatch", bucket=bucket, rows=n,
        ), _telemetry.timed(
            "serve_stage", stage="dispatch", bucket=bucket, rows=n,
        ):
            out = self._dispatch_resilient(core, padded, deadline_hint)
        occupancy = n / bucket
        return out[:n], occupancy

    def _pad(self, ladder, points: np.ndarray):
        """Pad one PIP dispatch to its bucket, counted where it happens:
        ``padded_rows / dispatches`` against ``batched_rows`` is the
        occupancy the ladder costs."""
        with _trace.span("serve.pad", rows=int(points.shape[0])) as sp:
            padded, n = ladder.pad(points)
            sp.set(bucket=int(padded.shape[0]))
        self._dispatches += 1
        self._padded_rows += int(padded.shape[0])
        return padded, n

    def _dispatch_mixed(self, ladder, core, knn, points, deadline_hint, reqs):
        """Split a mixed batch by request kind: ALL PIP rows go through
        one padded core dispatch (their co-batching benefit is
        unchanged), KNN rows group by k into one frontend dispatch each.
        Answers come back as a :class:`_MixedOut` keyed by each request's
        row interval; occupancy is the rows-weighted mean over the
        device dispatches actually issued."""
        bounds, off = [], 0
        for r in reqs:
            bounds.append((r, off, off + r.n))
            off += r.n
        segs = {}
        degraded, reason, attempts = False, None, 0
        occ_rows, rows_total = 0.0, 0

        pip = [(r, a, b) for (r, a, b) in bounds if r.kind != "knn"]
        if pip:
            pts = np.concatenate([points[a:b] for (_r, a, b) in pip])
            padded, n = self._pad(ladder, pts)
            bucket = padded.shape[0]
            with _trace.span(
                "serve.dispatch", bucket=bucket, rows=n,
            ), _telemetry.timed(
                "serve_stage", stage="dispatch", bucket=bucket, rows=n,
            ):
                out = self._dispatch_resilient(core, padded, deadline_hint)
            if isinstance(out, DegradedResult):
                degraded, reason, attempts = True, out.reason, out.attempts
            vals = np.asarray(out[:n])
            o = 0
            for (r, a, b) in pip:
                segs[(a, b)] = vals[o : o + r.n]
                o += r.n
            occ_rows += (n / bucket) * n
            rows_total += n

        knn_reqs = [(r, a, b) for (r, a, b) in bounds if r.kind == "knn"]
        if knn_reqs:
            if knn is None:
                raise RuntimeError(
                    "KNN request admitted but the engine has no KNN frontend"
                )
            default_s = (
                None
                if deadline_hint is None
                else max(float(deadline_hint), 0.05) + self.watchdog_grace_s
            )
            by_k: dict[int, list] = {}
            for item in knn_reqs:
                by_k.setdefault(item[0].k, []).append(item)
            for k, group in sorted(by_k.items()):
                pts = np.concatenate([points[a:b] for (_r, a, b) in group])
                n = int(pts.shape[0])
                with _trace.span(
                    "serve.dispatch", rows=n, kind="knn", k=k,
                ), _telemetry.timed(
                    "serve_stage", stage="dispatch", rows=n,
                    kind="knn", k=k,
                ):
                    out, occ = knn.dispatch(pts, k, default_s=default_s)
                if isinstance(out, DegradedResult):
                    degraded, reason, attempts = (
                        True, out.reason, out.attempts
                    )
                vals = np.asarray(out)
                o = 0
                for (r, a, b) in group:
                    segs[(a, b)] = vals[o : o + r.n]
                    o += r.n
                occ_rows += float(occ) * n
                rows_total += n

        occupancy = occ_rows / rows_total if rows_total else 1.0
        view = _MixedOut(
            segs, degraded=degraded, reason=reason, attempts=attempts
        )
        return view, occupancy

    def _on_cold_compile(self, bucket: int, signatures: int) -> None:
        """Core callback: a post-warmup dispatch introduced a new
        compile signature — the bounded-compile contract's tripwire."""
        _telemetry.record(
            "serve_compile", bucket=bucket, signatures=signatures,
        )

    def _dispatch_resilient(self, core, padded, deadline_hint) -> np.ndarray:
        """The core's guarded execute under the batch's deadline: the
        ``serve.dispatch`` watchdog site, transient retry, and exact-f64
        host-oracle degradation — all composed by the dispatch core."""
        default_s = (
            None
            if deadline_hint is None
            else max(float(deadline_hint), 0.05) + self.watchdog_grace_s
        )
        return core.execute_resilient(
            "serve.dispatch", padded, default_s=default_s
        )

    # ------------------------------------------------------- quarantine

    def _derive_park(self, raw: np.ndarray) -> np.ndarray:
        """Index-aware park point for poisoned rows: walk outward from
        the request's own finite bounding box until a cell NOT in the
        resident index answers (`runtime/quarantine.find_park_point`)."""
        from ..runtime import quarantine as _quarantine

        finite = raw[np.isfinite(raw).all(axis=1)]
        if finite.size:
            bounds = (
                float(finite[:, 0].min()), float(finite[:, 1].min()),
                float(finite[:, 0].max()), float(finite[:, 1].max()),
            )
        else:
            bounds = (0.0, 0.0, 1.0, 1.0)
        if self.admission.bounds is not None:
            bounds = self.admission.bounds

        def assign(pts):
            dev = jnp.asarray(np.asarray(pts, dtype=np.float64))
            if self.cell_dtype is not None:
                dev = dev.astype(self.cell_dtype)
            return self.index_system.point_to_cell(dev, self.resolution)

        return _quarantine.find_park_point(
            assign, np.asarray(self.index.cells), bounds
        )


def _decode_knn_future(req, k: int) -> Future:
    """Chain the request's raw wire future ((n, 2k) f64 rows) into one
    resolving to a batched :class:`~mosaic_tpu.knn.frontend.KNNAnswer`.
    Quarantined rows were answered at the park point — mask them back to
    the sentinel (``ids=-1, distance=inf``) so a poisoned coordinate can
    never surface a real neighbour. Exceptions (Overloaded sheds,
    injected faults) pass through untranslated."""
    from ..knn.frontend import KNNAnswer, decode_knn

    fut: Future = Future()

    def _done(raw: Future) -> None:
        if raw.cancelled():
            fut.cancel()
            return
        exc = raw.exception()
        if exc is not None:
            fut.set_exception(exc)
            return
        try:
            out = raw.result()
            degraded = isinstance(out, DegradedResult) or bool(
                getattr(out, "degraded", False)
            )
            reason = getattr(out, "reason", None) if degraded else None
            ids, dist = decode_knn(np.asarray(out), k)
            if req.quarantine is not None:
                dist = dist.copy()
                bad = [r for (_b, r) in req.quarantine.rows]
                ids[bad] = -1
                dist[bad] = np.inf
            fut.set_result(
                KNNAnswer(
                    ids=ids, distance=dist,
                    degraded=degraded, reason=reason,
                )
            )
        except BaseException as e:  # noqa: BLE001 — delivered via future
            fut.set_exception(e)

    req.future.add_done_callback(_done)
    return fut
