"""Durable raster scans: the tile twin of `sql/stream.py`.

A MODIS-scale zonal scan is minutes of device time over thousands of
tiles — long enough that device loss mid-scan is an operational
certainty, exactly the regime `StreamJoin.run_durable` was built for.
This module reuses that machinery for tiles: the scan runs in segments
of ``snapshot_every`` tiles, persisting the fold accumulators (count /
sum / min / max per zone, all f64-exact) and the tile cursor to a
checksummed snapshot (`runtime/checkpoint.py`) after each segment. Kill
the process anywhere and :meth:`RasterStream.resume` finishes the scan
— converging to a final fold bit-identical to the uninterrupted run,
because the accumulators snapshot exactly and the tile order (row-major
over the tile grid, the `raster/zonal.py` contract) is deterministic.

Resilience per segment matches the point stream: each device dispatch
sits under the ``raster.zonal`` watchdog deadline and the bounded
transient-retry budget; past the budget the segment's tiles degrade to
the f64 host twin (`host_zone_partial`), which is bit-identical to the
device partial, so degradation changes latency, never the answer.

Tracing: one ``raster.scan`` span per durable run, its context
persisted in every snapshot sidecar so a resume JOINS the killed run's
trace instead of starting a fresh one.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from ..dispatch import core as _dispatch, pipeline as _pipeline
from ..obs import trace as _trace
from ..runtime import (
    checkpoint as _checkpoint,
    telemetry as _telemetry,
)
from ..runtime.errors import RetryExhausted
from ..tune import resolve as _tune_resolve

__all__ = ["RasterScanResult", "RasterStream"]


def _zonal():
    """`raster/zonal.py`, imported lazily: that module composes on the
    join layer of THIS package, so a module-level import here would be
    a cycle (sql → raster_stream → raster.zonal → sql)."""
    from ..raster import tiles, zonal

    return tiles, zonal


@dataclasses.dataclass
class RasterScanResult:
    """One durable raster scan: the zonal fold + durability metrics
    (``snapshots`` written, ``degraded_tiles`` answered by the host
    twin, ``resumed_from`` tile cursor when this call was a resume)."""

    stats: "ZonalResult"  # noqa: F821 — resolved lazily, see _zonal()
    ntiles: int
    pixels: int
    wall_s: float
    pixels_per_sec: float
    metrics: dict = dataclasses.field(default_factory=dict)


class RasterStream:
    """Durable tiled zonal-statistics scans against one ChipIndex.

    Construction compiles nothing; the per-tile fold executables live
    in the wrapped :class:`~mosaic_tpu.raster.zonal.ZonalEngine` and are
    keyed by tile shape, so every raster with the same tile shape
    replays the same programs.
    """

    def __init__(
        self,
        chip_index,
        index_system,
        resolution: int,
        *,
        found_cap: "int | None" = None,
        heavy_cap: "int | None" = None,
        probe: "str | None" = None,
        convex_cap: "int | None" = None,
        mesh=None,
        profile=None,
    ):
        # profile-consumed knobs fold at this host entry point: explicit
        # arg > env knob > profile > built-in default (tune/resolve.py);
        # the tile/window knobs resolve per scan, where they apply
        self._profile = profile
        knobs = _tune_resolve.resolve_knobs(
            "raster_stream", profile,
            explicit={"probe": probe},
            defaults={"probe": "adaptive"},
        )
        probe = knobs["probe"]
        # the stream always folds on the exact jnp lane (f64, or int32
        # where `fold_lane` finds a narrow integer raster's tile sums
        # exact) — the durable contract is bit-identity through
        # kill/resume, and the f32 Pallas lane only holds it on
        # exact-summable data
        _tiles, zonal = _zonal()
        self.engine = zonal.ZonalEngine(
            index_system, resolution, chip_index=chip_index,
            found_cap=found_cap, heavy_cap=heavy_cap,
            probe=probe, convex_cap=convex_cap,
            lane="fold", mesh=mesh,
        )
        self.chip_index = chip_index
        self.index_system = index_system
        self.resolution = int(resolution)

    @property
    def num_zones(self) -> int:
        return self.engine.num_zones

    # -------------------------------------------------------------- API
    def scan(
        self,
        raster,
        *,
        band: int = 1,
        expr=None,
        tile: "tuple[int, int] | None" = None,
        run_dir: "str | None" = None,
        snapshot_every: int = 8,
        watchdog_default_s: float = 600.0,
        retry_policy=None,
        window: "int | None" = None,
    ) -> RasterScanResult:
        """Scan one band — or a fused expression tree over the band
        stack (``expr=``, `mosaic_tpu.expr`) — into per-zone (count,
        sum, min, max). With ``run_dir`` the scan is durable: interrupt
        anywhere and :meth:`resume` finishes it. Durable expression
        scans snapshot the tree's structural hash; resume refuses a
        different tree.

        Tiles ride the pipelined execution core
        (`dispatch/pipeline.py`): up to ``window`` tile folds are in
        flight at once (default: the ``MOSAIC_STREAM_WINDOW`` knob),
        so tile i's device fold overlaps tile i+1's host probe/patch —
        double-buffering for free. Accumulation and snapshots happen
        at the ordered drain, so the fold order (and therefore the
        result, bit for bit) is the synchronous loop's."""
        return self._run(
            raster, band=band, expr=expr, tile=tile, run_dir=run_dir,
            snapshot_every=int(snapshot_every), start_tile=0, acc0=None,
            resumed_from=None, watchdog_default_s=watchdog_default_s,
            retry_policy=retry_policy, trace_parent=None, window=window,
        )

    def resume(
        self,
        run_dir: str,
        raster,
        *,
        expr=None,
        watchdog_default_s: float = 600.0,
        retry_policy=None,
        window: "int | None" = None,
    ) -> RasterScanResult:
        """Restart an interrupted durable scan from the newest VALID
        snapshot under ``run_dir``. The snapshot's raster fingerprint,
        tile shape, band, zone count — and for expression scans the
        expression hash — must match: resuming a fold against different
        pixels OR a different tree would silently merge garbage."""
        loaded = _checkpoint.load_latest(run_dir)
        if loaded is None:
            raise FileNotFoundError(
                f"no valid snapshot under {run_dir!r} — nothing to resume"
            )
        step, arrays, meta = loaded
        want_fp = meta.get("raster_sha256")
        if want_fp and want_fp != _checkpoint.fingerprint(
            np.ascontiguousarray(raster.data)
        ):
            raise ValueError(
                "snapshot raster fingerprint mismatch — this is not "
                "the raster the interrupted scan was folding"
            )
        if int(meta.get("num_zones", self.num_zones)) != self.num_zones:
            raise ValueError(
                f"snapshot zone count {meta.get('num_zones')} != this "
                f"stream's {self.num_zones}"
            )
        want_expr = meta.get("expr_sha256")
        have_expr = None
        if expr is not None:
            from .. import expr as _expr  # lazy: see _zonal()

            have_expr = _expr.tree_hash(expr)
        if want_expr != have_expr:
            raise ValueError(
                "snapshot expression mismatch — the interrupted scan "
                f"folded tree {want_expr!r}, resume was given "
                f"{have_expr!r}; pass the same expression (structural "
                "equality) or none at all"
            )
        tile = tuple(meta["tile"]) if meta.get("tile") else None
        return self._run(
            raster, band=int(meta.get("band", 1)), expr=expr, tile=tile,
            run_dir=run_dir,
            snapshot_every=int(meta.get("snapshot_every", 8)),
            start_tile=int(step),
            acc0={k: np.asarray(v) for k, v in arrays.items()},
            resumed_from=int(step),
            watchdog_default_s=watchdog_default_s,
            retry_policy=retry_policy,
            trace_parent=_trace.SpanContext.from_dict(meta.get("trace")),
            window=window,
        )

    # ------------------------------------------------------------ engine
    def _run(
        self, raster, *, band, expr, tile, run_dir, snapshot_every,
        start_tile, acc0, resumed_from, watchdog_default_s,
        retry_policy, trace_parent, window=None,
    ) -> RasterScanResult:
        tiles, _zn = _zonal()
        # per-scan knobs: an explicit tile (or a resume's snapshot tile)
        # wins, then MOSAIC_RASTER_TILE / MOSAIC_STREAM_WINDOW, then the
        # constructor's TuningProfile, then the built-in defaults
        knobs = _tune_resolve.resolve_knobs(
            "raster_stream.scan", self._profile,
            explicit={"raster_tile": tile, "stream_window": window},
            defaults={"raster_tile": None, "stream_window": None},
        )
        tile, window = knobs["raster_tile"], knobs["stream_window"]
        plan = tiles.plan_tiles(raster, tile)
        th, tw = plan.shape
        g = self.num_zones
        snapshot_every = max(1, int(snapshot_every))
        root = _trace.start_span(
            "raster.scan",
            parent=trace_parent,
            ntiles=plan.ntiles, th=th, tw=tw, band=band,
            zones=g, resumed_from=resumed_from,
            fused=expr is not None,
        )
        try:
            return self._run_traced(
                raster, plan=plan, band=band, expr=expr,
                run_dir=run_dir,
                snapshot_every=snapshot_every, start_tile=start_tile,
                acc0=acc0, resumed_from=resumed_from,
                watchdog_default_s=watchdog_default_s,
                retry_policy=retry_policy, root=root, window=window,
            )
        except BaseException as e:  # noqa: BLE001 — stamped, re-raised
            root.set(error=type(e).__name__)
            raise
        finally:
            root.end()

    def _run_traced(
        self, raster, *, plan, band, expr, run_dir, snapshot_every,
        start_tile, acc0, resumed_from, watchdog_default_s,
        retry_policy, root, window=None,
    ) -> RasterScanResult:
        tiles, zonal = _zonal()
        th, tw = plan.shape
        g = self.num_zones
        eng = self.engine
        expr_sha = None
        if expr is None:
            stage_dt, fold_lane = eng.fold_staging(raster, band, plan)
            vals, mask = tiles.stack_tiles(
                raster, plan, band, dtype=stage_dt
            )
        else:
            # fused expression scan: stage the whole referenced band
            # stack; per tile ONE program computes the tree and folds it
            from .. import expr as _expr  # lazy: see _zonal()
            from ..expr import compile as _ec, eval as _ee

            value, kind, by, _stats = _expr.terminal_of(expr)
            if kind != "zonal" or (by or "zones") != "zones":
                raise ValueError(
                    "RasterStream.scan(expr=...) folds zones — use a "
                    "zones zonal terminal (or a bare value tree)"
                )
            _expr.validate(
                expr, raster.num_bands, has_zones=True, by="zones"
            )
            expr_sha = _expr.tree_hash(expr)
            expr_bands = _expr.bands_of(value)
            vals, mask = _ee._stack_bands(raster, plan, expr_bands)
            acc_name = str(np.dtype(eng.acc_dtype).name)
            expr_prog = _ec.zonal_program(
                value, th, tw, g, acc_name,
                eng.index_system, eng.resolution,
            )
            expr_sig = _ec.signature_of(
                value, th, tw, g, acc_name,
                eng.index_system, eng.resolution, eng.mesh,
            )
            band = 0  # snapshot meta: fused scans read the stack
            # the fused program computes in f64 whatever the bands hold
            stage_dt, fold_lane = np.dtype(np.float64), "wide"
        if acc0 is None:
            cnt_acc = np.zeros(g, np.int64)
            sum_acc = np.zeros(g, np.float64)
            min_acc = np.full(g, np.inf)
            max_acc = np.full(g, -np.inf)
        else:
            cnt_acc = np.asarray(acc0["count"], np.int64).copy()
            sum_acc = np.asarray(acc0["sum"], np.float64).copy()
            min_acc = np.asarray(acc0["min"], np.float64).copy()
            max_acc = np.asarray(acc0["max"], np.float64).copy()
        meta = None
        if run_dir is not None:
            meta = {
                "ntiles": plan.ntiles,
                "tile": [th, tw],
                "band": int(band),
                "num_zones": g,
                "snapshot_every": int(snapshot_every),
                "raster_sha256": _checkpoint.fingerprint(
                    np.ascontiguousarray(raster.data)
                ),
                "expr_sha256": expr_sha,
                "trace": root.context.as_dict(),
            }
        host = getattr(self.chip_index, "host", None)
        degraded = [0]
        counters = {"snapshots": 0}
        #: this scan's own count of pixels the host re-joined
        tally = {"patched_pixels": 0}
        start = int(start_tile)
        win = _pipeline.resolve_window(window)

        # tiles ride the pipelined execution core: launch dispatches
        # tile t's fold WITHOUT the blocking pull (the probe's host
        # patch still completes here — it is host work by construction),
        # the ordered drain pulls the partials under the watchdog and
        # the caller-thread commit accumulates them, so the fold
        # order — and therefore the float result, bit for bit — is the
        # synchronous loop's. Fault plans trip inside the launch guard
        # (the watchdog runs maybe_fail under the retry wrapper):
        # transient errors retry/degrade, non-transient ones abort.
        def launch(i):
            t = start + i

            if expr is None:
                def dispatch(t=t):
                    return eng._tile_zone_stats_async(
                        plan, t, vals[t].reshape(-1),
                        mask[t].reshape(-1), tally,
                    )
            else:
                def dispatch(t=t):
                    # probe + epsilon patch, then the fused
                    # expression+fold program — one launch
                    geom = eng._tile_zone_rows(plan, t, tally=tally)
                    seg = np.where(
                        geom >= 0, geom, -1
                    ).astype(np.int32)
                    return _ec.run_zonal_async(
                        expr_prog, expr_sig,
                        np.asarray(plan.gt, np.float64),
                        plan.origins[t], vals[t], mask[t], seg,
                    )

            with _trace.span(
                "raster.zonal", step=t, n=1, pipelined=True,
                fold_lane=fold_lane, values_dtype=stage_dt.name,
            ):
                try:
                    return ("dev", _dispatch.guarded_call(
                        "raster.zonal", dispatch,
                        default_s=watchdog_default_s,
                        policy=retry_policy,
                    ))
                except RetryExhausted as e:
                    if host is None:
                        raise
                    _telemetry.record(
                        "degraded", label="raster.zonal", step=t,
                        attempts=e.attempts,
                        error=repr(e.last)[:200],
                    )
                    if expr is None:
                        return ("host", zonal.host_zone_partial(
                            zonal.host_tile_centers(plan, t),
                            vals[t].reshape(-1),
                            mask[t].reshape(-1),
                            host, self.index_system,
                            self.resolution, g,
                        ))
                    return ("host", _expr.host_expr_tile_partial(
                        value, vals[t], mask[t],
                        zonal.host_tile_centers(plan, t),
                        index_system=self.index_system,
                        resolution=self.resolution,
                        host=host, num_segments=g,
                        by="zones",
                    ))

        def land(i, handle):
            # runs under the drain watchdog, whose deadline ABANDONS
            # the worker thread — pull ALL four partials here and
            # mutate nothing, so a worker finishing late changes
            # nothing and a mid-pull transient replays a tile whose
            # effects were never applied
            kind, (cnt, s, mn, mx) = handle
            return (
                kind,
                np.asarray(cnt, np.int64),  # blocks: the drain's pull
                np.asarray(s, np.float64),
                np.asarray(mn, np.float64),
                np.asarray(mx, np.float64),
            )

        def commit(i, pulled):
            nonlocal cnt_acc, sum_acc
            kind, cnt, s, mn, mx = pulled
            if kind == "host":
                # degradation counts at materialization, not launch —
                # a degraded in-flight tile later discarded by a
                # transient is re-run (and counted once) by the replay
                degraded[0] += 1
            live = cnt > 0
            cnt_acc += cnt
            sum_acc = sum_acc + s
            min_acc[live] = np.minimum(min_acc[live], mn[live])
            max_acc[live] = np.maximum(max_acc[live], mx[live])
            se = start + i + 1
            # the snapshot write runs here on the caller thread —
            # outside the drain-watchdog deadline, like the
            # synchronous loop — and swallows its own failures, so
            # nothing after the accumulator fold can raise a
            # transient that would replay (and double-count) the tile
            if run_dir is not None and (
                (se - start) % snapshot_every == 0 or se == plan.ntiles
            ):
                payload = {
                    "count": cnt_acc, "sum": sum_acc,
                    "min": min_acc, "max": max_acc,
                }
                with _trace.span("raster.snapshot", step=se):
                    try:
                        _checkpoint.save_snapshot(
                            run_dir, se, payload, meta
                        )
                        counters["snapshots"] += 1
                    except Exception as e:  # lint: broad-except-ok (durability degrades — coarser resume point — but a sick disk must not kill the scan)
                        _telemetry.record(
                            "snapshot_skipped", run_dir=run_dir,
                            step=se, error=repr(e)[:200],
                        )

        def replay(lo, hi):
            # tiles carry no cross-tile device state, so the
            # synchronous path IS launch + pull + commit — the full
            # guarded retry/degradation budget applies per tile
            for j in range(lo, hi + 1):
                commit(j, land(j, launch(j)))

        t0 = time.perf_counter()
        pstats = _pipeline.execute_pipeline(
            plan.ntiles - start, launch, land,
            drain_site="raster.pipeline.drain", commit=commit,
            replay=replay, window=win,
            watchdog_default_s=watchdog_default_s,
        )
        degraded_tiles = degraded[0]
        snapshots = counters["snapshots"]
        wall = time.perf_counter() - t0
        n_run = plan.ntiles - int(start_tile)
        px_run = n_run * th * tw
        _telemetry.record(
            "raster_stage", stage="scan",
            seconds=round(wall, 6), ntiles=plan.ntiles,
            th=th, tw=tw, zones=g, snapshots=snapshots,
            degraded_tiles=degraded_tiles, resumed_from=resumed_from,
            window=pstats.window,
            pixels_per_sec=round(px_run / max(wall, 1e-9), 1),
        )
        # one event a scan: what the tile loop met (the pixels it was
        # handed, those that could fold, those the host re-joined)
        _telemetry.record(
            "raster_scan", seconds=round(wall, 6), tiles=n_run,
            pixels=plan.pixels, valid_pixels=int(np.count_nonzero(mask)),
            patched_pixels=tally["patched_pixels"],
            degraded_tiles=degraded_tiles, window=pstats.window,
            fold_lane=fold_lane, values_dtype=stage_dt.name,
        )
        live = cnt_acc > 0
        stats = zonal.ZonalResult(
            keys=np.nonzero(live)[0].astype(np.int64),
            count=cnt_acc[live],
            sum=sum_acc[live],
            min=min_acc[live],
            max=max_acc[live],
            band=band,
            pixels=int(cnt_acc.sum()),
        )
        return RasterScanResult(
            stats=stats,
            ntiles=plan.ntiles,
            pixels=plan.pixels,
            wall_s=wall,
            pixels_per_sec=px_run / max(wall, 1e-9),
            metrics={
                "degraded": degraded_tiles > 0,
                "degraded_tiles": degraded_tiles,
                "snapshots": snapshots,
                "resumed_from": resumed_from,
                "run_dir": run_dir,
                "pipeline": pstats.as_dict(),
            },
        )

