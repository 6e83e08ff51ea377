"""Zonal statistics frontends: raster tiles → grid cells / vector zones.

Reference analog: the `RST_RasterToGrid{Avg,Min,Max,Count}` family
(`expressions/raster/base/RasterToGridExpression.scala:55-92`) and the
classic zonal-statistics workload of the raster literature — here as
bounded-shape device pipelines over the tile plan of `raster/tiles.py`:

- :func:`zonal_grid` — fold every valid pixel into its containing grid
  cell (H3/BNG). Cell assignment runs on device per tile; the set of
  touched cells is data-dependent, so per tile the device fold runs
  dense over ``TH*TW`` segments (static shape, one compile signature)
  and the host merges the per-tile partials keyed by cell id.
- :func:`zonal_zones` — fold every valid pixel into the vector zone
  that contains it, resolved through the SAME machinery as point joins:
  cell assignment, then the PIP probe against the ChipIndex (core-chip
  pixels resolve without an edge test, border pixels walk the adaptive
  probe lanes from the serving/stream engines). Assign + probe + fold
  fuse into one program per tile shape.

Fold contract (the bit-identity spine, pinned by tests): per-tile
partials are computed with an f64 accumulator (under x64) in row-major
pixel order, then merged in row-major TILE order with a left fold —
or, for a narrow integer raster (int8 / uint8 / int16, uint16 on small
tiles), at the pixels' own width: `kernels.zonal.fold_lane` says from
the storage dtype, the tile's pixel count and the zone count alone
when one tile's sum is exact in int32; the zones fold then stages the
raster's own dtype (a quarter of the f64 put), folds count, sum, min
and max in int32 lanes and hands the host the same numbers, bit for
bit, that the f64 fold would (an int32 partial enters the host's f64
tile merge exactly; min and max select, they round nothing). The
host oracles (:func:`host_zonal_grid_oracle`,
:func:`host_zonal_zones_oracle`) mirror exactly that decomposition in
pure numpy f64 — per-tile sequential accumulation, then the same
left-fold merge — so device results are required to be bit-identical,
not merely close. Counts and min/max are order-free; it is the sums
that make the order part of the contract.

The Pallas fold lane (``lane="tiled"``, `kernels/zonal.py`) runs the
zones fold at f32 on the MXU/VPU tile grid; it holds bit-identity only
on exact-summable values (integer-valued pixels, like the MODIS-style
fixtures) and is the TPU bench lane, not the default.
"""

from __future__ import annotations

import dataclasses
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..dispatch import core as _dispatch
from ..kernels.zonal import fold_lane, zonal_fold, zonal_tiled
from ..obs import stages as _stages, trace as _trace
from ..runtime import faults as _faults, telemetry as _telemetry
from ..runtime.errors import CapacityOverflow
from ..runtime.platform import interpret_kernels
from ..sql.join import (
    EDGE_BAND_K,
    OVERFLOW,
    host_join,
    pip_join_points,
    resolve_probe_mode,
)
from ..tune import resolve as _tune_resolve
from .tiles import (
    TilePlan,
    assign_tile_cells,
    plan_tiles,
    stack_tiles,
    tile_centers,
)

__all__ = [
    "ZonalEngine",
    "ZonalResult",
    "host_zonal_grid_oracle",
    "host_zonal_zones_oracle",
    "resolve_zonal_lane",
    "zonal_grid",
    "zonal_zones",
]

def resolve_zonal_lane(lane: str = "auto") -> str:
    """Resolve the fold lane HERE, on the host, before any value is
    closed over by a jitted program (same discipline as
    `join.resolve_probe_mode`): ``MOSAIC_RASTER_LANE`` overrides
    ``auto``; explicit arguments win over the env. ``fold`` is the jnp
    segment-reduce (f64-capable, the bit-identity default), ``tiled``
    the f32 Pallas lane."""
    if lane == "auto":
        lane = os.environ.get("MOSAIC_RASTER_LANE", "fold")
    if lane not in ("fold", "tiled"):
        raise ValueError(
            f"unknown zonal lane {lane!r} (expected fold|tiled)"
        )
    return lane


def _acc_dtype():
    return jnp.float64 if jax.config.jax_enable_x64 else jnp.float32


@dataclasses.dataclass
class ZonalResult:
    """One band's zonal fold. ``keys`` are grid cell ids (grid mode) or
    zone rows 0..G-1 (zone mode); rows with ``count == 0`` are dropped
    before this is built, so every row is backed by real pixels."""

    keys: np.ndarray
    count: np.ndarray
    sum: np.ndarray
    min: np.ndarray
    max: np.ndarray
    band: int
    pixels: int  # valid pixels folded across all keys

    @property
    def mean(self) -> np.ndarray:
        return self.sum / np.maximum(self.count.astype(np.float64), 1.0)

    def stat(self, name: str) -> dict:
        """{key: value} for one statistic (reference's RST_RasterToGrid*
        return shape)."""
        vals = {
            "count": self.count, "sum": self.sum, "min": self.min,
            "max": self.max, "mean": self.mean,
        }[name]
        return {int(k): v.item() for k, v in zip(self.keys, vals)}


class ZonalEngine:
    """Compiled zonal pipelines over one (index_system, resolution) —
    the raster twin of `sql.StreamJoin`: closures are jitted once here,
    every raster folded through the same executables (one compile
    signature per tile shape).
    """

    def __init__(
        self,
        index_system,
        resolution: int,
        *,
        chip_index=None,
        found_cap: "int | None" = None,
        heavy_cap: "int | None" = None,
        probe: "str | None" = None,
        convex_cap: "int | None" = None,
        lane: "str | None" = None,
        mesh=None,
        profile=None,
    ):
        self.index_system = index_system
        self.resolution = int(resolution)
        self.chip_index = chip_index
        #: (program, tile pixels) already registered with `obs.stages`
        self._stages_seen: set = set()
        # profile-consumed knobs fold at this host entry point: explicit
        # arg > env knob > profile > built-in default (tune/resolve.py).
        # lane="auto" is the legacy spelling of "not passed".
        knobs = _tune_resolve.resolve_knobs(
            "zonal_engine", profile,
            explicit={
                "probe": probe,
                "zonal_lane": None if lane in (None, "auto") else lane,
            },
            defaults={"probe": "adaptive", "zonal_lane": "fold"},
        )
        probe = knobs["probe"]
        self.lane = resolve_zonal_lane(knobs["zonal_lane"])
        # placement resolves host-side once (dispatch core discipline):
        # with a mesh bound, the PIP probe runs data-parallel over the
        # pixel stream with the ChipIndex replicated — bit-identical to
        # single-device, so the fold contract is untouched
        self.mesh = _dispatch.resolve_mesh(mesh)
        self.num_zones = (
            0 if chip_index is None
            else int(np.asarray(chip_index.chip_geom).max()) + 1
        )
        # resolve the adaptive/force-lane knob before it is closed over
        # by the jitted fold (env changes cannot reach a compiled
        # program)
        probe = resolve_probe_mode(probe) if chip_index is not None else probe
        self.probe = probe
        acc_dt = _acc_dtype()
        self.acc_dtype = acc_dt
        lane_resolved = self.lane

        def assign(gt, origin, th: int, tw: int):
            return assign_tile_cells(
                gt, origin, (th, tw), index_system, resolution
            )

        self._assign = jax.jit(assign, static_argnums=(2, 3))

        def grid_fold(gt, origin, vals, seg, th: int, tw: int):
            # dense per-tile fold: segment ids are the tile-local dense
            # ranks the host computed from the device cell assignment;
            # num_segments == tile pixel count keeps the shape static
            del gt, origin
            return zonal_fold(
                vals, seg, th * tw, acc_dtype=acc_dt
            )

        self._grid_fold = jax.jit(grid_fold, static_argnums=(4, 5))

        if chip_index is not None:
            dtype = chip_index.border.verts.dtype
            g = self.num_zones
            host = getattr(chip_index, "host", None)
            self._host = host
            # chip-edge epsilon band (SURVEY §7 / `pip_join` recheck):
            # pixel centers within EDGE_BAND_K ulps of a probed chip edge
            # may flip parity between the f32 device probe and exact f64
            # — those are re-joined on the host oracle per tile. Cell
            # assignment here is f64 on device (tile centers are f64), so
            # the cell-margin/runner-up tiers of the full pip_join
            # recheck are unnecessary: only the parity band can drift.
            eps2 = None
            if host is not None:
                eps2 = jnp.asarray(
                    (EDGE_BAND_K * float(np.finfo(np.dtype(dtype)).eps)
                     * host.coord_scale) ** 2,
                    dtype=dtype,
                )

            def probe_core(pts, cells, index):
                with jax.named_scope("pip.recentre"):
                    shifted = (pts - index.border.shift).astype(dtype)
                out = pip_join_points(
                    shifted, cells, index,
                    heavy_cap=heavy_cap, found_cap=found_cap,
                    edge_eps2=eps2,
                    probe=probe, convex_cap=convex_cap,
                )
                if eps2 is None:
                    return out, jnp.zeros(out.shape, bool)
                return out  # (geom, near) under the epsilon band

            if self.mesh is not None:
                # per-pixel results depend only on the pixel center and
                # the replicated index — sharding the probe stream over
                # the mesh is bit-identical by construction
                probe_core = _dispatch.sharded_pointwise(
                    probe_core, self.mesh, n_out=2,
                    check_rep=_dispatch.probe_check_rep(probe),
                )

            def zones_probe(gt, origin, index, th: int, tw: int):
                cells = assign_tile_cells(
                    gt, origin, (th, tw), index_system, resolution
                )
                pts = tile_centers(
                    jnp.asarray(gt), jnp.asarray(origin), th=th, tw=tw
                )
                return probe_core(pts, cells, index)

            self._zones_probe = jax.jit(zones_probe, static_argnums=(3, 4))

            @jax.named_scope("zonal.fold")
            def zones_fold(vals, seg):
                if lane_resolved == "tiled":
                    return zonal_tiled(
                        vals, seg, g,
                        interpret=interpret_kernels(),
                    )
                return zonal_fold(vals, seg, g, acc_dtype=acc_dt)

            self._zones_fold = jax.jit(zones_fold)

    def _register_stages(self, fn, args, rows: int, dtype=None) -> None:
        """Tell `obs.stages` how to lower one of the tile's two programs
        again (shapes only, once a program, tile shape and value dtype;
        no lowering here), so that a device trace can name its ops by
        stage."""
        key = (id(fn), rows, dtype)
        if key not in self._stages_seen:
            self._stages_seen.add(key)
            _stages.register(fn, _stages.shapes_of(args), rows=rows)

    def fold_staging(self, raster, band: int, plan) -> tuple:
        """(dtype the zones fold stages ``band``'s tiles in, the fold
        lane's name) — the raster's own dtype where
        `kernels.zonal.fold_lane` says a tile sums exactly in int32,
        else f64 and ``"wide"``. Decided here, on the host, from the
        storage dtype, the tile shape and the zone count."""
        dt = raster.band(band).values.dtype
        th, tw = plan.shape
        lane = fold_lane(dt, th * tw, self.num_zones)
        return (dt if lane == "int32" else np.dtype(np.float64)), lane

    def _tile_zone_rows(
        self, plan, t: int, maskb=None, tally: "dict | None" = None,
    ) -> np.ndarray:
        """(TH*TW,) zone row per pixel center of tile ``t`` (negative =
        outside every zone): device probe with the epsilon band, exact
        f64 host re-join of the banded pixels. The host patch is what
        makes downstream folds bit-identical to the f64 oracle even for
        pixel centers landing exactly on zone edges. ``maskb`` narrows
        the patch to pixels that can contribute; ``None`` (the
        expression path, where validity is decided INSIDE the fused
        program) patches every banded pixel — membership is
        band-independent, so the two are equivalent on every pixel that
        reaches a fold. ``tally["patched_pixels"]`` (a scan's own count)
        grows by the pixels the host re-joined."""
        th, tw = plan.shape
        if self.mesh is not None and (th * tw) % self.mesh.size:
            raise ValueError(
                f"tile of {th * tw} pixels does not divide over the "
                f"{self.mesh.size}-device mesh — pick a tile shape whose "
                "pixel count is a multiple of the device count"
            )
        gt6 = np.asarray(plan.gt, np.float64)
        args = (gt6, plan.origins[t], self.chip_index, th, tw)
        self._register_stages(self._zones_probe, args, th * tw)
        with _trace.span("raster.probe", tile=t):
            geom_d, near_d = self._zones_probe(*args)
            geom = np.array(geom_d)  # blocks: the probe's pull
            near = None if self._host is None else np.asarray(near_d)
        if (geom == OVERFLOW).any():
            raise CapacityOverflow(
                f"zonal probe overflow on tile {t}: "
                f"{int((geom == OVERFLOW).sum())} pixels exceeded the "
                "heavy/found/convex caps — leave caps at None for exact "
                "sizing"
            )
        if near is not None:
            with _trace.span("raster.patch", tile=t) as sp:
                if maskb is not None:
                    near = near & maskb
                rows = int(np.count_nonzero(near))
                sp.set(rows=rows)
                if rows:
                    pts = host_tile_centers(plan, t)[near]
                    geom[near] = np.asarray(
                        host_join(
                            pts, self._host, self.index_system,
                            self.resolution,
                        )
                    )
            if tally is not None:
                tally["patched_pixels"] += rows
        return geom

    def _tile_zone_stats_async(
        self, plan, t: int, vals_flat, mask_flat,
        tally: "dict | None" = None,
    ):
        """One tile's zone partial as DEVICE arrays — async dispatch,
        no blocking pull. The probe + epsilon host patch
        (:meth:`_tile_zone_rows`) still complete on the host (the patch
        is a host re-join by construction), but the (g,)-fold's results
        are returned as futures so a pipelined caller can overlap this
        tile's fold with the next tile's probe and pull at its drain
        point."""
        maskb = np.asarray(mask_flat, bool)
        geom = self._tile_zone_rows(plan, t, maskb, tally)
        with _trace.span("raster.fold", tile=t):
            seg = np.where(maskb & (geom >= 0), geom, -1).astype(np.int32)
            self._register_stages(
                self._zones_fold, (vals_flat, seg), int(seg.shape[0]),
                str(vals_flat.dtype),
            )
            return self._zones_fold(
                jnp.asarray(vals_flat), jnp.asarray(seg)
            )

    def _tile_zone_stats(self, plan, t: int, vals_flat, mask_flat):
        """One tile's zone partial ((g,) count, sum, min, max as numpy):
        probe + epsilon patch via :meth:`_tile_zone_rows`, then the
        device fold over the corrected segments. The numpy returns are
        the blocking pulls (what a real stall would block on)."""
        cnt, s, mn, mx = self._tile_zone_stats_async(
            plan, t, vals_flat, mask_flat
        )
        return (
            np.asarray(cnt), np.asarray(s), np.asarray(mn),
            np.asarray(mx),
        )

    # ------------------------------------------------------------- grid
    def grid(
        self, raster, band: int = 1,
        tile: "tuple[int, int] | None" = None,
    ) -> ZonalResult:
        """Fold one band into grid cells: per-key (count, sum, min, max)
        merged across tiles in row-major tile order."""
        plan = plan_tiles(raster, tile)
        th, tw = plan.shape
        vals, mask = stack_tiles(raster, plan, band, dtype=np.float64)
        gt6 = np.asarray(plan.gt, np.float64)
        merged: dict[int, list] = {}
        t0 = time.perf_counter()
        assign_s = 0.0
        with _trace.span(
            "raster.zonal", mode="grid", ntiles=plan.ntiles, band=band
        ):
            for t in range(plan.ntiles):
                _faults.maybe_fail("raster.zonal")
                ta = time.perf_counter()
                with _trace.span("raster.assign", tile=t):
                    cells = np.asarray(
                        self._assign(gt6, plan.origins[t], th, tw)
                    )
                assign_s += time.perf_counter() - ta
                mflat = mask[t].reshape(-1)
                uniq, inv = np.unique(
                    cells[mflat], return_inverse=True
                )
                if uniq.size == 0:
                    continue
                seg = np.full(th * tw, -1, np.int32)
                seg[mflat] = inv.astype(np.int32)
                cnt, s, mn, mx = self._grid_fold(
                    gt6, plan.origins[t], vals[t].reshape(-1), seg,
                    th, tw,
                )
                cnt = np.asarray(cnt)[: uniq.size]
                s = np.asarray(s)[: uniq.size]
                mn = np.asarray(mn)[: uniq.size]
                mx = np.asarray(mx)[: uniq.size]
                for k, c, sv, mnv, mxv in zip(uniq, cnt, s, mn, mx):
                    row = merged.get(int(k))
                    if row is None:
                        merged[int(k)] = [int(c), sv, mnv, mxv]
                    else:
                        row[0] += int(c)
                        row[1] += sv  # left fold in tile order
                        row[2] = min(row[2], mnv)
                        row[3] = max(row[3], mxv)
        seconds = time.perf_counter() - t0
        _telemetry.record(
            "raster_stage", stage="assign",
            seconds=round(assign_s, 6), ntiles=plan.ntiles,
        )
        _telemetry.record(
            "raster_stage", stage="zonal",
            seconds=round(max(seconds - assign_s, 0.0), 6),
            mode="grid", ntiles=plan.ntiles, cells=len(merged),
            pixels=plan.pixels,
            pixels_per_sec=round(plan.pixels / max(seconds, 1e-9), 1),
        )
        return _result_from_dict(merged, band)

    # ------------------------------------------------------------ zones
    def zones(
        self, raster, band: int = 1,
        tile: "tuple[int, int] | None" = None,
    ) -> ZonalResult:
        """Fold one band into vector zones through the PIP probe. Zone
        keys are geometry rows 0..G-1; pixels outside every zone (or
        nodata, or pad) fold nowhere."""
        if self.chip_index is None:
            raise ValueError(
                "ZonalEngine was built without a chip_index — zones "
                "folds need the vector side"
            )
        plan = plan_tiles(raster, tile)
        if self.lane == "fold":
            stage_dt, fold = self.fold_staging(raster, band, plan)
        else:  # the Pallas lane's own f32 accumulators
            stage_dt, fold = np.dtype(np.float32), None
        vals, mask = stack_tiles(raster, plan, band, dtype=stage_dt)
        g = self.num_zones
        acc_np = np.float64 if self.lane == "fold" else np.float32
        cnt_acc = np.zeros(g, np.int64)
        sum_acc = np.zeros(g, acc_np)
        min_acc = np.full(g, np.inf)
        max_acc = np.full(g, -np.inf)
        t0 = time.perf_counter()
        with _trace.span(
            "raster.zonal", mode="zones", ntiles=plan.ntiles,
            zones=g, band=band, lane=self.lane, fold_lane=fold,
            values_dtype=stage_dt.name,
        ):
            for t in range(plan.ntiles):
                _faults.maybe_fail("raster.zonal")
                cnt, s, mn, mx = self._tile_zone_stats(
                    plan, t, vals[t].reshape(-1), mask[t].reshape(-1)
                )
                cnt = np.asarray(cnt).astype(np.int64)
                live = cnt > 0
                cnt_acc += cnt
                sum_acc = sum_acc + np.asarray(s)  # tile-order left fold
                mn = np.asarray(mn, np.float64)
                mx = np.asarray(mx, np.float64)
                min_acc[live] = np.minimum(min_acc[live], mn[live])
                max_acc[live] = np.maximum(max_acc[live], mx[live])
        seconds = time.perf_counter() - t0
        _telemetry.record(
            "raster_stage", stage="zonal",
            seconds=round(seconds, 6), mode="zones",
            ntiles=plan.ntiles, zones=g, lane=self.lane,
            fold_lane=fold, values_dtype=stage_dt.name,
            pixels=plan.pixels,
            pixels_per_sec=round(plan.pixels / max(seconds, 1e-9), 1),
        )
        live = cnt_acc > 0
        return ZonalResult(
            keys=np.nonzero(live)[0].astype(np.int64),
            count=cnt_acc[live],
            sum=sum_acc[live].astype(np.float64),
            min=min_acc[live],
            max=max_acc[live],
            band=band,
            pixels=int(cnt_acc.sum()),
        )

    # ------------------------------------------------------ expressions
    def map(
        self, expr, raster, *, tile: "tuple[int, int] | None" = None,
        by: "str | None" = None, watchdog_default_s: float = 600.0,
        retry_policy=None,
    ):
        """Evaluate a fused expression tree (`mosaic_tpu.expr`) over
        ``raster``: one device program per tile bucket runs band math,
        masking, and the terminal zonal fold in a single launch.
        Zonal terminals return a :class:`ZonalResult`; ``.join()``
        terminals return per-pixel (zone, value, valid) planes."""
        from .. import expr as _expr  # local: expr imports this module

        _value, kind, _by, _stats = _expr.terminal_of(expr)
        if kind == "join":
            return _expr.eval.map_join(self, expr, raster, tile=tile)
        return _expr.map_zonal(
            self, expr, raster, tile=tile, by=by,
            watchdog_default_s=watchdog_default_s,
            retry_policy=retry_policy,
        )

    def warmup_expr(
        self, expr, raster, *, tile: "tuple[int, int] | None" = None,
        by: "str | None" = None,
    ) -> tuple:
        """Precompile the probe and fused programs one :meth:`map` call
        will dispatch (by executing them on zero tiles — AOT lowering
        does not warm the jit dispatch cache); returns the registered
        expression signature for `expr.freeze` bookkeeping."""
        from .. import expr as _expr  # local: expr imports this module

        return _expr.warmup_expr(self, expr, raster, tile=tile, by=by)


def _result_from_dict(merged: dict, band: int) -> ZonalResult:
    keys = np.array(sorted(merged), dtype=np.int64)
    rows = [merged[int(k)] for k in keys]
    return ZonalResult(
        keys=keys,
        count=np.array([r[0] for r in rows], dtype=np.int64),
        sum=np.array([r[1] for r in rows], dtype=np.float64),
        min=np.array([r[2] for r in rows], dtype=np.float64),
        max=np.array([r[3] for r in rows], dtype=np.float64),
        band=band,
        pixels=int(sum(r[0] for r in rows)),
    )


def zonal_grid(
    raster, resolution, *, index_system=None, band: int = 1,
    tile: "tuple[int, int] | None" = None,
) -> ZonalResult:
    """One-shot raster→grid-cell zonal fold (build a
    :class:`ZonalEngine` once and reuse it when folding many rasters —
    the engine holds the compile cache)."""
    if index_system is None:
        from ..context import current_context

        index_system = current_context().index_system
    resolution = index_system.resolution_arg(resolution)
    eng = ZonalEngine(index_system, resolution)
    return eng.grid(raster, band=band, tile=tile)


def zonal_zones(
    raster, chip_index, index_system, resolution, *, band: int = 1,
    tile: "tuple[int, int] | None" = None, probe: str = "adaptive",
    lane: str = "auto",
) -> ZonalResult:
    """One-shot raster→vector-zone zonal fold via the PIP probe."""
    eng = ZonalEngine(
        index_system, index_system.resolution_arg(resolution),
        chip_index=chip_index, probe=probe, lane=lane,
    )
    return eng.zones(raster, band=band, tile=tile)


# ---------------------------------------------------------------- oracles


def host_tile_centers(plan: TilePlan, t: int) -> np.ndarray:
    """(TH*TW, 2) f64 pixel centers of tile ``t``, computed on the host
    with the same affine expression (and operation order) as the device
    :func:`~mosaic_tpu.raster.tiles.tile_centers` — f64 on both sides,
    so the coordinates agree bit for bit."""
    th, tw = plan.shape
    r0, c0 = (int(v) for v in plan.origins[t])
    x0, sx, rx, y0, ry, sy = (float(v) for v in plan.gt)
    rr = np.arange(th, dtype=np.float64)[:, None] + float(r0) + 0.5
    cc = np.arange(tw, dtype=np.float64)[None, :] + float(c0) + 0.5
    x = x0 + cc * sx + rr * rx
    y = y0 + cc * ry + rr * sy
    return np.stack(
        [np.broadcast_to(x, (th, tw)).reshape(-1),
         np.broadcast_to(y, (th, tw)).reshape(-1)],
        axis=-1,
    )


def host_zone_partial(
    pts, vals, maskf, host, index_system, resolution, g: int,
):
    """One tile's zone fold on the host, f64 and sequential — the
    degradation twin of the device tile fold ((g,) i64 count, (g,) f64
    sum, (g,) min, (g,) max). The durable raster scan substitutes this
    for a tile whose device dispatch exhausted its retry budget; being
    bit-identical to the device partial, a degraded segment does not
    perturb the fold contract."""
    geom = np.asarray(host_join(pts, host, index_system, resolution))
    seg = np.where(np.asarray(maskf, bool) & (geom >= 0), geom, -1)
    cnt = np.zeros(g, np.int64)
    s = np.zeros(g, np.float64)
    mn = np.full(g, np.inf)
    mx = np.full(g, -np.inf)
    for gg, v in zip(seg, np.asarray(vals, np.float64)):
        if gg >= 0:
            cnt[gg] += 1
            s[gg] += v
            mn[gg] = min(mn[gg], v)
            mx[gg] = max(mx[gg], v)
    return cnt, s, mn, mx


def _host_tile_views(raster, plan: TilePlan, band: int):
    """Yield (t, (P,) f64 values, (P,) bool mask, (P, 2) f64 centers)
    per tile in row-major tile order — the decomposition both oracles
    share with the device path."""
    th, tw = plan.shape
    b = raster.band(band)
    vals_full = b.values.astype(np.float64)
    mask_full = b.mask
    h, w = plan.raster_shape
    for t, (r0, c0) in enumerate(plan.origins):
        vals = np.zeros((th, tw), np.float64)
        mask = np.zeros((th, tw), bool)
        r1 = min(int(r0) + th, h)
        c1 = min(int(c0) + tw, w)
        sub = vals_full[int(r0):r1, int(c0):c1]
        vals[: sub.shape[0], : sub.shape[1]] = sub
        mask[: sub.shape[0], : sub.shape[1]] = mask_full[
            int(r0):r1, int(c0):c1
        ]
        vals[~mask] = 0
        yield t, vals.reshape(-1), mask.reshape(-1), host_tile_centers(
            plan, t
        )


def _oracle_fold(acc: dict, seg, vals, keys_of=int):
    """One tile's sequential f64 fold into fresh partials, then a
    left-fold merge into ``acc`` — mirroring the device contract."""
    part: dict = {}
    for g, v in zip(seg, vals):
        if g < 0:
            continue
        row = part.get(keys_of(g))
        if row is None:
            part[keys_of(g)] = [1, v, v, v]
        else:
            row[0] += 1
            row[1] += v
            row[2] = min(row[2], v)
            row[3] = max(row[3], v)
    for k, (c, s, mn, mx) in part.items():
        row = acc.get(k)
        if row is None:
            acc[k] = [c, s, mn, mx]
        else:
            row[0] += c
            row[1] += s
            row[2] = min(row[2], mn)
            row[3] = max(row[3], mx)


def host_zonal_grid_oracle(
    raster, resolution, index_system, *, band: int = 1,
    tile: "tuple[int, int] | None" = None,
) -> ZonalResult:
    """Pure-host f64 twin of :meth:`ZonalEngine.grid`: same tile
    decomposition, per-tile sequential accumulation, same tile-order
    merge — the device fold must match this bit for bit."""
    plan = plan_tiles(raster, tile)
    acc: dict = {}
    for _t, vals, mask, pts in _host_tile_views(raster, plan, band):
        cells = np.asarray(
            index_system.point_to_cell(jnp.asarray(pts), resolution)
        ).astype(np.int64)
        seg = np.where(mask, cells, -1)
        _oracle_fold(acc, seg, vals)
    return _result_from_dict(acc, band)


def host_zonal_zones_oracle(
    raster, chip_index, index_system, resolution, *, band: int = 1,
    tile: "tuple[int, int] | None" = None,
) -> ZonalResult:
    """Pure-host f64 twin of :meth:`ZonalEngine.zones`: zone membership
    from the exact f64 host join (`join.host_join`), fold mirroring the
    tile decomposition."""
    host = getattr(chip_index, "host", None)
    if host is None:
        raise ValueError("chip_index carries no HostRecheck tables")
    plan = plan_tiles(raster, tile)
    acc: dict = {}
    for _t, vals, mask, pts in _host_tile_views(raster, plan, band):
        geom = np.asarray(
            host_join(pts, host, index_system, resolution)
        )
        seg = np.where(mask & (geom >= 0), geom, -1)
        _oracle_fold(acc, seg, vals)
    return _result_from_dict(acc, band)
