"""Tile pipeline: fixed-shape, mask-carrying raster tiles.

A raster streams through the device the same way points do: in bounded
shapes. XLA specializes one executable per input shape, so tiling a
raster at its natural (ragged) edge shapes would compile one program per
raster — the raster twin of the serving engine's unbounded-compile
problem. Every tile therefore has the SAME shape, drawn from the serve
bucket ladder applied per axis (`serve/bucket.py`): the requested tile
shape is snapped up to the ladder, edge tiles are padded, and a boolean
mask carries validity (in-bounds AND not nodata) so pad pixels are inert
in every fold. One tile shape == one compile signature for the whole
assign→join→fold pipeline, regardless of raster dimensions.

Tile order is row-major over the tile grid and is part of the fold
contract: `raster/zonal.py` merges per-tile partials in exactly this
order, and its f64 host oracle mirrors the same decomposition, which is
what makes the device fold bit-comparable to the oracle (float addition
is order-sensitive; fixing the order removes the ambiguity).

The geotransform→pixel-center→cell-ID assignment runs on device
(`tile_centers` / `assign_tile_cells`): a tile is described to the
device by its origin alone, so the staged tensors are just (T, TH, TW)
values + mask, and the affine + cell math fuses into the same program
as the probe and the fold.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import trace as _trace
from ..runtime import telemetry as _telemetry
from ..serve.bucket import BucketLadder

#: per-axis tile ladder bounds: 32 keeps toy fixtures honest (pad+mask
#: paths exercised), 2048 bounds one tile's VMEM/HBM footprint
DEFAULT_MIN_TILE = 32
DEFAULT_MAX_TILE = 2048

#: the default tile shape when neither the caller nor the
#: ``MOSAIC_RASTER_TILE`` knob says otherwise
DEFAULT_TILE = (256, 256)


def default_tile_shape() -> tuple[int, int]:
    """The process-default tile shape: ``MOSAIC_RASTER_TILE`` ("THxTW",
    e.g. "512x512") when set, else :data:`DEFAULT_TILE`. Read here — in
    host planning code, never inside a traced program — so the knob can
    never be baked stale into a compiled executable."""
    raw = os.environ.get("MOSAIC_RASTER_TILE")
    if not raw:
        return DEFAULT_TILE
    try:
        th, tw = (int(p) for p in raw.lower().split("x"))
        if th < 1 or tw < 1:
            raise ValueError(raw)
        return th, tw
    except Exception as e:
        raise ValueError(
            f"MOSAIC_RASTER_TILE must look like '256x256', got {raw!r}"
        ) from e


@dataclasses.dataclass(frozen=True)
class TilePlan:
    """The static decomposition of one raster into fixed-shape tiles.

    ``shape`` is the ladder-snapped (TH, TW) every tile dispatches at;
    ``origins`` is the (T, 2) int32 [row0, col0] table in row-major tile
    order (the fold-merge order). The plan is pure geometry — it holds
    no pixels, so it is cheap to persist in a snapshot sidecar and cheap
    to recompute on resume.
    """

    shape: tuple[int, int]
    requested: tuple[int, int]
    raster_shape: tuple[int, int]  # (H, W)
    gt: tuple
    origins: np.ndarray

    @property
    def ntiles(self) -> int:
        return int(self.origins.shape[0])

    @property
    def pixels(self) -> int:
        """Real (unpadded) pixel count covered by the plan."""
        return int(self.raster_shape[0]) * int(self.raster_shape[1])

    @property
    def padded_pixels(self) -> int:
        """Pixels actually dispatched (tiles × tile area) — the pad
        overhead the mask renders inert."""
        return self.ntiles * self.shape[0] * self.shape[1]


def plan_tiles(
    raster,
    tile: "tuple[int, int] | None" = None,
    *,
    min_tile: int = DEFAULT_MIN_TILE,
    max_tile: int = DEFAULT_MAX_TILE,
) -> TilePlan:
    """Decompose ``raster`` into a row-major grid of fixed-shape tiles.

    The requested ``tile`` (default: :func:`default_tile_shape`) is
    snapped UP per axis to the serve bucket ladder, so the set of
    possible compile signatures is the ladder's square, not the integers.
    """
    th_req, tw_req = tile if tile is not None else default_tile_shape()
    ladder = BucketLadder(
        min_bucket=min_tile, max_bucket=max_tile, growth=2
    )
    h, w = int(raster.height), int(raster.width)
    th = ladder.bucket_for(min(max(th_req, 1), max_tile))
    tw = ladder.bucket_for(min(max(tw_req, 1), max_tile))
    ny = max(1, -(-h // th))
    nx = max(1, -(-w // tw))
    origins = np.empty((ny * nx, 2), dtype=np.int32)
    t = 0
    for iy in range(ny):
        for ix in range(nx):
            origins[t] = (iy * th, ix * tw)
            t += 1
    return TilePlan(
        shape=(th, tw),
        requested=(int(th_req), int(tw_req)),
        raster_shape=(h, w),
        gt=tuple(raster.gt),
        origins=origins,
    )


def stack_tiles(
    raster,
    plan: TilePlan,
    band: int = 1,
    dtype=np.float64,
) -> tuple[np.ndarray, np.ndarray]:
    """Stage one band as ((T, TH, TW) ``dtype`` values, (T, TH, TW) bool
    mask). Mask True = in-bounds AND not nodata (NaN nodata handled like
    :attr:`RasterBand.mask`); pad pixels carry value 0 and mask False,
    so they are inert in every downstream fold.

    ``dtype`` is the caller's fold's: f64 for the grid fold, the
    expression compiler and every zones fold on the wide lane; the
    raster's own dtype where `ZonalEngine.fold_staging` found the int32
    lane (an int16 tile is then put as 131 KB, not 524 KB, and nothing
    is converted here). Handing a dtype the band's values do not fit
    is the caller's error: numpy casts as ``ndarray.__setitem__``
    does."""
    th, tw = plan.shape
    b = raster.band(band)
    t0 = time.perf_counter()
    with _trace.span(
        "raster.tile", ntiles=plan.ntiles, th=th, tw=tw, band=band
    ):
        vals_full = b.values
        mask_full = b.mask
        t = plan.ntiles
        vals = np.zeros((t, th, tw), dtype=dtype)
        mask = np.zeros((t, th, tw), dtype=bool)
        h, w = plan.raster_shape
        for i, (y0, x0) in enumerate(plan.origins):
            y1 = min(int(y0) + th, h)
            x1 = min(int(x0) + tw, w)
            sub = vals_full[int(y0):y1, int(x0):x1]
            vals[i, : sub.shape[0], : sub.shape[1]] = sub
            mask[i, : sub.shape[0], : sub.shape[1]] = mask_full[
                int(y0):y1, int(x0):x1
            ]
        # nodata pixels contribute value 0 under a False mask (keeps
        # NaNs out of the staged tensor entirely — a NaN times a zero
        # mask is still NaN, so zeroing here is load-bearing)
        vals[~mask] = 0
    _telemetry.record(
        "raster_stage", stage="tile",
        seconds=round(time.perf_counter() - t0, 6),
        ntiles=t, th=th, tw=tw,
        pixels=plan.pixels, padded_pixels=plan.padded_pixels,
    )
    return vals, mask


@functools.partial(jax.jit, static_argnames=("th", "tw"))
@jax.named_scope("zonal.centers")
def tile_centers(gt6, origin, *, th: int, tw: int):
    """((TH*TW, 2) f64) world coordinates of one tile's pixel centers,
    computed on device from the geotransform and the tile origin alone.
    Shape is static per tile shape — one compile signature — while the
    origin and geotransform stay traced arguments."""
    gt6 = jnp.asarray(gt6, jnp.float64)
    origin = jnp.asarray(origin, jnp.float64)
    r = (
        jnp.arange(th, dtype=jnp.float64)[:, None]
        + origin[0] + jnp.asarray(0.5, jnp.float64)
    )
    c = (
        jnp.arange(tw, dtype=jnp.float64)[None, :]
        + origin[1] + jnp.asarray(0.5, jnp.float64)
    )
    x = gt6[0] + c * gt6[1] + r * gt6[2]
    y = gt6[3] + c * gt6[4] + r * gt6[5]
    x = jnp.broadcast_to(x, (th, tw)).reshape(-1)
    y = jnp.broadcast_to(y, (th, tw)).reshape(-1)
    return jnp.stack([x, y], axis=-1)


def assign_tile_cells(gt, origin, shape, index_system, resolution):
    """(TH*TW,) int64 cell ids of one tile's pixel centers (device).
    Composable: traceable inside an outer jit, so the zonal frontends
    fuse assign + probe + fold into one program."""
    th, tw = shape
    xy = tile_centers(jnp.asarray(gt), jnp.asarray(origin), th=th, tw=tw)
    with jax.named_scope("pip.cells"):
        return index_system.point_to_cell(xy, resolution).astype(jnp.int64)
