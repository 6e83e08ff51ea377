"""The plain reference of raster zonal statistics: every pixel centre is
placed from the geotransform in f64 numpy, given the smallest zone id whose
polygon contains it by the point reference's even-odd ray casting, and the
valid pixels' values are folded per zone in int64. No grid, no tiles, no
chip table, nothing of the program and nothing the program made."""

from __future__ import annotations

import numpy as np

from benchmark.references import pip_bruteforce

NO_ZONE = pip_bruteforce.NO_MATCH


def pixel_zones(rings, gt, shape, block_rows: int = 200) -> np.ndarray:
    """``(height, width)`` int32: the zone of each pixel centre ``(x0 +
    (c + 0.5) sx + (r + 0.5) rx, y0 + (c + 0.5) ry + (r + 0.5) sy)``, or
    ``NO_ZONE``. Computed ``block_rows`` rows at a time, so that a
    2400 x 2400 plane needs the point reference's temporaries for 480,000
    points only."""
    height, width = (int(v) for v in shape)
    x0, sx, rx, y0, ry, sy = (float(v) for v in gt)
    cols = np.arange(width, dtype=np.float64) + 0.5
    out = np.empty((height, width), dtype=np.int32)
    for r0 in range(0, height, block_rows):
        rows = np.arange(r0, min(r0 + block_rows, height), dtype=np.float64) + 0.5
        x = x0 + cols[None, :] * sx + rows[:, None] * rx
        y = y0 + cols[None, :] * ry + rows[:, None] * sy
        pts = np.stack([x.reshape(-1), y.reshape(-1)], axis=-1)
        out[r0:r0 + rows.size] = pip_bruteforce.answers(rings, pts).reshape(
            rows.size, width)
    return out


def stats(zones, values, nodata, num_zones: int) -> dict:
    """Per zone ``0..num_zones-1`` of one scene: ``count``, ``sum``, ``min``,
    ``max`` (int64 arrays) over the pixels whose value is not ``nodata``
    and whose centre lies in the zone; an empty zone has count 0 and
    sum, min, max 0."""
    values = np.asarray(values)
    keep = (zones >= 0) & (values != nodata)
    z = zones[keep]
    order = np.argsort(z, kind="stable")
    z, v = z[order], values[keep][order].astype(np.int64)
    bounds = np.searchsorted(z, np.arange(num_zones + 1))
    count = np.diff(bounds).astype(np.int64)
    out = {"count": count}
    live = count > 0
    starts = bounds[:-1][live]
    for name, fold in (("sum", np.add), ("min", np.minimum), ("max", np.maximum)):
        full = np.zeros(num_zones, dtype=np.int64)
        if starts.size:
            # reduceat folds [starts[i], starts[i+1]); the live zones'
            # runs are contiguous in the sorted order
            full[live] = fold.reduceat(v, starts)
        out[name] = full
    return out
