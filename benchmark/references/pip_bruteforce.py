"""The plain reference of a point-in-polygon join: for each point, the
smallest zone id whose polygon contains it, or -1 — even-odd ray casting
in f64 numpy against the zone rings themselves. No grid, no tessellation,
no chip table, nothing of the program and nothing the program made."""

from __future__ import annotations

import numpy as np

NO_MATCH = -1


def _inside(px, py, ring) -> np.ndarray:
    ax, ay = ring[:, 0], ring[:, 1]
    bx, by = np.roll(ax, -1), np.roll(ay, -1)
    inside = np.zeros(px.shape[0], dtype=bool)
    for x1, y1, x2, y2 in zip(ax, ay, bx, by):
        if y1 == y2:
            continue
        straddles = (y1 > py) != (y2 > py)
        xi = x1 + (py - y1) * (x2 - x1) / (y2 - y1)
        inside ^= straddles & (px < xi)
    return inside


def answers(rings, points) -> np.ndarray:
    """(N,) int32 zone ids for ``points`` (N, 2) f64."""
    p = np.asarray(points, dtype=np.float64)
    px, py = p[:, 0], p[:, 1]
    out = np.full(p.shape[0], NO_MATCH, dtype=np.int32)
    # descending, so that the smallest containing zone is written last
    for z in range(len(rings) - 1, -1, -1):
        ring = np.asarray(rings[z], dtype=np.float64)
        x0, y0 = ring.min(axis=0)
        x1, y1 = ring.max(axis=0)
        idx = np.nonzero(
            (px >= x0) & (px <= x1) & (py >= y0) & (py <= y1)
        )[0]
        if idx.size:
            hit = _inside(px[idx], py[idx], ring)
            out[idx[hit]] = z
    return out
