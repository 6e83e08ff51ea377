"""The plain reference of a k-nearest-neighbour search whose landmarks are
polygons: for each footprint, every candidate point's `st_distance` to it
in the coordinates' own units — 0.0 where the point is inside the footprint
by the even-odd rule over ALL its rings (so a point in a courtyard is
outside) or on its boundary, else the least distance to a segment of any
ring — in f64 numpy, a footprint at a time and an edge at a time, and the k
smallest by (distance, candidate id). No grid, no rings of cells, no index,
nothing of the program and nothing the program made.

A footprint is a list of open rings, each an ``(n, 2)`` f64 array (the
building generator's form). Candidates too far to rank are set aside first,
exactly: the k-th smallest distance to the footprint's FIRST VERTEX bounds
the k-th smallest distance to the footprint from above (a polygon is no
farther than any of its points), and a candidate is no nearer the footprint
than its bounding box, so one whose box distance exceeds that bound cannot
be among the k. ``prune=False`` evaluates every candidate (a test holds the
two equal)."""

from __future__ import annotations

import numpy as np


def polygon_distance(rings, px, py):
    """(M,) f64 distances from the points ``(px, py)`` to one footprint."""
    d2 = np.full(px.shape, np.inf)
    odd = np.zeros(px.shape, dtype=bool)
    for ring in rings:
        ring = np.asarray(ring, dtype=np.float64)
        nxt = np.roll(ring, -1, axis=0)
        for (ax, ay), (bx, by) in zip(ring, nxt):
            dx, dy = bx - ax, by - ay
            rx, ry = px - ax, py - ay
            len2 = dx * dx + dy * dy
            t = np.clip((rx * dx + ry * dy) / len2, 0.0, 1.0) if len2 > 0 else 0.0
            d2 = np.minimum(d2, (rx - t * dx) ** 2 + (ry - t * dy) ** 2)
            if ay != by:  # a ray towards +x from the point crosses the edge
                cross = ((ay > py) != (by > py)) & (
                    px < ax + (py - ay) * dx / dy
                )
                odd ^= cross
    return np.where(odd, 0.0, np.sqrt(d2))


def answers(footprints, candidates, k: int, prune: bool = True):
    """``(ids (L, k) int64, distances (L, k) f64)``, ranked; -1 / inf
    where there are fewer than k candidates."""
    cd = np.asarray(candidates, dtype=np.float64)
    cx, cy = np.ascontiguousarray(cd[:, 0]), np.ascontiguousarray(cd[:, 1])
    n, m = len(footprints), cd.shape[0]
    kk = min(k, m)
    ids = np.full((n, k), -1, dtype=np.int64)
    dist = np.full((n, k), np.inf)
    for i in range(n if kk else 0):
        rings = footprints[i]
        near = np.arange(m)
        if prune:
            v = np.asarray(rings[0][0], dtype=np.float64)
            dv = np.sqrt((cx - v[0]) ** 2 + (cy - v[1]) ** 2)
            bound = np.partition(dv, kk - 1)[kk - 1]
            allp = np.concatenate([np.asarray(r) for r in rings])
            (x0, y0), (x1, y1) = allp.min(axis=0), allp.max(axis=0)
            # a box grown by the bound holds every candidate whose distance
            # to the footprint's own box is within it (and a few more)
            near = np.flatnonzero(
                (cx >= x0 - bound) & (cx <= x1 + bound)
                & (cy >= y0 - bound) & (cy <= y1 + bound)
            )
        d = polygon_distance(rings, cx[near], cy[near])
        order = np.lexsort((near, d))[:kk]
        ids[i, :kk] = near[order]
        dist[i, :kk] = d[order]
    return ids, dist


def distances(footprints, candidates, ids):
    """(L, k) f64: the true distance from each footprint to the candidates
    ``ids`` names for it (inf where an id is -1)."""
    cd = np.asarray(candidates, dtype=np.float64)
    ids = np.asarray(ids)
    out = np.full(ids.shape, np.inf)
    for i, rings in enumerate(footprints):
        got = cd[np.clip(ids[i], 0, None)]
        d = polygon_distance(rings, got[:, 0], got[:, 1])
        out[i] = np.where(ids[i] >= 0, d, np.inf)
    return out
