"""The land-parcel layer, made by the benchmark (the deployment's input): a
cadastre of lots that TILE their street blocks, in metres on the British
National Grid (EPSG:27700).

Street blocks of ``block_m`` stand on a grid of ``street_m`` streets inside
``box`` (x0, y0, x1, y1). A block is two rows of lots, back to back: the front
row's lots are rectangles of 5-30 m frontage (log-uniform) whose depths differ
from lot to lot, so the line between the two rows is a staircase; the rear
row has a frontage partition of its own, and each of its lots takes the
staircase under it as its own boundary — a rectangle where no step falls
inside it, an L with one, a U, a Z or a comb with more (4-12 vertices; a
rear lot that would hold more than four steps is split). Every lot shares
its side and rear boundaries with its neighbours exactly: the same float64
numbers, vertex for vertex where both have one. Half the blocks are mirrored,
so the bent lots stand on both sides of a street. Every draw comes from
``seed``: the same parameters give the same layer, coordinate for coordinate.

    {"count": 196608, "box": [524000, 175000, 536000, 185000], "seed": 40}

``fabric(params)`` returns ``(parcels, layout)``: a parcel is one ``(n, 2)``
f64 open ring, counter-clockwise; ``layout`` holds what a theme layer needs to
run along the parcels' own boundaries (`themes.py`): the blocks' origins and,
for every block row, the two frontage lines (the lots' street-side edges) with
the lot corners on them.
"""

from __future__ import annotations

import math
from types import SimpleNamespace

import numpy as np

DEFAULTS = {
    "block_m": [200.0, 60.0],
    "street_m": [10.0, 16.0],
    "frontage_m": [5.0, 30.0],
    "depth_share": [0.38, 0.62],
    "max_steps": 4,
}


def _partition(length: float, lo: float, hi: float, rng) -> np.ndarray:
    """Lot corners 0 = x_0 < ... < x_n = length, frontages log-uniform in
    [lo, hi] and then stretched to fill the block exactly."""
    n = max(int(length / ((hi - lo) / math.log(hi / lo))), 1)
    w = np.exp(rng.uniform(math.log(lo), math.log(hi), n))
    xs = np.concatenate([[0.0], np.cumsum(w)]) * (length / w.sum())
    xs[-1] = length
    return xs


def _rear_lot(x0, x1, front_xs, depth, ly):
    """The rear lot over [x0, x1]: the staircase of the front row's depths
    from left to right, then the block's far frontage back."""
    inner = np.nonzero((front_xs > x0) & (front_xs < x1))[0]
    first = int(np.searchsorted(front_xs, x0, side="right")) - 1
    pts = [(x0, depth[first])]
    k = first
    for i in inner:
        # the front row's corner i lies between lots i - 1 and i
        if depth[i] != depth[k]:
            pts.append((front_xs[i], depth[k]))
            pts.append((front_xs[i], depth[i]))
        k = i
    pts.append((x1, depth[k]))
    pts.append((x1, ly))
    pts.append((x0, ly))
    return np.asarray(pts, dtype=np.float64)


def _block(p, rng):
    """One block's lots in block coordinates (x along 0..lx, y across
    0..ly) and its two frontage partitions."""
    lx, ly = p["block_m"]
    lo, hi = p["frontage_m"]
    front = _partition(lx, lo, hi, rng)
    # depths on a 0.25 m lattice: neighbours now and then share one, and
    # the staircase then has no step between them
    depth = np.round(
        rng.uniform(*p["depth_share"], front.shape[0] - 1) * ly * 4.0
    ) / 4.0
    rear = _partition(lx, lo, hi, rng)
    cuts = [rear[0]]
    for a, b in zip(rear[:-1], rear[1:]):
        inside = front[(front > a) & (front < b)]
        # at most max_steps staircase corners inside one rear lot
        for k in range(p["max_steps"], inside.shape[0], p["max_steps"]):
            cuts.append(0.5 * (inside[k - 1] + inside[k]))
        cuts.append(b)
    rear = np.asarray(cuts)
    lots = [
        np.array([[front[i], 0.0], [front[i + 1], 0.0],
                  [front[i + 1], depth[i]], [front[i], depth[i]]])
        for i in range(front.shape[0] - 1)
    ]
    lots += [
        _rear_lot(rear[k], rear[k + 1], front, depth, ly)
        for k in range(rear.shape[0] - 1)
    ]
    if rng.uniform() < 0.5:  # mirrored: the bent lots face the other street
        lots = [
            np.column_stack([r[:, 0], ly - r[:, 1]])[::-1] for r in lots
        ]
        front, rear = rear, front
    return lots, front, rear


def fabric(params: dict):
    """``count`` parcels and the layout, block after block, row-major from
    the south-west, cut at ``count``."""
    p = dict(DEFAULTS, **params)
    count = int(p["count"])
    rng = np.random.default_rng(int(p["seed"]))
    x0, y0, x1, y1 = (float(v) for v in p["box"])
    lx, ly = p["block_m"]
    smin, smax = p["street_m"]
    nx = int((x1 - x0 - smax) // (lx + smax))
    ny = int((y1 - y0 - smax) // (ly + smax))
    sx = rng.uniform(smin, smax, nx + 1)
    sy = rng.uniform(smin, smax, ny + 1)
    ox = x0 + np.cumsum(sx[:-1]) + lx * np.arange(nx)
    oy = y0 + np.cumsum(sy[:-1]) + ly * np.arange(ny)
    parcels: list = []
    fronts: dict = {}
    rows_made = 0
    for j in range(ny):
        for i in range(nx):
            lots, low, high = _block(p, rng)
            origin = np.array([ox[i], oy[j]])
            parcels.extend(lot + origin for lot in lots)
            fronts[(i, j)] = (low + ox[i], high + ox[i])
        rows_made = j + 1
        if len(parcels) >= count:
            break
    if len(parcels) < count:
        raise ValueError(
            f"{nx} x {ny} blocks hold {len(parcels)} parcels, fewer than "
            f"count={count}"
        )
    layout = SimpleNamespace(
        box=(x0, y0, x1, y1), block=(lx, ly), ox=ox, oy=oy, nx=nx, ny=ny,
        rows_made=rows_made, fronts=fronts,
    )
    return parcels[:count], layout


def vertex_counts(parcels) -> np.ndarray:
    return np.asarray([r.shape[0] for r in parcels])
