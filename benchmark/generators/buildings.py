"""The building layer, made by the benchmark (the deployment's input, as a
model's weights are): the fabric of a dense borough, not uniform scatter.

Street blocks of 80 x 270 m stand on a grid of 15-25 m streets. Each block
has one large footprint at an end (40-150 m long, a superellipse outline of
24-64 vertices, every second one with an inner courtyard ring) and is
otherwise cut into lots along its two long frontages: a row-house block
holds houses 5-8 m wide that share their side walls, a detached block holds
a rectangle, an L or a U of 8-30 m on every lot and a small rear building
behind most of them. No two footprints overlap (row houses touch along a
wall). The layer is made in metres on a local plane and mapped to lon/lat
around ``centre`` (a degree of latitude is 111,320 m, of longitude that
times cos(lat0)); every draw comes from ``seed``, so the same parameters
give the same layer, coordinate for coordinate.

    {"count": 65536, "centre": [-73.95, 40.70], "seed": 11, ...}

``fabric(params)`` returns ``(footprints, kinds)``: a footprint is a list of
open rings, each an ``(n, 2)`` f64 array, the outer ring first and counter-
clockwise, a courtyard after it and clockwise; ``kinds[i]`` is 0 for a row
house, 1 for a detached building, 2 for a large footprint.
"""

from __future__ import annotations

import math

import numpy as np

_M_PER_DEG = 111_320.0
ROW, DETACHED, LARGE = 0, 1, 2

DEFAULTS = {
    "block_m": [270.0, 80.0],
    "street_m": [15.0, 25.0],
    "row_block_share": 0.50,
    "row_width_m": [5.0, 8.0],
    "row_depth_m": [9.0, 14.0],
    "lot_width_m": [14.0, 24.0],
    "detached_m": [8.0, 30.0],
    "rear_share": 0.8,
    "large_share_of_blocks": 0.9,
    "large_m": [40.0, 150.0],
    "large_verts": [24, 64],
    "courtyard_share": 0.5,
    "setback_m": 3.0,
}


def _rect(x0, y0, x1, y1):
    return np.array([[x0, y0], [x1, y0], [x1, y1], [x0, y1]])


def _ell(x0, y0, x1, y1, cx, cy):
    """An L: the rectangle less its upper right corner from (cx, cy)."""
    return np.array(
        [[x0, y0], [x1, y0], [x1, cy], [cx, cy], [cx, y1], [x0, y1]]
    )


def _you(x0, y0, x1, y1, ax, bx, cy):
    """A U: the rectangle less a notch [ax, bx] x [cy, y1] in its top."""
    return np.array(
        [[x0, y0], [x1, y0], [x1, y1], [bx, y1], [bx, cy], [ax, cy],
         [ax, y1], [x0, y1]]
    )


def _flip(ring, ly):
    """Mirror a ring across the block's long axis (the other frontage) and
    keep it counter-clockwise."""
    out = ring.copy()
    out[:, 1] = ly - out[:, 1]
    return out[::-1]


def _superellipse(cx, cy, a, b, n, rng, jitter):
    t = (np.arange(n) + rng.uniform(0.0, 1.0)) * (2.0 * math.pi / n)
    c, s = np.cos(t), np.sin(t)
    r = 1.0 + jitter * rng.uniform(-1.0, 1.0, n)
    x = cx + a * r * np.sign(c) * np.abs(c) ** 0.5
    y = cy + b * r * np.sign(s) * np.abs(s) ** 0.5
    return np.column_stack([x, y])


def _frontage_row(x0, x1, p, rng):
    """Row houses along y = setback, sharing their side walls."""
    lo, hi = p["row_width_m"]
    n = max(int((x1 - x0) / (0.5 * (lo + hi))), 1)
    w = rng.uniform(lo, hi, n)
    xs = x0 + np.concatenate([[0.0], np.cumsum(w)]) * ((x1 - x0) / w.sum())
    d = rng.uniform(*p["row_depth_m"], n)
    y0 = p["setback_m"]
    return [_rect(xs[i], y0, xs[i + 1], y0 + d[i]) for i in range(n)], ROW


def _frontage_detached(x0, x1, ly, p, rng):
    lo, hi = p["lot_width_m"]
    n = max(int((x1 - x0) / (0.5 * (lo + hi))), 1)
    w = rng.uniform(lo, hi, n)
    xs = x0 + np.concatenate([[0.0], np.cumsum(w)]) * ((x1 - x0) / w.sum())
    smin, smax = p["detached_m"]
    y0 = p["setback_m"] + 1.0
    out = []
    for i in range(n):
        lot = xs[i + 1] - xs[i]
        bw = rng.uniform(smin, max(min(lot - 3.0, smax), smin + 0.5))
        bd = rng.uniform(smin, 16.0)
        ax = xs[i] + rng.uniform(1.5, max(lot - bw - 1.5, 1.6))
        shape = rng.uniform()
        if shape < 0.5:
            out.append(_rect(ax, y0, ax + bw, y0 + bd))
        elif shape < 0.8:
            out.append(_ell(
                ax, y0, ax + bw, y0 + bd,
                ax + bw * rng.uniform(0.35, 0.65),
                y0 + bd * rng.uniform(0.4, 0.7),
            ))
        else:
            out.append(_you(
                ax, y0, ax + bw, y0 + bd, ax + bw * rng.uniform(0.25, 0.4),
                ax + bw * rng.uniform(0.6, 0.75),
                y0 + bd * rng.uniform(0.4, 0.7),
            ))
        if rng.uniform() < p["rear_share"]:
            rw = rng.uniform(smin, min(lot - 3.0, 11.0))
            rx = xs[i] + rng.uniform(1.5, max(lot - rw - 1.5, 1.6))
            ry = 0.5 * ly - 2.0 - rng.uniform(8.0, 10.0)
            out.append(_rect(rx, ry, rx + rw, 0.5 * ly - 2.0))
    return out, DETACHED


def _block(p, rng):
    """One block's footprints in block coordinates (x along 0..lx, y across
    0..ly), as ``[(rings, kind), ...]``."""
    lx, ly = p["block_m"]
    out = []
    x0, x1 = 0.0, lx
    if rng.uniform() < p["large_share_of_blocks"]:
        lo, hi = p["large_m"]
        s = math.exp(rng.uniform(math.log(lo), math.log(hi)))
        at_end = rng.uniform() < 0.5
        cx = lx - 0.5 * s if at_end else 0.5 * s
        n = 2 * int(rng.integers(p["large_verts"][0] // 2,
                                 p["large_verts"][1] // 2 + 1))
        a, b = 0.5 * s - 2.0, 0.5 * ly - 4.0
        rings = [_superellipse(cx, 0.5 * ly, a, b, n, rng, 0.04)]
        if rng.uniform() < p["courtyard_share"]:
            k = rng.uniform(0.35, 0.5)
            m = 2 * int(rng.integers(4, 9))
            rings.append(
                _superellipse(cx, 0.5 * ly, k * a, k * b, m, rng, 0.0)[::-1]
            )
        out.append((rings, LARGE))
        if at_end:
            x1 = lx - s - 6.0
        else:
            x0 = s + 6.0
    rows = rng.uniform() < p["row_block_share"]
    for side in (0, 1):
        if rows:
            rings, kind = _frontage_row(x0, x1, p, rng)
        else:
            rings, kind = _frontage_detached(x0, x1, ly, p, rng)
        if side:
            rings = [_flip(r, ly) for r in rings]
        out.extend(([r], kind) for r in rings)
    return out


def fabric(params: dict):
    """``count`` footprints of the fabric and their kinds, block after
    block, row-major from the south-west, cut at ``count``."""
    p = dict(DEFAULTS, **params)
    count = int(p["count"])
    rng = np.random.default_rng(int(p["seed"]))
    lx, ly = p["block_m"]
    lon0, lat0 = p["centre"]
    kx = 1.0 / (_M_PER_DEG * math.cos(math.radians(lat0)))
    ky = 1.0 / _M_PER_DEG
    smid = 0.5 * (p["street_m"][0] + p["street_m"][1])
    # as many blocks as a square box needs at about 50 footprints a block
    blocks = max(int(math.ceil(count / 40.0)), 1)
    nx = max(int(math.ceil(math.sqrt(blocks * (ly + smid) / (lx + smid)))), 1)
    ny = -(-blocks // nx)
    sx = rng.uniform(*p["street_m"], nx)
    sy = rng.uniform(*p["street_m"], ny)
    ox = np.concatenate([[0.0], np.cumsum(lx + sx)])
    oy = np.concatenate([[0.0], np.cumsum(ly + sy)])
    footprints, kinds = [], []
    for j in range(ny):
        for i in range(nx):
            for rings, kind in _block(p, rng):
                footprints.append([
                    np.column_stack([
                        lon0 + (r[:, 0] + ox[i] - 0.5 * ox[-1]) * kx,
                        lat0 + (r[:, 1] + oy[j] - 0.5 * oy[-1]) * ky,
                    ])
                    for r in rings
                ])
                kinds.append(kind)
            if len(footprints) >= count:
                break
        if len(footprints) >= count:
            break
    if len(footprints) < count:
        raise ValueError(
            f"{nx} x {ny} blocks hold {len(footprints)} footprints, "
            f"fewer than count={count}"
        )
    return footprints[:count], np.asarray(kinds[:count], dtype=np.int8)


def footprints_bbox(footprints) -> tuple:
    allp = np.concatenate([f[0] for f in footprints])
    return (
        float(allp[:, 0].min()), float(allp[:, 1].min()),
        float(allp[:, 0].max()), float(allp[:, 1].max()),
    )
