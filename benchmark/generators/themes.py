"""The theme layers a parcel layer is overlaid with, made by the benchmark
from a seed, in metres on the British National Grid: what a planning or
property-risk analyst holds beside the cadastre.

``districts(layout, params, seed)`` — a PARTITION of the parcels' box into
``grid`` (columns, rows) statistical districts (16 x 24: 750 x 417 m). Neighbours share ONE vertex
chain, made once and walked forwards by one and backwards by the other, so a
shared boundary is the same float64 numbers on both sides. The chains between
a district and the one above it run along the parcels' own street frontages —
the lots' street-side edges, exactly: they follow one side of a street, cross
it (where no parcel lies) and follow the other, a vertex every 15-40 m, on a
lot corner where one is near. The chains between a district and the one beside
it wander north across blocks and streets alike, a vertex every 15-40 m, and
cut lots as they come. Returns ``(polygons, stats)``: a polygon is a list of
open rings, here one, counter-clockwise; ``stats["along_parcel_share"]`` is
the share of the shared boundaries' length that runs on a frontage line.

``flood(layout, params, seed)`` — ``rivers`` corridors crossing the box from
west to east, each in three nested bands (a band is wider than the one inside
it everywhere, so bands overlap one another; ``band_share`` is the share of
the box the rivers' bands of one rank cover together), banks wiggling at the
same vertex spacing, and dry islands inside the innermost band (one a
stretch of the river, so no two overlap), which are hole rings of all three. Returns ``(polygons, stats)``: a polygon is its outer
ring followed by its islands, clockwise.
"""

from __future__ import annotations

import math

import numpy as np

SPACING_M = (15.0, 40.0)

DISTRICT_DEFAULTS = {
    "grid": [16, 24],
    "wander_m": 14.0,
    "run_m": [60.0, 220.0],
    "snap_m": 6.0,
}

FLOOD_DEFAULTS = {
    "rivers": 3,
    "band_share": [0.10, 0.18, 0.30],
    "meander_m": [100.0, 260.0],
    "wavelength_m": [3000.0, 5000.0],
    "bank_noise_m": 4.0,
    "islands_per_river": 4,
    "island_m": [40.0, 90.0],
}


def _steps(length: float, rng) -> np.ndarray:
    """0 = t_0 < ... < t_n = length with every step in SPACING_M."""
    lo, hi = SPACING_M
    n = max(int(math.ceil(length / (0.5 * (lo + hi)))), 1)
    while n * hi < length:
        n += 1
    w = rng.uniform(lo, hi, n)
    w *= length / w.sum()
    # the stretch may push a step out of the range: redraw towards the mean
    w = np.clip(w, lo, hi)
    w *= length / w.sum()
    t = np.concatenate([[0.0], np.cumsum(w)])
    t[-1] = length
    return t


# ---------------------------------------------------------------- districts


def _frontage_lines(layout, j: int):
    """The two frontage lines of the street under block row ``j`` (above
    row ``j - 1``): ``(y_low, corners_low, y_high, corners_high)`` — the top
    frontage of the row below and the bottom frontage of the row above, with
    the lot corners' x on each (sorted)."""
    lx, ly = layout.block
    y_low = layout.oy[j - 1] + ly
    y_high = layout.oy[j]
    low = np.concatenate([
        layout.fronts[(i, j - 1)][1] for i in range(layout.nx)
        if (i, j - 1) in layout.fronts
    ]) if j - 1 < layout.rows_made else np.zeros(0)
    high = np.concatenate([
        layout.fronts[(i, j)][0] for i in range(layout.nx)
        if (i, j) in layout.fronts
    ]) if j < layout.rows_made else np.zeros(0)
    return y_low, np.sort(low), y_high, np.sort(high)


def _street_chain(xa: float, xb: float, lines, p, rng):
    """A chain from (xa, mid-street) to (xb, mid-street) that runs on the
    two frontage lines in turn. Returns ``(points, on_line_length)``."""
    y_low, c_low, y_high, c_high = lines
    mid = 0.5 * (y_low + y_high)
    pts = [(xa, mid)]
    on_line = 0.0
    x = xa
    side = bool(rng.integers(0, 2))
    lo, hi = SPACING_M
    while xb - x > 2.0 * hi + lo:
        y, corners = (y_high, c_high) if side else (y_low, c_low)
        # cross (part of) the street to the frontage line, run along it
        x_on = x + 0.6 * rng.uniform(lo, hi)
        last = xb - 0.6 * rng.uniform(lo, hi)
        run_end = min(x_on + rng.uniform(*p["run_m"]), last)
        if last - run_end < 2.0 * hi + lo:
            run_end = last  # too little left for one more run: go on
        t = x_on + _steps(run_end - x_on, rng)
        if corners.shape[0] > 1:
            k = np.clip(np.searchsorted(corners, t), 1, corners.shape[0] - 1)
            near = np.where(
                np.abs(corners[k - 1] - t) < np.abs(corners[k] - t),
                corners[k - 1], corners[k],
            )
            snap = (np.abs(near - t) <= p["snap_m"]) & (near < xb - 5.0)
            t = np.where(snap, near, t)
            t = t[np.concatenate([[True], np.diff(t) > 1.0])]
        pts.extend((float(v), y) for v in t)
        on_line += float(t[-1] - t[0])
        x = float(t[-1])
        side = not side
    pts.append((xb, mid))
    return np.asarray(pts, dtype=np.float64), on_line


def _wander_chain(a, b, p, rng):
    """A chain from node ``a`` up to node ``b``: straight across the street
    band at both ends, then a walk whose sideways offset is a smoothed
    random one, a vertex every 15-40 m of the walk."""
    (xa, ya), (xb, yb) = a, b
    lead = 30.0
    t = _steps(yb - ya - 2.0 * lead, rng)
    n = t.shape[0]
    off = rng.normal(0.0, p["wander_m"], n)
    off = np.convolve(np.pad(off, 2, mode="edge"), np.ones(5) / 5.0, "valid")
    off += rng.normal(0.0, 0.35 * p["wander_m"], n)
    off[0] = off[-1] = 0.0
    s = t / t[-1]
    xs = xa + (xb - xa) * s + off
    ys = ya + lead + t
    return np.concatenate([
        [[xa, ya]], np.column_stack([xs, ys]), [[xb, yb]],
    ])


def _edge_chain(a, b, rng):
    """A straight chain along the box's edge, a vertex every 15-40 m."""
    a, b = np.asarray(a, float), np.asarray(b, float)
    t = _steps(float(np.hypot(*(b - a))), rng)
    return a + (b - a) * (t / t[-1])[:, None]


def districts(layout, params: dict, seed: int):
    p = dict(DISTRICT_DEFAULTS, **params)
    rng = np.random.default_rng([int(seed), 0xD157])
    gx, gy = (int(v) for v in p["grid"])
    x0, y0, x1, y1 = layout.box
    # node rows sit in streets: the street under block row j
    rows = np.round(np.linspace(0, layout.ny, gy + 1)).astype(int)
    node_y = np.empty(gy + 1)
    node_y[0], node_y[-1] = y0, y1
    lines = {}
    for r in range(1, gy):
        j = int(np.clip(rows[r], 1, layout.ny - 1))
        lines[r] = _frontage_lines(layout, j)
        node_y[r] = 0.5 * (lines[r][0] + lines[r][2])
    node_x = np.empty((gy + 1, gx + 1))
    cell_w = (x1 - x0) / gx
    for r in range(gy + 1):
        node_x[r] = x0 + cell_w * np.arange(gx + 1)
        node_x[r, 1:-1] += rng.uniform(-0.12, 0.12, gx - 1) * cell_w
    horiz, vert = {}, {}
    on_line = shared = 0.0
    for r in range(gy + 1):
        for c in range(gx):
            a, b = (node_x[r, c], node_y[r]), (node_x[r, c + 1], node_y[r])
            if r in (0, gy):
                horiz[(r, c)] = _edge_chain(a, b, rng)
            else:
                chain, run = _street_chain(a[0], b[0], lines[r], p, rng)
                horiz[(r, c)] = chain
                on_line += run
                shared += float(np.hypot(*np.diff(chain, axis=0).T).sum())
    for r in range(gy):
        for c in range(gx + 1):
            a, b = (node_x[r, c], node_y[r]), (node_x[r + 1, c], node_y[r + 1])
            if c in (0, gx):
                vert[(r, c)] = _edge_chain(a, b, rng)
            else:
                chain = _wander_chain(a, b, p, rng)
                vert[(r, c)] = chain
                shared += float(np.hypot(*np.diff(chain, axis=0).T).sum())
    polygons = []
    for r in range(gy):
        for c in range(gx):
            ring = np.concatenate([
                horiz[(r, c)][:-1], vert[(r, c + 1)][:-1],
                horiz[(r + 1, c)][::-1][:-1], vert[(r, c)][::-1][:-1],
            ])
            polygons.append([ring])
    stats = {
        "districts": len(polygons),
        "along_parcel_share": on_line / shared,
        "shared_boundary_km": shared / 1000.0,
        "vertices": int(sum(pg[0].shape[0] for pg in polygons)),
    }
    return polygons, stats


# -------------------------------------------------------------------- flood


def _bank(xs_box, centre, half, noise, rng):
    """One bank: x at 15-40 m of path (nearly: the bank's slope is gentle),
    y = centre(x) + half(x) + noise."""
    x0, x1 = xs_box
    t = x0 + _steps(x1 - x0, rng)
    return np.column_stack([
        t, centre(t) + half(t) + rng.uniform(-noise, noise, t.shape[0]),
    ])


def _island(cx, cy, a, b, rng):
    """A wiggly closed loop, CLOCKWISE (a hole), a vertex every 15-40 m."""
    per = math.pi * (3 * (a + b) - math.sqrt((3 * a + b) * (a + 3 * b)))
    n = max(int(per / 27.0), 6)
    th = (np.arange(n) + rng.uniform(0, 1)) * (2 * math.pi / n)
    r = 1.0 + 0.12 * rng.uniform(-1, 1, n)
    ring = np.column_stack([cx + a * r * np.cos(th), cy + b * r * np.sin(th)])
    return ring[::-1]


def flood(layout, params: dict, seed: int):
    p = dict(FLOOD_DEFAULTS, **params)
    rng = np.random.default_rng([int(seed), 0xF100D])
    x0, y0, x1, y1 = layout.box
    n_riv = int(p["rivers"])
    height = y1 - y0
    shares = [float(s) for s in p["band_share"]]
    polygons = []
    islands_made = 0
    for k in range(n_riv):
        yc = y0 + height * (k + 0.5) / n_riv + rng.uniform(-0.04, 0.04) * height
        amp = rng.uniform(*p["meander_m"])
        lam = rng.uniform(*p["wavelength_m"])
        ph = rng.uniform(0, 2 * math.pi)
        amp2, lam2, ph2 = 0.15 * amp, 0.45 * lam, rng.uniform(0, 2 * math.pi)

        def centre(t, yc=yc, amp=amp, lam=lam, ph=ph, amp2=amp2, lam2=lam2,
                   ph2=ph2):
            return (
                yc + amp * np.sin(2 * math.pi * t / lam + ph)
                + amp2 * np.sin(2 * math.pi * t / lam2 + ph2)
            )

        wob = rng.uniform(0, 2 * math.pi, 2)
        holes = []
        inner_half = 0.5 * shares[0] * height / n_riv
        n_isl = int(p["islands_per_river"])
        reach = (x1 - x0 - 800.0) / max(n_isl, 1)
        for k in range(n_isl):
            a = min(rng.uniform(*p["island_m"]), 0.17 * reach)
            b = min(rng.uniform(0.4, 0.6) * a, 0.4 * inner_half)
            # one island a stretch of the river: two never overlap
            cx = x0 + 400.0 + (k + rng.uniform(0.2, 0.8)) * reach
            holes.append(_island(cx, float(centre(np.array([cx]))[0]), a, b, rng))
        islands_made += len(holes)
        for rank, share in enumerate(shares):
            mean_half = 0.5 * share * height / n_riv

            def half(t, mean_half=mean_half, wob=wob):
                # the same swell on every band, so they stay nested
                return mean_half * (
                    1.0 + 0.18 * np.sin(2 * math.pi * t / 1900.0 + wob[0])
                )

            noise = min(p["bank_noise_m"], 0.1 * mean_half)
            south = _bank((x0, x1), centre, lambda t: -half(t), noise, rng)
            north = _bank((x0, x1), centre, half, noise, rng)
            outer = np.concatenate([south, north[::-1]])
            polygons.append([outer] + [h.copy() for h in holes])
    stats = {
        "polygons": len(polygons), "rivers": n_riv, "islands": islands_made,
        "vertices": int(sum(r.shape[0] for pg in polygons for r in pg)),
    }
    return polygons, stats


def polygon_area(rings) -> float:
    """|outer| - |holes| of one polygon (the shoelace, about its first
    vertex)."""
    total = 0.0
    for i, r in enumerate(rings):
        d = r - r[0]
        a = 0.5 * abs(float(np.sum(
            d[:, 0] * np.roll(d[:, 1], -1) - np.roll(d[:, 0], -1) * d[:, 1]
        )))
        total += a if i == 0 else -a
    return total
