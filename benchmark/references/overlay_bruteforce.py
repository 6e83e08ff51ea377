"""The plain reference of a polygon-polygon overlay: the area two polygons
share, in f64 numpy on the WHOLE geometries — no grid, no chips, no clipper,
nothing of the program and nothing the program made.

For a polygon ``A`` (rings of either direction: a hole is a ring that runs
the other way) and a point above a baseline ``y0`` under everything,

    1_A(x, y) = sum over the edges e of A whose x-range holds x of
                s_e * [y < y_e(x)],      s_e = +1 where e runs towards -x,

(the edges over a point, top edges counting +1 and bottom edges -1). So

    area(A ∩ B) = sum_e sum_f s_e s_f * integral over the edges' common
                  x-range of (min(y_e(x), y_f(x)) - y0) dx,

and two straight edges cross at most once over that range, so each integral
is a closed form: one or two trapezoids. Vertical edges have no x-range and
add nothing.
"""

from __future__ import annotations

import numpy as np


def edges_of(rings):
    """``(x0, y0, x1, y1, s)`` of every non-vertical edge of a polygon's
    rings, each edge turned to run towards +x; ``s`` is +1 where the ring
    ran it towards -x. Counter-clockwise outer rings and clockwise holes."""
    xa, ya, xb, yb = [], [], [], []
    for r in rings:
        r = np.asarray(r, dtype=np.float64)
        n = np.roll(r, -1, axis=0)
        xa.append(r[:, 0]); ya.append(r[:, 1])
        xb.append(n[:, 0]); yb.append(n[:, 1])
    xa, ya, xb, yb = (np.concatenate(v) for v in (xa, ya, xb, yb))
    keep = xa != xb
    xa, ya, xb, yb = xa[keep], ya[keep], xb[keep], yb[keep]
    back = xb < xa
    s = np.where(back, 1.0, -1.0)
    return (
        np.where(back, xb, xa), np.where(back, yb, ya),
        np.where(back, xa, xb), np.where(back, ya, yb), s,
    )


def area_of(rings) -> float:
    """|polygon| by the same edges: sum_e s_e * integral of y_e."""
    x0, y0, x1, y1, s = edges_of(rings)
    base = min(y0.min(), y1.min())
    return float(np.sum(s * 0.5 * ((y0 - base) + (y1 - base)) * (x1 - x0)))


def intersection_area(a_edges, b_edges) -> float:
    """area(A ∩ B) from the two polygons' :func:`edges_of`."""
    ax0, ay0, ax1, ay1, sa = a_edges
    bx0, by0, bx1, by1, sb = b_edges
    if not ax0.size or not bx0.size:
        return 0.0
    base = min(ay0.min(), ay1.min(), by0.min(), by1.min())
    lo = np.maximum(ax0[:, None], bx0[None, :])
    hi = np.minimum(ax1[:, None], bx1[None, :])
    ii, jj = np.nonzero(hi > lo)
    if not ii.size:
        return 0.0
    lo, hi = lo[ii, jj], hi[ii, jj]
    ka = (ay1[ii] - ay0[ii]) / (ax1[ii] - ax0[ii])
    kb = (by1[jj] - by0[jj]) / (bx1[jj] - bx0[jj])
    # both edges' heights over the baseline at the two ends of the range
    a_lo = ay0[ii] + ka * (lo - ax0[ii]) - base
    a_hi = ay0[ii] + ka * (hi - ax0[ii]) - base
    b_lo = by0[jj] + kb * (lo - bx0[jj]) - base
    b_hi = by0[jj] + kb * (hi - bx0[jj]) - base
    d_lo, d_hi = a_lo - b_lo, a_hi - b_hi
    crossing = d_lo * d_hi < 0.0
    # without a crossing the lower edge is lower at both ends
    whole = 0.5 * (np.minimum(a_lo, b_lo) + np.minimum(a_hi, b_hi)) * (hi - lo)
    # with one, at x*: the lower one before it, the other after it
    with np.errstate(divide="ignore", invalid="ignore"):
        t = np.where(crossing, d_lo / (d_lo - d_hi), 0.0)
    xs = lo + t * (hi - lo)
    y_star = a_lo + (a_hi - a_lo) * t
    split = (
        0.5 * (np.minimum(a_lo, b_lo) + y_star) * (xs - lo)
        + 0.5 * (y_star + np.minimum(a_hi, b_hi)) * (hi - xs)
    )
    return float(np.sum(sa[ii] * sb[jj] * np.where(crossing, split, whole)))


def bbox_of(rings):
    r = np.asarray(rings[0], dtype=np.float64)
    return r[:, 0].min(), r[:, 1].min(), r[:, 0].max(), r[:, 1].max()


def overlay(parcels, sample, polygons):
    """For every sampled parcel (a list of rings each) and every polygon
    whose box meets the parcel's: ``(parcel ids, polygon ids, areas)`` —
    every pair is listed, a disjoint one with 0.0."""
    boxes = np.asarray([bbox_of(pg) for pg in polygons])
    edges = [edges_of(pg) for pg in polygons]
    out_p, out_q, out_a = [], [], []
    for i in sample:
        rings = parcels[int(i)]
        x0, y0, x1, y1 = bbox_of(rings)
        meets = np.nonzero(
            (boxes[:, 0] <= x1) & (boxes[:, 2] >= x0)
            & (boxes[:, 1] <= y1) & (boxes[:, 3] >= y0)
        )[0]
        pe = edges_of(rings)
        for q in meets:
            qx0, qy0, qx1, qy1, qs = edges[q]
            near = (qx1 > x0) & (qx0 < x1)  # an edge over the parcel's x-range
            qe = (qx0[near], qy0[near], qx1[near], qy1[near], qs[near])
            out_p.append(int(i))
            out_q.append(int(q))
            out_a.append(intersection_area(pe, qe))
    return (
        np.asarray(out_p, np.int64), np.asarray(out_q, np.int64),
        np.asarray(out_a, np.float64),
    )
