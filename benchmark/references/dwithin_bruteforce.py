"""The plain reference of the distance self-join: float64 numpy, every
pair ``a < b`` of a set of tracks, the exact least segment-segment
distance against ``r_a + r_b``. No grid, no cover, no candidate
generation, nothing of the package under test.

``buffer(A, r_a)`` meets ``buffer(B, r_b)`` where ``dist(A, B) <= r_a +
r_b`` (round buffers; the source's polygonised ones fall short of that by
at most 0.48% of a radius, which the configuration states). The distance
of two polylines is the least over their segment pairs; two segments are
0 apart where they cross, else the least of the four distances from an
end of one to the other. So over a pair of tracks: the least distance
from a vertex of either to a segment of the other, or 0 where any two
segments cross.

Pairs are pruned by one exact test only: two tracks whose bounding boxes
lie farther apart than the threshold cannot be within it. Everything is
computed in blocks, each pair in a frame of its own (the first vertex of
``a``), so a distance keeps float64's relative precision wherever on the
globe the tracks lie.

A track of one vertex is a point (a segment of no length).
"""

from __future__ import annotations

import numpy as np

#: pairs a block of the exact distance holds (its (pairs, 15, 15)
#: temporaries stay in the cache: a block of 8,192 took twice the seconds)
BLOCK_PAIRS = 1 << 8
#: rows of the box test's blocks
BLOCK_ROWS = 128


def _padded(xy, offsets, rows, width):
    """(R, width, 2) vertices of ``rows``, the last repeated to the width,
    and their counts."""
    n = offsets[rows + 1] - offsets[rows]
    j = np.minimum(np.arange(width)[None, :], n[:, None] - 1)
    return xy[offsets[rows][:, None] + j], n


def _point_segment(px, py, ax, ay, bx, by):
    """Distance from points to segments ``a -> b``, elementwise."""
    dx, dy = bx - ax, by - ay
    rx, ry = px - ax, py - ay
    len2 = dx * dx + dy * dy
    dot = rx * dx + ry * dy
    t = np.clip(
        np.divide(dot, len2, out=np.zeros_like(dot), where=len2 > 0), 0.0, 1.0
    )
    return np.hypot(rx - t * dx, ry - t * dy)


def _orient(ax, ay, bx, by, cx, cy):
    return (bx - ax) * (cy - ay) - (by - ay) * (cx - ax)


def distances(xy, offsets, a, b) -> np.ndarray:
    """(P,) f64 least distance of tracks ``a[p]`` and ``b[p]``."""
    xy = np.asarray(xy, np.float64)
    offsets = np.asarray(offsets, np.int64)
    a, b = np.asarray(a, np.int64), np.asarray(b, np.int64)
    out = np.empty(a.shape[0], np.float64)
    if not a.size:
        return out
    width = int((offsets[1:] - offsets[:-1])[np.concatenate([a, b])].max())
    for s in range(0, a.shape[0], BLOCK_PAIRS):
        ia, ib = a[s : s + BLOCK_PAIRS], b[s : s + BLOCK_PAIRS]
        va, _ = _padded(xy, offsets, ia, width)
        vb, _ = _padded(xy, offsets, ib, width)
        origin = va[:, :1]
        va, vb = va - origin, vb - origin
        # (the pad repeats the last vertex: its segments have no length
        # and add nothing a real vertex does not)
        best = np.full(ia.shape[0], np.inf)
        for p, q in ((va, vb), (vb, va)):
            qa, qb = q, np.concatenate([q[:, 1:], q[:, -1:]], axis=1)
            d = _point_segment(
                p[:, :, None, 0], p[:, :, None, 1],
                qa[:, None, :, 0], qa[:, None, :, 1],
                qb[:, None, :, 0], qb[:, None, :, 1],
            )
            best = np.minimum(best, d.min(axis=(1, 2)))
        if width > 1:
            a0, a1 = va[:, :-1, None], va[:, 1:, None]
            b0, b1 = vb[:, None, :-1], vb[:, None, 1:]
            o1 = _orient(a0[..., 0], a0[..., 1], a1[..., 0], a1[..., 1],
                         b0[..., 0], b0[..., 1])
            o2 = _orient(a0[..., 0], a0[..., 1], a1[..., 0], a1[..., 1],
                         b1[..., 0], b1[..., 1])
            o3 = _orient(b0[..., 0], b0[..., 1], b1[..., 0], b1[..., 1],
                         a0[..., 0], a0[..., 1])
            o4 = _orient(b0[..., 0], b0[..., 1], b1[..., 0], b1[..., 1],
                         a1[..., 0], a1[..., 1])
            # a proper crossing; a touch reads 0 from the end's distance
            cross = ((o1 > 0) != (o2 > 0)) & ((o3 > 0) != (o4 > 0)) \
                & (o1 != 0) & (o2 != 0) & (o3 != 0) & (o4 != 0)
            best = np.where(cross.any(axis=(1, 2)), 0.0, best)
        out[s : s + BLOCK_PAIRS] = best
    return out


def box_candidates(xy, offsets, rows, radius):
    """Pairs ``(a, b)`` of ``rows`` with ``a < b`` whose bounding boxes are
    no farther apart than ``radius[a] + radius[b]`` (with a rounding
    step's room): every other pair is farther apart than its threshold."""
    xy = np.asarray(xy, np.float64)
    offsets = np.asarray(offsets, np.int64)
    rows = np.sort(np.asarray(rows, np.int64))
    radius = np.asarray(radius, np.float64)
    starts = offsets[rows]
    if np.any(offsets[rows + 1] <= starts):
        raise ValueError("a track with no vertex")
    # (rows need not be consecutive: reduce each run by itself)
    idx = np.stack([starts, offsets[rows + 1]], axis=1).reshape(-1)
    last = idx[-1] == xy.shape[0]
    idx = idx[:-1] if last else idx
    lo = np.minimum.reduceat(xy, idx, axis=0)[0::2]
    hi = np.maximum.reduceat(xy, idx, axis=0)[0::2]
    r = radius[rows]
    out_a, out_b = [], []
    for s in range(0, rows.shape[0], BLOCK_ROWS):
        e = min(s + BLOCK_ROWS, rows.shape[0])
        # (rows are sorted: a row's partners b > a stand from the block's
        # first row on)
        gap = np.maximum(
            np.maximum(lo[None, s:, :] - hi[s:e, None, :],
                       lo[s:e, None, :] - hi[None, s:, :]), 0.0,
        )
        d = np.hypot(gap[..., 0], gap[..., 1])
        thr = r[s:e, None] + r[None, s:]
        near = d <= thr * (1.0 + 1e-9)
        i, j = np.nonzero(near)
        keep = i < j
        out_a.append(rows[s + i[keep]])
        out_b.append(rows[s + j[keep]])
    return np.concatenate(out_a), np.concatenate(out_b)


def within(xy, offsets, radius, rows=None, key=None, rel_tol: float = 1e-12):
    """The answer over ``rows`` (default: all): ``(pairs (P, 2) i64 sorted
    with a < b, ambiguous (Q, 2) i64)`` — the pairs with ``dist <= r_a +
    r_b`` and, apart, those whose distance lies within ``rel_tol`` of the
    threshold (relative): a pair no arithmetic can be held to. ``key``:
    only pairs of equal key are compared (the window)."""
    offsets = np.asarray(offsets, np.int64)
    radius = np.broadcast_to(
        np.asarray(radius, np.float64), (offsets.shape[0] - 1,)
    )
    rows = np.arange(offsets.shape[0] - 1) if rows is None else np.asarray(rows, np.int64)
    groups = [rows]
    if key is not None:
        key = np.asarray(key)
        groups = [rows[key[rows] == k] for k in np.unique(key[rows])]
    pairs, unsure = [], []
    for g in groups:
        a, b = box_candidates(xy, offsets, g, radius)
        d = distances(xy, offsets, a, b)
        thr = radius[a] + radius[b]
        close = np.abs(d - thr) <= rel_tol * thr
        ab = np.column_stack([a, b])
        pairs.append(ab[(d <= thr) & ~close])
        unsure.append(ab[close])

    def ordered(parts):
        ab = np.concatenate(parts) if parts else np.zeros((0, 2), np.int64)
        return ab[np.lexsort((ab[:, 1], ab[:, 0]))].astype(np.int64)

    return ordered(pairs), ordered(unsure)
