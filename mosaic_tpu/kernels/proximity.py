"""Device-side distance-join kernel: the least distance of two polylines.

A candidate row of the distance join (`sql.proximity`) names two PIECES —
runs of at most ``V`` vertices of two lines, padded to ``V`` by repeating
the last vertex — in one local frame. Their distance is the least over
their segment pairs; two segments are 0 apart where they cross, else the
least of the four distances from an end of one to the other. Over the two
pieces that is

- the least distance from a vertex of either to a segment of the other —
  `knn.index.edge_terms`, the term the footprint KNN's edge program
  runs — and
- 0 where any segment of one properly crosses a segment of the other (a
  touch reads 0 from the end's distance, so the test is strict and a
  segment of no length, a pad or a point, crosses nothing).

:func:`piece_distance` is that, written once against the array-API subset
numpy and jax.numpy share (``xp``), vertex-major — ``(V, P)`` arrays, the
candidate rows along the lanes — as an unrolled loop over one piece's
vertices against all of the other's segments at once: the working set is
``V x P``, never ``V x V x P``. :func:`classify` turns a distance into the
row's answer: 1 within the threshold, 0 beyond it, and 2 inside the band
around it, where the arithmetic cannot tell and the f64 host lane answers
from the whole lines.

The device lane is this code under ``jnp`` in the accelerated dtype
(float32 on the TPU, float64 under x64 elsewhere:
`sql.overlay.overlay_acc_dtype`'s rule); the host lane and the oracle are
this code under ``np`` in float64 — elementwise IEEE operations agree
bitwise between numpy and XLA CPU, so off the TPU under x64 the two give
the same distances bit for bit.
"""

from __future__ import annotations

import jax.numpy as jnp

from ..knn.index import edge_terms
from .overlay import _scope

__all__ = ["MISS", "HIT", "BAND", "classify", "piece_distance", "pair_frame"]

#: a candidate row's answer
MISS, HIT, BAND = 0, 1, 2


def pair_frame(ta, tb, V: int, xp=jnp):
    """Two gathered piece rows, FIELD-major ``(2V + 5, P)`` — ``V`` x then
    ``V`` y coordinates relative to the piece's own first vertex, that
    vertex (relative to the table's shift) as a high and a low word an
    axis, the radius; the candidate rows along the lanes, so a field is
    one contiguous read — as ``(ax, ay, bx, by)`` vertex-major ``(V, P)``
    in the frame of A's first vertex, the threshold ``r_a + r_b`` and the
    frame's extent (the largest coordinate either piece holds in it).

    The two origins' difference is formed word by word: the high words of
    two nearby origins differ by an exactly representable amount, so the
    difference keeps the low words' precision whatever the table's
    extent — the frame costs the accelerated dtype nothing of its
    mantissa (`sql.overlay._pack_rings` subtracts its cell corners on the
    host for the same reason)."""
    with _scope("proximity.gather", xp):
        ox = (tb[2 * V] - ta[2 * V]) + (tb[2 * V + 1] - ta[2 * V + 1])
        oy = (tb[2 * V + 2] - ta[2 * V + 2]) + (tb[2 * V + 3] - ta[2 * V + 3])
        ax, ay = ta[:V], ta[V : 2 * V]
        bx, by = tb[:V] + ox[None, :], tb[V : 2 * V] + oy[None, :]
        thr = ta[2 * V + 4] + tb[2 * V + 4]
        extent = xp.maximum(
            xp.maximum(xp.abs(ax).max(axis=0), xp.abs(ay).max(axis=0)),
            xp.maximum(xp.abs(bx).max(axis=0), xp.abs(by).max(axis=0)),
        )
    return ax, ay, bx, by, thr, extent


def _segments(x, y, xp):
    """A piece's ``V - 1`` segments: starts, ends and ``1 / length^2`` (0
    for a segment of no length)."""
    x0, y0, x1, y1 = x[:-1], y[:-1], x[1:], y[1:]
    dx, dy = x1 - x0, y1 - y0
    len2 = dx * dx + dy * dy
    one = xp.asarray(1.0, len2.dtype)
    inv = xp.where(len2 > 0, one / xp.where(len2 > 0, len2, one), 0.0 * one)
    return x0, y0, x1, y1, inv


def piece_distance(ax, ay, bx, by, xp=jnp):
    """``(d2, crosses)`` of candidate rows: the least squared distance
    from a vertex of either piece to a segment of the other, and whether
    any two of their segments properly cross. ``(V, P)`` in, ``(P,)`` out."""
    V = ax.shape[0]
    with _scope("proximity.segpairs", xp):
        sa = _segments(ax, ay, xp)
        sb = _segments(bx, by, xp)
        best = None
        for px, py, seg in ((bx, by, sa), (ax, ay, sb)):
            for j in range(V):
                d2, _ = edge_terms(px[j][None, :], py[j][None, :], *seg, xp=xp)
                d2 = d2.min(axis=0)
                best = d2 if best is None else xp.minimum(best, d2)
        crosses = xp.zeros(best.shape, bool)
        a0x, a0y, a1x, a1y, _ = sa
        for j in range(V - 1):
            b0x, b0y = bx[j][None, :], by[j][None, :]
            b1x, b1y = bx[j + 1][None, :], by[j + 1][None, :]
            dax, day = a1x - a0x, a1y - a0y
            dbx, dby = b1x - b0x, b1y - b0y
            o1 = dax * (b0y - a0y) - day * (b0x - a0x)
            o2 = dax * (b1y - a0y) - day * (b1x - a0x)
            o3 = dbx * (a0y - b0y) - dby * (a0x - b0x)
            o4 = dbx * (a1y - b0y) - dby * (a1x - b0x)
            hit = (
                ((o1 > 0) & (o2 < 0)) | ((o1 < 0) & (o2 > 0))
            ) & (((o3 > 0) & (o4 < 0)) | ((o3 < 0) & (o4 > 0)))
            crosses = crosses | hit.any(axis=0)
    return best, crosses


def classify(d2, crosses, thr, band, live, xp=jnp):
    """The row's answer (int8): ``HIT`` where the pieces cross or lie
    within ``thr``, ``MISS`` beyond it, ``BAND`` where the distance is
    within ``band`` of the threshold (the f64 host lane's to answer);
    ``MISS`` where the row is not ``live``."""
    with _scope("proximity.fold", xp):
        d = xp.sqrt(d2)
        near = ~crosses & (xp.abs(d - thr) <= band)
        code = xp.where(
            near, BAND, xp.where(crosses | (d <= thr), HIT, MISS)
        )
        code = xp.where(live, code, MISS).astype(xp.int8)
    return code
