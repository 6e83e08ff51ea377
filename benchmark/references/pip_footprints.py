"""The plain reference of a point-in-footprint join: for each point, the
smallest footprint id whose polygon contains it, or -1 — even-odd ray
casting in f64 numpy over every ring of a footprint (an inner courtyard
ring flips the parity back: a point in a courtyard is outside). Candidates
come from a uniform lon/lat bucket grid over the footprints' bounding
boxes, so 65,536 footprints cost one pass over the points and not 65,536.
No H3, no tessellation, no chip table, nothing of the program and nothing
the program made.

A footprint is a list of open rings, each an ``(n, 2)`` f64 array (the
building generator's form); a bare ``(n, 2)`` array is a footprint of one
ring, so a zone layer in `pip_bruteforce`'s form is taken as it is."""

from __future__ import annotations

import numpy as np

NO_MATCH = -1
#: buckets per axis at most; a bucket is at least as wide as the median
#: footprint bbox, so a footprint lies in a handful of buckets
_MAX_BUCKETS = 2048


def _rings(footprint) -> list:
    if isinstance(footprint, np.ndarray):
        return [footprint]
    return list(footprint)


def _edges(footprints):
    """Every edge of every ring: (E, 4) ax, ay, bx, by and the footprint
    id per edge, grouped by footprint; plus the (F, 4) bboxes."""
    a, b, owner = [], [], []
    boxes = np.empty((len(footprints), 4))
    for f, fp in enumerate(footprints):
        rings = [np.asarray(r, dtype=np.float64) for r in _rings(fp)]
        for r in rings:
            a.append(r)
            b.append(np.roll(r, -1, axis=0))
            owner.append(np.full(r.shape[0], f, dtype=np.int64))
        outer = rings[0]
        boxes[f] = (*outer.min(axis=0), *outer.max(axis=0))
    e = np.concatenate([np.concatenate(a), np.concatenate(b)], axis=1)
    return e, np.concatenate(owner), boxes


def _ranges(starts, lens):
    """Indices start .. start + len of every range, one after another, and
    the range each index came from."""
    off = np.concatenate([[0], np.cumsum(lens)])
    which = np.repeat(np.arange(lens.shape[0]), lens)
    return starts[which] + np.arange(off[-1]) - off[:-1][which], which


def answers(footprints, points, chunk: int = 1 << 16) -> np.ndarray:
    """(N,) int32 footprint ids for ``points`` (N, 2) f64."""
    p = np.asarray(points, dtype=np.float64)
    out = np.full(p.shape[0], NO_MATCH, dtype=np.int32)
    if not len(footprints) or not p.shape[0]:
        return out
    edges, owner, boxes = _edges(footprints)
    e_off = np.searchsorted(owner, np.arange(len(footprints) + 1))
    # the bucket grid over the layer's box
    lo, hi = boxes[:, :2].min(axis=0), boxes[:, 2:].max(axis=0)
    size = np.maximum(np.median(boxes[:, 2:] - boxes[:, :2], axis=0), 1e-12)
    nb = np.clip(np.ceil((hi - lo) / size), 1, _MAX_BUCKETS).astype(np.int64)
    size = np.maximum((hi - lo) / nb, 1e-300)

    def bucket(xy):
        return np.clip(((xy - lo) / size).astype(np.int64), 0, nb - 1)

    # (bucket, footprint) for every bucket a footprint's bbox touches
    b0, b1 = bucket(boxes[:, :2]), bucket(boxes[:, 2:])
    nxy = b1 - b0 + 1
    k, f = _ranges(np.zeros(len(footprints), np.int64), nxy[:, 0] * nxy[:, 1])
    key = (b0[f, 0] + k // nxy[f, 1]) * nb[1] + b0[f, 1] + k % nxy[f, 1]
    order = np.argsort(key, kind="stable")
    key, members = key[order], f[order]
    inside_box = ((p >= lo) & (p <= hi)).all(axis=1)
    rows = np.nonzero(inside_box)[0]
    for s in range(0, rows.shape[0], chunk):
        r = rows[s : s + chunk]
        pb = bucket(p[r])
        pk = pb[:, 0] * nb[1] + pb[:, 1]
        m0 = np.searchsorted(key, pk)
        # (point, candidate footprint) pairs, kept where the bbox holds it
        mi, pi = _ranges(m0, np.searchsorted(key, pk, side="right") - m0)
        cand = members[mi]
        px, py = p[r[pi], 0], p[r[pi], 1]
        bx = boxes[cand]
        keep = (px >= bx[:, 0]) & (px <= bx[:, 2]) & (py >= bx[:, 1]) & (
            py <= bx[:, 3]
        )
        pi, cand, px, py = pi[keep], cand[keep], px[keep], py[keep]
        # (pair, edge) crossings of the +x ray, summed per pair
        ei, qi = _ranges(e_off[cand], e_off[cand + 1] - e_off[cand])
        x1, y1, x2, y2 = edges[ei].T
        qy = py[qi]
        straddles = (y1 > qy) != (y2 > qy)
        with np.errstate(divide="ignore", invalid="ignore"):
            xi = x1 + (qy - y1) * (x2 - x1) / (y2 - y1)
        crossed = straddles & (px[qi] < xi)
        inside = np.bincount(qi, weights=crossed, minlength=cand.shape[0])
        hit = inside.astype(np.int64) & 1 == 1
        # smallest containing footprint id per point
        best = np.full(r.shape[0], np.iinfo(np.int32).max, dtype=np.int64)
        np.minimum.at(best, pi[hit], cand[hit])
        found = best != np.iinfo(np.int32).max
        out[r[found]] = best[found]
    return out
