"""The plain reference of a k-nearest-neighbour search: for each landmark,
every candidate's Euclidean distance in the coordinates' own units (what
`st_distance` gives two points) in f64 numpy, a landmark at a time, and
the k smallest by (distance, candidate id). No grid, no rings,
no index, nothing of the program and nothing the program made."""

from __future__ import annotations

import numpy as np

def answers(landmarks, candidates, k: int):
    """``(ids (L, k) int64, distances (L, k) f64)``, ranked; -1 / inf
    where there are fewer than k candidates. One landmark at a time: a
    row of 1,000,000 distances is 8 MB and stays in the host's cache (a
    block of 64 rows read 16 times slower a landmark)."""
    lm = np.asarray(landmarks, dtype=np.float64)
    cd = np.asarray(candidates, dtype=np.float64)
    cx, cy = np.ascontiguousarray(cd[:, 0]), np.ascontiguousarray(cd[:, 1])
    n, m = lm.shape[0], cd.shape[0]
    kk = min(k, m)
    ids = np.full((n, k), -1, dtype=np.int64)
    dist = np.full((n, k), np.inf)
    for i in range(n if kk else 0):
        d = np.sqrt((lm[i, 0] - cx) ** 2 + (lm[i, 1] - cy) ** 2)
        # the k smallest and every candidate tied with the kth, then the
        # (distance, id) order among those few
        near = np.flatnonzero(d <= np.partition(d, kk - 1)[kk - 1])
        order = near[np.lexsort((near, d[near]))][:kk]
        ids[i, :kk] = order
        dist[i, :kk] = d[order]
    return ids, dist


def distances(landmarks, candidates, ids):
    """(L, k) f64: the true distance from each landmark to the candidates
    ``ids`` names for it (inf where an id is -1)."""
    lm = np.asarray(landmarks, dtype=np.float64)
    cd = np.asarray(candidates, dtype=np.float64)
    ids = np.asarray(ids)
    got = cd[np.clip(ids, 0, None)]
    d = np.sqrt(
        (lm[:, None, 0] - got[..., 0]) ** 2 + (lm[:, None, 1] - got[..., 1]) ** 2
    )
    return np.where(ids >= 0, d, np.inf)
