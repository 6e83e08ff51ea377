"""Mesh-sharded distance evaluation for the SpatialKNN ring step.

Reference analog: `models/knn/SpatialKNN.scala:202-235` — the reference's
showcase DISTRIBUTED model runs its per-iteration join + `st_distance`
over Spark partitions. Here the iteration's (landmark, candidate) pair
batch shards over every device of a `jax.sharding.Mesh`: the two
geometry columns are replicated (small side — the same broadcast role as
the reference's landmark table), row indices shard over the pair axis,
and each device gathers its rows locally and evaluates the dense
distance kernel. No collective is needed in the step itself (the pair
axis is embarrassingly parallel; the top-k merge stays on host in
`models/knn`).
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..core.geometry.device import DeviceGeometry, take_rows
from ..dispatch import core as _dispatch
from ..runtime import telemetry as _telemetry
from .dist_overlay import geom_specs


@_dispatch.bounded_cache("knn_sharded_distance", 8)
def _sharded_distance_fn(mesh: Mesh):
    """One jitted shard_map per mesh — KNN calls this every ring
    iteration, so the jit object must persist for XLA's trace cache to
    hit (a fresh closure per call would recompile every iteration).
    Lives in the dispatch cache registry as ``knn_sharded_distance``."""
    from ..functions.geometry import _distance_dense, _vmap_pair

    row = P(mesh.axis_names)
    rep = geom_specs(P())

    def step(dls, dcs, lrows, crows):
        return _vmap_pair(
            _distance_dense, take_rows(dls, lrows), take_rows(dcs, crows)
        )

    return jax.jit(
        jax.shard_map(
            step, mesh=mesh, in_specs=(rep, rep, row, row), out_specs=row
        )
    )


def distributed_pair_distances(
    mesh: Mesh, dl: DeviceGeometry, dc: DeviceGeometry,
    li: np.ndarray, ci: np.ndarray,
) -> np.ndarray:
    """(P,) f64 — distance(dl[li[p]], dc[ci[p]]), pair axis sharded.

    Pads the pair axis with row 0 to a power-of-two multiple of the mesh
    size, so successive ring iterations share compiled programs (the pad
    results are sliced off before returning — any valid row is filler).
    """
    n = int(li.shape[0])
    if n == 0:
        return np.zeros(0)
    npad = mesh.size
    while npad < n:
        npad <<= 1
    lip = np.concatenate([li, np.zeros(npad - n, dtype=li.dtype)])
    cip = np.concatenate([ci, np.zeros(npad - n, dtype=ci.dtype)])
    out = _sharded_distance_fn(mesh)(dl, dc, lip, cip)
    return np.asarray(out, dtype=np.float64)[:n]


def knn_cache_stats(emit: bool = True) -> dict:
    """Compatibility view over the unified dispatch cache registry
    (`dispatch.cache_stats` is the full surface; this keeps the
    historical ``{"sharded_distance": {...}}`` dict shape).

    Each live entry pins one jitted shard_map program (and its `Mesh`
    key) for the cache's lifetime. The lru is bounded (maxsize 8: a
    process rarely cycles more than a couple of mesh shapes; eviction
    just costs one recompile on the next ring iteration over that mesh).
    Emits one ``knn_cache_stats`` telemetry event (``emit=False`` reads
    silently).
    """
    stats = {"sharded_distance": _dispatch.cache_view("knn_sharded_distance")}
    if emit:
        _telemetry.record("knn_cache_stats", **stats)
    return stats


def clear_knn_caches() -> dict:
    """Drop every cached per-mesh distance program (through
    `dispatch.clear_caches`); returns the pre-clear
    :func:`knn_cache_stats`. The next ring iteration per mesh pays one
    recompile. Emits ``knn_caches_cleared`` telemetry.
    """
    stats = knn_cache_stats(emit=False)
    _dispatch.clear_caches(names=("knn_sharded_distance",), emit=False)
    _telemetry.record("knn_caches_cleared", **stats)
    return stats
