"""Mesh-sharded pairwise predicate evaluation for the overlay join.

Reference analog: the BNG overlay workload's exact-predicate stage runs as
Spark tasks over the candidate-pair partitions
(`notebooks/examples/python/BritishNationalGrid.py`); here the candidate
chip-pair axis shards over every device of a `jax.sharding.Mesh` and each
device evaluates its slice of the row-wise `st_intersects` batch — no
collective is needed (the pair axis is embarrassingly parallel; the
reduction back to geometry pairs stays on host in `sql.overlay`).
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..core.geometry.device import DeviceGeometry


def geom_specs(row: P) -> DeviceGeometry:
    """DeviceGeometry-shaped PartitionSpec tree: every pair-axis leaf gets
    ``row`` (shard or replicate), the shared (2,) shift is always
    replicated. One builder for every mesh consumer of geometry columns
    (dist_overlay, dist_knn)."""
    return DeviceGeometry(
        verts=row,
        ring_len=row,
        ring_is_hole=row,
        n_rings=row,
        geom_type=row,
        shift=P(),
    )


def _pad_pair_axis(dg: DeviceGeometry, pad: int) -> DeviceGeometry:
    """Grow every pair-axis leaf by ``pad`` empty rows, by field identity.

    The shared (2,) ``shift`` keeps its invariant shape — it is not a pair
    column, even when the pair count happens to equal 2.
    """

    def grow(x):
        widths = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
        return jax.numpy.pad(x, widths)

    return dataclasses.replace(
        dg,
        **{
            f.name: grow(getattr(dg, f.name))
            for f in dataclasses.fields(dg)
            if f.name != "shift"
        },
    )


def distributed_pair_intersects(
    mesh: Mesh, da: DeviceGeometry, db: DeviceGeometry
) -> np.ndarray:
    """(N,) bool — row-wise intersects, the pair axis sharded over ``mesh``.

    ``da``/``db`` are `functions.geometry._pair_pack`-style device columns
    with a shared shift; the row count is padded here to the mesh size
    (pad rows are empty geometries that never intersect).
    """
    # the per-pair vmap recipe is shared with the single-device path —
    # one copy only (functions.geometry owns it)
    from ..core.geometry.predicates import intersects as _dense
    from ..functions.geometry import _PAIR_AXES, _vmap_pair

    n = int(da.verts.shape[0])
    pad = (-n) % mesh.size
    if pad:
        da = _pad_pair_axis(da, pad)
        db = _pad_pair_axis(db, pad)

    spec = geom_specs(P(mesh.axis_names))

    def step(a, b):
        return _vmap_pair(_dense, a, b)

    out = jax.jit(
        jax.shard_map(
            step, mesh=mesh, in_specs=(spec, spec), out_specs=P(mesh.axis_names)
        )
    )(da, db)
    return np.asarray(out)[:n]
