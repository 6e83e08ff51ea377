"""Deployment builder ``knn_footprints``: a day's events (taxi pickups) held
resident on the device in a `mosaic_tpu.knn.KNNIndex`, and a borough's
building footprints as the layer landmark tables are drawn from — the
reference's SpatialKNN notebook with its own shapes: every building
POLYGON gets its k nearest pickup POINTS. Sizes come from the
configuration's file; candidates and layer are the same every run (one fixed
seed each). The tables of a run are the traffic kind's to draw from
``--seed`` (`traffic_kinds/knn_polygon_transform.py`).

The candidates' side is built by `deployments/knn_candidates.py` (enable
the grid, `build_knn_index`) inside ``setup_s`` every run; this builder adds
the footprint layer to what that one returns. It needs a program whose
`SpatialKNN.transform` answers polygon landmarks on the block lane
(`mosaic_tpu.knn.engine.poly_block_topk_prog`): on a program without it
this raises at once, before a candidate or a footprint is made and before
anything compiles — that program tessellates every table on the host for
seconds a call, makes every (landmark, candidate) pair on the host, and
asks the chip to gather two padded geometry columns a pair, tens of
gigabytes a launch.
"""

from __future__ import annotations

from types import SimpleNamespace


def pack(footprints, srid: int = 4326):
    """A list of footprints (each a list of open rings, the outer first) as
    one packed POLYGON column, in array code."""
    import numpy as np

    from mosaic_tpu.core.types import GeometryType, PackedGeometry

    rings = [r for f in footprints for r in f]
    n = len(footprints)
    return PackedGeometry(
        xy=np.concatenate(rings) if rings else np.zeros((0, 2)),
        ring_offsets=np.concatenate([[0], np.cumsum([len(r) for r in rings])]),
        part_offsets=np.concatenate(
            [[0], np.cumsum([len(f) for f in footprints])]
        ),
        geom_offsets=np.arange(n + 1),
        geom_type=np.full(n, int(GeometryType.POLYGON), dtype=np.uint8),
        srid=np.full(n, srid, dtype=np.int32),
    )


def take(col, idx):
    """Rows ``idx`` of a packed single-part POLYGON column, in array code
    (`PackedGeometry.take` copies a geometry at a time)."""
    import numpy as np

    from mosaic_tpu.core.types import PackedGeometry
    from mosaic_tpu.knn.index import expand_ranges

    idx = np.asarray(idx, dtype=np.int64)
    nrings = np.diff(col.part_offsets)[idx]
    rings = expand_ranges(col.part_offsets[idx], nrings)
    nverts = np.diff(col.ring_offsets)[rings]
    verts = expand_ranges(col.ring_offsets[rings], nverts)
    return PackedGeometry(
        xy=col.xy[verts],
        ring_offsets=np.concatenate([[0], np.cumsum(nverts)]),
        part_offsets=np.concatenate([[0], np.cumsum(nrings)]),
        geom_offsets=np.arange(idx.size + 1),
        geom_type=col.geom_type[idx], srid=col.srid[idx],
    )


def build(ctx) -> SimpleNamespace:
    from mosaic_tpu.knn import engine

    if not hasattr(engine, "poly_block_topk_prog"):
        raise RuntimeError(
            "this program's SpatialKNN.transform tessellates polygon "
            "landmarks on the host every call and evaluates host-made "
            "(landmark, candidate) pairs over two padded geometry columns: "
            "the building-footprint KNN deployment needs the polygon block "
            "lane (mosaic_tpu.knn.engine.poly_block_topk_prog)"
        )
    import numpy as np

    # the candidates' side is the point deployment's, key for key: its
    # builder makes it (spans ``layer_build``, ``index_build``)
    dep = ctx.spec.module("deployments", "knn_candidates").build(ctx)
    buildings = ctx.spec.module("generators", "buildings")
    with ctx.spans.span("pool_build"):
        footprints, kinds = buildings.fabric(ctx.config["landmarks"])
        layer = pack(footprints)
    dep.footprints, dep.kinds, dep.layer = footprints, kinds, layer
    dep.take, dep.pack = take, pack
    xy = dep.candidates
    x0, y0, x1, y1 = buildings.footprints_bbox(footprints)
    inside = int(np.count_nonzero(
        (xy[:, 0] >= x0) & (xy[:, 0] <= x1) & (xy[:, 1] >= y0) & (xy[:, 1] <= y1)
    ))
    verts = np.diff(layer.ring_offsets[layer.part_offsets])
    ctx.say(
        "footprint_layer", footprints=len(footprints),
        kinds=np.bincount(kinds, minlength=3).tolist(),
        mean_vertices=round(float(verts.mean()), 3),
        over_16_vertices=int((verts > 16).sum()),
        with_a_hole=int((np.diff(layer.part_offsets) > 1).sum()),
        fabric_box=[round(v, 5) for v in (x0, y0, x1, y1)],
        candidates_in_fabric_box=inside,
        fabric_build_s=round(ctx.spans.seconds("pool_build"), 3),
    )
    return dep
