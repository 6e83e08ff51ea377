"""Deployment builder ``knn_candidates``: a day's events (taxi pickups) as
a table of points held resident on the device in a `mosaic_tpu.knn.KNNIndex`,
the way the reference's SpatialKNN notebook holds its candidate table while
landmark tables are transformed against it. Sizes come from the
configuration's file; the table is the same every run (one fixed seed).

Built on the program's normal path (enable the grid, `build_knn_index`)
inside ``setup_s`` every run. It needs a program whose `SpatialKNN.transform`
takes a resident index and whose search is array code: on a program without
them (`mosaic_tpu.knn.engine` is missing) it raises at once, before a
candidate is made and before anything compiles — that program's
`build_knn_index` walks a million candidates in per-geometry Python, and
its `transform` a hundred thousand landmarks, for hours.
"""

from __future__ import annotations

from types import SimpleNamespace


def build(ctx) -> SimpleNamespace:
    import importlib.util

    if importlib.util.find_spec("mosaic_tpu.knn.engine") is None:
        raise RuntimeError(
            "this program's SpatialKNN.transform re-tessellates its "
            "candidates every call and loops in Python once a landmark: the "
            "resident-KNN deployment needs the array ring engine "
            "(mosaic_tpu.knn.engine) and transform(landmarks, KNNIndex)"
        )
    import jax
    import numpy as np

    import mosaic_tpu
    from mosaic_tpu.knn import build_knn_index
    from mosaic_tpu.models import SpatialKNN

    cfg = ctx.config
    cand = cfg["candidates"]
    points = ctx.spec.module("generators", "points")
    bbox = tuple(cand["bbox"])
    grid = mosaic_tpu.enable_mosaic(cfg["index_system"]).index_system
    res = cfg["resolution"]
    with ctx.spans.span("layer_build"):
        gen = points.make_generator(cand["points"], bbox, int(cand["count"]))
        xy = np.asarray(gen(points.seed_key(int(cand["seed"]))))
    with ctx.spans.span("index_build"):
        index = build_knn_index(xy, grid, res)
    model_args = dict(cfg["model"])
    model = SpatialKNN(index=grid, resolution=res, **model_args)
    pb = index.points
    dep = SimpleNamespace(
        candidates=xy, grid=grid, res=res, index=index, model=model,
        model_args=model_args, k=int(model_args["k_neighbours"]), bbox=bbox,
        batch=int(cfg["batch_rows_per_chip"]),
        index_bytes=sum(
            int(getattr(a, "nbytes", 0))
            for a in jax.tree_util.tree_leaves((pb.x, pb.y, pb.rid))
        ),
        reference=ctx.spec.module("references", cfg["reference"]),
    )
    ctx.say(
        "deployment", candidates=int(xy.shape[0]), resolution=res,
        cells=int(pb.ucells.shape[0]), blocks=pb.n_blocks,
        block_width=pb.width, fullest_cell=int(pb.count.max()),
        dtype=str(index.dtype), index_mb=round(dep.index_bytes / 1e6, 1),
        layer_build_s=round(ctx.spans.seconds("layer_build"), 3),
        index_build_s=round(ctx.spans.seconds("index_build"), 3),
        model=model_args, row=cfg["row"],
    )
    return dep
