"""Deployment builder ``overlay_layers``: a region's land parcels chipped
once on the British National Grid and kept, the way the reference's BNG
overlay notebook chips both polygon tables with ``grid_tessellateexplode``
before it joins them on the cell id — the side of the overlay that stays
while one theme layer after another is laid over it. Sizes come from the
configuration's file; the parcel layer is the same every run (one fixed
seed). The theme layers come from ``--seed`` and are the traffic kind's to
make (`traffic_kinds/overlay_measures_loop.py`).

Built on the program's normal path (``enable_mosaic("BNG")``,
`mosaic_tpu.core.tessellate.tessellate`) inside ``setup_s`` every run. It
needs a program whose overlay clips non-convex windows on the device
(`mosaic_tpu.kernels.overlay.fan_area`) in a cell-local frame: on a program
without them it raises at once, before a layer is made and before anything
compiles — that program sends every geometry pair with a non-convex theme
chip, half of a call's pairs here, through a per-row Python loop over the
native boolean engine, and sizes its recheck band from ``np.finfo`` where the
chip emulates float64 64 times coarser.
"""

from __future__ import annotations

from types import SimpleNamespace


def pack(polygons, srid: int = 27700):
    """A list of polygons (each a list of open rings, the outer first) as
    one geometry column."""
    from mosaic_tpu.core.types import GeometryBuilder, GeometryType

    b = GeometryBuilder()
    for rings in polygons:
        b.add_geometry(GeometryType.POLYGON, [rings], srid=srid)
    return b.build()


def build(ctx) -> SimpleNamespace:
    from mosaic_tpu.kernels import overlay as kernels

    if not hasattr(kernels, "fan_area"):
        raise RuntimeError(
            "this program's overlay_measures clips on the device only where "
            "the right chip is convex and answers every other border x "
            "border pair one row at a time in Python (expr/host_oracle.py "
            "host_pair_override), in ONE frame for the whole data with a "
            "band sized from np.finfo: the BNG parcel overlay needs the fan "
            "kernel (mosaic_tpu.kernels.overlay.fan_area) and the cell-local "
            "frame that came with it"
        )
    import numpy as np

    import mosaic_tpu
    from mosaic_tpu import expr
    from mosaic_tpu.core.tessellate import tessellate

    cfg = ctx.config
    gen = ctx.spec.module("generators", "parcels")
    with ctx.spans.span("layer_build"):
        parcels, layout = gen.fabric(cfg["parcels"])
    grid = mosaic_tpu.enable_mosaic(cfg["index_system"]).index_system
    res = cfg["resolution"]
    polygons = [[p] for p in parcels]
    col = pack(polygons)
    with ctx.spans.span("tessellate"):
        table = tessellate(col, grid, res)
    one = np.asarray(grid.cell_boundary(
        np.asarray(table.cell_id[:1], np.int64)
    ), np.float64)[0]
    extent = one.max(axis=0) - one.min(axis=0)
    dep = SimpleNamespace(
        parcels=polygons, layout=layout, col=col, table=table, grid=grid,
        res=res, measure=getattr(expr, cfg["measure"])(),
        cell_area=float(extent[0] * extent[1]),
        reference=ctx.spec.module("references", cfg["reference"]),
        pack=pack,
    )
    verts = gen.vertex_counts(parcels)
    ctx.say(
        "deployment", parcels=len(parcels), chips=len(table),
        core_chips=table.core_count(), resolution=res,
        cell_area_m2=dep.cell_area,
        vertices=dict(zip(*(v.tolist() for v in np.unique(
            verts, return_counts=True)))),
        blocks=f"{layout.nx}x{layout.rows_made}",
        layer_build_s=round(ctx.spans.seconds("layer_build"), 3),
        tessellate_s=round(ctx.spans.seconds("tessellate"), 3),
        row=cfg["row"], measure=cfg["measure"],
    )
    return dep
