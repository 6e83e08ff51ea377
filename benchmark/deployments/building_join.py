"""Deployment builder ``building_join``: a borough's building footprints
tessellated on a grid fine enough to hold them and kept on the device as a
chip index, the way the reference's OpenStreetMaps notebook chips building
polygons and its Quickstart joins points to chips. Sizes come from the
configuration's file; `zone_join`'s namespace, so the stream's traffic kind
runs on it unchanged.

Built on the program's normal path (enable the grid, `tessellate`,
`build_chip_index`) inside ``setup_s`` every run. It needs a program whose
stream chooses its cell-assignment precision from the index (at this
resolution f32 cells put a hundredth of the points into a neighbour cell)
and bounds tier 2's intermediates; on a program without them it raises at
once, before the layer is made and before anything compiles.
"""

from __future__ import annotations

from types import SimpleNamespace


def build(ctx) -> SimpleNamespace:
    import jax

    import mosaic_tpu
    from mosaic_tpu.core.tessellate import tessellate
    from mosaic_tpu.core.types import GeometryBuilder, GeometryType
    from mosaic_tpu.sql import stream
    from mosaic_tpu.sql.join import build_chip_index

    if not hasattr(stream, "stream_cell_dtype"):
        raise RuntimeError(
            "this program's StreamJoin assigns cells in float32 whatever "
            "the resolution and does not report a cell_dtype: the "
            "building-footprint deployment needs the cell-assignment rule "
            "and the chunked tier 2 that came with it"
        )
    cfg = ctx.config
    gen = ctx.spec.module("generators", "buildings")
    with ctx.spans.span("layer_build"):
        footprints, _kinds = gen.fabric(cfg["buildings"])
    grid = mosaic_tpu.enable_mosaic(cfg["index_system"]).index_system
    res = cfg["resolution"]
    b = GeometryBuilder()
    for rings in footprints:
        b.add_geometry(GeometryType.POLYGON, [rings], srid=4326)
    with ctx.spans.span("index_build"):
        with ctx.spans.span("tessellate"):
            table = tessellate(b.build(), grid, res, keep_core_geoms=False)
        index = build_chip_index(table)
    dep = SimpleNamespace(
        rings=footprints, grid=grid, res=res, index=index,
        bbox=gen.footprints_bbox(footprints),
        mesh=int(cfg["mesh"]) if cfg.get("mesh") else None,
        batch=int(cfg["batch_rows_per_chip"]),
        index_bytes=sum(
            int(getattr(a, "nbytes", 0))
            for a in jax.tree_util.tree_leaves(index)
        ),
        reference=ctx.spec.module("references", cfg["reference"]),
    )
    cells = int(index.cells.shape[0])
    ctx.say(
        "deployment", footprints=len(footprints), chips=len(table),
        core_chips=table.core_count(), cells=cells,
        heavy_cells=index.num_heavy_cells,
        heavy_share=round(index.num_heavy_cells / cells, 4),
        convex_cells=index.num_convex_cells,
        E1=int(index.cell_edges.shape[1]),
        M1=int(index.cell_slot_geom.shape[1]),
        E2=int(index.heavy_edges.shape[1]),
        M2=int(index.heavy_slot_geom.shape[1]),
        index_mb=round(dep.index_bytes / 1e6, 1),
        layer_build_s=round(ctx.spans.seconds("layer_build"), 3),
        tessellate_s=round(ctx.spans.seconds("tessellate"), 3),
        index_build_s=round(ctx.spans.seconds("index_build"), 3),
        row=cfg["row"], mesh=dep.mesh,
    )
    return dep
