"""Deployment builder ``zone_join``: a zone layer tessellated on a grid
and held on the device as a chip index, the way the reference Quickstart
joins pickups to taxi zones. Sizes come from the configuration's file.

Built as `chip_smoke.py` `build_deployment` builds it (enable the grid,
`tessellate`, `build_chip_index`), inside ``setup_s`` every run: users pay
it, and no cache hides it.
"""

from __future__ import annotations

from types import SimpleNamespace


def build(ctx) -> SimpleNamespace:
    import jax

    import mosaic_tpu
    from mosaic_tpu.core.tessellate import tessellate
    from mosaic_tpu.core.types import GeometryBuilder, GeometryType
    from mosaic_tpu.sql.join import build_chip_index

    cfg = ctx.config
    zones_mod = ctx.spec.module("generators", "zones")
    z = cfg["zones"]
    rings = zones_mod.star_lattice(
        z["nx"], z["ny"], tuple(z["bbox"]), seed=z["seed"],
        verts=z["verts"], jitter=z["jitter"],
    )
    grid = mosaic_tpu.enable_mosaic(cfg["index_system"]).index_system
    res = cfg["resolution"]
    b = GeometryBuilder()
    for ring in rings:
        b.add_geometry(GeometryType.POLYGON, [[ring]], srid=4326)
    with ctx.spans.span("index_build"):
        table = tessellate(b.build(), grid, res, keep_core_geoms=False)
        index = build_chip_index(table)
    dep = SimpleNamespace(
        rings=rings, grid=grid, res=res, index=index,
        bbox=zones_mod.rings_bbox(rings),
        mesh=int(cfg["mesh"]) if cfg.get("mesh") else None,
        batch=int(cfg["batch_rows_per_chip"]),
        index_bytes=sum(
            int(getattr(a, "nbytes", 0))
            for a in jax.tree_util.tree_leaves(index)
        ),
        reference=ctx.spec.module("references", cfg["reference"]),
    )
    ctx.say(
        "deployment", zones=len(rings), chips=len(table),
        cells=int(index.cells.shape[0]), heavy_cells=index.num_heavy_cells,
        index_mb=round(dep.index_bytes / 1e6, 1),
        index_build_s=round(ctx.spans.seconds("index_build"), 3),
        row=cfg["row"], mesh=dep.mesh,
    )
    return dep
