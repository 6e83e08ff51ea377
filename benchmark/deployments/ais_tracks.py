"""Deployment builder ``ais_tracks``: a day's AIS over the US Gulf coast as
the reference's ship-to-ship notebook prepares it — cargo vessels' pings
grouped into one LINESTRING a vessel a 15-minute window — joined with
itself by distance: which pairs of vessels came within their buffers'
reach of each other in the same window. Nothing stays resident between
calls: a screening job hands the join one block of windows after another,
so what this builder makes is what does not change from table to table —
the grid, the fleet's places and lanes (one fixed ``layout_seed``), the
generator and the plain reference. The tables of a run are the traffic
kind's to draw from ``--seed`` (`traffic_kinds/dwithin_join_loop.py`).

It needs a program with the distance join (`mosaic_tpu.sql.proximity`):
on a program without it this raises at once, before a track is made and
before anything compiles — that program's only path to the answer buffers
and tessellates every track on the host, a row at a time in Python (25 ms a
track: two hours for a day), and decides nearly every candidate by an f64
`st_intersects` a row.
"""

from __future__ import annotations

import importlib.util
from types import SimpleNamespace


def pack(xy, offsets, srid: int = 4326):
    """Tracks (a CSR of vertices) as one packed LINESTRING column, in
    array code."""
    import numpy as np

    from mosaic_tpu.core.types import GeometryType, PackedGeometry

    n = offsets.shape[0] - 1
    part = np.arange(n + 1, dtype=np.int64)
    return PackedGeometry(
        xy=xy, ring_offsets=offsets, part_offsets=part, geom_offsets=part,
        geom_type=np.full(n, int(GeometryType.LINESTRING), dtype=np.uint8),
        srid=np.full(n, srid, dtype=np.int32),
    )


def build(ctx) -> SimpleNamespace:
    if importlib.util.find_spec("mosaic_tpu.sql.proximity") is None:
        raise RuntimeError(
            "this program has no distance join: its only path to the "
            "ship-to-ship answer is st_buffer + tessellate + intersects_join, "
            "a Python loop over a native call a track on the host and an f64 "
            "st_intersects a candidate row. The AIS deployment needs "
            "mosaic_tpu.sql.proximity.dwithin_join (the lattice cover with a "
            "reach, the keyed self equi-join, the segment-pair kernel)"
        )
    import mosaic_tpu

    cfg = ctx.config
    gen = ctx.spec.module("generators", "ais_tracks")
    with ctx.spans.span("index_build"):
        # what stays while tables come and go: the grid (its tables are
        # first touched here) and the fleet's places and lanes
        grid = mosaic_tpu.enable_mosaic(cfg["index_system"]).index_system
        layout = gen.layout(cfg["fleet"])
        grid.lattice_coords(layout["places"], cfg["resolution"])
    dep = SimpleNamespace(
        grid=grid, res=cfg["resolution"], fleet=cfg["fleet"], layout=layout,
        vessels=int(cfg["fleet"]["vessels"]), gen=gen, pack=pack,
        reference=ctx.spec.module("references", cfg["reference"]),
    )
    ctx.say(
        "deployment", vessels=dep.vessels, places=len(layout["places"]),
        lanes=len(layout["lanes"]), resolution=dep.res,
        index_build_s=round(ctx.spans.seconds("index_build"), 3),
    )
    return dep
