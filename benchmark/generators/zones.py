"""The zone layer, made by the benchmark (it is the deployment's input, as
a model's weights are): a lattice of star-shaped polygons over a bounding
box. The arithmetic is `mosaic_tpu.datasets.synthetic_zones`' (copied, so
the plain reference gets its polygons from no code of the program; a test
holds the copy to the original)."""

from __future__ import annotations

import numpy as np


def star_lattice(nx, ny, bbox, seed=7, verts=10, jitter=0.45) -> list:
    """``nx*ny`` open rings, each a ``(verts, 2)`` f64 array, row-major
    from the south-west; adjacent zones overlap slightly."""
    rng = np.random.default_rng(seed)
    xmin, ymin, xmax, ymax = bbox
    dx = (xmax - xmin) / nx
    dy = (ymax - ymin) / ny
    rings = []
    for j in range(ny):
        for i in range(nx):
            cx = xmin + (i + 0.5) * dx
            cy = ymin + (j + 0.5) * dy
            ang = np.sort(rng.uniform(0.0, 2 * np.pi, verts))
            rad = 0.62 + jitter * rng.uniform(-0.5, 0.5, verts)
            rings.append(np.column_stack(
                [cx + rad * dx * np.cos(ang), cy + rad * dy * np.sin(ang)]
            ))
    return rings


def rings_bbox(rings) -> tuple:
    allp = np.concatenate(rings)
    return (
        float(allp[:, 0].min()), float(allp[:, 1].min()),
        float(allp[:, 0].max()), float(allp[:, 1].max()),
    )
