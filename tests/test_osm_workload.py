"""OSM-buildings tessellation workload (BASELINE config #2).

Reference analog: the OpenStreetMaps notebook
(`notebooks/examples/python/OpenStreetMaps/`) chips building polygons
with grid_tessellate — the opposite regime from the taxi-zone workload:
thousands of SMALL polygons, each spanning only a handful of cells at a
resolution where cell size ~ building size. Synthetic buildings
(rotated rectangles + L-shapes, deterministic) stand in for the OSM
extract; structural digests are golden-pinned and area conservation is
asserted per building.

This is the CPU-sized copy (800 footprints, tessellation only). The
full-size run is the benchmark's configuration `osm-buildings-h3r11`
(`benchmark/configs/osm-buildings-h3r11.json`, cell `osm-buildings.join`):
a borough's fabric at H3 res 11, tessellated, indexed and joined to pings
through `StreamJoin.run` on the chip; `tests/test_building_join.py` holds
its pieces at small size.
"""

import json
import os
from pathlib import Path

import numpy as np
import pytest

from mosaic_tpu.core.index.h3 import H3IndexSystem
from mosaic_tpu.core.tessellate import tessellate
from mosaic_tpu.core.types import GeometryBuilder, GeometryType

GOLDEN = Path(__file__).parent / "goldens" / "osm_workload.json"
RES = 12  # ~300 m2 hex cells: building-scale
N_BUILDINGS = 800
BBOX = (-73.99, 40.72, -73.95, 40.75)


def _buildings(n=N_BUILDINGS, seed=20):
    """Rotated rectangles (80%) and L-shapes (20%), ~10-60 m across."""
    rng = np.random.default_rng(seed)
    b = GeometryBuilder()
    deg = 1.0 / 111_000.0  # ~meters to degrees at NYC latitude
    for i in range(n):
        cx = rng.uniform(BBOX[0], BBOX[2])
        cy = rng.uniform(BBOX[1], BBOX[3])
        w, h = rng.uniform(10, 60, 2) * deg
        th = rng.uniform(0, np.pi)
        c, s = np.cos(th), np.sin(th)
        R = np.array([[c, -s], [s, c]])
        if i % 5 == 0:  # L-shape: rectangle minus a corner quadrant
            base = np.array(
                [
                    [0, 0], [w, 0], [w, h / 2], [w / 2, h / 2],
                    [w / 2, h], [0, h],
                ]
            )
        else:
            base = np.array([[0, 0], [w, 0], [w, h], [0, h]])
        ring = (base - [w / 2, h / 2]) @ R.T + [cx, cy]
        b.add_ring(ring)
        b.end_part()
        b.end_geom(GeometryType.POLYGON, 4326)
    return b.build()


@pytest.fixture(scope="module")
def table():
    return tessellate(_buildings(), H3IndexSystem(), RES, keep_core_geoms=True)


def test_osm_profile_structure(table):
    from mosaic_tpu.core.geometry import oracle

    col = _buildings()
    n_chips = len(table.cell_id)
    core = int(np.asarray(table.is_core).sum())
    # building-scale cells: nearly every chip is a border chip, and each
    # building spans only a handful of cells
    per_geom = np.bincount(np.asarray(table.geom_id), minlength=N_BUILDINGS)
    assert (per_geom >= 1).all()
    assert np.median(per_geom) <= 8
    # area conservation per building (clipped chips tile each polygon)
    chip_area = oracle.area(table.chips)
    per_area = np.zeros(N_BUILDINGS)
    np.add.at(per_area, np.asarray(table.geom_id), chip_area)
    want = oracle.area(col)
    rel = np.abs(per_area - want) / want
    # cell-boundary vertex precision (~1e-9 deg seams between adjacent
    # res-12 hexagons) bounds conservation for building-sized polygons;
    # absolute leakage stays < 4e-12 deg^2 (~50 cm^2) per building
    assert rel.max() < 1e-4, rel.max()
    assert np.abs(per_area - want).max() < 4e-12

    dig = {
        "n_chips": n_chips,
        "core": core,
        "cells_xor": int(np.bitwise_xor.reduce(np.asarray(table.cell_id))),
        "median_chips_per_building": float(np.median(per_geom)),
        "max_chips_per_building": int(per_geom.max()),
    }
    if GOLDEN.exists() and not os.environ.get("MOSAIC_UPDATE_GOLDENS"):
        want_dig = json.loads(GOLDEN.read_text())
        assert want_dig == dig, (want_dig, dig)
    else:
        GOLDEN.parent.mkdir(exist_ok=True)
        GOLDEN.write_text(json.dumps(dig, indent=1, sort_keys=True))
        pytest.skip("golden created; rerun to compare")


def test_osm_profile_join_roundtrip(table):
    """Building centroids must join back to their own building."""
    from mosaic_tpu.core.geometry import oracle
    from mosaic_tpu.sql.join import build_chip_index, pip_join

    col = _buildings()
    cent = oracle.centroid(col)
    # L-shape centroids stay inside for this construction; verify and
    # keep only interior centroids to make the assertion exact
    inside = np.asarray(
        [oracle.contains_points(col, g, cent[g : g + 1])[0] for g in range(len(col))]
    )
    index = build_chip_index(table)
    match = np.asarray(
        pip_join(cent, col, H3IndexSystem(), RES, chip_index=index)
    )
    # randomly-placed buildings overlap (~2%), so a centroid may join a
    # DIFFERENT containing building; correct = matched building contains it
    rows = np.nonzero(inside)[0]
    assert (match[rows] >= 0).all()
    for i in rows:
        m = int(match[i])
        assert m == i or oracle.contains_points(col, m, cent[i : i + 1])[0], (
            i, m,
        )
