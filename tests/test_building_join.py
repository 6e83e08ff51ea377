"""The building-footprint deployment's pieces at small size on the CPU
(the benchmark's configuration `osm-buildings-h3r11` is the full-size run
on the chip): the fabric generator, batched against looped tessellation,
chunked against whole tier 2, the stream's cell-precision rule, the
`heavy_rows` count, the index spans, and `StreamJoin.run` on a fabric index
against the plain footprint reference."""

import numpy as np
import pytest

import jax.numpy as jnp

import mosaic_tpu
from mosaic_tpu.core import tessellate as tess_mod
from mosaic_tpu.core.tessellate import tessellate
from mosaic_tpu.core.types import GeometryBuilder, GeometryType
from mosaic_tpu.runtime import telemetry
from mosaic_tpu.sql import join as join_mod
from mosaic_tpu.sql.join import build_chip_index, host_join, pip_join_points
from mosaic_tpu.sql.stream import (
    CELL_F32_MAX_ULP_SHARE,
    StreamJoin,
    stream_cell_dtype,
)

from benchmark.generators import buildings, zones
from benchmark.references import pip_footprints

FABRIC = {"centre": [-73.95, 40.70], "seed": 11}


def _config() -> dict:
    """The benchmark's configuration file, as it is run."""
    import json
    import os

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(
            here, "benchmark", "configs", "osm-buildings-h3r11.json")) as f:
        return json.load(f)


def _column(footprints):
    b = GeometryBuilder()
    for rings in footprints:
        rings = [rings] if isinstance(rings, np.ndarray) else rings
        b.add_geometry(GeometryType.POLYGON, [rings], srid=4326)
    return b.build()


@pytest.fixture(scope="module")
def grid():
    return mosaic_tpu.enable_mosaic("H3").index_system


@pytest.fixture(scope="module")
def fabric2000():
    return buildings.fabric(dict(FABRIC, count=2000))


@pytest.fixture(scope="module")
def index2000(grid, fabric2000):
    return build_chip_index(
        tessellate(_column(fabric2000[0]), grid, 11, keep_core_geoms=False)
    )


# ------------------------------------------------------------ the fabric

def test_fabric_same_seed_same_layer_other_seed_another():
    a, ka = buildings.fabric(dict(FABRIC, count=700))
    b, kb = buildings.fabric(dict(FABRIC, count=700))
    c, _ = buildings.fabric(dict(FABRIC, count=700, seed=12))
    assert np.array_equal(ka, kb) and len(a) == len(b) == 700
    assert all(np.array_equal(r, s) for f, g in zip(a, b) for r, s in zip(f, g))
    assert not np.array_equal(a[5][0], c[5][0])


def test_fabric_shares_and_sizes():
    """The configuration's own layer, at its own count."""
    params = _config()["buildings"]
    assert 65536 <= params["count"] <= 262144
    fp, kinds = buildings.fabric(params)
    assert len(fp) == params["count"]
    share = [(kinds == k).mean() for k in (0, 1, 2)]
    for got, want in zip(share, (0.60, 0.38, 0.02)):
        assert abs(got - want) <= 0.02, share
    large = [f for f, k in zip(fp, kinds) if k == buildings.LARGE]
    assert all(24 <= f[0].shape[0] <= 64 for f in large)
    yards = np.mean([len(f) == 2 for f in large])
    assert 0.4 <= yards <= 0.6
    assert all(len(f) == 1 and f[0].shape[0] in (4, 6, 8)
               for f, k in zip(fp, kinds) if k != buildings.LARGE)
    # 1,500 to 2,500 footprints a km2 with the streets
    x0, y0, x1, y1 = buildings.footprints_bbox(fp)
    km2 = (x1 - x0) * 111.32 * np.cos(np.radians(40.7)) * (y1 - y0) * 111.32
    assert 1500 <= len(fp) / km2 <= 2500


def test_fabric_no_two_footprints_overlap():
    """No sampled point lies in two footprints' outer rings: the smallest
    and the largest containing id agree on every one."""
    fp, _ = buildings.fabric(dict(FABRIC, count=3000))
    outer = [f[0] for f in fp]
    x0, y0, x1, y1 = buildings.footprints_bbox(fp)
    rng = np.random.default_rng(3)
    pts = np.column_stack(
        [rng.uniform(x0, x1, 400000), rng.uniform(y0, y1, 400000)])
    first = pip_footprints.answers(outer, pts)
    last = pip_footprints.answers(outer[::-1], pts)
    last = np.where(last >= 0, len(outer) - 1 - last, -1)
    assert np.array_equal(first, last) and (first >= 0).mean() > 0.2


# ------------------------------------------- tessellation: batched = looped

def _layer(name):
    if name == "fabric":
        return _column(buildings.fabric(dict(FABRIC, count=500))[0]), 11
    rings = zones.star_lattice(6, 6, (-74.3, 40.4, -73.6, 41.0))
    return _column(rings), 9


@pytest.mark.parametrize("keep_core", [False, True])
@pytest.mark.parametrize("layer", ["fabric", "taxi-zones"])
def test_batched_tessellation_equals_the_loop(grid, monkeypatch, layer, keep_core):
    col, res = _layer(layer)
    fast = tessellate(col, grid, res, keep_core_geoms=keep_core)
    monkeypatch.setattr(tess_mod, "_FAST_MAX_VERTS", 0)  # every polygon loops
    loop = tessellate(col, grid, res, keep_core_geoms=keep_core)
    assert len(fast) == len(loop) > len(col)
    for f in ("geom_id", "cell_id", "is_core", "has_geom"):
        assert np.array_equal(getattr(fast, f), getattr(loop, f)), f
    for f in ("xy", "ring_offsets", "part_offsets", "geom_offsets",
              "geom_type", "srid", "geom_has_z"):
        a, b = getattr(fast.chips, f), getattr(loop.chips, f)
        assert a.dtype == b.dtype and np.array_equal(a, b), f
    assert fast.chips.z is None and loop.chips.z is None
    if layer == "fabric":  # the large footprints went through the loop
        assert fast.core_count() > 0 and fast.chips.rings_per_geom().max() == 2


def test_batched_tessellation_small_pair_chunks(grid, monkeypatch):
    col, res = _layer("fabric")
    whole = tessellate(col, grid, res, keep_core_geoms=False)
    monkeypatch.setattr(tess_mod, "_FAST_PAIR_CHUNK", 97)
    cut = tessellate(col, grid, res, keep_core_geoms=False)
    assert np.array_equal(whole.cell_id, cut.cell_id)
    assert np.array_equal(whole.chips.xy, cut.chips.xy)


def test_index_spans_carry_the_layer_and_the_tables(grid, fabric2000):
    col = _column(fabric2000[0][:300])
    with telemetry.capture() as events:
        table = tessellate(col, grid, 11, keep_core_geoms=False)
        index = build_chip_index(table)
    spans = {e["name"]: e for e in events if e.get("event") == "span"}
    t, b = spans["index.tessellate"], spans["index.build"]
    assert (t["geometries"], t["chips"], t["core_chips"]) == (
        300, len(table), table.core_count())
    assert (b["cells"], b["heavy"], b["convex"]) == (
        index.num_cells, index.num_heavy_cells, index.num_convex_cells)
    assert (b["E1"], b["M1"], b["E2"], b["M2"]) == (
        index.cell_edges.shape[1], index.cell_slot_geom.shape[1],
        index.heavy_edges.shape[1], index.heavy_slot_geom.shape[1])
    assert b["spilled"] == 0 and b["table_bytes"] > 0 and b["heavy"] > 0
    # the probe's shape: one gather of a row of 3 words a bucket entry
    assert (b["hash_row_words"], b["hash_gathers"]) == (
        3 * index.table_cell.shape[1], 1)


def test_refusal_names_the_chip_counts_and_a_resolution(grid):
    """40 slivers of one cell are more than the 32 parity bits of a tier."""
    cx, cy = grid.cell_center(grid.point_to_cell(
        np.array([[-73.95, 40.7]]), 9))[0]
    slivers = [
        np.array([[cx - 5e-4 + i * 2e-5, cy - 2e-4],
                  [cx - 5e-4 + i * 2e-5 + 1e-5, cy - 2e-4],
                  [cx - 5e-4 + i * 2e-5 + 1e-5, cy + 2e-4],
                  [cx - 5e-4 + i * 2e-5, cy + 2e-4]])
        for i in range(40)
    ]
    table = tessellate(_column(slivers), grid, 9, keep_core_geoms=False)
    with pytest.raises(ValueError) as err:
        build_chip_index(table)
    said = str(err.value)
    assert "40 in tier 2" in said and "1 resolution finer" in said


# ----------------------------------------------- tier 2: chunked = whole

def _points(fabric, n, seed):
    x0, y0, x1, y1 = buildings.footprints_bbox(fabric[0])
    rng = np.random.default_rng(seed)
    return np.column_stack([rng.uniform(x0, x1, n), rng.uniform(y0, y1, n)])


@pytest.mark.parametrize("probe,found_cap", [
    ("scatter", None),        # in place
    ("scatter", 2999),        # compacted
    ("adaptive", None),       # the Pallas lane (interpreted), compacted
    ("adaptive-heavy", 2999),
])
def test_chunked_tier2_equals_whole(grid, index2000, fabric2000, monkeypatch,
                                    probe, found_cap):
    pts = _points(fabric2000, 3000, 21)
    cells = jnp.asarray(grid.point_to_cell(pts, 11))
    shifted = jnp.asarray(pts - index2000.host.shift, jnp.float32)
    kw = dict(found_cap=found_cap, probe=probe)
    whole = np.asarray(pip_join_points(shifted, cells, index2000, **kw))
    monkeypatch.setattr(join_mod, "_TIER1_CHUNK", 640)  # tier 2's is 256
    cut = np.asarray(pip_join_points(shifted, cells, index2000, **kw))
    assert np.array_equal(whole, cut)
    want = host_join(pts, index2000.host, grid, 11)
    assert (whole != want).mean() < 1e-3
    assert (want >= 0).mean() > 0.2 and index2000.num_heavy_cells > 50
    out, heavy = join_mod.pip_join_points_heavy(shifted, cells, index2000, **kw)
    assert np.array_equal(np.asarray(out), whole)
    host = index2000.host
    u = np.clip(np.searchsorted(host.cells, np.asarray(cells)), 0,
                host.cells.size - 1)
    assert np.array_equal(
        np.asarray(heavy),
        (host.cells[u] == np.asarray(cells)) & (host.cell_heavy[u] >= 0))


# ------------------------- tier 2: in place = compacted, and which is run

TIER2_CASES = [
    # probe, found_cap: the caller of `_heavy_tier`, its engine
    ("scatter", None),          # tier 1 in place, the row gather
    ("scatter", 2999),          # tier 1 compacted (K1 slots), the row gather
    ("adaptive-heavy", None),   # tier 1 compacted, the Pallas lane
    ("adaptive", 2999),
]


def _join3000(grid, index, fabric, **kw):
    pts = _points(fabric, 3000, 21)
    cells = jnp.asarray(grid.point_to_cell(pts, 11))
    shifted = jnp.asarray(pts - index.host.shift, jnp.float32)
    # (rows, near) where banded, else (rows, the mask of heavy rows)
    join = (pip_join_points if "edge_eps2" in kw
            else join_mod.pip_join_points_heavy)
    return [np.asarray(a) for a in join(shifted, cells, index, **kw)]


@pytest.mark.parametrize("banded", [False, True])
@pytest.mark.parametrize("probe,found_cap", TIER2_CASES)
def test_tier2_in_place_equals_compacted(grid, index2000, fabric2000,
                                         monkeypatch, probe, found_cap, banded):
    """A tier 2 whose cap cuts no rows probes the wide rows in place; under
    the parent's rule (always compact, into as many slots as rows) the
    answers, the band mask and the overflow marks are the same row for
    row."""
    kw = dict(probe=probe, found_cap=found_cap)
    if banded:
        kw["edge_eps2"] = jnp.asarray(1e-9, jnp.float32)
    seen = []
    real = join_mod.tier2_compacts
    monkeypatch.setattr(
        join_mod, "tier2_compacts",
        lambda rows, cap: seen.append((rows, cap)) or real(rows, cap))
    mine = _join3000(grid, index2000, fabric2000, **kw)
    assert seen == [(found_cap or 3000, None)] and not real(*seen[0])
    monkeypatch.setattr(join_mod, "tier2_compacts", lambda rows, cap: True)
    parents = _join3000(grid, index2000, fabric2000, **kw)
    assert len(mine) == len(parents) == 2
    for a, b in zip(mine, parents):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    out, second = mine
    assert (out >= 0).mean() > 0.2 and (out == -1).any()
    assert second.sum() > (20 if banded else 500)  # near rows | heavy rows
    # full caps (the dispatch core's) cut nothing either
    full = _join3000(grid, index2000, fabric2000, heavy_cap=3000, **kw)
    assert np.array_equal(full[0], out)


@pytest.mark.parametrize("probe,found_cap", TIER2_CASES[:3])
def test_a_capped_tier2_still_compacts_and_marks_overflow(
        grid, index2000, fabric2000, monkeypatch, probe, found_cap):
    """``heavy_cap`` under the row count: the first ``heavy_cap`` heavy rows
    are answered as the uncapped join answers them, the rest read
    OVERFLOW; the rule says so and `_compact` runs for it."""
    whole, heavy = _join3000(
        grid, index2000, fabric2000, probe=probe, found_cap=found_cap)
    calls = []
    real = join_mod._compact
    monkeypatch.setattr(
        join_mod, "_compact",
        lambda flag, cap: calls.append(cap) or real(flag, cap))
    capped, heavy_c = _join3000(
        grid, index2000, fabric2000, probe=probe, found_cap=found_cap,
        heavy_cap=64)
    assert 64 in calls and join_mod.tier2_compacts(found_cap or 3000, 64)
    assert np.array_equal(heavy, heavy_c) and heavy.sum() > 500
    over = capped == join_mod.OVERFLOW
    assert over.sum() == heavy.sum() - 64 and not over[~heavy].any()
    assert np.array_equal(over[heavy], np.arange(heavy.sum()) >= 64)
    assert np.array_equal(capped[~over], whole[~over])


@pytest.mark.parametrize("rows,cap,compacts", [
    (4096, None, False),     # no cap: the stream
    (4096, 0, False),
    (4096, 4096, False),     # the rows: full-bucket caps, K2 = K1
    (4096, 8192, False),     # clipped to the rows
    (4096, 4095, True),      # cuts one slot
    (4096, 1024, True),
    (4096, 1, True),         # K2 = 8 < rows
    (8, 1, False),           # K2 = 8 >= rows
    (4, None, False),        # rows < 8
    (4, 2, False),
])
def test_tier2_compacts_rule(rows, cap, compacts):
    assert join_mod.tier2_compacts(rows, cap) is compacts


@pytest.mark.parametrize("n,heavy_cells,found_cap,heavy_cap,probe,wb,want", [
    (4096, 0, None, 16, "scatter", "scatter", False),     # no tier 2 at all
    (4096, 9, None, None, "scatter", "scatter", False),
    (4096, 9, None, 4096, "scatter", "scatter", False),
    (4096, 9, None, 2048, "scatter", "scatter", True),
    (4096, 9, 2048, 2048, "scatter", "scatter", False),   # K2 = K1
    (4096, 9, 2048, 2048, "scatter", "direct", True),     # tier 1 in place
    (4096, 9, 2048, 1024, "scatter", "gather", True),
    (4096, 9, None, None, "adaptive", "scatter", False),
    (4096, 9, 1024, 2048, "adaptive-heavy", "scatter", False),
    (4096, 9, 1024, 512, "adaptive", "scatter", True),
])
def test_tier2_compacted_of_a_join_program(
        n, heavy_cells, found_cap, heavy_cap, probe, wb, want):
    """The counter's verdict: tier 2's rule on the rows tier 1 hands it
    (the batch in place, ``K1`` slots compacted), False without heavy
    cells."""
    assert join_mod.tier2_compacted(
        n, heavy_cells, found_cap, heavy_cap, probe, wb) is want


@pytest.mark.parametrize("heavy_cap,compacts", [(None, False), (1024, True)])
def test_stream_loop_on_a_heavy_index_scatters_only_under_a_cap(
        grid, index2000, fabric2000, heavy_cap, compacts):
    """The stream's loop on an index with heavy cells, package defaults:
    no scatter in its lowered text and no instruction under `pip.compact`;
    tier 2 is there. A ``heavy_cap`` under the slot's rows brings both."""
    from mosaic_tpu.obs import stages

    ring = jnp.asarray(np.stack(
        [_points(fabric2000, 2048, s) for s in (41, 42)]))
    sj = StreamJoin(index2000, grid, 11, heavy_cap=heavy_cap)
    text = sj._loop.lower(ring, index2000, 2, False).as_text()
    assert ("stablehlo.scatter" in text) == compacts
    if not compacts:
        assert "scatter" not in text
    stages.clear()
    try:
        sj.compile(ring, 2)
        found = set(stages.tables({"jit_loop"})["jit_loop"].values())
    finally:
        stages.clear()
    assert {"pip.tier1", "pip.tier2", "pip.hash_probe", "pip.cells"} <= found
    assert ("pip.compact" in found) == compacts
    res = sj.run(ring, 2)
    assert res.metrics["tier2_compacted"] is compacts
    assert res.metrics["compacted"] is False and res.overflow == 0


# ------------------------------------------------- the cell-precision rule

@pytest.mark.parametrize("res,want", [
    (9, "float32"), (11, "float64"), (12, "float64"),
])
def test_cell_dtype_rule_over_the_nyc_box(grid, fabric2000, res, want):
    col = _column(fabric2000[0][1:9])
    index = build_chip_index(tessellate(col, grid, res, keep_core_geoms=False))
    assert jnp.dtype(stream_cell_dtype(index, grid, res)).name == want
    assert StreamJoin(index, grid, res).cell_dtype == want
    # the rule's own number: one f32 ulp at |lon| 74 over the cell radius
    share = float(np.spacing(np.float32(74.0))) / grid.buffer_radius(res)
    assert (share <= CELL_F32_MAX_ULP_SHARE) == (want == "float32")


@pytest.mark.parametrize("explicit", [jnp.float32, jnp.bfloat16, jnp.float64])
def test_an_explicit_cell_dtype_wins(grid, index2000, explicit):
    sj = StreamJoin(index2000, grid, 11, cell_dtype=explicit)
    assert sj.cell_dtype == jnp.dtype(explicit).name


# --------------------------------------- the stream on a fabric index

def test_stream_run_against_the_plain_reference(grid, index2000, fabric2000):
    """Package defaults; every row of two steps against `pip_footprints`;
    the limit is the configuration's."""
    limit = _config()["guarantees"]["stream_max_disagreement"]
    ring = jnp.asarray(np.stack(
        [_points(fabric2000, 16384, s) for s in (31, 32)]))
    sj = StreamJoin(index2000, grid, 11)
    with telemetry.capture() as events:
        res = sj.run(ring, 2, collect=True)
    want = pip_footprints.answers(
        fabric2000[0], np.asarray(ring).reshape(-1, 2))
    got = res.outs.reshape(-1)
    assert (got != want).mean() <= limit and res.overflow == 0
    assert res.matches == int((want >= 0).sum()) or (got != want).any()
    # heavy rows: a numpy count over the host's tables
    host = index2000.host
    cells = np.asarray(grid.point_to_cell(np.asarray(ring).reshape(-1, 2), 11))
    u = np.clip(np.searchsorted(host.cells, cells), 0, host.cells.size - 1)
    heavy = int(((host.cells[u] == cells) & (host.cell_heavy[u] >= 0)).sum())
    assert res.metrics["heavy_rows"] == heavy > 1000
    assert res.metrics["cell_dtype"] == sj.cell_dtype == "float64"
    span = next(e for e in events
                if e.get("event") == "span" and e["name"] == "stream.run")
    assert (span["heavy_rows"], span["rows"], span["cell_dtype"]) == (
        heavy, 2 * 16384, "float64")
    # the f32-cell stream (the parent's behaviour) loses a share of rows
    # the rule's stream does not
    f32 = StreamJoin(index2000, grid, 11, cell_dtype=jnp.float32).run(
        ring, 2, collect=True)
    assert (f32.outs.reshape(-1) != want).mean() > 10 * max(
        (got != want).mean(), 1e-4)


def test_an_index_without_heavy_cells_counts_none(grid):
    """H == 0: the fold keeps its three entries, `heavy_rows` is 0."""
    rings = zones.star_lattice(2, 2, (-74.3, 40.4, -73.6, 41.0))
    index = build_chip_index(
        tessellate(_column(rings), grid, 7, keep_core_geoms=False))
    assert index.num_heavy_cells == 0
    rng = np.random.default_rng(4)
    ring = jnp.asarray(np.stack([np.column_stack(
        [rng.uniform(-74.3, -73.6, 2048), rng.uniform(40.4, 41.0, 2048)])]))
    sj = StreamJoin(index, grid, 7)
    acc, _ = sj.compile(ring, 1)
    assert acc.shape == (3,) and sj.cell_dtype == "float32"
    assert sj.run(ring, 1).metrics["heavy_rows"] == 0
