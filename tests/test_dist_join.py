"""Distributed join parity on the virtual 8-device CPU mesh.

The multi-chip correctness evidence: `distributed_join_step` on a
``(dp, cell)`` mesh must produce exactly the single-device
`pip_join_points` result, for several mesh shapes, with uneven shard
padding, and for both the sharded- and replicated-hash-table layouts.
Reference semantics: `sql/join/PointInPolygonJoin.scala:68-84` (equi-join
on cell + ``is_core || st_contains``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from mosaic_tpu.core.index.h3 import H3IndexSystem
from mosaic_tpu.core.tessellate import tessellate
from mosaic_tpu.datasets import random_points, synthetic_zones
from mosaic_tpu.parallel import (
    distributed_join_step,
    make_mesh,
    pad_index_for_shards,
)
from mosaic_tpu.parallel.dist_join import (
    _gather_index,
    _index_specs,
    pad_points,
)
from mosaic_tpu.sql.join import _probe_slot, build_chip_index, pip_join_points

RES = 7
BBOX = (-74.05, 40.60, -73.85, 40.78)


@pytest.fixture(scope="module")
def problem():
    h3 = H3IndexSystem()
    zones = synthetic_zones(3, 3, bbox=BBOX)
    table = tessellate(zones, h3, RES, keep_core_geoms=False)
    index = build_chip_index(table)
    pts = random_points(301, bbox=BBOX, seed=5)  # odd: forces point padding
    cells = np.asarray(h3.point_to_cell(jnp.asarray(pts), RES))
    shift = np.asarray(index.border.shift, dtype=np.float64)
    shifted = (pts - shift).astype(np.asarray(index.border.verts).dtype)
    single = np.asarray(pip_join_points(jnp.asarray(shifted), jnp.asarray(cells), index))
    return h3, index, shifted, cells, single, len(zones)


def _run(mesh, index, shifted, cells, num_zones, table_size):
    index = pad_index_for_shards(index, mesh.shape["cell"])
    p, c = pad_points(shifted, cells, mesh.size)
    step = distributed_join_step(mesh, num_zones, table_size=table_size)
    match, counts = step(jnp.asarray(p), jnp.asarray(c), index)
    return np.asarray(match)[: shifted.shape[0]], np.asarray(counts)


@pytest.mark.parametrize("cell_axis", [1, 2, 4, 8])
def test_mesh_shapes_match_single_device(problem, devices, cell_axis):
    h3, index, shifted, cells, single, nz = problem
    mesh = make_mesh(8, cell_axis=cell_axis)
    T = int(index.table_cell.shape[0])
    match, counts = _run(mesh, index, shifted, cells, nz, T)
    np.testing.assert_array_equal(match, single)
    # psum'd per-zone histogram == host bincount of the single-device match
    expect = np.bincount(single[single >= 0], minlength=nz)
    np.testing.assert_array_equal(counts, expect)


def test_replicated_table_path(problem, devices):
    """table_size=None keeps the hash table replicated — same answer."""
    h3, index, shifted, cells, single, nz = problem
    mesh = make_mesh(8, cell_axis=2)
    match, _ = _run(mesh, index, shifted, cells, nz, None)
    np.testing.assert_array_equal(match, single)


def test_indivisible_table_falls_back_to_replicated(problem, devices):
    """A table size the cell axis doesn't divide must still be correct."""
    h3, index, shifted, cells, single, nz = problem
    mesh = make_mesh(8, cell_axis=4)
    # claim a non-divisible T: the step must choose the replicated layout
    match, _ = _run(mesh, index, shifted, cells, nz, int(index.table_cell.shape[0]) + 1)
    np.testing.assert_array_equal(match, single)


def test_pad_index_roundtrip(problem):
    """Padding preserves the single-device join result exactly."""
    h3, index, shifted, cells, single, nz = problem
    padded = pad_index_for_shards(index, 8)
    assert int(padded.cells.shape[0]) % 8 == 0
    assert int(padded.chip_geom.shape[0]) % 8 == 0
    out = np.asarray(
        pip_join_points(jnp.asarray(shifted), jnp.asarray(cells), padded)
    )
    np.testing.assert_array_equal(out, single)


def test_pad_points_sentinels_never_match(problem, devices):
    h3, index, shifted, cells, single, nz = problem
    p, c = pad_points(shifted, cells, 8)
    assert p.shape[0] % 8 == 0
    mesh = make_mesh(8, cell_axis=2)
    idx = pad_index_for_shards(index, 2)
    step = distributed_join_step(mesh, nz, table_size=int(idx.table_cell.shape[0]))
    match, _ = step(jnp.asarray(p), jnp.asarray(c), idx)
    match = np.asarray(match)
    assert (match[shifted.shape[0] :] == -1).all()


def test_cell_sharded_table_rows_are_probed_after_the_all_gather(
        problem, devices):
    """The probe's table takes the table's spec: padded for the shards it
    is the index's own, each chip holds T / shards of its rows, and after
    the step's all-gather the probe finds what one device finds — while
    the host-side copies of the table stay sharded (no ICI spent)."""
    h3, index, shifted, cells, single, nz = problem
    mesh = make_mesh(8, cell_axis=4)
    idx = pad_index_for_shards(index, 4)
    np.testing.assert_array_equal(idx.table_rows, index.table_rows)
    T = int(index.table_rows.shape[0])
    specs = _index_specs(P("cell"), P("cell"))
    assert specs.table_rows == specs.table_cell == P("cell")
    assert _index_specs(P("cell"), P()).table_rows == P()

    def probe(pcells, shard):
        assert shard.table_rows.shape[0] == T // 4
        full = _gather_index(shard, "cell", table_sharded=True)
        assert full.table_rows.shape[0] == T
        assert full.table_cell.shape[0] == T // 4
        return _probe_slot(pcells, full)

    _, c = pad_points(shifted, cells, mesh.size)
    got = jax.shard_map(
        probe, mesh=mesh, in_specs=(P(mesh.axis_names), specs),
        out_specs=P(mesh.axis_names),
    )(jnp.asarray(c), idx)
    want = np.asarray(_probe_slot(jnp.asarray(c), index))
    assert (want >= 0).any() and (want[cells.shape[0]:] == -1).all()
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("res, bbox, n, bucket, packed", [
    (9, BBOX, 3, 1, True),
    (11, (-74.00, 40.70, -73.96, 40.73), 2, 2, False),
], ids=["packs", "does-not-pack"])
def test_cost_index_shapes_read_what_they_read_before_the_row_table(
        res, bbox, n, bucket, packed):
    """`benchmark/harness/cost.py` sizes `join_hbm_share.stream`'s bytes a
    row from `table_cell.shape[1]` and `table_pack.shape[0] > 0`: on an
    index whose ids pack and on one whose ids cannot, both read what they
    read before the probe moved to `table_rows` (the values are the
    parent commit's), so the share counts the same bytes whatever
    implements the probe."""
    from benchmark.harness import cost

    index = build_chip_index(tessellate(
        synthetic_zones(n, n, bbox=bbox), H3IndexSystem(), res,
        keep_core_geoms=False))
    shapes = cost.index_shapes(index)
    assert shapes["hash_bucket"] == bucket
    assert shapes["hash_packed"] is packed
    assert index.table_rows.shape == (index.table_cell.shape[0], 3 * bucket)
