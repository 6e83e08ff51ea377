"""AOT TPU-platform lowering of the hot programs, runnable without a TPU.

`jax.jit(...).trace(...).lower(lowering_platforms=("tpu",))` runs the full
Mosaic/StableHLO lowering pipeline for the TPU target on any host — it is
the stage where round 2's Pallas kernel failed on hardware (invalid block
shapes) and where a stray f64 constant inside a kernel dies today. Keeping
these green on CPU CI means a TPU compile failure can only come from the
final XLA backend stage, not from our programs.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mosaic_tpu.core.index.h3 import H3IndexSystem
from mosaic_tpu.core.tessellate import tessellate
from mosaic_tpu.datasets import random_points, synthetic_zones
from mosaic_tpu.sql.join import _probe_slot, build_chip_index, pip_join_points

BBOX = (-74.05, 40.60, -73.85, 40.78)


@pytest.fixture(scope="module")
def problem():
    h3 = H3IndexSystem()
    zones = synthetic_zones(4, 4, bbox=BBOX)
    table = tessellate(zones, h3, 7, keep_core_geoms=False)
    return h3, build_chip_index(table), len(zones)


@pytest.fixture(scope="module")
def heavy_problem():
    """The same zones at an edge cap of 8: some cells are heavy."""
    h3 = H3IndexSystem()
    table = tessellate(
        synthetic_zones(4, 4, bbox=BBOX), h3, 7, keep_core_geoms=False)
    return h3, build_chip_index(table, edge_cap=8)


def _tpu_lower(traced):
    return traced.lower(lowering_platforms=("tpu",)).as_text()


def test_pallas_pip_kernel_lowers_for_tpu():
    from mosaic_tpu.core.geometry import wkt
    from mosaic_tpu.core.geometry.device import pack_to_device
    from mosaic_tpu.kernels.pip import edge_planes, pip_zone

    polys = wkt.from_wkt(["POLYGON ((0 0, 10 0, 10 10, 0 10, 0 0))"] * 3)
    dev = pack_to_device(polys, dtype=jnp.float32)
    planes, n_g = edge_planes(dev)
    pts = jnp.zeros((2048, 2), jnp.float32)

    def f(points, planes):
        return pip_zone(points, planes, n_real_g=n_g)

    hlo = _tpu_lower(jax.jit(f).trace(pts, planes))
    assert "tpu_custom_call" in hlo  # the Pallas kernel actually lowered


@pytest.mark.parametrize("banded", [False, True])
@pytest.mark.parametrize("heavy_rows", [40, 128, 129, 300])
def test_pallas_heavy_kernel_lowers_for_tpu(heavy_rows, banded):
    """`pip_heavy_tiled`, plain and banded (the recheck kernel), with
    heavy-row counts on both sides of the 128-lane tile."""
    from mosaic_tpu.kernels.pip import pip_heavy_tiled

    H, E2, M2, K = heavy_rows, 48, 2, 1000
    args = (
        jnp.zeros(K, jnp.float32), jnp.zeros(K, jnp.float32),
        jnp.zeros(K, jnp.int32),
        jnp.zeros((H, E2, 4), jnp.float32), jnp.zeros((H, E2), jnp.uint32),
        jnp.zeros((H, M2), jnp.int32),
    )

    def f(px, py, rows, edges, ebits, geom):
        return pip_heavy_tiled(
            px, py, rows, edges, ebits, geom,
            eps2=jnp.float32(1e-6) if banded else None,
        )

    hlo = _tpu_lower(jax.jit(f).trace(*args))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("segments", [35, 263])
def test_pallas_zonal_kernel_lowers_for_tpu(segments):
    """`zonal_tiled` on both sides of one 128-segment accumulator block:
    typed f32 fill constants (a python float is an f64 constant under
    x64, which Mosaic cannot cast) and a legal (1, tile_s) block."""
    from mosaic_tpu.kernels.zonal import zonal_tiled

    vals = jnp.ones(5000, jnp.float32)
    seg = jnp.zeros(5000, jnp.int32)
    hlo = _tpu_lower(zonal_tiled.trace(vals, seg, segments))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("dt, scatters", [(np.int16, 0), (np.float64, 4)])
def test_zonal_fold_lane_in_the_tpu_lowering(dt, scatters):
    """A full int16 tile into 256 zones lowers to the int32 lane: no
    scatter and no f64 anywhere in the program (the chip emulates f64);
    the same call on f64 values is the four-scatter program."""
    from mosaic_tpu.kernels.zonal import zonal_fold

    def f(vals, seg):
        return zonal_fold(vals, seg, 256, acc_dtype=jnp.float64)

    hlo = _tpu_lower(jax.jit(f).trace(
        jnp.zeros(65536, dt), jnp.zeros(65536, jnp.int32)))
    assert hlo.count('"stablehlo.scatter"') == scatters
    assert ("f64" in hlo) == (scatters > 0)


def test_tier1_fetch_in_the_tpu_lowering(problem):
    """Tier 1 fetches a cell's row by gathers on the TPU target: the
    edge row and the packed int32 row come from (U, .) operands, and no
    (K, U) one-hot contraction is in the program."""
    h3, index, _ = problem
    U, E1 = index.cell_ebits.shape
    M1 = index.cell_slot_geom.shape[1]
    K = 4096
    pts = jnp.asarray(random_points(K, bbox=BBOX, seed=3), jnp.float32)
    cells = h3.point_to_cell(pts, 7).astype(jnp.int64)
    hlo = _tpu_lower(jax.jit(pip_join_points).trace(pts, cells, index))
    lines = hlo.splitlines()
    assert not [
        ln for ln in lines if "dot_general" in ln and f"tensor<{K}x{U}x" in ln
    ]
    gathers = [ln for ln in lines if '"stablehlo.gather"' in ln]
    for row in (f"tensor<{U}x{4 * E1}xf32>", f"tensor<{U}x{E1 + 2 * M1 + 1}xi32>"):
        assert sum(f"({row}," in ln for ln in gathers) == 1, row


@pytest.mark.parametrize("edge_cap", [None, 8], ids=["light", "heavy"])
def test_probe_is_one_u32_row_gather(problem, heavy_problem, edge_cap):
    """The hash probe fetches a point's bucket with ONE gather, from the
    (T, 3B) u32 row table: in the TPU-target lowering and in the optimized
    HLO a CPU can compile, and nothing of the table's size is 64 bits wide
    (the chip would split an int64 table into halves on every launch)."""
    h3, index = (problem if edge_cap is None else heavy_problem)[:2]
    T, W = index.table_rows.shape
    assert index.table_rows.dtype == jnp.uint32 and W % 3 == 0
    cells = h3.point_to_cell(
        jnp.asarray(random_points(4096, bbox=BBOX, seed=3), jnp.float32), 7
    ).astype(jnp.int64)
    traced = jax.jit(_probe_slot).trace(cells, index)
    hlo = _tpu_lower(traced)
    gathers = [ln for ln in hlo.splitlines() if '"stablehlo.gather"' in ln]
    assert len(gathers) == 1 and f"(tensor<{T}x{W}xui32>," in gathers[0]
    assert f"tensor<{T}x" not in hlo.replace(f"tensor<{T}x{W}xui32>", "")
    opt = traced.lower().compile().as_text()
    lines = opt.splitlines()
    assert sum(" gather(" in ln for ln in lines) == 1
    assert not [ln for ln in lines if f"64[{T}," in ln]
    # the whole join reads the table through that one gather too
    pts = jnp.zeros((4096, 2), jnp.float32)
    join = _tpu_lower(jax.jit(pip_join_points).trace(pts, cells, index))
    assert sum(
        '"stablehlo.gather"' in ln and f"(tensor<{T}x" in ln
        for ln in join.splitlines()) == 1


def _gathers_of(hlo, operand):
    return [ln for ln in hlo.splitlines()
            if '"stablehlo.gather"' in ln and f"({operand}," in ln]


@pytest.mark.parametrize("probe", ["scatter", "adaptive"])
@pytest.mark.parametrize("edge_cap", [None, 8], ids=["light", "heavy"])
def test_the_batch_join_reads_the_table_once_in_the_counts_program(
        problem, heavy_problem, edge_cap, probe):
    """`pip_join`'s two programs on the TPU target (ISSUE 50): the counts
    program gathers the (T, 3B) table once and returns the (N,) int32 slot
    column beside the (3,) counts; it gathers `cell_convex` over the rows
    under an adaptive probe only, `cell_heavy` where the index has heavy
    cells. The join handed that column gathers no table row at all and
    takes no 64-bit input of the batch's length."""
    from mosaic_tpu.dispatch import core as dispatch

    h3, index = (problem if edge_cap is None else heavy_problem)[:2]
    T, W = index.table_rows.shape
    U = index.cell_convex.shape[0]
    assert index.num_convex_cells > 0 and index.cell_heavy.shape == (U,)
    N = 4096
    pts = jnp.asarray(random_points(N, bbox=BBOX, seed=3), jnp.float32)
    cells = h3.point_to_cell(pts, 7).astype(jnp.int64)
    counts = _tpu_lower(dispatch.jit_counts().trace(cells, index, probe=probe))
    table = f"tensor<{T}x{W}xui32>"
    assert len(_gathers_of(counts, table)) == 1
    # per-row class lookups: (U,) int32 columns gathered to (N,)
    per_row = [ln for ln in _gathers_of(counts, f"tensor<{U}xi32>")
               if f"-> tensor<{N}xi32>" in ln]
    assert len(per_row) == (
        int(probe != "scatter") + int(index.num_heavy_cells > 0))
    main = next(ln for ln in counts.splitlines() if "@main(" in ln)
    results = main.split("->", 1)[1]
    assert results.count("tensor<") == 2
    assert "tensor<3xi64>" in results and f"tensor<{N}xi32>" in results
    u = jax.ShapeDtypeStruct((N,), jnp.int32)
    for kw in ({}, {"found_cap": 1024}, {"edge_eps2": jnp.float32(1e-9)},
               {"probe": probe}):
        static = {k: v for k, v in kw.items() if k != "edge_eps2"}
        dynamic = {k: v for k, v in kw.items() if k == "edge_eps2"}
        join = _tpu_lower(jax.jit(
            functools.partial(pip_join_points, **static)
        ).trace(pts, None, index, slots=u, **dynamic))
        assert not _gathers_of(join, table), kw
        assert table not in join  # not even as an argument
        assert f"tensor<{N}xi64>" not in join
        assert f"tensor<{N}xui64>" not in join
        # and the probing call of the same statics holds exactly one
        probing = _tpu_lower(jax.jit(
            functools.partial(pip_join_points, **static)
        ).trace(pts, cells, index, **dynamic))
        assert len(_gathers_of(probing, table)) == 1, kw


@pytest.mark.parametrize("heavy_cap,scatters", [
    (None, False), (4096, False), (2048, True),
])
def test_tier2_in_the_tpu_lowering(heavy_problem, heavy_cap, scatters):
    """On an index with heavy cells the TPU-target program fetches the
    wide rows from the (H, E2, 4) table for every one of the K rows in
    place, with no scatter, unless ``heavy_cap`` cuts rows."""
    h3, index = heavy_problem
    H, E2 = index.heavy_ebits.shape
    assert H > 0
    K = 4096
    pts = jnp.asarray(random_points(K, bbox=BBOX, seed=3), jnp.float32)
    cells = h3.point_to_cell(pts, 7).astype(jnp.int64)
    hlo = _tpu_lower(jax.jit(
        functools.partial(pip_join_points, heavy_cap=heavy_cap)
    ).trace(pts, cells, index))
    assert ('"stablehlo.scatter"' in hlo) == scatters
    rows = K if not scatters else heavy_cap
    wide = [ln for ln in hlo.splitlines() if '"stablehlo.gather"' in ln
            and f"(tensor<{H}x{E2}x4xf32>," in ln]
    assert len(wide) == 1 and f"-> tensor<{rows}x{E2}x4xf32>" in wide[0]


def test_bench_step_lowers_for_tpu(problem):
    h3, index, _ = problem
    dtype = index.border.verts.dtype
    pts = jnp.asarray(random_points(16384, bbox=BBOX, seed=1))

    @functools.partial(jax.jit, static_argnames=("found_cap", "heavy_cap"))
    def step(points_f64, chip_index, found_cap, heavy_cap):
        cells = h3.point_to_cell(points_f64.astype(jnp.float32), 7)
        shifted = (points_f64 - chip_index.border.shift).astype(dtype)
        return pip_join_points(
            shifted,
            cells.astype(jnp.int64),
            chip_index,
            heavy_cap=heavy_cap,
            found_cap=found_cap,
        )

    hlo = _tpu_lower(step.trace(pts, index, 4096, 1024))
    assert len(hlo) > 1000


def test_stream_loop_assigns_once_a_batch_in_the_tpu_lowering(problem):
    """`StreamJoin`'s prefetching loop at four steps holds the cell
    assignment twice — the prologue's, for batch 0, and the scan body's,
    for batch i + 1 — and the body's sits under ONE conditional whose
    other branch hands the carried cells on: the prologue and three
    guarded prefetches make four assignments for four batches. Until
    PR 53 the body's ran unguarded, five for four, the fifth's cells
    dropped with the carry (`PERF.md` section 6, PR 53). Pinned on the
    traced program and on its TPU lowering, so that a later edit cannot
    bring the fifth back unseen."""
    from mosaic_tpu.sql.stream import StreamJoin

    h3, index, _ = problem
    ring = jnp.asarray(np.stack(
        [random_points(4096, bbox=BBOX, seed=s) for s in (1, 2)]))
    sj = StreamJoin(index, h3, 7)
    assert sj.prefetch
    traced = sj._loop.trace(ring, index, 4, False)

    def in_cells(eqn):
        return "pip.cells" in str(eqn.source_info.name_stack)

    program = traced.jaxpr.jaxpr
    assert any(in_cells(e) for e in program.eqns)  # the prologue's
    (scan,) = [e for e in program.eqns if e.primitive.name == "scan"]
    assert scan.params["length"] == 4
    body = scan.params["jaxpr"].jaxpr
    assert not any(in_cells(e) for e in body.eqns)  # none unguarded
    (guard,) = [e for e in body.eqns if e.primitive.name == "cond"]
    kept, assigned = (b.jaxpr for b in guard.params["branches"])
    assert not kept.eqns and kept.outvars == kept.invars[-1:]
    assert sum(map(in_cells, assigned.eqns)) > len(assigned.eqns) // 2
    # the lowering: one `while`, and one conditional more than the two
    # assignments bring (H3's digit pipeline holds some of its own)
    hlo = traced.lower(lowering_platforms=("tpu",)).as_text(debug_info=True)
    own = _tpu_lower(sj.assign.trace(ring[0])).count('"stablehlo.case"(')
    assert hlo.count("stablehlo.while") == 1
    assert hlo.count('"stablehlo.case"(') == 2 * own + 1
    assert "pip.cells" in hlo  # the scope `obs/stages.py` names the ops by


def test_dist_join_step_lowers_for_tpu(problem, devices):
    from mosaic_tpu.parallel import (
        distributed_join_step,
        make_mesh,
        pad_index_for_shards,
    )
    from mosaic_tpu.parallel.dist_join import pad_points

    h3, index, nz = problem
    mesh = make_mesh(8)
    idx = pad_index_for_shards(index, mesh.shape["cell"])
    pts = random_points(512, bbox=BBOX, seed=2)
    cells = np.asarray(h3.point_to_cell(jnp.asarray(pts), 7))
    shifted = (pts - np.asarray(index.border.shift)).astype(
        np.asarray(index.border.verts).dtype
    )
    p, c = pad_points(shifted, cells, 8)
    step = distributed_join_step(
        mesh, nz, table_size=int(idx.table_cell.shape[0])
    )
    hlo = _tpu_lower(step.trace(jnp.asarray(p), jnp.asarray(c), idx))
    assert "all-gather" in hlo or "all_gather" in hlo  # ICI collective present


def test_distance_join_programs_lower_for_tpu():
    """The distance join's gather and segment-pair predicate at float32,
    the dtype the chip runs them in (`sql.proximity`): field-major rows
    in, one int8 a candidate row out, no float64 array (a weak-typed
    scalar bound of `clip` is converted where it is used)."""
    from mosaic_tpu.sql import proximity as P

    width, rows = 2 * P.PIECE_VERTS + 5, 4096
    table = jnp.zeros((512, width), jnp.float32)
    idx = jnp.zeros(rows, jnp.int32)
    hlo = _tpu_lower(P._gather_program().trace(idx, idx, idx, idx, table, table))
    assert f"{width}x{rows}xf32" in hlo.replace(" ", "")
    fields = jnp.zeros((width, rows), jnp.float32)
    hlo = _tpu_lower(P._segpair_program().trace(
        fields, fields, jnp.zeros(rows, bool), np.float32(1e-6)))
    assert f"{rows}xf64" not in hlo and f"{rows}xi8" in hlo.replace(" ", "")


@pytest.mark.parametrize("rows, bucket", [(4096, 1024), (1024, 1024)])
def test_the_emission_holds_one_scatter_one_gather_and_no_search(rows, bucket):
    """The equi-join's emission lowered for the TPU is what the marks form
    is made of and no more: ONE scatter (the rows' span offsets onto the
    slots), ONE gather (``(lo - off)[li]``) and no ``while`` — a
    ``searchsorted`` is a loop of some twenty rounds, each a gather of
    ``bucket`` indices, which the chip pays per index (`PERF.md` section
    6, PR 49). int32 throughout: no 64-bit integer array reaches the
    chip."""
    from mosaic_tpu.sql.overlay import _emit_program

    spans = jnp.zeros(rows, jnp.int32)
    traced = _emit_program(bucket).trace(spans, spans, 7, np.int32(3))
    hlo = traced.lower(lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert hlo.count('"stablehlo.scatter"(') == 1
    assert hlo.count('"stablehlo.gather"(') == 1
    assert "stablehlo.while" not in hlo and "stablehlo.sort" not in hlo
    assert "overlay.emit" in hlo  # the scope `obs/stages.py` names the ops by
    flat = hlo.replace(" ", "")
    assert f"{bucket}xi64" not in flat and f"{rows}xi64" not in flat
    assert f"tensor<{bucket}xi32>" in flat
