"""Streaming join pipeline (sql/stream.py): the CPU-provable contracts.

The TPU numbers live in STREAM_1B artifacts; what must hold on any
backend is bit-identity and accounting:

1. cycling an HBM-resident ring through the scanned loop returns exactly
   the per-batch path's rows and stats (ring reuse changes nothing);
2. the double-buffered prefetch path equals the non-prefetch path (cell
   assignment is deterministic — pipelining changes scheduling, never
   values);
3. every pipeline stage emits a `stream_stage` telemetry event with a
   non-negative measured duration;
4. memory accounting never reports zero (the round-5
   ``peak_hbm_bytes: 0`` artifact bug): when the backend exposes no
   memory stats, the live-buffer census lower-bounds the peak.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mosaic_tpu.core.geometry import wkt
from mosaic_tpu.core.index import CustomIndexSystem, GridConf
from mosaic_tpu.core.tessellate import tessellate
from mosaic_tpu.runtime import telemetry
from mosaic_tpu.sql.join import build_chip_index, pip_join_points
from mosaic_tpu.sql.stream import (
    StreamJoin,
    fold_stats,
    generator_rate,
    hbm_peak,
    ring_from_host,
)

# the custom grid's cell pipeline is pure arithmetic — it keeps the
# scanned loop's compile cheap on CPU (the H3 digit pipeline costs
# minutes to compile here; the contracts are index-system-agnostic)
CUSTOM = CustomIndexSystem(GridConf(-180, 180, -90, 90, 2, 10.0, 10.0))
RES = 3
ZONES = [
    "POLYGON ((1 1, 13 2, 12 11, 6 14, 2 9, 1 1), "
    "(5 5, 5 8, 8 8, 8 5, 5 5))",
    "POLYGON ((20 0, 30 0, 30 10, 25 4, 20 10, 20 0))",
    "MULTIPOLYGON (((-20 -20, -12 -20, -12 -12, -20 -12, -20 -20)), "
    "((-8 -8, -2 -8, -2 -2, -8 -2, -8 -8)))",
]
K, BATCH, NB = 3, 4096, 7  # NB > K: the ring must cycle


def _zones_index(grid=CUSTOM, **kw):
    return build_chip_index(
        tessellate(wkt.from_wkt(ZONES), grid, RES, keep_core_geoms=False),
        **kw,
    )


@pytest.fixture(scope="module")
def index():
    return _zones_index()


@pytest.fixture(scope="module")
def ring():
    rng = np.random.default_rng(0)
    return ring_from_host(
        [rng.uniform((-25, -25), (35, 20), (BATCH, 2)) for _ in range(K)]
    )


@pytest.fixture(scope="module")
def sj(index):
    return StreamJoin(index, CUSTOM, RES, prefetch=True)


def test_ring_cycling_bit_identical_to_per_batch(index, ring, sj):
    """Scanned ring loop == one pip_join_points call per batch, row for
    row — including cycled slots (iterations K..NB-1 re-visit ring
    rows)."""
    res = sj.run(ring, NB, collect=True)
    assert res.outs.shape == (NB, BATCH)
    shift = np.asarray(index.border.shift, dtype=np.float64)
    dtype = index.border.verts.dtype
    for i in range(NB):
        pts = np.asarray(ring[i % K])
        cells = CUSTOM.point_to_cell(
            jnp.asarray(pts, dtype=jnp.float32), RES
        ).astype(jnp.int64)
        want = np.asarray(
            pip_join_points(
                jnp.asarray(pts - shift, dtype=dtype), cells, index
            )
        )
        np.testing.assert_array_equal(res.outs[i], want)
    assert res.matches == int((res.outs >= 0).sum())
    assert res.overflow == 0
    assert res.matches > 0  # the workload must actually hit polygons


def test_run_batched_matches_scanned_loop(ring, sj):
    rs = sj.run(ring, NB, collect=True)
    rb = sj.run_batched(ring, NB)
    np.testing.assert_array_equal(rs.outs, rb.outs)
    assert (rs.checksum, rs.matches, rs.overflow) == (
        rb.checksum, rb.matches, rb.overflow
    )


def test_prefetch_equals_non_prefetch(index, ring, sj):
    """Double-buffering the cell assignment must be invisible in the
    results (it only changes what overlaps what)."""
    sj0 = StreamJoin(index, CUSTOM, RES, prefetch=False)
    r1 = sj.run(ring, NB, collect=True)
    r0 = sj0.run(ring, NB, collect=True)
    np.testing.assert_array_equal(r1.outs, r0.outs)
    assert (r1.checksum, r1.matches, r1.overflow) == (
        r0.checksum, r0.matches, r0.overflow
    )
    assert r1.prefetch and not r0.prefetch


class _CountingGrid(CustomIndexSystem):
    """`CUSTOM` with a host callback in every TRACED `point_to_cell`: one
    entry of ``ran`` for each time a compiled assignment executes (a
    trace adds none, and the guarded branch not taken adds none)."""

    def __init__(self, conf):
        super().__init__(conf)
        self.ran = []

    def point_to_cell(self, pts, res):
        if isinstance(pts, jax.core.Tracer):
            jax.debug.callback(lambda: self.ran.append(1))
        return super().point_to_cell(pts, res)


@pytest.fixture(scope="module")
def counting():
    grid = _CountingGrid(CUSTOM.conf)
    return grid, _zones_index(grid)


@pytest.mark.parametrize("prefetch", [True, False])
@pytest.mark.parametrize("nb", [1, 2, K + 1, NB])
def test_a_dispatch_assigns_cells_once_a_batch(counting, ring, nb, prefetch):
    """``run(ring, nb)`` EXECUTES ``nb`` cell assignments: under prefetch
    the prologue's and one in every iteration but the last (it ran
    ``nb + 1`` until PR 53 and dropped the last one's cells with the
    carry: batch ``nb`` belongs to no dispatch); without prefetch one an
    iteration, as ever. Executions, not traces: the second run of a
    compiled loop counts the same."""
    grid, index = counting
    sj = StreamJoin(index, grid, RES, prefetch=prefetch)
    for _ in range(2):
        grid.ran.clear()
        sj.run(ring, nb)
        jax.effects_barrier()
        assert len(grid.ran) == nb


@pytest.fixture(scope="module")
def heavy_index():
    """`ZONES` at an edge cap of 4: 25 of its cells are heavy, so the
    loop's fold has its fourth entry (``counted``)."""
    index = _zones_index(edge_cap=4)
    assert index.num_heavy_cells > 0
    return index


def _heavy_rows(sj, ring, nb):
    """Rows of batches [0, nb) whose cell is heavy: a numpy count over
    the host's tables on the stream's own (jitted, per-batch) cells."""
    host = sj.index.host
    if not sj.index.num_heavy_cells:
        return 0
    cells = np.concatenate(
        [np.asarray(sj.assign(ring[i % K])) for i in range(nb)]
    )
    u = np.clip(np.searchsorted(host.cells, cells), 0, host.cells.size - 1)
    return int(((host.cells[u] == cells) & (host.cell_heavy[u] >= 0)).sum())


@pytest.mark.parametrize("heavy", [False, True])
@pytest.mark.parametrize("nb", [1, 2, K, K + 1, NB])
def test_every_dispatch_length_equals_the_unpipelined_paths(
        index, heavy_index, ring, tmp_path, nb, heavy):
    """The guarded prefetch is invisible in the answers at every dispatch
    length — one batch, two, under the ring, the ring, past it (wrapping):
    ``run``'s fold and collected rows are ``run_batched``'s, an unbroken
    ``run_durable``'s (whose segments DO read their last assignment: it
    is the snapshot's) and the unprefetched loop's, bit for bit, also
    over heavy cells, whose row count is the host tables' own."""
    ix = heavy_index if heavy else index
    sj = StreamJoin(ix, CUSTOM, RES)
    got = sj.run(ring, nb, collect=True)
    fold = (got.checksum, got.matches, got.overflow)
    assert got.outs.shape == (nb, BATCH) and got.matches > 0
    unprefetched = StreamJoin(ix, CUSTOM, RES, prefetch=False).run(
        ring, nb, collect=True
    )
    others = {
        "uncollected": sj.run(ring, nb),
        "batched": sj.run_batched(ring, nb),
        "durable": sj.run_durable(
            ring, nb, run_dir=str(tmp_path), snapshot_every=2, collect=True
        ),
        "unprefetched": unprefetched,
    }
    for name, r in others.items():
        assert fold == (r.checksum, r.matches, r.overflow), name
        if r.outs is not None:
            np.testing.assert_array_equal(got.outs, r.outs, err_msg=name)
    heavy_rows = _heavy_rows(sj, ring, nb)
    assert (heavy_rows > 0) == heavy
    assert got.metrics["heavy_rows"] == heavy_rows
    assert unprefetched.metrics["heavy_rows"] == heavy_rows


def test_step_stats_folds_step(ring, sj):
    out = sj.step(ring[0])
    want = np.asarray(fold_stats(out))
    got = np.asarray(sj.step_stats(ring[0]))
    np.testing.assert_array_equal(got, want)


def test_telemetry_stage_timings(index, ring):
    """Every stage event carries a non-negative measured duration."""
    with telemetry.capture() as events:
        sj = StreamJoin(index, CUSTOM, RES, prefetch=True)
        sj.compile(ring, 4)
        sj.run(ring, 4)
        generator_rate(
            lambda k: jax.random.uniform(k, (256, 2), dtype=jnp.float64),
            jax.random.PRNGKey(1), 3, 256,
        )
    stages = [e for e in events if e["event"] == "stream_stage"]
    names = {e["stage"] for e in stages}
    assert {"compile", "join_loop", "gen_compile", "gen_loop"} <= names
    for e in stages:
        assert e["seconds"] >= 0.0, e
    loop = [e for e in stages if e["stage"] == "join_loop"][0]
    assert loop["n_batches"] == 4 and loop["batch"] == BATCH
    assert loop["points_per_sec"] > 0


def test_ring_from_host_shape_and_residency(ring):
    assert ring.shape == (K, BATCH, 2)
    assert ring.dtype == jnp.float64


def test_hbm_peak_never_zero(ring):
    """The r05 artifact recorded peak_hbm_bytes: 0 — the census fallback
    must always see at least the resident ring."""
    peak, source = hbm_peak()
    assert peak > 0
    assert source  # a named source, never silent
