"""Geo-expression compiler: fusion, bit-identity, and cache hygiene.

The contract under test (ISSUE 13): an expression tree — band math,
masking, zonal terminal — lowered by `mosaic_tpu.expr` runs as ONE
device program per tile-bucket signature, and its per-zone results are
bit-identical to (a) the staged pipeline of existing rst_*/zonal ops
and (b) a pure-numpy f64 interpreter of the same tree, on adversarial
fixtures: NaN-nodata speckle, pixel centers landing EXACTLY on zone
edges, multi-band planar tiles. Structurally equal trees share one
compiled program; after ``freeze()`` a novel signature trips the
cold-compile tripwire; durable expression scans refuse to resume
against a different tree.
"""

import numpy as np
import pytest

from mosaic_tpu import expr as E
from mosaic_tpu.core.geometry import wkt
from mosaic_tpu.core.index import CustomIndexSystem, GridConf
from mosaic_tpu.core.tessellate import tessellate
from mosaic_tpu.dispatch import core as dispatch
from mosaic_tpu.expr import compile as expr_compile
from mosaic_tpu.functions.raster import rst_mapbands, rst_ndvi
from mosaic_tpu.raster import Raster
from mosaic_tpu.raster.zonal import ZonalEngine, zonal_zones
from mosaic_tpu.runtime import checkpoint, faults, telemetry
from mosaic_tpu.runtime.retry import RetryPolicy
from mosaic_tpu.sql import RasterStream
from mosaic_tpu.sql.join import build_chip_index

CUSTOM = CustomIndexSystem(GridConf(-180, 180, -90, 90, 2, 10.0, 10.0))
RES = 3

#: same adversarial zone set as test_raster_zonal.py: edges cross the
#: (32, 32) tile boundaries and the x=6 / y=8 edges run EXACTLY through
#: pixel centers of the fixture raster; zone 0 carries a hole
ZONES = [
    "POLYGON ((6 -20, 50 -25, 70 10, 40 8, 6 8, 6 -20), "
    "(20 -10, 30 -10, 30 -2, 20 -2, 20 -10))",
    "POLYGON ((55 -50, 85 -50, 85 -20, 70 -35, 55 -20, 55 -50))",
    "POLYGON ((2 -55, 20 -55, 20 -40, 2 -40, 2 -55))",
]

FAST = RetryPolicy(max_attempts=3, base_delay_s=0.0, jitter=0.0)


@pytest.fixture(scope="module")
def index():
    col = wkt.from_wkt(ZONES)
    return build_chip_index(
        tessellate(col, CUSTOM, RES, keep_core_geoms=False)
    )


@pytest.fixture(scope="module")
def engine(index):
    return ZonalEngine(CUSTOM, RES, chip_index=index)


def _mk_raster(h=75, w=90, bands=3, seed=5):
    """Multi-band 75x90 @ (32, 32) -> 3x3 padded tile grid; pixel
    centers at integer world coordinates (x = col, y = 15 - row); NaN
    nodata with ~8% speckle per band (NaN pixels are INVALID — the
    bit-identity contract masks NaN out, it never reaches a fold)."""
    rng = np.random.default_rng(seed)
    data = rng.uniform(0.0, 100.0, (bands, h, w))
    for b in range(bands):
        speck = rng.random((h, w)) < 0.08
        data[b][speck] = np.nan
    return Raster(
        data=data,
        gt=(-0.5, 1.0, 0.0, 15.5, 0.0, -1.0),
        srid=0,
        nodata=float("nan"),
    )


def _planar_raster(h=75, w=90, bands=3):
    """Multi-band planar tiles: each band constant per (32, 32) tile,
    adversarial for min == max == mean collapses and for any lowering
    that confuses band rows."""
    data = np.zeros((bands, h, w))
    for b in range(bands):
        for ti, r0 in enumerate(range(0, h, 32)):
            for tj, c0 in enumerate(range(0, w, 32)):
                data[b, r0:r0 + 32, c0:c0 + 32] = (
                    10.0 * (b + 1) + ti + 0.5 * tj
                )
    return Raster(
        data=data, gt=(-0.5, 1.0, 0.0, 15.5, 0.0, -1.0), srid=0,
        nodata=float("nan"),
    )


@pytest.fixture(scope="module")
def raster():
    return _mk_raster()


#: the acceptance pipeline: NDVI, cloud mask, zonal fold
def _pipeline():
    return (
        E.ndvi(nir=2, red=1)
        .mask_where(E.band(3) < 80.0)
        .zonal(by="zones")
    )


def _assert_result_equal(got, want):
    np.testing.assert_array_equal(got.keys, want.keys)
    np.testing.assert_array_equal(got.count, want.count)
    np.testing.assert_array_equal(got.sum, want.sum)  # bitwise: f64
    np.testing.assert_array_equal(got.min, want.min)
    np.testing.assert_array_equal(got.max, want.max)


# --------------------------------------------------------------- ast


class TestAst:
    def test_structural_equality_and_hash(self):
        a = _pipeline()
        b = _pipeline()
        assert a == b
        assert E.structure_key(a) == E.structure_key(b)
        assert E.tree_hash(a) == E.tree_hash(b)
        assert E.tree_hash(a) != E.tree_hash(
            E.ndvi(nir=3, red=1).zonal(by="zones")
        )

    def test_eq_is_a_method_not_dunder(self):
        # __eq__ stays structural (dataclass) so trees are dict keys;
        # pixel equality is spelled .eq()/.ne()
        node = E.band(1).eq(E.band(2))
        assert isinstance(node, E.Compare)
        assert node.op == "eq"

    def test_bands_of_and_terminal(self):
        e = _pipeline()
        value, kind, by, stats = E.terminal_of(e)
        assert kind == "zonal" and by == "zones"
        assert list(E.bands_of(value)) == [1, 2, 3]
        assert set(stats) == {"count", "sum", "min", "max", "mean"}

    def test_validation_errors(self):
        with pytest.raises(ValueError, match="out of range"):
            E.validate(E.band(4).zonal(), 3)
        with pytest.raises(TypeError, match="numeric"):
            E.validate(E.band(1) + (E.band(2) < 1.0), 3)
        with pytest.raises(ValueError, match="grid"):
            E.validate(
                (E.band(1) + E.zone_data((1.0,))).zonal(by="grid"), 3
            )
        with pytest.raises(ValueError, match="terminal"):
            E.validate(E.band(1).zonal() + E.band(2), 3)
        with pytest.raises(ValueError, match="vector side"):
            E.validate(
                (E.band(1) + E.zone_data((1.0,))).zonal(), 3,
                has_zones=False,
            )
        with pytest.raises(TypeError, match="numeric value tree"):
            E.validate((E.band(1) < 2.0).zonal(), 3)


# ------------------------------------------------- fused == staged == oracle


class TestBitIdentity:
    def test_fused_equals_staged_and_oracle(self, engine, raster, index):
        """The acceptance pipeline, three ways: (1) fused — one program
        per tile does NDVI + mask + fold; (2) staged — NDVI computed
        into a NaN-nodata raster by numpy, masked by numpy, folded by
        the pre-existing zonal path; (3) the f64 host interpreter."""
        e = _pipeline()
        fused = engine.map(e, raster, tile=(32, 32))

        nir = raster.data[1]
        red = raster.data[0]
        cloud = raster.data[2]
        staged_px = (nir - red) / (nir + red)
        keep = np.isfinite(cloud) & (cloud < 80.0)
        staged_px = np.where(keep, staged_px, np.nan)
        staged_r = Raster(
            data=staged_px[None], gt=raster.gt, srid=0,
            nodata=float("nan"),
        )
        staged = zonal_zones(
            staged_r, index, CUSTOM, RES, tile=(32, 32)
        )
        _assert_result_equal(fused, staged)

        oracle = E.host_expr_zonal_oracle(
            raster, e, index_system=CUSTOM, resolution=RES,
            chip_index=index, tile=(32, 32),
        )
        _assert_result_equal(fused, oracle)

    def test_edge_pixels_fold_identically(self, engine, raster, index):
        """Pixel centers exactly on the x=6 / y=8 zone edges go through
        the epsilon-band host re-join in BOTH lanes — membership of the
        fused fold must match the staged path bit for bit (counts too,
        not just sums)."""
        e = (E.band(1) * 2.0 - E.band(2)).zonal(by="zones")
        fused = engine.map(e, raster, tile=(32, 32))
        staged_px = raster.data[0] * 2.0 - raster.data[1]
        staged = zonal_zones(
            Raster(
                data=staged_px[None], gt=raster.gt, srid=0,
                nodata=float("nan"),
            ),
            index, CUSTOM, RES, tile=(32, 32),
        )
        _assert_result_equal(fused, staged)

    def test_planar_tiles(self, engine, index):
        """Per-tile-constant bands: min == max per zone-tile overlap,
        and any band-row confusion in the lowering shows instantly."""
        r = _planar_raster()
        e = E.norm_diff(E.band(2), E.band(1)).zonal(by="zones")
        fused = engine.map(e, r, tile=(32, 32))
        oracle = E.host_expr_zonal_oracle(
            r, e, index_system=CUSTOM, resolution=RES,
            chip_index=index, tile=(32, 32),
        )
        _assert_result_equal(fused, oracle)

    def test_where_and_boolean_ops(self, engine, raster, index):
        e = E.where(
            (E.band(1) < 30.0) | (E.band(2) > 70.0),
            E.band(3),
            E.band(1) - E.band(2),
        ).zonal(by="zones")
        fused = engine.map(e, raster, tile=(32, 32))
        oracle = E.host_expr_zonal_oracle(
            raster, e, index_system=CUSTOM, resolution=RES,
            chip_index=index, tile=(32, 32),
        )
        _assert_result_equal(fused, oracle)

    def test_grid_mode(self, engine, raster):
        """by="grid": the fused program folds by index cell; oracle is
        the numpy interpreter + sequential dict fold."""
        e = E.ndvi(nir=2, red=1).zonal(by="grid")
        fused = engine.map(e, raster, tile=(32, 32))
        oracle = E.host_expr_zonal_oracle(
            raster, e, index_system=CUSTOM, resolution=RES,
            tile=(32, 32), by="grid",
        )
        _assert_result_equal(fused, oracle)

    def test_nan_detectable_in_tree(self, engine, raster, index):
        """band.ne(band) is the in-tree NaN probe — on a NaN-nodata
        raster every valid pixel is finite, so the probe is all-False
        and where() keeps the first branch everywhere."""
        e = E.where(
            E.band(1).ne(E.band(1)), E.const(-1.0), E.band(1)
        ).zonal(by="zones")
        fused = engine.map(e, raster, tile=(32, 32))
        plain = engine.map(E.band(1).zonal(by="zones"), raster,
                           tile=(32, 32))
        _assert_result_equal(fused, plain)


# ----------------------------------------------------- one-program fusion


class TestFusion:
    def test_warm_map_compiles_nothing(self, engine, raster):
        """THE acceptance criterion: after warmup the 3-op pipeline is
        exactly one device program per tile bucket — a warm map adds
        ZERO backend compiles."""
        e = _pipeline()
        engine.warmup_expr(e, raster, tile=(32, 32))
        n0 = dispatch.backend_compiles()
        engine.map(e, raster, tile=(32, 32))
        assert dispatch.backend_compiles() == n0

    def test_structural_sharing_one_compile(self, engine, raster):
        """Two independently-built equal trees key the same cached
        program: the second map is a pure cache hit."""
        a = (E.band(1) + E.band(2) * 0.25).mask_where(
            E.band(3) < 99.0
        ).zonal(by="zones")
        b = (E.band(1) + E.band(2) * 0.25).mask_where(
            E.band(3) < 99.0
        ).zonal(by="zones")
        assert a is not b and a == b
        engine.map(a, raster, tile=(32, 32))
        before = dispatch.cache_view("expr_programs")
        n0 = dispatch.backend_compiles()
        got_b = engine.map(b, raster, tile=(32, 32))
        after = dispatch.cache_view("expr_programs")
        assert after["misses"] == before["misses"]  # no new program
        assert after["hits"] > before["hits"]
        assert dispatch.backend_compiles() == n0
        _assert_result_equal(
            got_b, engine.map(a, raster, tile=(32, 32))
        )

    def test_post_freeze_cold_compile_tripwire(self, engine, raster):
        """freeze() arms the tripwire: a NOVEL tree after it increments
        cold_compiles and emits an ``expr_compile`` event."""
        sigs = expr_compile.signatures()
        frozen = expr_compile._frozen
        try:
            engine.warmup_expr(_pipeline(), raster, tile=(32, 32))
            expr_compile.freeze()
            cold0 = expr_compile.cold_compiles()
            # warm tree: no trip
            engine.map(_pipeline(), raster, tile=(32, 32))
            assert expr_compile.cold_compiles() == cold0
            novel = (E.band(1) * 7.75 - E.band(3)).zonal(by="zones")
            with telemetry.capture() as ev:
                engine.map(novel, raster, tile=(32, 32))
            assert expr_compile.cold_compiles() == cold0 + 1
            trips = [e for e in ev if e["event"] == "expr_compile"]
            assert len(trips) == 1 and trips[0]["after_freeze"]
        finally:
            expr_compile._frozen = frozen
            expr_compile._signatures.update(sigs)

    def test_first_build_opens_compile_span(self, engine, raster):
        """Satellite 2: the first execution of a signature sits under a
        ``dispatch.compile`` span (site=expr) that timeline attribution
        classifies as *compile*, with a backend_compiles delta."""
        from mosaic_tpu.obs import timeline

        novel = (E.band(2) / (E.band(1) + 123.25)).zonal(by="zones")
        with telemetry.capture() as ev:
            engine.map(novel, raster, tile=(32, 32))
        comp = [
            e for e in ev
            if e["event"] == "span" and e["name"] == "dispatch.compile"
            and e.get("site") == "expr"
        ]
        assert len(comp) == 1
        assert comp[0]["backend_compiles"] >= 1
        assert (
            timeline.classify_key("span.dispatch.compile") == "compile"
        )
        # warm repeat: no compile span at all
        with telemetry.capture() as ev2:
            engine.map(novel, raster, tile=(32, 32))
        assert not [
            e for e in ev2
            if e["event"] == "span" and e["name"] == "dispatch.compile"
        ]

    def test_fused_launches_once_a_tile_where_the_staged_ops_launch_twice(
        self, engine, raster
    ):
        """The fusion claim, counted: ``map`` dispatches every tile once;
        the op sequence it replaces (``rst_mapbands`` into a NaN-nodata
        raster, then ``zones`` over that raster) dispatches every tile
        twice — and both give the same bits."""
        value = E.ndvi(nir=2, red=1).mask_where(E.band(3) < 80.0)
        with telemetry.capture() as fused_ev:
            fused = engine.map(value.zonal(by="zones"), raster,
                               tile=(32, 32))
        with telemetry.capture() as staged_ev:
            px = rst_mapbands([raster], value, tile=(32, 32))[0]
            staged = engine.zones(px, tile=(32, 32))
        _assert_result_equal(fused, staged)

        def tiles(events, event, stage):
            return sum(
                int(e["ntiles"]) for e in events
                if e["event"] == event and e.get("stage") == stage
            )

        n = tiles(fused_ev, "expr_stage", "map")
        assert n == 9  # 75 x 90 at (32, 32)
        assert tiles(fused_ev, "raster_stage", "zonal") == 0
        assert tiles(staged_ev, "expr_stage", "pixels") == n
        assert tiles(staged_ev, "raster_stage", "zonal") == n

    def test_map_emits_expr_stage(self, engine, raster):
        with telemetry.capture() as ev:
            engine.map(_pipeline(), raster, tile=(32, 32))
        stages = [e for e in ev if e["event"] == "expr_stage"]
        assert len(stages) == 1
        st = stages[0]
        assert st["stage"] == "map" and st["mode"] == "zones"
        assert st["pixels"] > 0 and st["pixels_per_sec"] > 0


# -------------------------------------------------------- guarded path


class TestDegradation:
    def test_exhausted_tile_degrades_bit_identically(
        self, engine, raster
    ):
        e = _pipeline()
        clean = engine.map(e, raster, tile=(32, 32))
        with telemetry.capture() as ev:
            with faults.transient_errors(
                3, sites=("expr.map",)
            ):
                got = engine.map(
                    e, raster, tile=(32, 32), retry_policy=FAST
                )
        _assert_result_equal(got, clean)
        degr = [e2 for e2 in ev if e2["event"] == "degraded"]
        assert degr and degr[0]["label"] == "expr.map"

    def test_transient_faults_retry_to_clean(self, engine, raster):
        e = _pipeline()
        clean = engine.map(e, raster, tile=(32, 32))
        with telemetry.capture() as ev:
            with faults.transient_errors(2, sites=("expr.map",)):
                got = engine.map(
                    e, raster, tile=(32, 32), retry_policy=FAST
                )
        _assert_result_equal(got, clean)
        assert [
            e2["event"] for e2 in ev
        ].count("transient_retry") == 2


# ------------------------------------------------------- pixel frontends


class TestPixelFrontends:
    def test_rst_ndvi_matches_numpy(self, raster):
        out = rst_ndvi([raster])[0]
        assert out.num_bands == 1 and out.data.shape == (1, 75, 90)
        nir, red = raster.data[1], raster.data[0]
        want = (nir - red) / (nir + red)
        valid = np.isfinite(nir) & np.isfinite(red)
        np.testing.assert_array_equal(
            out.data[0][valid], want[valid]
        )
        assert np.isnan(out.data[0][~valid]).all()

    def test_rst_mapbands_mask_where(self, raster):
        e = E.band(1).mask_where(E.band(2) < 50.0)
        out = rst_mapbands([raster], e)[0].data[0]
        b1, b2 = raster.data[0], raster.data[1]
        keep = np.isfinite(b1) & np.isfinite(b2) & (b2 < 50.0)
        np.testing.assert_array_equal(out[keep], b1[keep])
        assert np.isnan(out[~keep]).all()

    def test_rst_mapbands_cell_of_needs_resolution(self, raster):
        with pytest.raises(ValueError, match="resolution"):
            rst_mapbands([raster], E.cell_of(), index=CUSTOM)

    def test_map_join_zones_raster(self, engine, raster):
        zones, vals, valid = engine.map(
            E.ndvi(nir=2, red=1).join(), raster, tile=(32, 32)
        )
        assert zones.shape == (75, 90) and vals.shape == (75, 90)
        assert (zones[~valid] == -1).all()
        assert set(np.unique(zones)) <= {-1, 0, 1, 2}


# ---------------------------------------------------------- durable scan


class TestExprScan:
    @pytest.fixture(scope="class")
    def stream(self, index):
        return RasterStream(index, CUSTOM, RES)

    def test_fused_scan_matches_map_and_oracle(
        self, stream, engine, raster, index
    ):
        e = _pipeline()
        fused = stream.scan(r := raster, expr=e, tile=(32, 32))
        _assert_result_equal(
            fused.stats, engine.map(e, r, tile=(32, 32))
        )
        _assert_result_equal(
            fused.stats,
            E.host_expr_zonal_oracle(
                r, e, index_system=CUSTOM, resolution=RES,
                chip_index=index, tile=(32, 32),
            ),
        )

    def test_kill_resume_and_expr_hash_refusals(
        self, stream, raster, tmp_path
    ):
        e = _pipeline()
        clean = stream.scan(raster, expr=e, tile=(32, 32))
        d = str(tmp_path / "fused")
        with faults.inject(
            fail_first=99, skip_first=4, sites=("raster.zonal",),
            exc_factory=lambda s: RuntimeError("simulated device loss"),
        ):
            with pytest.raises(RuntimeError, match="device loss"):
                stream.scan(
                    raster, expr=e, tile=(32, 32), run_dir=d,
                    snapshot_every=2, retry_policy=FAST,
                )
        assert checkpoint.list_snapshots(d) == [2, 4]
        # a durable expression scan snapshots the tree hash: resuming
        # with a different tree (or none) must refuse, not fold garbage
        with pytest.raises(ValueError, match="expression mismatch"):
            stream.resume(
                d, raster, expr=E.ndvi(nir=3, red=1).zonal(),
                retry_policy=FAST,
            )
        with pytest.raises(ValueError, match="expression mismatch"):
            stream.resume(d, raster, retry_policy=FAST)
        r = stream.resume(d, raster, expr=e, retry_policy=FAST)
        _assert_result_equal(r.stats, clean.stats)
        assert r.metrics["resumed_from"] == 4

    def test_plain_snapshot_refuses_expr_resume(
        self, stream, raster, tmp_path
    ):
        d = str(tmp_path / "plain")
        with faults.inject(
            fail_first=99, skip_first=2, sites=("raster.zonal",),
            exc_factory=lambda s: RuntimeError("boom"),
        ):
            with pytest.raises(RuntimeError):
                stream.scan(
                    raster, tile=(32, 32), run_dir=d,
                    snapshot_every=2, retry_policy=FAST,
                )
        with pytest.raises(ValueError, match="expression mismatch"):
            stream.resume(
                d, raster, expr=_pipeline(), retry_policy=FAST
            )

    def test_scan_rejects_non_zonal_terminals(self, stream, raster):
        with pytest.raises(ValueError, match="zones"):
            stream.scan(
                raster, expr=E.ndvi().zonal(by="grid"), tile=(32, 32)
            )
