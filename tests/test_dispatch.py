"""The unified dispatch core (ISSUE 11): one compile-cache/execution
path for batch, stream, serve, and raster, with a sharded lane.

Contracts under test:

1. **Sharded bit-identity.** Every frontend taking ``mesh=`` — batch
   `pip_join`, `StreamJoin`, `ServeEngine`, `ZonalEngine`/`RasterStream`
   — returns EXACTLY the single-device bits at mesh size 1, 2, 4, and 8
   (the conftest forces 8 virtual CPU devices), and matches the f64
   host oracle. Per-point results depend only on the point and the
   replicated index, so this is structural, not approximate.
2. **Compile discipline.** After `warmup()` there is at most one
   compile per `(bucket, index, mesh)` signature — co-batched serve
   traffic and batch `pip_join(mesh=...)` calls replay the same
   process-wide executables (zero cold compiles, zero new XLA backend
   compiles where the meter exists).
3. **One observability surface.** `dispatch.cache_stats()` /
   `clear_caches()` cover every registered program cache and emit
   telemetry; the legacy per-frontend views serve from the registry.
4. **Ring donation.** `StreamJoin(donate_ring=True)` warms the donating
   executable on scratch (the caller's ring survives `compile()`) and
   reports whether the backend applied the donation.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from mosaic_tpu.core.geometry import wkt
from mosaic_tpu.core.index import CustomIndexSystem, GridConf
from mosaic_tpu.core.tessellate import tessellate
from mosaic_tpu.dispatch import core as dispatch
from mosaic_tpu.dispatch.bucket import BucketLadder, backend_compiles
from mosaic_tpu.raster import Raster
from mosaic_tpu.raster.zonal import ZonalEngine, host_zonal_zones_oracle
from mosaic_tpu.runtime import telemetry
from mosaic_tpu.serve import ServeEngine
from mosaic_tpu.sql import RasterStream
from mosaic_tpu.sql.join import build_chip_index, host_join, pip_join
from mosaic_tpu.sql.stream import StreamJoin, ring_from_host

CUSTOM = CustomIndexSystem(GridConf(-180, 180, -90, 90, 2, 10.0, 10.0))
RES = 3
BBOX = (-25.0, -25.0, 35.0, 20.0)
ZONES = [
    "POLYGON ((1 1, 13 2, 12 11, 6 14, 2 9, 1 1), "
    "(5 5, 5 8, 8 8, 8 5, 5 5))",
    "POLYGON ((20 0, 30 0, 30 10, 25 4, 20 10, 20 0))",
    "MULTIPOLYGON (((-20 -20, -12 -20, -12 -12, -20 -12, -20 -20)), "
    "((-8 -8, -2 -8, -2 -2, -8 -2, -8 -8)))",
]
MESHES = (1, 2, 4, 8)


@pytest.fixture(scope="module")
def index():
    col = wkt.from_wkt(ZONES)
    return build_chip_index(
        tessellate(col, CUSTOM, RES, keep_core_geoms=False)
    )


@pytest.fixture(scope="module")
def points():
    rng = np.random.default_rng(7)
    return rng.uniform(BBOX[:2], BBOX[2:], (1024, 2))


# --------------------------------------------------- mesh normalization


class TestResolveMesh:
    def test_none_without_knob_is_single_device(self, monkeypatch):
        monkeypatch.delenv("MOSAIC_MESH", raising=False)
        assert dispatch.resolve_mesh(None) is None

    @pytest.mark.parametrize("raw,n", [("2", 2), ("dp4", 4), ("8", 8)])
    def test_env_knob(self, monkeypatch, raw, n):
        monkeypatch.setenv("MOSAIC_MESH", raw)
        assert dispatch.resolve_mesh(None).size == n

    @pytest.mark.parametrize("raw", ["", "0", "1"])
    def test_env_knob_degenerate_is_single_device(self, monkeypatch, raw):
        monkeypatch.setenv("MOSAIC_MESH", raw)
        assert dispatch.resolve_mesh(None) is None

    def test_env_knob_garbage_raises(self, monkeypatch):
        monkeypatch.setenv("MOSAIC_MESH", "lots")
        with pytest.raises(ValueError, match="MOSAIC_MESH"):
            dispatch.resolve_mesh(None)

    def test_int_and_mesh_passthrough(self):
        m = dispatch.resolve_mesh(4)
        assert m.size == 4 and m.axis_names == ("dp",)
        assert dispatch.resolve_mesh(m) is m
        assert dispatch.resolve_mesh(1) is None

    def test_too_many_devices_raises(self):
        with pytest.raises(ValueError, match="devices"):
            dispatch.data_mesh(99)


# ------------------------------------------- sharded ≡ single ≡ oracle


class TestShardedBitIdentity:
    @pytest.mark.parametrize("mesh", MESHES)
    def test_pip_join(self, index, points, mesh):
        single = pip_join(
            points, None, CUSTOM, RES, chip_index=index, recheck=False
        )
        oracle = host_join(points, index.host, CUSTOM, RES)
        np.testing.assert_array_equal(single, oracle)
        sharded = pip_join(
            points, None, CUSTOM, RES, chip_index=index,
            recheck=False, mesh=mesh,
        )
        np.testing.assert_array_equal(np.asarray(sharded), oracle)

    @pytest.mark.parametrize("mesh", MESHES)
    def test_dispatch_core_padded(self, index, points, mesh):
        """The core itself, no frontend around it: one padded batch
        through a ``dp``-sized mesh is the single-device core's answer
        and the f64 oracle's, under ONE signature a core."""
        ladder = BucketLadder(1024, 1024)
        oracle = host_join(points, index.host, CUSTOM, RES)

        def run(dp):
            core = dispatch.DispatchCore(
                index, CUSTOM, RES, ladder=ladder,
                mesh=None if dp == 1 else dp,
            )
            padded, n = core.ladder.pad(points)
            out = np.asarray(core.execute_padded(padded))[:n]
            assert len(core.signatures) == 1
            return out

        single = run(1)
        np.testing.assert_array_equal(single, oracle)
        np.testing.assert_array_equal(run(mesh), single)

    def test_pip_join_mesh_rejects_recheck(self, index, points):
        with pytest.raises(ValueError, match="recheck"):
            pip_join(
                points, None, CUSTOM, RES, chip_index=index,
                recheck=True, mesh=2,
            )

    @pytest.mark.parametrize("mesh", MESHES)
    def test_stream_join(self, index, mesh):
        rng = np.random.default_rng(3)
        batches = [
            rng.uniform((-25, -25), (35, 20), (1024, 2)) for _ in range(2)
        ]
        ring = ring_from_host(batches)
        base = StreamJoin(index, CUSTOM, RES).run(ring, 3, collect=True)
        got = StreamJoin(index, CUSTOM, RES, mesh=mesh).run(
            ring, 3, collect=True
        )
        assert (got.checksum, got.matches, got.overflow) == (
            base.checksum, base.matches, base.overflow
        )
        np.testing.assert_array_equal(
            np.asarray(got.outs), np.asarray(base.outs)
        )
        # every scanned batch also matches the f64 host oracle (batches
        # 2.. re-visit ring rows 0..)
        for i in range(3):
            np.testing.assert_array_equal(
                np.asarray(got.outs)[i],
                host_join(batches[i % 2], index.host, CUSTOM, RES),
            )

    def test_stream_join_batch_must_divide(self, index):
        sj = StreamJoin(index, CUSTOM, RES, mesh=8)
        with pytest.raises(ValueError, match="divide"):
            sj.step(jnp.zeros((100, 2)))

    @pytest.mark.parametrize("mesh", MESHES)
    def test_serve_engine(self, index, mesh):
        rng = np.random.default_rng(11)
        reqs = [
            rng.uniform(BBOX[:2], BBOX[2:], (n, 2))
            for n in (17, 64, 130, 1000)
        ]
        want = [host_join(p, index.host, CUSTOM, RES) for p in reqs]
        with ServeEngine(
            index, CUSTOM, RES, ladder=BucketLadder(64, 1024),
            bounds=BBOX, max_wait_s=0.0, mesh=mesh,
        ) as eng:
            for p, w in zip(reqs, want):
                np.testing.assert_array_equal(
                    np.asarray(eng.join(p, deadline_s=60.0)), w
                )

    @pytest.mark.parametrize("mesh", MESHES)
    def test_zonal_zones(self, index, mesh):
        r = _mk_raster()
        base = ZonalEngine(CUSTOM, RES, chip_index=index).zones(
            r, tile=(32, 32)
        )
        got = ZonalEngine(CUSTOM, RES, chip_index=index, mesh=mesh).zones(
            r, tile=(32, 32)
        )
        want = host_zonal_zones_oracle(r, index, CUSTOM, RES, tile=(32, 32))
        for a in ("keys", "count", "sum", "min", "max"):
            np.testing.assert_array_equal(getattr(got, a), getattr(base, a))
            np.testing.assert_array_equal(getattr(got, a), getattr(want, a))

    def test_raster_stream_scan(self, index):
        r = _mk_raster()
        base = RasterStream(index, CUSTOM, RES).scan(r, tile=(32, 32))
        got = RasterStream(index, CUSTOM, RES, mesh=4).scan(r, tile=(32, 32))
        for a in ("keys", "count", "sum", "min", "max"):
            np.testing.assert_array_equal(
                getattr(got.stats, a), getattr(base.stats, a)
            )


def _mk_raster(h=75, w=90, seed=5):
    rng = np.random.default_rng(seed)
    data = rng.uniform(0, 100, (1, h, w))
    data[0][rng.random((h, w)) < 0.1] = -9.0
    return Raster(
        data=data, gt=(-0.5, 1.0, 0.0, 15.5, 0.0, -1.0), srid=0,
        nodata=-9.0,
    )


# ------------------------------------------------- compile discipline


class TestCompileDiscipline:
    def test_warmup_one_compile_per_signature_across_frontends(self, index):
        """After warmup, serve dispatches AND batch pip_join(mesh=...)
        calls introduce zero new signatures and zero new XLA backend
        compiles — the executables are process-shared, keyed on
        (bucket, index, mesh)."""
        ladder = BucketLadder(64, 512)
        with ServeEngine(
            index, CUSTOM, RES, ladder=ladder, bounds=BBOX,
            max_wait_s=0.0, mesh=2,
        ) as eng:
            report = eng.warmup()
            assert report["signatures"] == len(ladder.buckets)
            assert len(eng.core.signatures) == len(ladder.buckets)
            t0 = backend_compiles()
            rng = np.random.default_rng(0)
            for n in (5, 64, 65, 200, 512, 30):
                eng.join(
                    rng.uniform(BBOX[:2], BBOX[2:], (n, 2)),
                    deadline_s=60.0,
                )
            # the batch frontend rides the same compiled programs
            pip_join(
                rng.uniform(BBOX[:2], BBOX[2:], (300, 2)), None, CUSTOM,
                RES, chip_index=index, recheck=False, mesh=2,
            )
            t1 = backend_compiles()
            assert eng.core.cold_compiles == 0
            assert len(eng.core.signatures) == len(ladder.buckets)
            if t0 is not None and t1 is not None:
                assert t1 - t0 == 0, "post-warmup dispatches recompiled"

    def test_warmup_emits_spans_and_stage_timings(self, index):
        core = dispatch.DispatchCore(
            index, CUSTOM, RES, ladder=BucketLadder(64, 128)
        )
        with telemetry.capture() as events:
            report = core.warmup()
        assert report["buckets"] == 2 and core.warmed
        stages = [
            e for e in events
            if e.get("event") == "dispatch_stage"
            and e.get("stage") == "warmup"
        ]
        assert [e["bucket"] for e in stages] == [64, 128]
        assert all(e["seconds"] >= 0 for e in stages)
        assert any(e.get("event") == "dispatch_warmup" for e in events)
        spans = [
            e for e in events
            if e.get("event") == "span" and e.get("name") == "dispatch.warmup"
        ]
        assert len(spans) == 1

    def test_post_freeze_compile_emits_event(self, index):
        core = dispatch.DispatchCore(
            index, CUSTOM, RES, ladder=BucketLadder(64, 128)
        )
        core.freeze()  # arm the tripwire without warming
        with telemetry.capture() as events:
            core.execute(np.zeros((10, 2)))
        assert core.cold_compiles == 1
        assert any(e.get("event") == "dispatch_compile" for e in events)

    def test_mesh_must_divide_min_bucket(self, index):
        with pytest.raises(ValueError, match="divide"):
            dispatch.DispatchCore(
                index, CUSTOM, RES, ladder=BucketLadder(4, 64), mesh=8
            )


# ---------------------------------------------- cache observability


class TestCacheRegistry:
    def test_cache_stats_covers_every_registered_cache(self, index):
        # the distributed caches register at module import; force it so
        # the registry names are present regardless of test ordering
        import mosaic_tpu.parallel.dist_join  # noqa: F401
        import mosaic_tpu.parallel.dist_knn  # noqa: F401

        # touch a program cache so the registry has something to report
        pip_join(
            np.zeros((8, 2)), None, CUSTOM, RES, chip_index=index,
            recheck=False,
        )
        with telemetry.capture() as events:
            stats = dispatch.cache_stats()
        assert any(
            e.get("event") == "dispatch_cache_stats" for e in events
        )
        for name in (
            "jit_join", "cells_prog", "stream_programs", "sharded_join",
            "dist_join_step", "knn_sharded_distance",
        ):
            assert set(stats[name]) == {
                "hits", "misses", "maxsize", "currsize"
            }, name
        # batch_cores carries eviction-policy extras on top of the base
        assert set(stats["batch_cores"]) == {
            "hits", "misses", "maxsize", "currsize",
            "evictions", "occupancy",
        }
        assert set(stats["jit_programs"]) == {"join", "counts", "compact"}

    def test_clear_caches_is_selective_and_emits(self, index):
        StreamJoin(index, CUSTOM, RES)  # populate stream_programs
        assert dispatch.cache_view("stream_programs")["currsize"] > 0
        before = dispatch.cache_view("cells_prog")["currsize"]
        assert before > 0
        with telemetry.capture() as events:
            pre = dispatch.clear_caches(names=("stream_programs",))
        assert any(
            e.get("event") == "dispatch_caches_cleared" for e in events
        )
        assert pre["stream_programs"]["currsize"] > 0  # pre-clear view
        assert dispatch.cache_view("stream_programs")["currsize"] == 0
        # unnamed caches survive a selective clear
        assert dispatch.cache_view("cells_prog")["currsize"] == before

    def test_unbounded_cache_rejected(self):
        with pytest.raises(ValueError, match="bounded"):
            dispatch.bounded_cache("nope", None)

    def test_legacy_views_serve_from_registry(self, index):
        from mosaic_tpu.parallel.dist_knn import knn_cache_stats
        from mosaic_tpu.sql.join import join_cache_stats

        legacy = join_cache_stats(emit=False)
        assert legacy["cells_prog"] == dispatch.cache_view("cells_prog")
        knn = knn_cache_stats(emit=False)
        assert knn["sharded_distance"] == dispatch.cache_view(
            "knn_sharded_distance"
        )

    def test_stream_program_bundle_is_shared(self, index):
        a = StreamJoin(index, CUSTOM, RES, prefetch=True)
        b = StreamJoin(index, CUSTOM, RES, prefetch=True)
        assert a._loop is b._loop  # one compiled scan, not one per join


# --------------------------------------------------------- donation


class TestRingDonation:
    def test_compile_preserves_ring_and_run_reports(self, index):
        rng = np.random.default_rng(9)
        ring = ring_from_host(
            [rng.uniform((-25, -25), (35, 20), (512, 2)) for _ in range(2)]
        )
        base = StreamJoin(index, CUSTOM, RES).run(ring, 3)
        sj = StreamJoin(index, CUSTOM, RES, donate_ring=True)
        sj.compile(ring, 3)
        assert not ring.is_deleted()  # warmed on scratch, not our ring
        res = sj.run(jnp.array(ring, copy=True), 3)
        assert (res.checksum, res.matches, res.overflow) == (
            base.checksum, base.matches, base.overflow
        )
        assert res.metrics["donate_ring"] is True
        assert isinstance(res.metrics["ring_donated"], bool)
        assert res.metrics["ring_bytes"] == int(ring.nbytes)
