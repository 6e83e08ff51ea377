"""Self-tuning workload optimizer (`mosaic_tpu/tune/`): the contracts.

1. **Knob precedence** — explicit arg > env knob > TuningProfile >
   built-in default, per knob at the resolver and per frontend at the
   entry point: every profile-consumed knob of all five ``profile=``
   frontends (`pip_join`, `StreamJoin`, `ServeEngine`, `ZonalEngine`,
   `RasterStream`) is asserted through the ``tune_resolve`` telemetry
   event its host entry records.
2. **Profile store refusal matrix** — corrupt versions skip
   newest-valid-wins with telemetry; all-corrupt/empty raises the typed
   `ProfileStoreCorrupt`; a tessellation-fingerprint mismatch on the
   newest valid version is a typed REFUSAL (never a silent fallback to
   an older matching version).
3. **Hot swap** — `ServeEngine.hot_swap` to a different-resolution
   recommended index introduces ZERO cold compiles and keeps answers
   equal to the device-path reference join.
4. Profiler statistics are sane and round-trip; recommendations are
   measurement-backed with machine-checkable rationales.
5. Satellites: `SampleStrategy` typed empty-input errors;
   `overlay.candidate_pairs` candidate-statistics telemetry.
"""

import json
import os

import numpy as np
import pytest

from mosaic_tpu.core.geometry import wkt
from mosaic_tpu.core.index import CustomIndexSystem, GridConf
from mosaic_tpu.core.tessellate import tessellate
from mosaic_tpu.dispatch import DispatchCore
from mosaic_tpu.raster import Raster
from mosaic_tpu.raster.zonal import ZonalEngine
from mosaic_tpu.runtime import telemetry
from mosaic_tpu.serve import BucketLadder, ServeEngine
from mosaic_tpu.sql.analyzer import SampleStrategy
from mosaic_tpu.sql.join import build_chip_index, pip_join, pip_join_points
from mosaic_tpu.sql.overlay import candidate_pairs
from mosaic_tpu.sql.raster_stream import RasterStream
from mosaic_tpu.sql.stream import StreamJoin, ring_from_host
from mosaic_tpu.tune import (
    KNOBS,
    ProfileFingerprintMismatch,
    ProfileStore,
    ProfileStoreCorrupt,
    TuningProfile,
    WorkloadProfile,
    index_fingerprint,
    profile_overlay,
    profile_points,
    profile_polygons,
    profile_raster,
    recommend,
    resolve_knob,
)

CUSTOM = CustomIndexSystem(GridConf(-180, 180, -90, 90, 2, 10.0, 10.0))
RES = 3
ZONES = [
    "POLYGON ((1 1, 13 2, 12 11, 6 14, 2 9, 1 1), "
    "(5 5, 5 8, 8 8, 8 5, 5 5))",
    "POLYGON ((20 0, 30 0, 30 10, 25 4, 20 10, 20 0))",
    "POLYGON ((-20 -20, -5 -20, -5 -5, -20 -5, -20 -20))",
]
BBOX = (-25.0, -25.0, 35.0, 20.0)

ALL_TUNE_ENV = (
    "MOSAIC_TUNE_PROBE", "MOSAIC_TUNE_WRITEBACK",
    "MOSAIC_TUNE_BATCH", "MOSAIC_TUNE_BUCKET_MIN", "MOSAIC_TUNE_BUCKET_MAX",
    "MOSAIC_STREAM_WINDOW", "MOSAIC_STREAM_PIPELINE",
    "MOSAIC_RASTER_TILE", "MOSAIC_RASTER_LANE",
)


@pytest.fixture(autouse=True)
def _clean_env(monkeypatch):
    for name in ALL_TUNE_ENV:
        monkeypatch.delenv(name, raising=False)
    yield


@pytest.fixture(scope="module")
def zones():
    return wkt.from_wkt(ZONES)


@pytest.fixture(scope="module")
def index(zones):
    return build_chip_index(
        tessellate(zones, CUSTOM, RES, keep_core_geoms=False)
    )


@pytest.fixture(scope="module")
def points():
    rng = np.random.default_rng(11)
    return rng.uniform(BBOX[:2], BBOX[2:], (2048, 2))


def _mk_raster(h=64, w=64, nodata=-9.0, seed=5):
    rng = np.random.default_rng(seed)
    data = rng.uniform(0, 100, (1, h, w))
    data[0][rng.random((h, w)) < 0.5] = nodata
    return Raster(
        data=data, gt=(-0.5, 1.0, 0.0, 15.5, 0.0, -1.0),
        srid=0, nodata=nodata,
    )


def _small_ring():
    rng = np.random.default_rng(3)
    return ring_from_host(
        [rng.uniform(BBOX[:2], BBOX[2:], (512, 2)) for _ in range(2)]
    )


def resolve_events(events, entry):
    return [
        e for e in events
        if e.get("event") == "tune_resolve" and e.get("entry") == entry
    ]


# --------------------------------------------------------------- resolver


class TestResolveKnob:
    def test_explicit_beats_everything(self, monkeypatch):
        monkeypatch.setenv("MOSAIC_TUNE_PROBE", "adaptive")
        prof = TuningProfile(probe="mxu")
        assert resolve_knob("probe", "scatter", prof, "x") == (
            "scatter", "explicit"
        )

    def test_env_beats_profile(self, monkeypatch):
        monkeypatch.setenv("MOSAIC_TUNE_PROBE", "adaptive")
        prof = TuningProfile(probe="scatter")
        assert resolve_knob("probe", None, prof, "x") == ("adaptive", "env")

    def test_profile_beats_default(self):
        prof = TuningProfile(probe="adaptive")
        assert resolve_knob("probe", None, prof, "scatter") == (
            "adaptive", "profile"
        )

    def test_default_when_nothing_set(self):
        assert resolve_knob("probe", None, None, "scatter") == (
            "scatter", "default"
        )

    def test_empty_env_is_unset(self, monkeypatch):
        monkeypatch.setenv("MOSAIC_TUNE_PROBE", "")
        assert resolve_knob("probe", None, None, "d") == ("d", "default")

    def test_env_parsers(self, monkeypatch):
        monkeypatch.setenv("MOSAIC_TUNE_BATCH", "4096")
        assert resolve_knob("batch_size", None, None, None) == (4096, "env")
        monkeypatch.setenv("MOSAIC_RASTER_TILE", "64x128")
        assert resolve_knob("raster_tile", None, None, None) == (
            (64, 128), "env"
        )
        # "0" must WIN with value False (force-off), not fall through
        monkeypatch.setenv("MOSAIC_STREAM_PIPELINE", "0")
        prof = TuningProfile(stream_pipeline=True)
        assert resolve_knob("stream_pipeline", None, prof, None) == (
            False, "env"
        )

    def test_malformed_env_raises(self, monkeypatch):
        monkeypatch.setenv("MOSAIC_TUNE_BATCH", "many")
        with pytest.raises(ValueError, match="malformed env value"):
            resolve_knob("batch_size", None, None, None)

    def test_resolution_has_no_env_layer(self, monkeypatch):
        # resolution changes the tessellation artifact, not the schedule:
        # no env spelling exists, so even a lookalike var is inert
        monkeypatch.setenv("MOSAIC_TUNE_RESOLUTION", "9")
        prof = TuningProfile(resolution=4)
        assert resolve_knob("resolution", None, prof, 3) == (4, "profile")

    def test_unknown_knob_rejected(self):
        with pytest.raises(KeyError, match="unknown tune knob"):
            resolve_knob("warp_factor", None, None, None)

    def test_every_knob_resolves_through_all_layers(self, monkeypatch):
        """The full matrix at the resolver: each knob accepts each layer."""
        profile_values = {
            "resolution": 5, "probe": "adaptive", "writeback": "sort",
            "batch_size": 2048, "bucket_min": 128, "bucket_max": 1024,
            "stream_window": 6, "stream_pipeline": True,
            "raster_tile": (64, 64), "zonal_lane": "tiled",
            "knn_lane": "voronoi",
        }
        env_values = {
            "probe": ("MOSAIC_TUNE_PROBE", "scatter", "scatter"),
            "writeback": ("MOSAIC_TUNE_WRITEBACK", "scatter", "scatter"),
            "batch_size": ("MOSAIC_TUNE_BATCH", "512", 512),
            "bucket_min": ("MOSAIC_TUNE_BUCKET_MIN", "64", 64),
            "bucket_max": ("MOSAIC_TUNE_BUCKET_MAX", "256", 256),
            "stream_window": ("MOSAIC_STREAM_WINDOW", "2", 2),
            "stream_pipeline": ("MOSAIC_STREAM_PIPELINE", "1", True),
            "raster_tile": ("MOSAIC_RASTER_TILE", "32x32", (32, 32)),
            "zonal_lane": ("MOSAIC_RASTER_LANE", "fold", "fold"),
            "knn_lane": ("MOSAIC_TUNE_KNN_LANE", "ring", "ring"),
        }
        assert set(KNOBS) == set(profile_values)
        prof = TuningProfile(**profile_values)
        for knob in KNOBS:
            # profile layer
            assert resolve_knob(knob, None, prof, "dflt") == (
                profile_values[knob], "profile"
            ), knob
            # default layer
            assert resolve_knob(knob, None, None, "dflt") == (
                "dflt", "default"
            ), knob
            # env layer (where one exists) beats profile
            if knob in env_values:
                var, raw, parsed = env_values[knob]
                monkeypatch.setenv(var, raw)
                assert resolve_knob(knob, None, prof, "dflt") == (
                    parsed, "env"
                ), knob
                monkeypatch.delenv(var)
            # explicit beats all
            assert resolve_knob(knob, "xx", prof, "dflt") == (
                "xx", "explicit"
            ), knob


# ------------------------------------------------- frontend entry points


class TestPipJoinPrecedence:
    PROFILE = TuningProfile(
        resolution=RES, probe="adaptive", writeback="scatter",
        batch_size=1024,
    )

    def run(self, points, index, **kw):
        with telemetry.capture() as events:
            out = pip_join(points, None, CUSTOM, kw.pop("resolution", None),
                           chip_index=index, **kw)
        (ev,) = resolve_events(events, "pip_join")
        return np.asarray(out), ev

    def test_profile_layer(self, points, index):
        out, ev = self.run(points, index, profile=self.PROFILE)
        for knob in ("resolution", "probe", "writeback", "batch_size"):
            assert ev[f"{knob}_source"] == "profile", (knob, ev)
        assert ev["probe"] == "adaptive" and ev["batch_size"] == 1024
        base, _ = self.run(points, index, resolution=RES)
        np.testing.assert_array_equal(out, base)

    def test_env_layer_beats_profile(self, points, index, monkeypatch):
        monkeypatch.setenv("MOSAIC_TUNE_PROBE", "scatter")
        monkeypatch.setenv("MOSAIC_TUNE_BATCH", "512")
        _, ev = self.run(points, index, profile=self.PROFILE)
        assert ev["probe_source"] == "env" and ev["probe"] == "scatter"
        assert ev["batch_size_source"] == "env" and ev["batch_size"] == 512
        # resolution has no env layer: still the profile's
        assert ev["resolution_source"] == "profile"

    def test_explicit_beats_env_and_profile(self, points, index, monkeypatch):
        monkeypatch.setenv("MOSAIC_TUNE_PROBE", "adaptive")
        _, ev = self.run(
            points, index, resolution=RES, probe="scatter",
            writeback="scatter", batch_size=256,
            profile=self.PROFILE,
        )
        for knob in ("resolution", "probe", "writeback", "batch_size"):
            assert ev[f"{knob}_source"] == "explicit", (knob, ev)

    def test_no_resolution_anywhere_is_typed(self, points, index):
        with pytest.raises(ValueError, match="resolution"):
            pip_join(points, None, CUSTOM, None, chip_index=index)


class TestStreamJoinPrecedence:
    def test_constructor_knobs(self, index, monkeypatch):
        prof = TuningProfile(probe="adaptive")
        with telemetry.capture() as events:
            StreamJoin(index, CUSTOM, RES, profile=prof)
        (ev,) = resolve_events(events, "stream_join")
        assert ev["probe_source"] == "profile"

        monkeypatch.setenv("MOSAIC_TUNE_PROBE", "scatter")
        with telemetry.capture() as events:
            StreamJoin(index, CUSTOM, RES, profile=prof)
        (ev,) = resolve_events(events, "stream_join")
        assert ev["probe_source"] == "env" and ev["probe"] == "scatter"

        with telemetry.capture() as events:
            StreamJoin(index, CUSTOM, RES, probe="scatter", profile=prof)
        (ev,) = resolve_events(events, "stream_join")
        assert ev["probe_source"] == "explicit"

    def test_durable_run_knobs(self, index, tmp_path, monkeypatch):
        """stream_window / stream_pipeline resolve per durable run."""
        ring = _small_ring()
        prof = TuningProfile(stream_window=2, stream_pipeline=True)
        sj = StreamJoin(index, CUSTOM, RES, profile=prof)

        with telemetry.capture() as events:
            sj.run_durable(ring, 2, run_dir=str(tmp_path / "a"))
        (ev,) = resolve_events(events, "stream_join.run_durable")
        assert ev["stream_pipeline_source"] == "profile"
        assert ev["stream_window_source"] == "profile"
        assert ev["stream_pipeline"] is True and ev["stream_window"] == 2

        monkeypatch.setenv("MOSAIC_STREAM_PIPELINE", "0")
        monkeypatch.setenv("MOSAIC_STREAM_WINDOW", "3")
        with telemetry.capture() as events:
            sj.run_durable(ring, 2, run_dir=str(tmp_path / "b"))
        (ev,) = resolve_events(events, "stream_join.run_durable")
        assert ev["stream_pipeline_source"] == "env"
        assert ev["stream_pipeline"] is False  # "0" forces OFF over profile
        assert ev["stream_window_source"] == "env"
        assert ev["stream_window"] == 3

        with telemetry.capture() as events:
            sj.run_durable(
                ring, 2, run_dir=str(tmp_path / "c"),
                pipeline=True, window=4,
            )
        (ev,) = resolve_events(events, "stream_join.run_durable")
        assert ev["stream_pipeline_source"] == "explicit"
        assert ev["stream_window_source"] == "explicit"
        assert ev["stream_window"] == 4


class TestServeEnginePrecedence:
    def test_profile_builds_ladder(self, index):
        prof = TuningProfile(
            probe="adaptive", writeback="scatter",
            bucket_min=64, bucket_max=256,
        )
        with telemetry.capture() as events:
            with ServeEngine(index, CUSTOM, RES, profile=prof) as eng:
                assert eng.ladder.buckets == (64, 128, 256)
        (ev,) = resolve_events(events, "serve_engine")
        for knob in ("probe", "writeback", "bucket_min", "bucket_max"):
            assert ev[f"{knob}_source"] == "profile", (knob, ev)

    def test_env_beats_profile(self, index, monkeypatch):
        monkeypatch.setenv("MOSAIC_TUNE_BUCKET_MIN", "128")
        monkeypatch.setenv("MOSAIC_TUNE_BUCKET_MAX", "512")
        monkeypatch.setenv("MOSAIC_TUNE_WRITEBACK", "scatter")
        prof = TuningProfile(bucket_min=64, bucket_max=256, writeback="sort")
        with telemetry.capture() as events:
            with ServeEngine(index, CUSTOM, RES, profile=prof) as eng:
                assert eng.ladder.buckets == (128, 256, 512)
        (ev,) = resolve_events(events, "serve_engine")
        assert ev["bucket_min_source"] == "env"
        assert ev["bucket_max_source"] == "env"
        assert ev["writeback_source"] == "env"

    def test_explicit_ladder_bypasses_bucket_knobs(self, index, monkeypatch):
        monkeypatch.setenv("MOSAIC_TUNE_BUCKET_MIN", "128")
        prof = TuningProfile(bucket_min=64, bucket_max=256)
        with ServeEngine(
            index, CUSTOM, RES, ladder=BucketLadder(32, 64), profile=prof
        ) as eng:
            assert eng.ladder.buckets == (32, 64)

    def test_explicit_probe_beats_all(self, index, monkeypatch):
        monkeypatch.setenv("MOSAIC_TUNE_PROBE", "adaptive")
        prof = TuningProfile(probe="adaptive")
        with telemetry.capture() as events:
            with ServeEngine(
                index, CUSTOM, RES, probe="scatter", profile=prof
            ):
                pass
        (ev,) = resolve_events(events, "serve_engine")
        assert ev["probe_source"] == "explicit" and ev["probe"] == "scatter"


class TestZonalEnginePrecedence:
    def test_all_layers(self, index, monkeypatch):
        prof = TuningProfile(probe="adaptive", zonal_lane="tiled")
        with telemetry.capture() as events:
            eng = ZonalEngine(CUSTOM, RES, chip_index=index, profile=prof)
        (ev,) = resolve_events(events, "zonal_engine")
        for knob in ("probe", "zonal_lane"):
            assert ev[f"{knob}_source"] == "profile", (knob, ev)
        assert eng.lane == "tiled"

        monkeypatch.setenv("MOSAIC_RASTER_LANE", "fold")
        with telemetry.capture() as events:
            eng = ZonalEngine(CUSTOM, RES, chip_index=index, profile=prof)
        (ev,) = resolve_events(events, "zonal_engine")
        assert ev["zonal_lane_source"] == "env" and eng.lane == "fold"

        with telemetry.capture() as events:
            eng = ZonalEngine(
                CUSTOM, RES, chip_index=index, lane="tiled",
                probe="scatter", profile=prof,
            )
        (ev,) = resolve_events(events, "zonal_engine")
        assert ev["zonal_lane_source"] == "explicit" and eng.lane == "tiled"
        assert ev["probe_source"] == "explicit"


class TestRasterStreamPrecedence:
    def test_constructor_knobs(self, index, monkeypatch):
        prof = TuningProfile(probe="scatter")
        with telemetry.capture() as events:
            RasterStream(index, CUSTOM, RES, profile=prof)
        (ev,) = resolve_events(events, "raster_stream")
        assert ev["probe_source"] == "profile"

        monkeypatch.setenv("MOSAIC_TUNE_PROBE", "adaptive")
        with telemetry.capture() as events:
            RasterStream(index, CUSTOM, RES, probe="scatter", profile=prof)
        (ev,) = resolve_events(events, "raster_stream")
        assert ev["probe_source"] == "explicit"

    def test_scan_knobs(self, index, monkeypatch):
        raster = _mk_raster()
        prof = TuningProfile(raster_tile=(32, 32), stream_window=2)
        rs = RasterStream(index, CUSTOM, RES, profile=prof)

        with telemetry.capture() as events:
            out_prof = rs.scan(raster)
        (ev,) = resolve_events(events, "raster_stream.scan")
        assert ev["raster_tile_source"] == "profile"
        assert ev["stream_window_source"] == "profile"

        monkeypatch.setenv("MOSAIC_RASTER_TILE", "16x16")
        with telemetry.capture() as events:
            rs.scan(raster)
        (ev,) = resolve_events(events, "raster_stream.scan")
        assert ev["raster_tile_source"] == "env"

        with telemetry.capture() as events:
            out_expl = rs.scan(raster, tile=(32, 32))
        (ev,) = resolve_events(events, "raster_stream.scan")
        assert ev["raster_tile_source"] == "explicit"
        # the tile shape is a schedule knob: answers are tile-invariant
        np.testing.assert_array_equal(
            np.asarray(out_prof.stats.keys), np.asarray(out_expl.stats.keys)
        )


# ------------------- what the chip decided is no longer a knob (PR 29)

def _hot_swap(index, points, **kw):
    with ServeEngine(index, CUSTOM, RES) as eng:
        eng.hot_swap(**kw)


#: every entry point that took ``lookup=`` before PR 29
ENTRY_POINTS = {
    "pip_join": lambda index, points, **kw: pip_join(
        points, None, CUSTOM, RES, chip_index=index, **kw
    ),
    "pip_join_points": lambda index, points, **kw: pip_join_points(
        points, np.zeros(len(points), np.int64), index, **kw
    ),
    "StreamJoin": lambda index, points, **kw: StreamJoin(
        index, CUSTOM, RES, **kw
    ),
    "DispatchCore": lambda index, points, **kw: DispatchCore(
        index, CUSTOM, RES, **kw
    ),
    "ServeEngine": lambda index, points, **kw: ServeEngine(
        index, CUSTOM, RES, **kw
    ),
    "ServeEngine.hot_swap": _hot_swap,
    "ZonalEngine": lambda index, points, **kw: ZonalEngine(
        CUSTOM, RES, chip_index=index, **kw
    ),
    "RasterStream": lambda index, points, **kw: RasterStream(
        index, CUSTOM, RES, **kw
    ),
}


@pytest.mark.parametrize("entry", sorted(ENTRY_POINTS))
def test_entry_point_takes_no_lookup(entry, index, points):
    """How tier 1 fetches a row is a fact of `pip_join_points`, not an
    argument of its callers: every entry point that once took ``lookup=``
    answers Python's TypeError — none swallows it in a ``**kwargs``."""
    with pytest.raises(TypeError, match="unexpected keyword.*lookup"):
        ENTRY_POINTS[entry](index, points, lookup="mxu")


def test_stream_join_names_the_one_fetch_and_compaction(index):
    """The benchmark's ``stream_ready`` line prints both fields; a cap
    says nothing about the found rate and picks no other lane."""
    for cap in (None, 64):
        sj = StreamJoin(index, CUSTOM, RES, found_cap=cap)
        assert (sj.lookup, sj.compaction) == ("gather", "scatter"), cap


def test_profile_stored_with_a_lookup_still_loads(index, tmp_path, monkeypatch):
    """A profile written before PR 29 carries ``"lookup": "gather"``: it
    passes the store's checksum, loads, and resolves as if the key were
    absent; a set MOSAIC_TUNE_LOOKUP is not read."""
    from mosaic_tpu.tune.store import _body_sha256

    store = ProfileStore(str(tmp_path))
    path = store.save(TuningProfile(probe="adaptive"))
    payload = json.loads(open(path).read())
    payload["profile"]["lookup"] = "gather"
    payload["sha256"] = _body_sha256(payload)
    with open(path, "w") as f:
        json.dump(payload, f)
    prof, _ = store.load_latest()
    assert prof == TuningProfile(probe="adaptive")
    monkeypatch.setenv("MOSAIC_TUNE_LOOKUP", "mxu")
    with telemetry.capture() as events:
        StreamJoin(index, CUSTOM, RES, profile=prof)
    (ev,) = resolve_events(events, "stream_join")
    assert ev["probe_source"] == "profile" and ev["probe"] == "adaptive"
    assert not [k for k in ev if "lookup" in k]


# ---------------------------------------------------------- profile store


class TestProfileStore:
    PROF = TuningProfile(resolution=5, probe="adaptive", batch_size=2048)

    def test_roundtrip(self, tmp_path):
        store = ProfileStore(str(tmp_path))
        store.save(self.PROF, fingerprint="abc123")
        store.save(TuningProfile(resolution=6), fingerprint="abc123")
        assert store.versions() == [1, 2]
        prof, payload = store.load_latest()
        assert prof.resolution == 6
        assert payload["profile_version"] == 2
        assert payload["fingerprint"] == "abc123"

    def test_empty_store_is_typed(self, tmp_path):
        with pytest.raises(ProfileStoreCorrupt, match="no tuning profile"):
            ProfileStore(str(tmp_path / "nope")).load_latest()

    def test_corrupt_newest_skips_to_older_valid(self, tmp_path):
        store = ProfileStore(str(tmp_path))
        store.save(self.PROF)
        p2 = store.save(TuningProfile(resolution=9))
        with open(p2, "w") as f:
            f.write("{ not json")
        with telemetry.capture() as events:
            prof, payload = store.load_latest()
        assert prof.resolution == 5 and payload["profile_version"] == 1
        skipped = [
            e for e in events
            if e.get("event") == "tune_profile_corrupt_skipped"
        ]
        assert len(skipped) == 1 and skipped[0]["profile_version"] == 2

    def test_checksum_tamper_is_corrupt(self, tmp_path):
        store = ProfileStore(str(tmp_path))
        path = store.save(self.PROF)
        payload = json.loads(open(path).read())
        payload["profile"]["batch_size"] = 4  # tamper without re-hashing
        with open(path, "w") as f:
            json.dump(payload, f)
        with pytest.raises(ProfileStoreCorrupt, match="failed validation"):
            store.load_latest()

    def test_unknown_format_version_is_corrupt(self, tmp_path):
        store = ProfileStore(str(tmp_path))
        path = store.save(self.PROF)
        payload = json.loads(open(path).read())
        payload["version"] = 99
        with open(path, "w") as f:
            json.dump(payload, f)
        with pytest.raises(ProfileStoreCorrupt):
            store.load_latest()

    def test_fingerprint_mismatch_is_refusal_not_fallback(self, tmp_path):
        """An older version DOES match the expected fingerprint — the
        store must still refuse: versions are one index's history, not a
        candidate pool."""
        store = ProfileStore(str(tmp_path))
        store.save(self.PROF, fingerprint="good")
        store.save(TuningProfile(resolution=9), fingerprint="stale")
        with pytest.raises(
            ProfileFingerprintMismatch, match="re-profile"
        ):
            store.load_latest(expect_fingerprint="good")

    def test_fingerprint_match_loads(self, tmp_path, index):
        store = ProfileStore(str(tmp_path))
        fp = index_fingerprint(index)
        store.save(self.PROF, fingerprint=fp)
        prof, payload = store.load_latest(expect_fingerprint=fp)
        assert prof.resolution == 5 and payload["fingerprint"] == fp

    def test_orphan_tmp_never_shadows(self, tmp_path):
        store = ProfileStore(str(tmp_path))
        store.save(self.PROF)
        # a kill mid-write leaves only the temp name behind
        open(os.path.join(str(tmp_path), "profile-v0002.json.tmp"),
             "w").close()
        assert store.versions() == [1]
        prof, _ = store.load_latest()
        assert prof.resolution == 5


# -------------------------------------------------------------- hot swap


class TestHotSwap:
    def test_swap_changes_resolution_without_cold_compiles(
        self, zones, index, points
    ):
        fine = build_chip_index(
            tessellate(zones, CUSTOM, RES + 1, keep_core_geoms=False)
        )
        prof = TuningProfile(
            resolution=RES + 1, probe="scatter",
            bucket_min=64, bucket_max=512,
        )
        q = points[:400]
        with ServeEngine(
            index, CUSTOM, RES, ladder=BucketLadder(64, 512),
            max_wait_s=0.001,
        ) as eng:
            eng.warmup()
            eng.join(q, timeout=30.0)  # traffic on the old core
            with telemetry.capture() as events:
                stats = eng.hot_swap(fine, profile=prof)
            assert stats["buckets"] == len(eng.ladder.buckets)
            assert eng.resolution == RES + 1
            assert [
                e for e in events if e.get("event") == "serve_swap"
            ], "hot_swap must record a serve_swap event"
            post = np.asarray(eng.join(q, timeout=30.0))
            assert eng.metrics()["cold_compiles"] == 0
        want = np.asarray(pip_join(
            q, None, CUSTOM, RES + 1, chip_index=fine, recheck=False,
            probe="scatter",
        ))
        np.testing.assert_array_equal(post.astype(np.int64),
                                      want.astype(np.int64))

    def test_profileless_swap_keeps_tuning(self, index):
        with ServeEngine(
            index, CUSTOM, RES, ladder=BucketLadder(64, 256),
            probe="scatter",
        ) as eng:
            eng.warmup()
            eng.hot_swap(index)
            assert eng.resolution == RES
            assert eng.probe == "scatter"
            assert eng.ladder.buckets == (64, 128, 256)
            assert eng.metrics()["cold_compiles"] == 0


# ------------------------------------------------- profiler + recommend


class TestProfiler:
    def test_points_profile_sane(self, index, points):
        with telemetry.capture() as events:
            prof = profile_points(points, index, CUSTOM, RES, sample=512)
        assert prof.kind == "points" and prof.n_sampled == 512
        assert 0.0 < prof.match_rate <= 1.0
        shares = prof.class_shares
        assert abs(sum(shares.values()) - 1.0) < 1e-9
        assert prof.chip_density["p50"] >= 1.0
        assert prof.band_fraction is not None
        assert 0.0 <= prof.band_fraction <= 1.0
        assert [e for e in events if e.get("event") == "tune_profile"]
        back = WorkloadProfile.from_dict(prof.as_dict())
        assert back == prof

    def test_polygons_profile_sane(self, zones):
        prof = profile_polygons(zones, CUSTOM)
        assert prof.kind == "polygons"
        assert isinstance(prof.optimal_resolution, int)
        assert prof.cells_per_geom["mean"] > 0

    def test_raster_profile_sane(self):
        prof = profile_raster(_mk_raster(), tile=(32, 32))
        assert prof.kind == "raster"
        assert 0.0 <= prof.tile_occupancy <= 1.0
        assert 0.4 < prof.nodata_fraction < 0.6  # 50% speckle by seed


class TestRecommend:
    def test_rationale_is_machine_checkable(self, zones, index, points):
        poly = recommend(profile_polygons(zones, CUSTOM))
        pts = recommend(profile_points(points, index, CUSTOM, RES))
        merged = TuningProfile.merged(poly, pts)
        assert merged.resolution == poly.resolution
        assert merged.probe == pts.probe
        assert merged.rationale and all(
            {"knob", "value", "rule", "evidence"} <= set(r)
            for r in merged.rationale
        )
        # every recommended knob has exactly its rationale entries
        recommended = {
            k for k, v in merged.as_dict().items()
            if k not in ("rationale", "source") and v is not None
        }
        assert {r["knob"] for r in merged.rationale} == recommended

    def test_dense_share_routes_adaptive(self):
        prof = WorkloadProfile(
            kind="points", n_sampled=4096,
            class_shares={"heavy": 0.3, "convex": 0.1, "light": 0.6},
        )
        rec = recommend(prof)
        assert rec.probe == "adaptive"
        (rule,) = [r for r in rec.rationale if r["knob"] == "probe"]
        assert rule["rule"] == "dense-share-router"

    def test_light_share_routes_scatter(self):
        prof = WorkloadProfile(
            kind="points", n_sampled=4096,
            class_shares={"heavy": 0.05, "convex": 0.05, "light": 0.9},
        )
        assert recommend(prof).probe == "scatter"

    def test_band_fraction_pins_fold_lane(self):
        prof = WorkloadProfile(
            kind="points", n_sampled=64, band_fraction=0.2
        )
        assert recommend(prof).zonal_lane == "fold"

    def test_sparse_raster_shrinks_tiles(self):
        sparse = WorkloadProfile(
            kind="raster", n_sampled=9, tile_occupancy=0.2
        )
        dense = WorkloadProfile(
            kind="raster", n_sampled=9, tile_occupancy=0.9
        )
        assert recommend(sparse).raster_tile == (128, 128)
        assert recommend(dense).raster_tile == (256, 256)


    def test_recommended_profile_through_the_store_answers_the_default_bits(
        self, zones, index, points, tmp_path
    ):
        """Profile, recommend, persist, load, join: whatever resolution
        and probe the rules pick, the rechecked answer is the f64
        oracle's, so it is the default knobs' answer bit for bit."""
        rec = TuningProfile.merged(
            recommend(profile_polygons(zones, CUSTOM)),
            recommend(profile_points(points, index, CUSTOM, RES)),
        )
        assert rec.resolution is not None and rec.probe is not None
        tuned = build_chip_index(tessellate(
            zones, CUSTOM, rec.resolution, keep_core_geoms=False
        ))
        store = ProfileStore(str(tmp_path))
        store.save(rec, fingerprint=index_fingerprint(tuned))
        loaded, _ = store.load_latest(
            expect_fingerprint=index_fingerprint(tuned)
        )
        assert loaded.as_dict() == rec.as_dict()
        got = pip_join(
            points, None, CUSTOM, None, chip_index=tuned, profile=loaded,
            recheck=True,
        )
        want = pip_join(
            points, None, CUSTOM, RES, chip_index=index, recheck=True
        )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


#: one profile a kind, every rule of its kind firing
RULE_PROFILES = {
    "points": WorkloadProfile(
        kind="points", n_sampled=4096, n_total=100_000, band_fraction=0.2,
        class_shares={"heavy": 0.1, "convex": 0.6, "light": 0.3},
    ),
    "polygons": WorkloadProfile(
        kind="polygons", n_sampled=3, optimal_resolution=4,
        cells_per_geom={"mean": 12.0},
    ),
    "overlay": WorkloadProfile(
        kind="overlay", n_sampled=8192, resolution=3,
        sure_fraction=0.2, border_fraction=0.8,
    ),
    "raster": WorkloadProfile(
        kind="raster", n_sampled=9, tile_occupancy=0.2
    ),
}


class TestRulesReadTheirArgument:
    @pytest.mark.parametrize("kind", sorted(RULE_PROFILES))
    def test_recommend_reads_no_file_and_no_directory(
        self, kind, tmp_path, monkeypatch
    ):
        """The same profile gives the same `TuningProfile` from any
        working directory, with every way to list or read a file made
        to raise for the length of the call."""
        import pathlib

        def refuse(*a, **k):
            raise AssertionError("recommend() touched the file system")

        prof = RULE_PROFILES[kind]
        answers = []
        for i in range(2):
            d = tmp_path / f"cwd{i}"
            d.mkdir()
            (d / "TREND.json").write_text("{not json")
            with monkeypatch.context() as m:
                m.chdir(d)
                for name in ("glob", "rglob", "iterdir", "open",
                             "read_text", "read_bytes"):
                    m.setattr(pathlib.Path, name, refuse)
                answers.append(recommend(prof).as_dict())
        assert answers[0] == answers[1]
        assert answers[0]["rationale"], "the profile fired no rule"
        assert set(answers[0]["source"]) == {"profile"}

    def test_no_kind_sets_a_stream_knob(self, index, tmp_path):
        """The durable loop's executor and window are the resolver's
        defaults to decide (pipeline off, window None): no rule has a
        chip reading to set them from."""
        for prof in RULE_PROFILES.values():
            rec = recommend(prof)
            assert rec.stream_window is None
            assert rec.stream_pipeline is None
            assert not {
                r["knob"] for r in rec.rationale
            } & {"stream_window", "stream_pipeline"}
        ring = _small_ring()
        sj = StreamJoin(
            index, CUSTOM, RES, profile=recommend(RULE_PROFILES["points"])
        )
        with telemetry.capture() as events:
            sj.run_durable(ring, 2, run_dir=str(tmp_path))
        (ev,) = resolve_events(events, "stream_join.run_durable")
        assert ev["stream_pipeline_source"] == "default"
        assert ev["stream_window_source"] == "default"
        assert ev["stream_pipeline"] is False and ev["stream_window"] is None


# ------------------------------------------------------------ satellites


class TestSampleStrategyErrors:
    def test_zero_rows_typed(self):
        rng = np.random.default_rng(0)
        with pytest.raises(ValueError, match="empty geometry column"):
            SampleStrategy(fraction=1.0).apply(0, rng)
        with pytest.raises(ValueError, match="empty geometry column"):
            SampleStrategy(fraction=1.0).apply(-3, rng)

    def test_zero_fraction_typed(self):
        with pytest.raises(ValueError, match="fraction"):
            SampleStrategy(fraction=0.0)

    def test_overrange_fraction_typed(self):
        with pytest.raises(ValueError, match="fraction"):
            SampleStrategy(fraction=1.5)

    def test_zero_limit_typed(self):
        with pytest.raises(ValueError, match="limit"):
            SampleStrategy(fraction=1.0, limit=0)


class TestOverlayCandidateTelemetry:
    def test_stats_recorded(self, zones):
        left = tessellate(zones, CUSTOM, RES, keep_core_geoms=False)
        with telemetry.capture() as events:
            lrows, rrows, sure = candidate_pairs(left, left)
        (ev,) = [
            e for e in events if e.get("event") == "overlay_candidates"
        ]
        assert ev["candidates"] == int(lrows.shape[0]) > 0
        assert 0.0 <= ev["sure_fraction"] <= 1.0
        assert abs(
            ev["sure_fraction"] + ev["border_fraction"] - 1.0
        ) < 1e-6
        assert ev["sure_fraction"] == pytest.approx(
            float(sure.sum()) / sure.shape[0], abs=1e-6
        )

    def test_disjoint_tables_record_zeros(self, zones):
        left = tessellate(zones, CUSTOM, RES, keep_core_geoms=False)
        far = wkt.from_wkt(
            ["POLYGON ((100 50, 110 50, 110 60, 100 60, 100 50))"]
        )
        right = tessellate(far, CUSTOM, RES, keep_core_geoms=False)
        with telemetry.capture() as events:
            lrows, _, _ = candidate_pairs(left, right)
        assert lrows.shape[0] == 0
        (ev,) = [
            e for e in events if e.get("event") == "overlay_candidates"
        ]
        assert ev["candidates"] == 0
        assert ev["sure_fraction"] == 0.0


class TestOverlayProfile:
    def test_overlay_profile_consumes_span_stats(self, zones):
        """PR 16 satellite: `profile_overlay` reads the sure/border
        split straight off the ``overlay.candidates`` span — no second
        pass over the tables."""
        with telemetry.capture() as events:
            prof = profile_overlay(zones, zones, CUSTOM, RES)
        assert prof.kind == "overlay" and prof.n_sampled > 0
        assert prof.resolution == RES
        assert 0.0 <= prof.sure_fraction <= 1.0
        assert abs(prof.sure_fraction + prof.border_fraction - 1.0) < 1e-6
        assert [e for e in events if e.get("event") == "tune_profile"]
        assert WorkloadProfile.from_dict(prof.as_dict()) == prof

    def test_border_dominated_recommends_finer_tessellation(self):
        prof = WorkloadProfile(
            kind="overlay", n_sampled=100, resolution=3,
            sure_fraction=0.2, border_fraction=0.8,
        )
        rec = recommend(prof)
        assert rec.resolution == 4
        (rule,) = [r for r in rec.rationale if r["knob"] == "resolution"]
        assert rule["rule"] == "border-dominated-finer-tessellation"
        assert rule["evidence"]["border_fraction"] == 0.8
        assert rule["evidence"]["threshold"] == 0.5

    def test_sure_dominated_keeps_resolution(self):
        prof = WorkloadProfile(
            kind="overlay", n_sampled=100, resolution=3,
            sure_fraction=0.9, border_fraction=0.1,
        )
        rec = recommend(prof)
        assert rec.resolution is None
        assert not [r for r in rec.rationale if r["knob"] == "resolution"]
