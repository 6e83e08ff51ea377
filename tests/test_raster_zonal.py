"""Zonal statistics: bit-identity against the f64 host oracles.

The contract under test (ISSUE 10): every zonal fold — grid cells,
vector zones, both kernel lanes, and the durable scan through any
kill/resume point — is bit-identical to a pure-host f64 oracle that
mirrors the tile decomposition, on adversarial fixtures: NaN nodata,
zone edges crossing tile boundaries, pixel centers landing EXACTLY on
zone edges, pad tiles from non-divisible shapes.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from mosaic_tpu.core.geometry import wkt
from mosaic_tpu.core.index import CustomIndexSystem, GridConf
from mosaic_tpu.core.tessellate import tessellate
from mosaic_tpu.kernels.pip import TilingError
from mosaic_tpu.kernels.zonal import fold_lane, zonal_fold, zonal_tiled
from mosaic_tpu.raster import Raster
from mosaic_tpu.raster.zonal import (
    ZonalEngine,
    host_zonal_grid_oracle,
    host_zonal_zones_oracle,
    resolve_zonal_lane,
    zonal_grid,
    zonal_zones,
)
from mosaic_tpu.runtime import checkpoint, faults, telemetry
from mosaic_tpu.runtime.retry import RetryPolicy
from mosaic_tpu.sql import RasterStream
from mosaic_tpu.sql.join import build_chip_index

CUSTOM = CustomIndexSystem(GridConf(-180, 180, -90, 90, 2, 10.0, 10.0))
RES = 3

#: zone edges cross the (32, 32) tile boundaries (x = 32/64, rows
#: 32/64), and the vertical x=6 / horizontal y=8 edges run EXACTLY
#: through pixel centers of the fixture raster (centers at integer
#: coordinates); zone 0 carries a hole
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


def _mk_raster(h=75, w=90, nodata=-9.0, seed=5, integer=False):
    """75x90 @ (32,32) tiles -> 3x3 grid, both axes padded; pixel
    centers at integer world coordinates (x = col, y = 15 - row)."""
    rng = np.random.default_rng(seed)
    if integer:
        data = rng.integers(0, 100, (1, h, w)).astype(np.float64)
    else:
        data = rng.uniform(0, 100, (1, h, w))
    speck = rng.random((h, w)) < 0.1
    if nodata is not None:
        data[0][speck] = nodata
    return Raster(
        data=data,
        gt=(-0.5, 1.0, 0.0, 15.5, 0.0, -1.0),
        srid=0,
        nodata=nodata,
    )


def _assert_result_equal(got, want):
    np.testing.assert_array_equal(got.keys, want.keys)
    np.testing.assert_array_equal(got.count, want.count)
    np.testing.assert_array_equal(got.sum, want.sum)  # bitwise: f64 fold
    np.testing.assert_array_equal(got.min, want.min)
    np.testing.assert_array_equal(got.max, want.max)
    assert got.pixels == want.pixels


# ------------------------------------------------------------------ kernels


def test_zonal_fold_matches_sequential_numpy():
    rng = np.random.default_rng(0)
    vals = rng.uniform(-50, 50, 4096)
    seg = rng.integers(-1, 37, 4096).astype(np.int32)
    cnt, s, mn, mx = (
        np.asarray(a) for a in zonal_fold(vals, seg, 37)
    )
    want_c = np.zeros(37, np.int64)
    want_s = np.zeros(37)
    want_mn = np.full(37, np.inf)
    want_mx = np.full(37, -np.inf)
    for g, v in zip(seg, vals):  # sequential: the fold's order contract
        if g >= 0:
            want_c[g] += 1
            want_s[g] += v
            want_mn[g] = min(want_mn[g], v)
            want_mx[g] = max(want_mx[g], v)
    np.testing.assert_array_equal(cnt, want_c)
    np.testing.assert_array_equal(s, want_s)
    live = want_c > 0
    np.testing.assert_array_equal(mn[live], want_mn[live])
    np.testing.assert_array_equal(mx[live], want_mx[live])


FOLD_DTYPES = (
    np.int8, np.uint8, np.int16, np.uint16, np.int32, np.float32,
    np.float64,
)
FOLD_SEGMENTS = 256  # the raster cell's zone count


def _dtype_limits(dt):
    dt = np.dtype(dt)
    info = np.iinfo(dt) if dt.kind in "iu" else np.finfo(dt)
    return info.min, info.max


def _fold_case(dt, pixels, content, rng):
    lo, hi = _dtype_limits(dt)
    seg = rng.integers(-1, FOLD_SEGMENTS, pixels).astype(np.int32)
    if np.dtype(dt).kind in "iu":
        vals = rng.integers(lo, int(hi) + 1, pixels).astype(dt)
    else:
        vals = rng.uniform(-50, 50, pixels).astype(dt)
    if content == "all_min":
        vals, seg = np.full(pixels, lo, dt), np.full(pixels, 5, np.int32)
    elif content == "all_max":
        vals, seg = np.full(pixels, hi, dt), np.full(pixels, 5, np.int32)
    elif content == "all_invalid":
        seg = np.full(pixels, -1, np.int32)
    return vals, seg


@pytest.mark.parametrize(
    "content", ["random", "all_min", "all_max", "all_invalid"])
@pytest.mark.parametrize("pixels", [4096, 65536])
@pytest.mark.parametrize("dt", FOLD_DTYPES, ids=lambda d: np.dtype(d).name)
def test_zonal_fold_exact_at_every_value_dtype(dt, pixels, content):
    """The zones fold's call (f64 accumulator offered) on every storage
    dtype: after the cast `RasterStream`'s ``land`` does, the four
    statistics are the sequential int64 / f64 numpy fold's, bit for bit
    — on the int32 lane and on the wide one, at the dtype's extremes and
    with one segment taking a whole 65,536-pixel tile."""
    vals, seg = _fold_case(dt, pixels, content, np.random.default_rng(3))
    with np.errstate(over="ignore"):  # 65,536 x f64's largest is inf
        cnt, s, mn, mx = zonal_fold(
            vals, seg, FOLD_SEGMENTS, acc_dtype=jnp.float64)
        assert mn.dtype == mx.dtype == np.dtype(dt)  # the values' own
        lane = fold_lane(dt, pixels, FOLD_SEGMENTS)
        assert s.dtype == (np.int32 if lane == "int32" else np.float64)
        cnt = np.asarray(cnt, np.int64)
        s, mn, mx = (np.asarray(a, np.float64) for a in (s, mn, mx))
        ok = seg >= 0
        want_c = np.zeros(FOLD_SEGMENTS, np.int64)
        np.add.at(want_c, seg[ok], 1)
        acc = np.int64 if np.dtype(dt).kind in "iu" else np.float64
        want_s = np.zeros(FOLD_SEGMENTS, acc)
        np.add.at(want_s, seg[ok], vals[ok].astype(acc))  # in pixel order
        want_mn = np.full(FOLD_SEGMENTS, np.inf)
        want_mx = np.full(FOLD_SEGMENTS, -np.inf)
        np.minimum.at(want_mn, seg[ok], vals[ok].astype(np.float64))
        np.maximum.at(want_mx, seg[ok], vals[ok].astype(np.float64))
    live = want_c > 0
    assert live.sum() == {"all_invalid": 0, "random": FOLD_SEGMENTS}.get(
        content, 1)
    np.testing.assert_array_equal(cnt, want_c)
    np.testing.assert_array_equal(s, want_s.astype(np.float64))
    np.testing.assert_array_equal(mn[live], want_mn[live])
    np.testing.assert_array_equal(mx[live], want_mx[live])


@pytest.mark.parametrize("dt, pixels, segments, lane", [
    (np.int16, 65536, 256, "int32"),
    (np.int16, 512 * 512, 256, "wide"),    # 2**18 x 2**15 leaves int32
    (np.int8, 65536, 256, "int32"),
    (np.uint8, 65536, 256, "int32"),
    (np.uint16, 32768, 256, "int32"),
    (np.uint16, 65536, 256, "wide"),       # 65,536 x 65,535 > 2**31 - 1
    (np.int16, 65536, 1 << 20, "wide"),    # past the dense form's lanes
    (np.int32, 1, 1, "wide"), (np.int32, 65536, 256, "wide"),
    (np.int64, 1024, 4, "wide"), (np.uint32, 1024, 4, "wide"),
    (np.float32, 1, 1, "wide"), (np.float32, 65536, 256, "wide"),
    (np.float64, 65536, 256, "wide"), (np.bool_, 1024, 4, "wide"),
])
def test_fold_lane_rule(dt, pixels, segments, lane):
    """The lane is a table over (storage dtype, tile pixels, segments):
    int32 exactly where a narrow integer tile's sum cannot leave it."""
    assert fold_lane(dt, pixels, segments) == lane
    assert fold_lane(np.dtype(dt).name, pixels, segments) == lane


def test_zonal_tiled_matches_fold_on_exact_summable():
    rng = np.random.default_rng(1)
    vals = rng.integers(0, 100, 5000).astype(np.float32)
    seg = rng.integers(-1, 19, 5000).astype(np.int32)
    cnt_t, s_t, mn_t, mx_t = (
        np.asarray(a)
        for a in zonal_tiled(vals, seg, 19, interpret=True)
    )
    cnt_f, s_f, mn_f, mx_f = (
        np.asarray(a)
        for a in zonal_fold(
            vals, seg, 19, acc_dtype=jnp.float32
        )
    )
    np.testing.assert_array_equal(cnt_t, cnt_f)
    np.testing.assert_array_equal(s_t, s_f)  # integer-valued: exact
    live = cnt_f > 0
    np.testing.assert_array_equal(mn_t[live], mn_f[live])
    np.testing.assert_array_equal(mx_t[live], mx_f[live])


def test_zonal_tiled_rejects_bad_tiling():
    vals = np.zeros(256, np.float32)
    seg = np.zeros(256, np.int32)
    with pytest.raises(TilingError):
        zonal_tiled(vals, seg, 4, tile_n=100, interpret=True)
    with pytest.raises(TilingError):
        zonal_tiled(vals, seg, 4, tile_s=64, interpret=True)


# --------------------------------------------------------------- lane knob


def test_lane_knob(monkeypatch):
    monkeypatch.delenv("MOSAIC_RASTER_LANE", raising=False)
    assert resolve_zonal_lane("auto") == "fold"
    monkeypatch.setenv("MOSAIC_RASTER_LANE", "tiled")
    assert resolve_zonal_lane("auto") == "tiled"
    assert resolve_zonal_lane("fold") == "fold"  # explicit beats env
    monkeypatch.setenv("MOSAIC_RASTER_LANE", "warp")
    with pytest.raises(ValueError, match="zonal lane"):
        resolve_zonal_lane("auto")


# ------------------------------------------------------------- grid oracle


def test_grid_bit_identical_to_oracle():
    r = _mk_raster()
    got = zonal_grid(r, RES, index_system=CUSTOM, tile=(32, 32))
    want = host_zonal_grid_oracle(r, RES, CUSTOM, tile=(32, 32))
    _assert_result_equal(got, want)
    # counts cover exactly the valid pixels
    assert got.pixels == int(r.band(1).mask.sum())


def test_grid_oracle_nan_nodata():
    r = _mk_raster(nodata=np.nan)
    got = zonal_grid(r, RES, index_system=CUSTOM, tile=(32, 32))
    want = host_zonal_grid_oracle(r, RES, CUSTOM, tile=(32, 32))
    _assert_result_equal(got, want)
    assert np.isfinite(got.sum).all()


def test_grid_tile_shape_invariant_keys():
    # sums are tile-order-dependent (documented), but keys/counts/min/
    # max are not: any tile shape must agree on those
    r = _mk_raster()
    a = zonal_grid(r, RES, index_system=CUSTOM, tile=(32, 32))
    b = zonal_grid(r, RES, index_system=CUSTOM, tile=(64, 128))
    np.testing.assert_array_equal(a.keys, b.keys)
    np.testing.assert_array_equal(a.count, b.count)
    np.testing.assert_array_equal(a.min, b.min)
    np.testing.assert_array_equal(a.max, b.max)
    np.testing.assert_allclose(a.sum, b.sum, rtol=1e-12)
    # mean/stat view
    st = a.stat("mean")
    assert st[int(a.keys[0])] == pytest.approx(a.sum[0] / a.count[0])


# ------------------------------------------------------------ zones oracle


def test_zones_bit_identical_to_oracle(index):
    r = _mk_raster()
    got = zonal_zones(r, index, CUSTOM, RES, tile=(32, 32))
    want = host_zonal_zones_oracle(r, index, CUSTOM, RES, tile=(32, 32))
    _assert_result_equal(got, want)
    assert set(got.keys) <= {0, 1, 2}
    assert len(got.keys) == 3  # every zone is hit by this fixture


def test_zones_oracle_nan_nodata_and_edge_centers(index):
    # NaN nodata + centers exactly on the x=6 / y=8 zone edges: device
    # probe and f64 host join must classify every such pixel identically
    r = _mk_raster(nodata=np.nan, seed=11)
    got = zonal_zones(r, index, CUSTOM, RES, tile=(32, 32))
    want = host_zonal_zones_oracle(r, index, CUSTOM, RES, tile=(32, 32))
    _assert_result_equal(got, want)


def test_zones_engine_reuse_and_hole(index):
    # hole pixels (zone 0's interior ring) fold nowhere: count over the
    # hole bbox interior must be absent from zone 0's pixels
    eng = ZonalEngine(CUSTOM, RES, chip_index=index)
    r = _mk_raster(nodata=None, seed=13)
    got = eng.zones(r, tile=(32, 32))
    want = host_zonal_zones_oracle(r, index, CUSTOM, RES, tile=(32, 32))
    _assert_result_equal(got, want)
    # engine reuse across rasters (same tile shape -> same executables)
    r2 = _mk_raster(seed=17)
    _assert_result_equal(
        eng.zones(r2, tile=(32, 32)),
        host_zonal_zones_oracle(r2, index, CUSTOM, RES, tile=(32, 32)),
    )


def test_zones_tiled_lane_agrees_on_integer_data(index):
    # the f32 Pallas lane holds bit-identity on exact-summable values
    r = _mk_raster(integer=True, seed=23)
    fold = ZonalEngine(
        CUSTOM, RES, chip_index=index, lane="fold"
    ).zones(r, tile=(32, 32))
    tiled = ZonalEngine(
        CUSTOM, RES, chip_index=index, lane="tiled"
    ).zones(r, tile=(32, 32))
    np.testing.assert_array_equal(tiled.keys, fold.keys)
    np.testing.assert_array_equal(tiled.count, fold.count)
    np.testing.assert_array_equal(tiled.sum, fold.sum)
    np.testing.assert_array_equal(tiled.min, fold.min)
    np.testing.assert_array_equal(tiled.max, fold.max)


def test_zones_requires_chip_index():
    eng = ZonalEngine(CUSTOM, RES)
    with pytest.raises(ValueError, match="chip_index"):
        eng.zones(_mk_raster())


def test_zonal_emits_stage_telemetry(index):
    with telemetry.capture() as ev:
        zonal_zones(_mk_raster(), index, CUSTOM, RES, tile=(32, 32))
    stages = [
        e.get("stage") for e in ev if e["event"] == "raster_stage"
    ]
    assert "tile" in stages and "zonal" in stages


# ------------------------------------------------------------ durable scan


@pytest.fixture(scope="module")
def stream(index):
    return RasterStream(index, CUSTOM, RES)


@pytest.fixture(scope="module")
def raster():
    return _mk_raster(seed=29)


@pytest.fixture(scope="module")
def clean(stream, raster):
    return stream.scan(raster, tile=(32, 32))


def test_scan_matches_engine_and_oracle(stream, raster, clean, index):
    want = host_zonal_zones_oracle(
        raster, index, CUSTOM, RES, tile=(32, 32)
    )
    _assert_result_equal(clean.stats, want)
    assert clean.ntiles == 9
    assert clean.pixels == 75 * 90


def test_durable_scan_equals_plain(stream, raster, clean, tmp_path):
    r = stream.scan(
        raster, tile=(32, 32), run_dir=str(tmp_path), snapshot_every=2,
    )
    _assert_result_equal(r.stats, clean.stats)
    # 9 tiles, every-2 boundaries: 2, 4, 6, 8, 9
    assert r.metrics["snapshots"] == 5
    assert checkpoint.list_snapshots(str(tmp_path)) == [2, 4, 6, 8, 9]


@pytest.mark.parametrize("kill_after", [2, 4, 6])
def test_scan_kill_and_resume_bit_identical(
    stream, raster, clean, tmp_path, kill_after
):
    """Fatal device loss after ``kill_after`` tiles; resume() from the
    newest snapshot converges to the clean fold bit for bit."""
    d = str(tmp_path / f"kill{kill_after}")
    with faults.inject(
        fail_first=99, skip_first=kill_after,
        sites=("raster.zonal",),
        exc_factory=lambda s: RuntimeError(f"simulated device loss @ {s}"),
    ):
        with pytest.raises(RuntimeError, match="simulated device loss"):
            stream.scan(
                raster, tile=(32, 32), run_dir=d, snapshot_every=2,
                retry_policy=FAST,
            )
    assert checkpoint.list_snapshots(d)
    r = stream.resume(d, raster, retry_policy=FAST)
    _assert_result_equal(r.stats, clean.stats)
    assert r.metrics["resumed_from"] == kill_after  # boundary == kill pt


def test_scan_transient_faults_retry_to_clean(stream, raster, clean, tmp_path):
    with telemetry.capture() as ev:
        with faults.transient_errors(2, sites=("raster.zonal",)):
            r = stream.scan(
                raster, tile=(32, 32), run_dir=str(tmp_path / "t"),
                snapshot_every=4, retry_policy=FAST,
            )
    _assert_result_equal(r.stats, clean.stats)
    assert r.metrics["degraded"] is False
    assert [e["event"] for e in ev].count("transient_retry") == 2


def test_scan_exhausted_tile_degrades_to_host(stream, raster, clean, tmp_path):
    """A tile whose retry budget exhausts is answered by the f64 host
    twin — bit-identical, so the final fold still equals clean."""
    with telemetry.capture() as ev:
        with faults.transient_errors(
            3, sites=("raster.zonal",)
        ):  # == FAST.max_attempts: tile 0's budget exhausts
            r = stream.scan(
                raster, tile=(32, 32), run_dir=str(tmp_path / "d"),
                snapshot_every=4, retry_policy=FAST,
            )
    assert r.metrics["degraded"] is True
    assert r.metrics["degraded_tiles"] == 1
    _assert_result_equal(r.stats, clean.stats)
    assert "degraded" in [e["event"] for e in ev]


def test_scan_snapshot_failure_does_not_kill_run(
    stream, raster, clean, tmp_path
):
    with telemetry.capture() as ev:
        with faults.transient_errors(999, sites=("raster.snapshot",)):
            # snapshot site is guarded by save_snapshot itself; simulate
            # a sick disk instead by pointing run_dir at a file
            p = tmp_path / "not_a_dir"
            p.write_text("x")
            r = stream.scan(
                raster, tile=(32, 32), run_dir=str(p), snapshot_every=4,
            )
    _assert_result_equal(r.stats, clean.stats)
    assert r.metrics["snapshots"] == 0
    assert "snapshot_skipped" in [e["event"] for e in ev]


def test_resume_rejects_wrong_raster(stream, raster, tmp_path):
    stream.scan(
        raster, tile=(32, 32), run_dir=str(tmp_path), snapshot_every=4,
    )
    other = _mk_raster(seed=99)
    with pytest.raises(ValueError, match="fingerprint"):
        stream.resume(str(tmp_path), other)


def test_resume_without_snapshots_raises(stream, raster, tmp_path):
    with pytest.raises(FileNotFoundError, match="nothing to resume"):
        stream.resume(str(tmp_path / "empty"), raster)


def test_scan_joins_trace_on_resume(stream, raster, tmp_path):
    d = str(tmp_path)
    with faults.inject(
        fail_first=99, skip_first=4, sites=("raster.zonal",),
        exc_factory=lambda s: RuntimeError("boom"),
    ):
        with telemetry.capture() as ev1:
            with pytest.raises(RuntimeError):
                stream.scan(
                    raster, tile=(32, 32), run_dir=d, snapshot_every=2,
                )
    with telemetry.capture() as ev2:
        stream.resume(d, raster)

    def scan_span(evs):
        return next(
            e for e in evs
            if e["event"] == "span" and e["name"] == "raster.scan"
        )

    first, second = scan_span(ev1), scan_span(ev2)
    # the resumed run joins the killed run's trace, not a fresh one
    assert second["trace_id"] == first["trace_id"]
    assert second["resumed_from"] == 4
    assert first["error"] == "RuntimeError"


# --------------------------------------- the scan against plain numpy


def _zone_rings():
    """ZONES as lists of (n, 2) float arrays (shell first, then holes),
    read off the WKT text: nothing of the program."""
    import re

    return [
        [np.array([[float(v) for v in p.split()] for p in ring.split(",")])
         for ring in re.findall(r"\(([^()]+)\)", text)]
        for text in ZONES
    ]


def _plain_zone_of(x, y):
    """Smallest zone whose polygon holds (x, y): even-odd ray casting over
    every ring of the polygon, so a hole's ring takes its points out."""
    out = np.full(x.shape, -1)
    for z, rings in reversed(list(enumerate(_zone_rings()))):
        inside = np.zeros(x.shape, bool)
        for ring in rings:
            a, b = ring[:-1], ring[1:]
            for (ax, ay), (bx, by) in zip(a, b):
                if ay == by:
                    continue
                xc = ax + (y - ay) * (bx - ax) / (by - ay)
                inside ^= ((ay > y) != (by > y)) & (x < xc)
        out[inside] = z
    return out


@pytest.mark.parametrize("seed", [101, 202])
def test_scan_equals_plain_numpy_zonal_statistics(stream, seed):
    """An int16 scene whose pixel centres lie on no zone edge: per zone the
    scan's count, sum, min and max are the plain numpy fold's, exactly."""
    rng = np.random.default_rng(seed)
    h, w, nodata = 75, 90, 32767
    data = rng.integers(0, 10_000, (1, h, w)).astype(np.int16)
    data[0][rng.random((h, w)) < 0.15] = nodata
    gt = (-0.13, 1.0, 0.0, 15.21, 0.0, -1.0)
    got = stream.scan(
        Raster(data=data, gt=gt, srid=0, nodata=nodata), tile=(32, 32)
    ).stats
    rows, cols = np.mgrid[0:h, 0:w]
    zone = _plain_zone_of(gt[0] + (cols + 0.5) * gt[1],
                          gt[3] + (rows + 0.5) * gt[5])
    keep = (zone >= 0) & (data[0] != nodata)
    assert sorted(np.unique(zone[keep])) == got.keys.tolist() == [0, 1, 2]
    for i, z in enumerate(got.keys):
        v = data[0][keep & (zone == z)].astype(np.int64)
        assert got.count[i] == v.size
        assert got.sum[i] == v.sum() and got.sum.dtype == np.float64
        assert (got.min[i], got.max[i]) == (v.min(), v.max())
    assert got.pixels == int(keep.sum())


# ------------------------------------------- the fold at the pixels' width


def _mk_int16_raster(seed=31, h=75, w=90, nodata=32767):
    """The fixture's geometry with int16 pixels over the WHOLE dtype
    (negative too; 32767 is nodata), 12% of them nodata."""
    rng = np.random.default_rng(seed)
    data = rng.integers(-32768, 32767, (1, h, w)).astype(np.int16)
    data[0][rng.random((h, w)) < 0.12] = nodata
    return Raster(
        data=data, gt=(-0.5, 1.0, 0.0, 15.5, 0.0, -1.0), srid=0,
        nodata=nodata,
    )


def _as_float64(r):
    return Raster(
        data=r.data.astype(np.float64), gt=r.gt, srid=r.srid,
        nodata=float(r.nodata),
    )


def _scan_event(events):
    (e,) = [e for e in events if e["event"] == "raster_scan"]
    return e


def test_int16_scan_is_the_float64_scan_bit_for_bit(stream, index, tmp_path):
    """An int16 raster folds on the int32 lane, the same pixels as f64 on
    the wide one: `RasterStream.scan`, `ZonalEngine.zones`, the host
    oracle and a resume from a mid-scan snapshot agree field for field."""
    r16 = _mk_int16_raster()
    r64 = _as_float64(r16)
    want = host_zonal_zones_oracle(r16, index, CUSTOM, RES, tile=(32, 32))
    with telemetry.capture() as ev16:
        got16 = stream.scan(r16, tile=(32, 32))
    with telemetry.capture() as ev64:
        got64 = stream.scan(r64, tile=(32, 32))
    e16, e64 = _scan_event(ev16), _scan_event(ev64)
    assert (e16["fold_lane"], e16["values_dtype"]) == ("int32", "int16")
    assert (e64["fold_lane"], e64["values_dtype"]) == ("wide", "float64")
    tiles16 = [e for e in ev16 if e["event"] == "span"
               and e["name"] == "raster.zonal"]
    assert len(tiles16) == 9 and all(
        (t["fold_lane"], t["values_dtype"]) == ("int32", "int16")
        for t in tiles16)
    for got in (got16.stats, got64.stats):
        _assert_result_equal(got, want)
        assert got.sum.dtype == got.min.dtype == np.float64
    assert got16.stats.min.min() < 0  # negatives reached the fold
    eng = ZonalEngine(CUSTOM, RES, chip_index=index)
    with telemetry.capture() as evz:
        _assert_result_equal(eng.zones(r16, tile=(32, 32)), want)
        _assert_result_equal(eng.zones(r64, tile=(32, 32)), want)
    assert [e["fold_lane"] for e in evz if e["event"] == "raster_stage"
            and e.get("stage") == "zonal"] == ["int32", "wide"]
    # killed after 4 tiles, resumed from the f64 snapshot
    d = str(tmp_path / "kill")
    with faults.inject(
        fail_first=99, skip_first=4, sites=("raster.zonal",),
        exc_factory=lambda s: RuntimeError(f"simulated device loss @ {s}"),
    ):
        with pytest.raises(RuntimeError, match="simulated device loss"):
            stream.scan(r16, tile=(32, 32), run_dir=d, snapshot_every=2,
                        retry_policy=FAST)
    _step, arrays, _meta = checkpoint.load_latest(d)
    assert {k: v.dtype.name for k, v in arrays.items()} == {
        "count": "int64", "sum": "float64", "min": "float64",
        "max": "float64"}
    resumed = stream.resume(d, r16, retry_policy=FAST)
    assert resumed.metrics["resumed_from"] == 4
    _assert_result_equal(resumed.stats, want)


def test_int16_scan_degraded_tile_still_matches(stream, index):
    """The host twin answers tile 0 of an int16 scan from the int16
    staging (it casts to f64 itself): the fold is still the oracle's."""
    r16 = _mk_int16_raster(seed=37)
    want = host_zonal_zones_oracle(r16, index, CUSTOM, RES, tile=(32, 32))
    with telemetry.capture() as ev:
        with faults.transient_errors(3, sites=("raster.zonal",)):
            r = stream.scan(r16, tile=(32, 32), retry_policy=FAST)
    assert r.metrics["degraded_tiles"] == 1
    assert _scan_event(ev)["fold_lane"] == "int32"
    _assert_result_equal(r.stats, want)
