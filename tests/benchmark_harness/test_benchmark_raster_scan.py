"""Traffic kind ``raster_scene_scan`` on the CPU: a tiny raster cell (a
192 x 192 int16 scene over 4 x 4 zones, 32 x 32 tiles: 36 a scene) added as
files to a temporary copy of the benchmark and run through the unchanged
harness; the plain reference ``zonal_bruteforce`` against a hand-made scene;
the new reader on a hand-made trace. A CPU run asserts answers, counts and
the result line's shape; it never states a device number.

The lower-precision control reads not correct by each of its two numbers
(pixel centres placed from a rounded geotransform; the float32 fold lane,
whose sums pass 2^24), and a run whose timed path is broken underneath (a
fold that drops one tile, in every scan or in one timed scan only; a tile
that degrades to the host twin) reads ``correct: false`` or counts in
``failed``. The scene is larger than the 96 x 96 the issue names so that a
zone's sum passes 2^24 here too (48 x 48 pixels a zone, values to 32,000)."""

import json
import os
import time
from types import SimpleNamespace

import numpy as np
import pytest

from bh_fixtures import REPO, _write, make_copy, tiny_config

from benchmark.harness.run_cell import run_cell
from benchmark.harness.spec import Spec

CELL = "tiny.scan"
SIDE, TILE, POOL = 192, 32, 2
TILES = (SIDE // TILE) ** 2
ZONAL_METRICS = [
    "scene_p50_ms.zonal", "tile_cycle_p50_ms.zonal", "probe_pull_p50_ms.zonal",
    "host_patch_p50_ms.zonal", "fold_launch_p50_ms.zonal",
    "drain_p50_ms.zonal", "patched_pixel_share.zonal",
    "probe_device_ms_per_tile.zonal", "fold_device_ms_per_tile.zonal",
    "fold_hbm_share.zonal", "scene_pool_build_s.zonal", "scan_warmup_s.zonal",
]
#: entries this cell shares with the other host-fed cells since PR 47
SHARED_METRICS = ["device_idle.batch", "compiles_in_window.batch",
                  "index_build_s", "warmup_s"]


def add_raster_cell(root: str) -> None:
    """``tiny.scan`` on a tiny raster configuration, as new files and
    appended entries; its name joins the ``workloads`` of whatever the real
    raster cell is listed under."""
    tree = os.path.join(root, "benchmark")
    cfg = tiny_config()
    cfg.update({
        "source": "test fixture (raster)", "row": "pixel",
        "reference": "zonal_bruteforce",
        "batch_rows_per_chip": SIDE * SIDE,
        "scene": {"height": SIDE, "width": SIDE, "bands": 1,
                  "dtype": "int16", "nodata": 32767},
    })
    cfg["zones"] = dict(cfg["zones"], nx=4, ny=4)
    _write(os.path.join(tree, "configs", "tiny-raster.json"), cfg)
    _write(os.path.join(tree, "traffic", "tiny-scenes.json"), {
        "kind": "raster_scene_scan", "pool_scenes": POOL, "valid_share": 0.85,
        "value_range": [20000, 32000], "noise": 300, "clouds": 6,
        "cloud_size": [0.05, 0.15], "layout_seed": 3,
        "arguments": {"tile": [TILE, TILE]},
        "control": {"geotransform_dtype": "bfloat16", "lane": "tiled"},
    })
    _write(os.path.join(tree, "workloads", CELL + ".json"),
           {"check": {"max_pixels_unlike": 0.0, "max_zones_unlike_sums": 0}})
    path = os.path.join(root, "BENCHMARK.json")
    with open(path, encoding="utf-8") as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-raster", "source": "test fixture (raster)",
        "file": "benchmark/configs/tiny-raster.json", "reduced": [],
        "why": "test fixture",
    })
    bench["workloads"].append({
        "name": CELL, "config": "tiny-raster", "traffic": "tiny-scenes",
        "chips": 1, "why": "test fixture"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "modis-zonal.scan" in m.get("workloads", []):
            m["workloads"].append(CELL)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(bench, f, indent=1)


@pytest.fixture()
def root(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    root = make_copy(tmp_path)
    add_raster_cell(root)
    return root


def _run(root, seed, *, trace=False, **kw):
    return run_cell(root, CELL, seed, kw.pop("seconds", 0.5), trace,
                    t_start=time.perf_counter(), rehearsal=True, **kw)


def _checks(out: str) -> dict:
    """``{name: line}`` of the run's ``[check]`` lines."""
    return {ln.split()[1].rstrip(":"): ln for ln in out.splitlines()
            if ln.startswith("[check] ")}


def test_raster_cell_untraced(root, capfd):
    line = _run(root, 4_000_000_411)
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device",
                         "checks"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["attempted"] % (SIDE * SIDE) == 0
    assert set(line["metrics"]) == {"batch_rows_per_s", "setup_s"}
    for v in line["metrics"].values():
        assert set(v) == {"value", "unit"} and v["value"] > 0
    out = capfd.readouterr().out
    checks = _checks(out)
    assert set(checks) == {
        "zonal_scans_unlike_repeat", "zonal_pixels_unlike_reference",
        "zonal_zones_unlike_reference_sums", "forbidden_events"}
    assert all(" ok " in ln and "value=0.0 " in ln for ln in checks.values())
    assert "compiles_in_window=0" in out
    assert f"tiles_per_scan={TILES} tile=({TILE}, {TILE})" in out
    assert f"pool={POOL} shape=({SIDE}, {SIDE}) dtype=int16 valid_share=0.85" in out


def test_raster_cell_traced_reads_the_programs_spans(root, capfd):
    line = _run(root, 51, trace=True, seconds=1.0)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    m = line["metrics"]
    assert "batch_rows_per_s" not in m and "setup_s" not in m
    # the device-trace metrics find nothing to read on the CPU; every other
    # new metric is there
    device = {"probe_device_ms_per_tile.zonal", "fold_device_ms_per_tile.zonal",
              "fold_hbm_share.zonal"}
    assert set(m) == (set(ZONAL_METRICS) - device) | (
        set(SHARED_METRICS) - {"device_idle.batch"})
    for name in set(ZONAL_METRICS) - device - {"patched_pixel_share.zonal"}:
        assert m[name]["value"] > 0, name
    assert m["compiles_in_window.batch"] == {"value": 0.0, "unit": "count"}
    assert 0.0 <= m["patched_pixel_share.zonal"]["value"] < 5.0
    assert m["index_build_s"]["value"] > 0 and m["warmup_s"]["value"] > 0
    # a scan is its tiles: the tile's pieces lie inside the tile's span
    assert m["scene_p50_ms.zonal"]["value"] > m["tile_cycle_p50_ms.zonal"]["value"]
    pieces = sum(m[k]["value"] for k in (
        "probe_pull_p50_ms.zonal", "host_patch_p50_ms.zonal",
        "fold_launch_p50_ms.zonal"))
    assert pieces < 1.5 * m["tile_cycle_p50_ms.zonal"]["value"]
    assert "nothing_to_read: metric=device_idle.batch" in capfd.readouterr().out


def test_the_traced_run_profiles_tiles_in_the_first_scans_middle(
        root, monkeypatch):
    from benchmark.harness.context import TraceSession

    calls = []
    monkeypatch.setattr(
        TraceSession, "start", lambda self: calls.append(("start", self.enabled)))
    monkeypatch.setattr(TraceSession, "stop", lambda self: calls.append(("stop",)))
    events = []
    from mosaic_tpu.runtime import telemetry

    def tiles(evt):
        if evt.get("event") == "span" and evt.get("name") == "raster.zonal" \
                and "step" in evt:
            events.append((evt["step"], len(calls)))

    telemetry.add_observer(tiles)
    try:
        with pytest.raises(Exception, match="xplane|trace"):
            _run(root, 52, trace=True)  # the stubbed profiler wrote no trace
    finally:
        telemetry.remove_observer(tiles)
    kind = Spec(root).module("traffic_kinds", "raster_scene_scan")
    first = min(kind.TRACE_FROM_TILE, TILES // 2)
    starts = [i for i, c in enumerate(calls) if c[0] == "start"]
    assert starts and all(c[1] for c in calls if c[0] == "start")
    # the warm-up's scans (POOL of them) start nothing; the first timed scan
    # starts the profiler after tile first - 1
    seen_before_start = [s for s, n in events if n <= starts[0]]
    assert len(seen_before_start) == POOL * TILES + first
    assert seen_before_start[-1] == first - 1


@pytest.mark.parametrize("seed", [61, 62, 4_000_000_613])
def test_sound_run_is_correct_and_each_control_number_is_not(root, seed, capfd):
    assert _run(root, seed)["correct"] is True
    capfd.readouterr()
    # the mix's control: the scanned scenes' geotransform rounded to
    # bfloat16 (pixel centres misplaced), the float32 fold lane for the sums
    assert _run(root, seed, control=True)["correct"] is False
    checks = _checks(capfd.readouterr().out)
    assert "FAILED" in checks["zonal_pixels_unlike_reference"]
    assert "FAILED" in checks["zonal_zones_unlike_reference_sums"]
    assert " ok " in checks["zonal_scans_unlike_repeat"]


def _dropping(monkeypatch, which):
    """The tile fold answers nothing for tile 7 in the scans ``which(n)``
    picks (n counts scans from 1): the zones lose that tile's pixels."""
    from mosaic_tpu.raster.zonal import ZonalEngine

    real, seen = ZonalEngine._tile_zone_stats_async, [0]

    def dropped(self, plan, t, vals, mask, tally=None):
        seen[0] += t == 0
        if t == 7 and which(seen[0]):
            mask = np.zeros_like(mask)
        return real(self, plan, t, vals, mask, tally)

    monkeypatch.setattr(ZonalEngine, "_tile_zone_stats_async", dropped)


def test_a_fold_that_drops_a_tile_in_every_scan_fails_the_reference(
        root, monkeypatch, capfd):
    _dropping(monkeypatch, lambda n: True)
    line = _run(root, 71)
    assert line["correct"] is False and line["attempted"] > 0
    checks = _checks(capfd.readouterr().out)
    # timed and repeated scans are wrong alike; the plain reference is not
    assert " ok " in checks["zonal_scans_unlike_repeat"]
    assert "FAILED" in checks["zonal_pixels_unlike_reference"]


def test_one_timed_scan_that_drops_a_tile_differs_from_the_repeat(
        root, monkeypatch, capfd):
    # scans 1..POOL are the warm-up's; POOL + 1 .. 2 POOL the first pass
    _dropping(monkeypatch, lambda n: n == 2 * POOL + 1)
    line = _run(root, 72, seconds=2.0)
    assert line["correct"] is False
    checks = _checks(capfd.readouterr().out)
    assert "FAILED" in checks["zonal_scans_unlike_repeat"]
    assert " ok " in checks["zonal_pixels_unlike_reference"]


def test_a_degraded_tile_counts_in_failed(root, monkeypatch):
    """Transient device failures past the retry budget inside the window:
    the tile is answered by the f64 host twin (right answer, wrong path),
    which counts its pixels in ``failed`` and is a forbidden event."""
    from mosaic_tpu.runtime import faults

    monkeypatch.setenv("MOSAIC_RETRY_BASE_S", "0.001")
    # the warm-up's tiles pass; every later launch fails
    with faults.transient_errors(
        100_000, sites=("raster.zonal",), skip_first=POOL * TILES
    ):
        line = _run(root, 73, seconds=0.2)
    assert line["correct"] is False
    assert line["failed"] >= TILE * TILE


# ----------------------------------------------------- the plain reference

def test_zonal_bruteforce_on_a_hand_made_scene():
    """Three zones over a 6 x 8 scene of 1-degree pixels: a square, an
    L-shape that shares the square's column, a triangle; one pixel is
    nodata, one column lies in no zone. Counted by hand."""
    spec = Spec(REPO)
    ref = spec.module("references", "zonal_bruteforce")
    rings = [
        np.array([[0, 0], [2, 0], [2, 2], [0, 2]], float),          # 2 x 2
        np.array([[0, 2], [4, 2], [4, 6], [2, 6], [2, 4], [0, 4]], float),
        np.array([[4, 0], [7, 0], [4, 2]], float),
    ]
    gt = (0.0, 1.0, 0.0, 6.0, 0.0, -1.0)  # row 0 is y in (5, 6)
    zones = ref.pixel_zones(rings, gt, (6, 8), block_rows=4)
    want = np.full((6, 8), -1)
    want[4:6, 0:2] = 0                       # y in (0, 2), x in (0, 2)
    want[2:4, 0:4] = 1                       # y in (2, 4), x in (0, 4)
    want[0:2, 2:4] = 1                       # y in (4, 6), x in (2, 4)
    want[5, 4:6] = 2                         # centres (4.5, 0.5), (5.5, 0.5)
    want[4, 4] = 2                           # (4.5, 1.5): the edge is at 4.75
    np.testing.assert_array_equal(zones, want)
    values = (np.arange(48, dtype=np.int16).reshape(6, 8) * 7) % 23
    values[5, 0] = 32767                     # nodata inside zone 0
    st = ref.stats(zones, values, 32767, 4)  # a fourth zone with no pixel
    for z in range(3):
        v = values[(want == z) & (values != 32767)].astype(np.int64)
        assert st["count"][z] == v.size and st["sum"][z] == v.sum()
        assert st["min"][z] == v.min() and st["max"][z] == v.max()
    assert st["count"].tolist() == [3, 12, 3, 0]
    assert [st[k][3] for k in ("sum", "min", "max")] == [0, 0, 0]
    assert all(st[k].dtype == np.int64 for k in st)


def test_zonal_bruteforce_imports_nothing_of_the_program():
    path = os.path.join(REPO, "benchmark", "references", "zonal_bruteforce.py")
    with open(path, encoding="utf-8") as f:
        text = f.read()
    assert "mosaic_tpu" not in text.replace("nothing of the program", "")
    assert "import jax" not in text


# ------------------------------------------------------------ the new reader

S = 1e9


def _reader_ctx(spec, monkeypatch, tr, table, counters):
    from mosaic_tpu.obs import stages

    monkeypatch.setattr(
        spec.module("readers", "_trace"), "of_run", lambda ctx: tr)
    monkeypatch.setattr(stages, "tables", lambda modules, rows: table)
    ctx = SimpleNamespace(
        spec=spec, counters=counters, device={"kind": "TPU v5 lite"},
        say=lambda what, **kv: None)
    return ctx


def test_zonal_tile_device_per_tile_and_roofline_share(monkeypatch):
    spec = Spec(REPO)
    reader = spec.module("readers", "zonal_tile_device")
    ops, modules = [], []
    for i in range(3):  # three tiles: probe 4 ms + 1 ms, fold 2 ms
        t = i * 0.02 * S
        modules += [("jit_zones_probe(1)", t, t + 0.006 * S),
                    ("jit_zones_fold(2)", t + 0.007 * S, t + 0.009 * S)]
        ops += [("%fusion.1 = f32[65536]{0} fusion()", t, t + 0.004 * S),
                ("%fusion.2 = f64[65536,2]{1,0} fusion()", t + 0.004 * S,
                 t + 0.005 * S),
                ("%scatter.3 = f64[257]{0} scatter()", t + 0.007 * S,
                 t + 0.009 * S)]
    tr = {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
          "program": [("raster.zonal", 0.0, 1.0, None)]}
    table = {
        "jit_zones_probe": {"fusion.1 f32[65536]": "pip.tier1",
                            "fusion.2 f64[65536,2]": "zonal.centers"},
        "jit_zones_fold": {"scatter.3 f64[257]": "zonal.fold"},
    }
    counters = {"tile_pixels": 65536, "zones": 256}
    ctx = _reader_ctx(spec, monkeypatch, tr, table, counters)
    probe = {"stage": ["zonal.centers", "pip.tier1", "pip.cells"],
             "module": "jit_zones_probe"}
    assert reader.read(ctx, probe) == pytest.approx(5.0)
    fold = {"stage": "zonal.fold", "module": "jit_zones_fold"}
    assert reader.read(ctx, fold) == pytest.approx(2.0)
    share = reader.read(ctx, dict(fold, measure="hbm_share"))
    least_s = (65536 * 12 + 4 * 256 * 8) / 819e9
    assert reader.fold_bytes(65536, 256) == 65536 * 12 + 8192
    assert share == pytest.approx(100 * least_s / 0.002) and share < 100
    # a program without these scopes, a module that never ran, no counters
    assert reader.read(ctx, dict(fold, stage="zonal.nope")) is None
    assert reader.read(ctx, dict(fold, module="jit_other")) is None
    bare = _reader_ctx(spec, monkeypatch, tr, table, {})
    assert reader.read(bare, dict(fold, measure="hbm_share")) is None
    monkeypatch.setattr(
        spec.module("readers", "_trace"), "of_run", lambda ctx: None)
    assert reader.read(ctx, fold) is None


def _fold_events(*lanes):
    """One ``raster.zonal`` span a scan, oldest first, as the program emits
    them (`mosaic_tpu/raster/zonal.py`: ``fold_lane``, ``values_dtype``)."""
    events = [{"event": "span", "name": "join.pip", "seconds": 0.1}]
    for i, (lane, dtype) in enumerate(lanes):
        e = {"event": "span", "name": "raster.zonal", "seconds": 0.4,
             "ts_mono": float(i), "lane": "device"}
        if lane is not None:
            e.update(fold_lane=lane, values_dtype=dtype)
        events.append(e)
    events.append({"event": "span", "name": "raster.tile", "seconds": 0.01})
    return events


@pytest.mark.parametrize("lanes, widths, tile_bytes", [
    # the int32 lane (PR 28): int16 pixels at their own width, int32 sums
    ([("int32", "int16")], (2, 4), 397_312),
    ([("int32", "uint8")], (1, 4), 65536 * 5 + 4 * 256 * 4),
    # the wide lane, and a program from before PR 28 that names no lane
    ([("wide", "float64")], (8, 8), 794_624),
    ([(None, None)], (8, 8), 794_624),
    ([], (8, 8), 794_624),
    # the newest event decides, either way
    ([("wide", "float64"), ("int32", "int16")], (2, 4), 397_312),
    ([("int32", "int16"), ("wide", "float64")], (8, 8), 794_624),
], ids=["int32-int16", "int32-uint8", "wide", "no-lane-named", "no-event",
        "newest-int32", "newest-wide"])
def test_fold_widths_follow_the_lane_the_program_names(lanes, widths,
                                                       tile_bytes):
    reader = Spec(REPO).module("readers", "zonal_tile_device")
    assert reader.fold_widths(_fold_events(*lanes)) == widths
    assert reader.fold_bytes(65536, 256, *widths) == tile_bytes


def test_fold_hbm_share_prices_the_lane_that_folded(monkeypatch):
    """`fold_hbm_share.zonal` through `read`: the same trace reads twice the
    share on the wide lane's bytes as on the int32 lane's int16 pixels — a
    mistyped field name would fall back to the wide price unseen."""
    spec = Spec(REPO)
    reader = spec.module("readers", "zonal_tile_device")
    modules = [("jit_zones_fold(2)", 0.0, 0.002 * S)]
    ops = [("%scatter.3 = s32[257]{0} scatter()", 0.0, 0.002 * S)]
    tr = {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
          "program": [("raster.zonal", 0.0, 1.0, None)]}
    table = {"jit_zones_fold": {"scatter.3 s32[257]": "zonal.fold"}}
    desc = spec.data("layer_metrics", "fold_hbm_share.zonal")
    shares = {}
    for lane, dtype in (("int32", "int16"), ("wide", "float64")):
        ctx = _reader_ctx(spec, monkeypatch, tr, table,
                          {"tile_pixels": 65536, "zones": 256})
        ctx.events = _fold_events((lane, dtype))
        shares[lane] = reader.read(ctx, desc["params"])
    assert shares["int32"] == pytest.approx(100 * (397_312 / 819e9) / 0.002)
    assert shares["wide"] == pytest.approx(100 * (794_624 / 819e9) / 0.002)
    # the program's own span carries the two fields under these names
    import inspect

    from mosaic_tpu.raster import zonal

    said = inspect.getsource(zonal)
    assert "fold_lane=fold" in said and "values_dtype=stage_dt.name" in said


def test_the_real_cell_lists_every_zonal_metric_and_the_shared_ones():
    spec = Spec(REPO)
    assert [m["name"] for m in spec.end_to_end("modis-zonal.scan")] == \
        ["setup_s", "batch_rows_per_s"]
    names = {m["name"] for m in spec.per_layer("modis-zonal.scan")}
    # at least these: a later PR may append an entry that lists the cell
    assert names >= set(ZONAL_METRICS) | set(SHARED_METRICS)
    cfg = spec.config("modis-zonal")
    assert cfg["scene"]["height"] == cfg["scene"]["width"] == 2400
    assert cfg["batch_rows_per_chip"] == 2400 * 2400
    assert cfg["zones"] == spec.config("taxi-zones-h3r9")["zones"]
    assert spec.traffic("scenes-host")["arguments"] == {}
    assert set(cfg["reduced"]) == {"scenes"}


# --------------------------------------------- the trace recorded on the chip

FIXTURE = os.path.join(REPO, "benchmark", "fixtures", "modis_zonal_v5e")
#: the tile metrics the recorded fixture holds (its trace: 16 tiles of the
#: first timed scan; its events: the last half second of the second)
RECORDED_TILE_METRICS = [
    "tile_cycle_p50_ms.zonal", "probe_pull_p50_ms.zonal",
    "host_patch_p50_ms.zonal", "fold_launch_p50_ms.zonal",
    "drain_p50_ms.zonal", "scene_p50_ms.zonal",
    "probe_device_ms_per_tile.zonal", "fold_device_ms_per_tile.zonal",
    "fold_hbm_share.zonal",
]


@pytest.fixture(scope="module")
def recorded():
    import gzip

    spec = Spec(REPO)
    with open(os.path.join(FIXTURE, "result.json"), encoding="utf-8") as f:
        result = json.load(f)
    with gzip.open(os.path.join(FIXTURE, "events.jsonl.gz"), "rt",
                   encoding="utf-8") as f:
        events = [json.loads(line) for line in f]
    with open(os.path.join(FIXTURE, "stage_tables.json"), encoding="utf-8") as f:
        tables = json.load(f)
    tr = spec.module("readers", "_trace").load(
        os.path.join(FIXTURE, "trace.xplane.pb.gz"))
    return SimpleNamespace(spec=spec, result=result, events=events,
                           tables=tables, tr=tr)


def test_recorded_tiles_run_both_programs_under_the_tiles_spans(recorded):
    tr = recorded.tr
    assert list(tr["devices"]) == ["/device:TPU:0"]
    names = {p[0] for p in tr["program"]}
    assert {"raster.zonal", "raster.probe", "raster.patch", "raster.fold",
            "stream.pipeline.drain"} <= names
    dev = tr["devices"]["/device:TPU:0"]
    runs = [m[0].split("(")[0] for m in dev["modules"]]
    assert set(runs) == {"jit_zones_probe", "jit_zones_fold"} <= set(recorded.tables)
    kind = recorded.spec.module("traffic_kinds", "raster_scene_scan")
    # the profiler covered TRACE_TILES launches, give or take the tile
    # under way when it started and stopped
    assert abs(runs.count("jit_zones_probe") - kind.TRACE_TILES) <= 2
    assert abs(runs.count("jit_zones_fold") - kind.TRACE_TILES) <= 2
    # every traced op has a stage of the program's own
    staged = {k for t in recorded.tables.values() for k, v in t.items()
              if v != "unscoped"}
    from benchmark.harness import xplane

    labels = {xplane.op_label(op[0]) for op in dev["ops"]}
    assert len(labels - staged) <= 0.02 * len(labels), sorted(labels - staged)


@pytest.mark.parametrize("name", RECORDED_TILE_METRICS)
def test_recorded_tiles_read_every_tile_metric(recorded, monkeypatch, name):
    from mosaic_tpu.obs import stages

    spec = recorded.spec
    ctx = _reader_ctx(spec, monkeypatch, recorded.tr, recorded.tables,
                      {"tile_pixels": 256 * 256, "zones": 256})
    ctx.events = recorded.events
    ctx.window = tuple(recorded.result["window"])
    desc = spec.data("layer_metrics", name)
    value = spec.module("readers", desc["reader"]).read(ctx, desc["params"])
    assert value is not None and value > 0.0
    unit = next(m["unit"] for m in spec.benchmark["per_layer"]
                if m["name"] == name)
    if unit == "%":
        assert value < 1.0, "the f64 scatter is nowhere near the roofline"
    elif name == "scene_p50_ms.zonal":
        assert 1000.0 < value < 10000.0
    else:
        assert value < 60.0, "milliseconds of one tile's piece"
    # the run that recorded the fixture read the same number from the same
    # trace (the span metrics read the events of its own window)
    if desc["reader"] == "zonal_tile_device":
        then = recorded.result["line"]["metrics"][name]
        assert value == pytest.approx(then["value"], rel=1e-6)
