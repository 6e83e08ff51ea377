"""The BNG parcel-overlay cell (`bng-parcels.overlay`) rehearsed on the CPU
at a small size: a temporary copy of the benchmark to which a tiny parcel
deployment is ADDED as new files and appended entries (the real
configuration's builder, reference, traffic kind, generators and metrics;
3,000 parcels in a 2 x 2 km box on the British National Grid at 100 m
cells, a 15-district partition and one river's three flood bands). The
cell's files resolve, the sound run reads correct, both lower-precision
controls and a broken path do not, a program without the fan kernel is
refused at once, the reference agrees with areas known in closed form, and
every metric this cell brought returns None where there is nothing to read."""

import json
import os
import shutil
import time

import numpy as np
import pytest

from bh_fixtures import REPO, _snapshot, _write

from benchmark.harness.run_cell import run_cell
from benchmark.harness.spec import Spec
from test_benchmark_program_spans import _ctx, check_entry

CELL, REAL = "tiny.overlay", "bng-parcels.overlay"
NEW_METRICS = [
    "call_p50_ms.overlay", "count_ms_per_call.overlay",
    "emit_ms_per_call.overlay", "launch_pull_ms_per_call.overlay",
    "glue_ms_per_call.overlay", "host_override_ms_per_call.overlay",
    "host_overridden_share.overlay", "clip_row_share.overlay",
    "fan_row_share.overlay", "device_busy_ms_per_call.overlay",
    "clip_device_ms_per_call.overlay", "candidates_device_ms_per_call.overlay",
    "clip_hbm_share.overlay",
]
#: entries this cell shares with other cells since PR 47 (one entry per
#: reader, parameters and moved metric; `test_benchmark_shared_entries.py`)
SHARED_METRICS = ["device_idle.batch", "compiles_in_window.batch",
                  "tessellate_s.build", "index_build_s", "warmup_s"]
BOX = [530000, 180000, 532000, 182000]


def make_copy(tmp) -> str:
    root = os.path.join(str(tmp), "copy")
    os.makedirs(root)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    shutil.copytree(
        os.path.join(REPO, "benchmark"), os.path.join(root, "benchmark"),
        ignore=shutil.ignore_patterns("__pycache__", ".traces", ".cache"),
    )
    before = _snapshot(root)
    tree = os.path.join(root, "benchmark")
    real = Spec(REPO).config("bng-parcels-100m")
    _write(os.path.join(tree, "configs", "tiny-parcels.json"), {
        "source": "test fixture", "rehearsal": True, "row": real["row"],
        "deployment": real["deployment"], "reference": real["reference"],
        "index_system": real["index_system"],
        "resolution": real["resolution"], "measure": real["measure"],
        "parcels": {"count": 3000, "box": BOX, "seed": 40},
        "chips": 1, "mesh": None, "reduced": {},
    })
    mix = Spec(REPO).traffic("themes-host")
    mix.pop("name")
    mix["layers"] = [
        {"name": "districts", "generator": "districts",
         "params": {"grid": [3, 5]}},
        {"name": "flood", "generator": "flood",
         "params": {"rivers": 1, "islands_per_river": 3,
                    "meander_m": [60, 120], "wavelength_m": [900, 1500]}},
    ]
    _write(os.path.join(tree, "traffic", "tiny-themes.json"), mix)
    # the box is a sixth of the real one across, so the float32 lattice of
    # its one frame is 8 times finer: the limit comes down with it (the
    # CPU's f64 lane reads 1e-13 here, that control 1.9e-7)
    check = dict(Spec(REPO).cell(REAL)["check"], sample_parcels=256,
                 max_area_error=2e-8)
    _write(os.path.join(tree, "workloads", CELL + ".json"), {"check": check})
    path = os.path.join(root, "BENCHMARK.json")
    with open(path, encoding="utf-8") as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-parcels", "source": "test fixture",
        "file": "benchmark/configs/tiny-parcels.json", "reduced": [],
        "why": "test fixture",
    })
    bench["workloads"].append({
        "name": CELL, "config": "tiny-parcels", "traffic": "tiny-themes",
        "chips": 1, "why": "test fixture",
    })
    for m in bench["end_to_end"] + bench["per_layer"]:
        if REAL in m.get("workloads", []):
            m["workloads"].append(CELL)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(bench, f, indent=1)
    after = _snapshot(root)
    changed = [p for p, h in before.items()
               if p != "BENCHMARK.json" and after.get(p) != h]
    assert not changed, f"the fixture edited existing files: {changed}"
    return root


@pytest.fixture()
def root(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    return make_copy(tmp_path)


def _run(root, seed, **kw):
    return run_cell(root, CELL, seed, 0.3, False,
                    t_start=time.perf_counter(), rehearsal=True, **kw)


def test_the_cells_files_resolve():
    spec = Spec(REPO)
    cell = spec.cell(REAL)
    assert cell["chips"] == 1 and cell["traffic"] == "themes-host"
    cfg = spec.config(cell["config"])
    assert cfg["index_system"] == "BNG" and cfg["resolution"] == 4
    assert cfg["mesh"] is None and set(cfg["reduced"]) == {"parcels"}
    for key in ("source", "assumed", "precision", "guarantees"):
        assert cfg[key]
    # the source names the reference's two pieces (the BNG join, the area
    # aggregate the measure carries it to) and the public layers behind the
    # sizes, and the file says why the measure and not the predicate is timed
    for word in ("BritishNationalGrid.py", "ST_IntersectionAggregate",
                 "ONS LSOA 2021", "EA Flood Zones", "HMLR INSPIRE"):
        assert word in cfg["source"]
    assert set(cfg["public_data"]) >= {"districts", "flood", "parcels", "box"}
    assert set(cfg["operation"]) == {
        "notebook", "timed", "why_the_measure", "not_timed"}
    # 384 districts of an urban LSOA's size tile the box
    x0, y0, x1, y1 = cfg["parcels"]["box"]
    grid = spec.traffic(cell["traffic"])["layers"][0]["params"]["grid"]
    km2 = (x1 - x0) * (y1 - y0) / 1e6 / (grid[0] * grid[1])
    assert km2 == pytest.approx(1572 / 4994, rel=0.02)
    mix = spec.traffic(cell["traffic"])
    assert [lay["name"] for lay in mix["layers"]] == ["districts", "flood"]
    assert len(mix["control"]["kinds"]) == 2
    for registry, name in (
        ("deployments", cfg["deployment"]), ("references", cfg["reference"]),
        ("traffic_kinds", mix["kind"]), ("generators", "parcels"),
        ("generators", "themes"),
    ):
        assert spec.module(registry, name)
    themes = spec.module("generators", "themes")
    assert all(hasattr(themes, lay["generator"]) for lay in mix["layers"])
    reported = {m["name"] for m in spec.end_to_end(REAL)}
    assert reported == {"batch_rows_per_s", "setup_s"}
    mine = {m["name"] for m in spec.per_layer(REAL)}
    # at least these: a later PR may append an entry that lists the cell
    assert mine >= set(NEW_METRICS) | set(SHARED_METRICS)
    assert set(cell["check"]) >= {"sample_parcels", "touch_area_m2",
                                  "max_area_error", "why"}


@pytest.mark.parametrize("seed", [41, 4_000_000_778])
def test_sound_run_is_correct_and_both_controls_are_not(root, seed, capsys):
    line = _run(root, seed)
    said = capsys.readouterr().out
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"batch_rows_per_s", "setup_s"}
    assert line["attempted"] > 3000
    assert line["checks"]["overlay_touch_pairs_nonzero"]["value"] == 0
    assert "control=None" in said
    # an odd seed takes the mix's second control, an even one its first
    for s, name in ((seed, None), (seed + 1, None)):
        control = _run(root, s, control=True)
        assert control["correct"] is False
        assert control["checks"]["overlay_area_error"]["value"] > \
            control["checks"]["overlay_area_error"]["limit"]
    said = capsys.readouterr().out
    assert "control=float32_global_frame" in said
    assert "control=float32\n" in said or "control=float32 " in said


def test_a_fold_altered_where_the_areas_are_summed_is_caught(root, monkeypatch):
    """The fused program's fold hands every geometry pair a thousandth
    more than its rows sum to: the pairs are all there, touches still read
    0.0, the area comparison with the reference catches it."""
    from mosaic_tpu.expr import compile as compiler

    real = compiler.zonal_fold_masked

    def altered(values, *a, **kw):
        cnt, s, mn, mx = real(values, *a, **kw)
        return cnt, s * 1.001, mn, mx

    monkeypatch.setattr(compiler, "zonal_fold_masked", altered)
    compiler.overlay_program.cache_clear()
    try:
        line = _run(root, 43)
    finally:
        compiler.overlay_program.cache_clear()
    assert line["correct"] is False and line["attempted"] > 0
    checks = line["checks"]
    assert checks["overlay_area_error"]["value"] > checks["overlay_area_error"]["limit"]
    assert checks["overlay_pairs_missing"]["value"] == 0


def test_a_program_without_the_fan_kernel_is_refused_at_once(root, monkeypatch):
    """The parent commit with these files: the builder raises before a
    layer is made and before anything compiles."""
    from mosaic_tpu.kernels import overlay as kernels

    gen = Spec(root).module("generators", "parcels")
    monkeypatch.setattr(gen, "fabric",
                        lambda *a, **k: pytest.fail("a layer was made"))
    monkeypatch.delattr(kernels, "fan_area")
    t0 = time.perf_counter()
    with pytest.raises(RuntimeError, match="fan kernel"):
        _run(root, 44)
    assert time.perf_counter() - t0 < 5.0


def test_reference_reads_areas_known_in_closed_form():
    ref = Spec(REPO).module("references", "overlay_bruteforce")
    sq = lambda x0, y0, s: np.array(  # noqa: E731
        [[x0, y0], [x0 + s, y0], [x0 + s, y0 + s], [x0, y0 + s]], float)
    a = [sq(0, 0, 10)]
    assert ref.area_of(a) == 100.0
    e = ref.edges_of
    assert ref.intersection_area(e(a), e([sq(4, 3, 10)])) == 6 * 7
    # a touch along an edge, at a vertex, and apart: exactly nothing
    for other in (sq(10, 0, 5), sq(10, 10, 5), sq(20, 20, 5)):
        assert ref.intersection_area(e(a), e([other])) == 0.0
    # a hole is a ring that runs the other way
    holed = [sq(0, 0, 10), sq(2, 2, 4)[::-1]]
    assert ref.area_of(holed) == 84.0
    assert ref.intersection_area(e(holed), e([sq(0, 0, 5)])) == 25.0 - 9.0
    # a triangle against a rotated square: one crossing an edge pair
    tri = [np.array([[0.0, 0.0], [8.0, 0.0], [0.0, 8.0]])]
    dia = [np.array([[4.0, -4.0], [8.0, 0.0], [4.0, 4.0], [0.0, 0.0]])]
    assert ref.intersection_area(e(tri), e(dia)) == pytest.approx(16.0)
    # a non-convex L, far from the origin: translation changes nothing
    ell = np.array([[0, 0], [6, 0], [6, 2], [2, 2], [2, 6], [0, 6]], float)
    far = np.array([530000.0, 180000.0])
    got = ref.intersection_area(e([ell + far]), e([sq(1, 1, 4) + far]))
    assert got == pytest.approx(4 + 3, abs=1e-9)
    pi, qi, ar = ref.overlay([a, [sq(50, 50, 2)]], [0, 1],
                             [[sq(4, 3, 10)], [sq(9, 9, 60)]])
    assert list(zip(pi, qi, ar)) == [(0, 0, 42.0), (0, 1, 1.0), (1, 1, 4.0)]


def test_generators_make_what_the_configuration_says():
    spec = Spec(REPO)
    parcels = spec.module("generators", "parcels")
    themes = spec.module("generators", "themes")
    rings, layout = parcels.fabric({"count": 3000, "box": BOX, "seed": 40})
    again, _ = parcels.fabric({"count": 3000, "box": BOX, "seed": 40})
    assert all(np.array_equal(a, b) for a, b in zip(rings, again))
    verts = parcels.vertex_counts(rings)
    assert verts.min() == 4 and verts.max() <= 12 and (verts > 4).mean() > 0.2
    ref = spec.module("references", "overlay_bruteforce")
    assert all(ref.area_of([r]) > 0 for r in rings[:200])  # counter-clockwise
    d, stats = themes.districts(layout, {"grid": [3, 5]}, 7)
    assert stats["districts"] == 15 and stats["along_parcel_share"] >= 1 / 3
    box_area = (BOX[2] - BOX[0]) * (BOX[3] - BOX[1])
    assert sum(themes.polygon_area(p) for p in d) == pytest.approx(box_area)
    # neighbours share one chain: a partition, so the districts' shares of
    # any parcel sum to the parcel
    pi, _qi, ar = ref.overlay([[r] for r in rings], np.arange(0, 3000, 37), d)
    total = np.zeros(3000)
    np.add.at(total, pi, ar)
    for i in np.arange(0, 3000, 37):
        assert total[i] == pytest.approx(ref.area_of([rings[i]]), abs=1e-8)
    other, _ = themes.districts(layout, {"grid": [3, 5]}, 8)
    assert not np.array_equal(d[0][0], other[0][0])
    f, fs = themes.flood(layout, {"rivers": 1, "islands_per_river": 3}, 7)
    assert fs["polygons"] == 3 and fs["islands"] == 3
    areas = [themes.polygon_area(p) for p in f]
    assert areas[0] < areas[1] < areas[2] and all(len(p) == 4 for p in f)
    # valid polygons on every seed: an island lies inside the innermost band
    # and no two overlap (a parcel on two islands at once would read a
    # negative area, in the program and in the reference alike)
    big = parcels.fabric({"count": 300, "seed": 40,
                          "box": [524000, 175000, 536000, 185000]})[1]
    for layout_, params, seeds in ((layout, {"rivers": 1, "islands_per_river": 3}, range(20)),
                                   (big, {}, [0, 1, 2, 4040104732])):
        for seed in seeds:
            for rings in themes.flood(layout_, params, seed)[0][::3]:
                outer, holes = ref.edges_of(rings[:1]), rings[1:]
                for i, h in enumerate(holes):
                    land = ref.edges_of([h[::-1]])
                    assert ref.intersection_area(land, outer) == \
                        pytest.approx(themes.polygon_area([h]), rel=1e-9)
                    for other in holes[i + 1:]:
                        assert ref.intersection_area(
                            land, ref.edges_of([other[::-1]])) == 0.0


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_metric_reads_nothing_on_an_empty_run(name):
    spec = Spec(REPO)
    entry = next(m for m in spec.benchmark["per_layer"] if m["name"] == name)
    assert REAL in entry["workloads"]
    check_entry(spec, name)
    # nor on a run of a program whose overlay has no root span, no counter
    # and no prepare span that names its pad
    desc = spec.data("layer_metrics", name)
    ctx = _ctx(spec, events=[
        {"event": "span", "name": "overlay.measures", "seconds": 0.1,
         "ts_mono": 1.0, "host_overridden": 3},
        {"event": "span", "name": "overlay.prepare", "seconds": 0.1,
         "ts_mono": 0.5},
    ], counters={"traced_steps": 2}, series={"traced_calls": [{}, {}]})
    assert spec.module("readers", desc["reader"]).read(
        ctx, desc["params"]) is None


def test_clip_hbm_share_prices_each_traced_call_at_its_own_pad(monkeypatch):
    spec = Spec(REPO)
    mod = spec.module("readers", "clip_hbm_share")
    assert mod.row_bytes(16, 4) == 272 and mod.row_bytes(12, 8) == 400
    districts = {"name": "overlay.call", "clip_rows": 210_000, "vpad": 16,
                 "acc": "float32"}
    flood = {"name": "overlay.call", "clip_rows": 60_000, "vpad": 12,
             "acc": "float32"}
    ctx = _ctx(
        spec, counters={"traced_steps": 2},
        series={"traced_calls": [districts, flood]},
        device={"kind": "TPU v5 lite"},
    )
    busy = spec.module("readers", "trace_stage_busy")
    asked = []

    def read(c, params):
        asked.append(params)
        return 10.0  # ms of the three scopes, a traced call

    monkeypatch.setattr(busy, "read", read)
    got = mod.read(ctx, {})
    assert asked == [{"stage": mod.STAGES, "steps": "traced_steps"}]
    moved = 210_000 * 272 + 60_000 * 208
    assert got == pytest.approx(100 * moved / 819e9 / 0.020)
    (said,) = [kv for what, kv in ctx.said if what == "clip_bytes"]
    assert said["vpad"] == [16, 12] and sum(said["bytes"]) == moved
    # the order of the pool's layers in the traced pass changes nothing
    ctx.series["traced_calls"] = [flood, districts]
    assert mod.read(ctx, {}) == pytest.approx(got)
    # a call whose span names no pad (an older program) is not priced
    ctx.series["traced_calls"] = [{"clip_rows": 5}, {}]
    assert mod.read(ctx, {}) is None
    ctx.series["traced_calls"] = [districts, flood]
    monkeypatch.setattr(busy, "read", lambda c, p: None)
    assert mod.read(ctx, {}) is None


def _call(sid, ts, right_rows, seconds, **children):
    """One overlay.call root on a layer of ``right_rows`` rows and its
    direct children (name -> seconds)."""
    def span(name, i, parent, s, **kw):
        return {"event": "span", "name": name, "span_id": i,
                "parent_id": parent, "seconds": s, "ts_mono": ts, **kw}

    return [span("overlay.call", sid, None, seconds, right_rows=right_rows)] + [
        span("overlay." + k, f"{sid}.{k}", sid, v) for k, v in children.items()
    ]


@pytest.mark.parametrize("calls", [4, 5, 6, 7, 23])
def test_per_call_metrics_do_not_read_the_parity_of_the_call_count(calls):
    """The loop alternates a 1.15 s districts call and a 0.61 s flood call:
    a percentile over all of them reads one layer or the other by the
    parity of the count; grouped by the layer it reads their mean."""
    spec = Spec(REPO)
    events = []
    for i in range(calls):
        districts = i % 2 == 0
        events += _call(
            f"c{i}", 10.0 + i, 17_250 if districts else 8_430,
            1.15 if districts else 0.61,
            count=0.24 if districts else 0.23,
            launch=0.01, pull=0.33 if districts else 0.05,
        )
    ctx = _ctx(spec, events=events)

    def read(name):
        desc = spec.data("layer_metrics", name)
        assert desc["reader"] == "span_child_by_group"
        assert desc["params"]["by"] == "right_rows"
        return spec.module("readers", desc["reader"]).read(ctx, desc["params"])

    assert read("call_p50_ms.overlay") == pytest.approx(880.0)
    assert read("count_ms_per_call.overlay") == pytest.approx(235.0)
    assert read("launch_pull_ms_per_call.overlay") == pytest.approx(200.0)
    # no call recorded such a child: nothing to read, as on the parent
    assert read("glue_ms_per_call.overlay") is None
    (said,) = [kv for what, kv in ctx.said
               if what == "span_by_group" and kv["child"] == "overlay.call"]
    assert said["17250"] == f"1150.0/{(calls + 1) // 2}"
    assert said["8430"] == f"610.0/{calls // 2}"
    # the mixed percentile it replaces does read the parity
    mixed = spec.module("readers", "span_child_percentile").read(
        ctx, {"root": "overlay.call", "child": "overlay.call", "q": 0.5,
              "scale": 1000})
    assert mixed == pytest.approx(1150.0 if calls % 2 else 610.0)


def test_span_child_by_group_window_and_ungrouped_roots():
    spec = Spec(REPO)
    mod = spec.module("readers", "span_child_by_group")
    p = {"root": "overlay.call", "child": "overlay.count", "by": "right_rows",
         "q": 0.5, "scale": 1000}
    events = _call("a", 10.0, 5, 1.0, count=0.2) + _call("b", 11.0, 7, 1.0, count=0.4)
    # a root outside the window (a warm-up call) is not read
    events += _call("w", 500.0, 7, 9.0, count=9.0)
    assert mod.read(_ctx(spec, events=events), p) == pytest.approx(300.0)
    # roots without the field form one group: the plain percentile
    for e in events:
        e.pop("right_rows", None)
    assert mod.read(_ctx(spec, events=events), p) == pytest.approx(200.0)
    assert mod.read(_ctx(spec), p) is None


def test_device_busy_is_the_mean_of_the_traced_pass():
    spec = Spec(REPO)
    desc = spec.data("layer_metrics", "device_busy_ms_per_call.overlay")
    ctx = _ctx(spec, counters={"traced_steps": 2},
               trace_reduction={"devices": 1, "busy_s": 0.95 + 0.45})
    assert spec.module("readers", desc["reader"]).read(
        ctx, desc["params"]) == pytest.approx(700.0)
