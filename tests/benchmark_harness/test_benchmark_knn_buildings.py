"""The building-footprint KNN cell (`nyc-knn-buildings.transform`) rehearsed
on the CPU at a small size: a temporary copy of the benchmark to which a
tiny deployment is ADDED as new files and appended entries (the real
configuration's builder, reference, traffic kind, generators and metrics; a
custom grid of 100 m cells, 6,000 clustered candidates, a 600-footprint
fabric, 2 tables of 256). The cell's files resolve, the sound run reads
correct, both controls and a broken path do not, a program without the
polygon block lane is refused at once, the reference agrees with distances
known in closed form and with itself unpruned, and every metric this cell
brought returns None where there is nothing to read."""

import json
import os
import shutil
import time

import numpy as np
import pytest

from bh_fixtures import REPO, _snapshot, _write

from benchmark.harness.run_cell import run_cell
from benchmark.harness.spec import Spec
from test_benchmark_program_spans import _ctx, _span, check_entry

CELL, REAL = "tiny.knn-buildings", "nyc-knn-buildings.transform"
NEW_METRICS = [
    "cover_ms_per_call.knn", "landmark_put_ms_per_call.knn",
    "seeds_per_landmark.knn", "edge_occupancy.knn",
    "edges_device_ms_per_call.knn", "edge_pair_hbm_share.knn",
]
SHARED_METRICS = ["index_build_s", "warmup_s"]
#: the sibling cell's `.knn` entries this cell's spans, counters and trace
#: feed too: since PR 47 the cell stands in their ``workloads`` (until then
#: six of them were twins under `.knn-buildings` names: none is left), and
#: in the entries every host-fed cell shares
SIBLING_METRICS = [
    "call_p50_ms.knn", "expand_ms_per_call.knn", "distance_ms_per_call.knn",
    "merge_ms_per_call.knn", "enqueue_ms_per_call.knn", "pull_ms_per_call.knn",
    "overlap_ms_per_call.knn", "slabs_per_call.knn", "iterations_per_call.knn",
    "launches_per_call.knn", "pulled_rows_per_call.knn",
    "device_busy_ms_per_call.knn",
]
HOST_FED_METRICS = ["device_idle.batch", "compiles_in_window.batch",
                    "pool_build_s.batch"]
#: these reckon the point block kernel (the edge kernel has its own three)
POINT_ONLY = ["pair_hbm_share.knn", "pairs_per_landmark.knn",
              "pair_occupancy.knn"]
#: at resolution 10 cells of 9.8e-4 degrees, the size of a large footprint
GRID, RES = "CUSTOM(-75,-73,40,42,2,1,1)", 10
CENTRE = [-74.02, 40.48]
BOX = [-74.03, 40.474, -74.01, 40.486]


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
    real = Spec(REPO).config("nyc-knn-buildings-h3r10")
    _write(os.path.join(tree, "configs", "tiny-knn-buildings.json"), {
        "source": "test fixture", "rehearsal": True, "row": real["row"],
        "deployment": real["deployment"], "reference": real["reference"],
        "index_system": GRID, "resolution": RES,
        "candidates": {
            "count": 6000, "seed": 5, "bbox": BOX,
            "points": {"hotspot_share": 0.6, "hotspots": 4, "zipf_s": 1.1,
                       "sigma_m": [100, 400], "lat0_deg": 40.5,
                       "layout_seed": 9},
        },
        "landmarks": dict(real["landmarks"], count=600, centre=CENTRE),
        "model": real["model"],
        "batch_rows_per_chip": 256, "chips": 1, "mesh": None,
        "reduced": {},
    })
    mix = Spec(REPO).traffic("footprints-host")
    mix.pop("name")
    _write(os.path.join(tree, "traffic", "tiny-footprints.json"), mix)
    check = dict(Spec(REPO).cell(REAL)["check"], sample_landmarks=96)
    _write(os.path.join(tree, "workloads", CELL + ".json"), {"check": check})
    path = os.path.join(root, "BENCHMARK.json")
    with open(path, encoding="utf-8") as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-knn-buildings", "source": "test fixture",
        "file": "benchmark/configs/tiny-knn-buildings.json", "reduced": [],
        "why": "test fixture",
    })
    bench["workloads"].append({
        "name": CELL, "config": "tiny-knn-buildings",
        "traffic": "tiny-footprints", "chips": 1, "why": "test fixture",
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
    assert cell["chips"] == 1 and cell["traffic"] == "footprints-host"
    assert cell["config"] == "nyc-knn-buildings-h3r10"
    cfg = spec.config(cell["config"])
    assert cfg["index_system"] == "H3" and cfg["resolution"] == 10
    assert cfg["mesh"] is None and cfg["reduced"] == {}
    assert cfg["batch_rows_per_chip"] == 50000
    for key in ("source", "assumed", "precision", "guarantees"):
        assert cfg[key]
    for word in ("SpatialKNN.scala:202-235", "GridRingNeighbours.scala:76-99",
                 "building polygons as landmarks", "res 10"):
        assert word in cfg["source"]
    assert len(cfg["source"]) <= 200
    # the candidates are the sibling configuration's, key for key
    sibling = spec.config("nyc-knn-h3r10")
    for key in ("count", "seed", "bbox", "points"):
        assert cfg["candidates"][key] == sibling["candidates"][key]
    assert cfg["model"] == sibling["model"]
    # the fabric is the buildings configuration's, at another centre
    osm = spec.config("osm-buildings-h3r11")["buildings"]
    assert cfg["landmarks"]["seed"] == osm["seed"]
    assert cfg["landmarks"]["count"] == 131072
    assert cfg["landmarks"]["centre"] == [-74.0195, 40.4825]
    assert cfg["guarantees"]["host_landmarks"] == 0
    mix = spec.traffic(cell["traffic"])
    assert mix["pool_tables"] == 2
    assert mix["pool_tables"] * cfg["batch_rows_per_chip"] <= 131072
    assert mix["control"]["kinds"] == ["float32", "first_vertex"]
    for registry, name in (
        ("deployments", cfg["deployment"]), ("references", cfg["reference"]),
        ("traffic_kinds", mix["kind"]), ("generators", "buildings"),
        ("generators", "points"), ("readers", "edge_pair_hbm_share"),
    ):
        assert spec.module(registry, name)
    assert [m["name"] for m in spec.end_to_end(REAL)] == \
        ["setup_s", "batch_rows_per_s"]
    mine = {m["name"] for m in spec.per_layer(REAL)}
    assert mine >= set(NEW_METRICS + SHARED_METRICS + SIBLING_METRICS
                       + HOST_FED_METRICS)
    assert not mine & set(POINT_ONLY)
    assert not [n for n in mine if n.endswith(".knn-buildings")]
    limits = cell["check"]
    assert limits["sample_landmarks"] == 512 and limits["why"]
    assert 0 < limits["max_distance_error"] < 1e-9
    # one slot of the sample and no more
    slots = 2 * limits["sample_landmarks"] * cfg["model"]["k_neighbours"]
    assert 1 / slots <= limits["max_wrong_share"] < 2 / slots


def test_sound_run_is_correct_and_both_controls_are_not(root, capsys):
    line = _run(root, 41)
    said = capsys.readouterr().out
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"batch_rows_per_s", "setup_s"}
    assert line["attempted"] >= 256
    assert line["checks"]["host_landmarks"] == {"value": 0.0, "limit": 0.0}
    win = next(s for s in said.splitlines() if "[bench] knn_window:" in s)
    field = lambda name: json.loads(  # noqa: E731
        win.split(name + "=", 1)[1].split("]", 1)[0] + "]")
    assert set(field("unrested")) == {0} and set(field("host_landmarks")) == {0}
    # several seeds a footprint, real edges among the padded ones
    assert min(field("seeds")) > 256
    assert all(0 < e < p for e, p in zip(field("edge_pairs"),
                                         field("edge_pairs_padded")))
    ref = next(s for s in said.splitlines() if "[bench] reference:" in s)
    assert int(ref.split("slots_at_zero=")[1].split()[0]) > 0
    ready = next(s for s in said.splitlines() if "[bench] knn_ready:" in s)
    assert "control=None" in ready and "index_dtype=float64" in ready
    # an even seed reads the float32 control, an odd one the first vertices
    f32 = _run(root, 42, control=True)
    said = capsys.readouterr().out
    assert "control=float32" in said and "index_dtype=float32" in said
    assert f32["correct"] is False
    assert f32["checks"]["knn_distance_error"]["value"] > 1e-10
    vertex = _run(root, 43, control=True)
    assert "control=first_vertex" in capsys.readouterr().out
    assert vertex["correct"] is False
    assert vertex["checks"]["knn_wrong_neighbour_share"]["value"] > 0.01


def test_containment_altered_where_it_is_evaluated_is_caught(root, monkeypatch):
    """The block program's edge loop finds no crossing: a pickup inside a
    footprint reads its distance to the nearest wall, not 0.0."""
    from mosaic_tpu.knn import engine

    real = engine.edge_terms

    def no_crossing(*a, **kw):
        d2, cross = real(*a, **kw)
        return d2, cross & False

    engine.poly_block_topk_prog.cache_clear()
    monkeypatch.setattr(engine, "edge_terms", no_crossing)
    try:
        line = _run(root, 44)
    finally:
        engine.poly_block_topk_prog.cache_clear()
    assert line["correct"] is False and line["attempted"] > 0
    assert line["checks"]["knn_distance_error"]["value"] > 1e-7


def test_a_program_without_the_polygon_block_lane_is_refused_at_once(
        root, monkeypatch):
    """The parent commit with these files: the builder raises before a
    candidate or a footprint is made and before anything compiles."""
    from mosaic_tpu.knn import engine

    spec = Spec(root)
    for gen, fn in (("points", "make_generator"), ("buildings", "fabric")):
        monkeypatch.setattr(
            spec.module("generators", gen), fn,
            lambda *a, **k: pytest.fail("a layer was made"))
    monkeypatch.delattr(engine, "poly_block_topk_prog")
    t0 = time.perf_counter()
    with pytest.raises(RuntimeError, match="polygon block lane"):
        _run(root, 45)
    assert time.perf_counter() - t0 < 5.0


# ------------------------------------------------------------ the reference

def _ref():
    return Spec(REPO).module("references", "knn_polygon_bruteforce")


def test_reference_distances_in_closed_form():
    ref = _ref()
    square = [np.array([[0.0, 0.0], [4.0, 0.0], [4.0, 4.0], [0.0, 4.0]]),
              np.array([[1.0, 1.0], [1.0, 3.0], [3.0, 3.0], [3.0, 1.0]])]
    pts = np.array([
        [0.5, 0.5],    # in the wall: inside
        [2.0, 2.0],    # in the courtyard: outside, 1 from its ring
        [2.0, 1.25],   # in the courtyard, 0.25 from its ring
        [4.0, 2.0],    # on an edge
        [0.0, 0.0],    # on a vertex
        [7.0, 8.0],    # beyond a corner: 3-4-5
        [-2.0, 2.0],   # beside an edge
        [1.0, 2.0],    # on the hole's ring
    ])
    d = ref.polygon_distance(square, pts[:, 0], pts[:, 1])
    assert d.tolist() == [0.0, 1.0, 0.25, 0.0, 0.0, 5.0, 2.0, 0.0]
    # an L: the notch is outside
    ell = [np.array([[0, 0], [4, 0], [4, 2], [2, 2], [2, 4], [0, 4]], float)]
    d = ref.polygon_distance(ell, np.array([3.0, 1.0, 3.0]),
                             np.array([3.0, 1.0, 2.5]))
    assert d.tolist() == [1.0, 0.0, 0.5]


def test_reference_ranks_ties_at_zero_by_id_and_pruning_changes_nothing():
    ref = _ref()
    buildings = Spec(REPO).module("generators", "buildings")
    rng = np.random.default_rng(8)
    fps, kinds = buildings.fabric({"count": 120, "centre": CENTRE, "seed": 3})
    box = buildings.footprints_bbox(fps)
    cand = np.column_stack([rng.uniform(box[0], box[2], 30000),
                            rng.uniform(box[1], box[3], 30000)])
    pick = [fps[i] for i in np.flatnonzero(kinds == 2)[:6]] + fps[:30]
    ids, dist = ref.answers(pick, cand, 5)
    ids_all, dist_all = ref.answers(pick, cand, 5, prune=False)
    assert np.array_equal(ids, ids_all) and np.array_equal(dist, dist_all)
    # a large footprint holds more than 5 candidates: all at 0.0, by id
    tied = np.flatnonzero((dist == 0.0).all(axis=1))
    assert tied.size >= 3
    for i in tied:
        d = ref.polygon_distance(pick[i], cand[:, 0], cand[:, 1])
        assert ids[i].tolist() == np.flatnonzero(d == 0.0)[:5].tolist()
    assert np.array_equal(ref.distances(pick, cand, ids), dist)
    # fewer candidates than k: the rest of the row stays empty
    ids, dist = ref.answers(pick[:3], cand[:2], 5)
    assert (ids[:, 2:] == -1).all() and np.isinf(dist[:, 2:]).all()
    assert np.isinf(ref.distances(pick[:3], cand[:2], ids)[:, 2:]).all()


def test_builder_packs_and_takes_in_array_code():
    spec = Spec(REPO)
    dep = spec.module("deployments", "knn_footprints")
    buildings = spec.module("generators", "buildings")
    fps, _ = buildings.fabric({"count": 300, "centre": CENTRE, "seed": 3})
    col = dep.pack(fps)
    assert len(col) == 300 and col.num_rings == sum(len(f) for f in fps)
    idx = np.array([0, 7, 8, 150, 299])
    sub, slow = dep.take(col, idx), col.take(idx)
    for name in ("xy", "ring_offsets", "part_offsets", "geom_offsets",
                 "geom_type", "srid"):
        assert np.array_equal(getattr(sub, name), getattr(slow, name)), name


# -------------------------------------------------------------- the metrics

@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_metric_is_this_cells_and_reads_nothing_on_an_empty_run(name):
    spec = Spec(REPO)
    entry = next(m for m in spec.benchmark["per_layer"] if m["name"] == name)
    # the polygon-landmark lane's own: this cell is in the list, the point
    # cell is not (it reads nothing, below); a later footprint cell appends
    assert REAL in entry["workloads"]
    assert "nyc-knn.transform" not in entry["workloads"]
    assert entry["moves"] == "batch_rows_per_s"
    assert entry["layer"] == (
        "kernels" if name == "edge_pair_hbm_share.knn" else "knn ring engine")
    check_entry(spec, name)
    # nor on a run of the point cell: its transform has no cover, no ring
    # table and no edge counter
    desc = spec.data("layer_metrics", name)
    ctx = _ctx(spec, events=[
        dict(_span("knn.transform", "t", None, 1.0, 5.0), landmarks=100000,
             pairs=10**8, pairs_padded=3 * 10**8, launches=40),
        _span("knn.expand", "e", "t", 0.2, 4.0),
    ], counters={"traced_steps": 2, "traced_pairs": 10**8})
    assert spec.module("readers", desc["reader"]).read(
        ctx, desc["params"]) is None


def check_sibling_entry(spec, name) -> None:
    """Both KNN cells are IN the entry's list (membership: the next KNN
    cell appends its name; the additivity test holds a copy with one
    appended to this) and no twin stands beside it."""
    by = {m["name"]: m for m in spec.benchmark["per_layer"]}
    assert {"nyc-knn.transform", REAL} <= set(by[name]["workloads"])
    assert name[: -len(".knn")] + ".knn-buildings" not in by
    check_entry(spec, name)


@pytest.mark.parametrize("name", SIBLING_METRICS)
def test_sibling_entry_lists_both_knn_cells_and_no_twin_is_left(name):
    check_sibling_entry(Spec(REPO), name)


def test_sibling_span_metrics_read_a_footprint_calls_spans():
    spec = Spec(REPO)
    events = []
    for c, ts in enumerate((10.0, 20.0, 30.0)):
        events += [
            dict(_span("knn.transform", f"t{c}", None, 0.8 + 0.1 * c, ts),
                 landmarks=50000),
            _span("knn.cover", f"c{c}", f"t{c}", 0.1, ts - 0.9),
            _span("knn.expand", f"e{c}.1", f"t{c}", 0.04, ts - 0.7),
            _span("knn.expand", f"e{c}.2", f"t{c}", 0.06 + 0.01 * c, ts - 0.5),
            _span("knn.distance", f"d{c}", f"t{c}", 0.5, ts - 0.4),
            _span("knn.pull", f"p{c}", f"d{c}", 0.4, ts - 0.3),
        ]
    ctx = _ctx(spec, events=events, counters={"compiles_in_window": 0})

    def read(name):
        desc = spec.data("layer_metrics", name)
        return spec.module("readers", desc["reader"]).read(ctx, desc["params"])

    assert read("call_p50_ms.knn") == pytest.approx(900.0)
    assert read("expand_ms_per_call.knn") == pytest.approx(110.0)
    assert read("distance_ms_per_call.knn") == pytest.approx(500.0)
    assert read("pull_ms_per_call.knn") == pytest.approx(400.0)
    assert read("compiles_in_window.batch") == 0
    # the two device metrics need a trace
    assert read("device_idle.batch") is None
    assert read("device_busy_ms_per_call.knn") is None


@pytest.mark.parametrize("name", SHARED_METRICS)
def test_start_up_metrics_list_the_cell(name):
    spec = Spec(REPO)
    # the start-up entries list no cells: every cell that reports `setup_s`
    # reads them, this one and its sibling among them (the whole-file rule
    # is `test_benchmark_shared_entries`' `check_start_up_entries`)
    for cell in (REAL, "nyc-knn.transform"):
        assert name in [m["name"] for m in spec.per_layer(cell)]


def test_span_and_counter_metrics_read_hand_made_events():
    spec = Spec(REPO)
    events = []
    for c, ts in enumerate((10.0, 20.0, 30.0)):
        events += [
            dict(_span("knn.transform", f"t{c}", None, 1.0, ts),
                 landmarks=50000, edge_pairs=400 + c, edge_pairs_padded=800),
            dict(_span("knn.cover", f"c{c}", f"t{c}", 0.1 + 0.01 * c, ts - 0.9),
                 landmarks=50000, seeds=62500),
            _span("knn.landmarks", f"l{c}", f"t{c}", 0.05, ts - 0.8),
        ]
    ctx = _ctx(spec, events=events)

    def read(name):
        desc = spec.data("layer_metrics", name)
        return spec.module("readers", desc["reader"]).read(ctx, desc["params"])

    assert read("cover_ms_per_call.knn") == pytest.approx(110.0)
    assert read("landmark_put_ms_per_call.knn") == pytest.approx(50.0)
    assert read("seeds_per_landmark.knn") == pytest.approx(1.25)
    assert read("edge_occupancy.knn") == pytest.approx(100 * 1203 / 2400)


def test_edge_pair_hbm_share_arithmetic(monkeypatch):
    from types import SimpleNamespace

    spec = Spec(REPO)
    mod = spec.module("readers", "edge_pair_hbm_share")
    assert mod.pair_bytes(8) == 28 and mod.pair_bytes(4) == 16
    assert mod.edge_bytes(8) == 16
    assert mod.STAGES == ["knn.gather", "knn.edges", "knn.topk"]
    ctx = _ctx(
        spec, counters={"traced_steps": 2, "traced_pairs": 17_000_000,
                        "traced_edge_rows": 6_500_000,
                        "traced_edge_pairs": 94_000_000},
        deployment=SimpleNamespace(index=SimpleNamespace(dtype=np.dtype("f8"))),
        device={"kind": "TPU v5 lite"},
    )
    busy = spec.module("readers", "trace_stage_busy")
    asked = []

    def fake(ctx, p):
        asked.append(p)
        return 300.0  # ms a traced call

    monkeypatch.setattr(busy, "read", fake)
    want = 100.0 * ((17_000_000 * 28 + 6_500_000 * 16) / 819e9) / 0.6
    assert mod.read(ctx, {}) == pytest.approx(want)
    assert asked[0]["stage"] == mod.STAGES
    # no traced call, or a program that counts no edge rows: no share
    ctx.counters["traced_edge_rows"] = 0
    assert mod.read(ctx, {}) is None
    ctx.counters.update(traced_edge_rows=6_500_000, traced_pairs=0)
    assert mod.read(ctx, {}) is None
