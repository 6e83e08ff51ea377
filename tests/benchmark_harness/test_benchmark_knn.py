"""The resident-KNN cell (`nyc-knn.transform`) rehearsed on the CPU at a
small size: a temporary copy of the benchmark to which a tiny candidate
deployment is ADDED as new files and appended entries (the real
configuration's builder, reference, traffic kind and metrics; a custom
grid, 8,000 clustered candidates, 256-landmark tables). The sound run reads
correct, the lower-precision control and a broken path do not, a program
without the ring engine is refused at once, the reference agrees with a
direct sort, and every reader this cell brought returns None where there
is nothing to read."""

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

CELL = "tiny.knn"
NEW_METRICS = [
    "call_p50_ms.knn", "expand_ms_per_call.knn", "distance_ms_per_call.knn",
    "merge_ms_per_call.knn", "iterations_per_call.knn",
    "pairs_per_landmark.knn", "pair_occupancy.knn", "launches_per_call.knn",
    "device_busy_ms_per_call.knn", "pair_hbm_share.knn",
]
#: what this cell reads through entries it shares with the other host-fed
#: cells since PR 47 (one entry per reader, parameters and moved metric):
#: `test_benchmark_shared_entries.py` holds each (entry, cell) pair
SHARED_METRICS = ["device_idle.batch", "compiles_in_window.batch",
                  "pool_build_s.batch"]
#: at resolution 8 cells of 3.9e-3 degrees
GRID, RES = "CUSTOM(-75,-73,40,42,2,1,1)", 8
BOX = [-74.3, 40.4, -73.6, 41.0]


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
    real = Spec(REPO).config("nyc-knn-h3r10")
    _write(os.path.join(tree, "configs", "tiny-knn.json"), {
        "source": "test fixture", "rehearsal": True, "row": "landmark",
        "deployment": real["deployment"], "reference": real["reference"],
        "index_system": GRID, "resolution": RES,
        "candidates": dict(real["candidates"], count=8000, bbox=BOX),
        "model": real["model"],
        "batch_rows_per_chip": 256, "chips": 1, "mesh": None,
        "reduced": {},
    })
    mix = Spec(REPO).traffic("landmarks-host")
    mix.pop("name")
    _write(os.path.join(tree, "traffic", "tiny-landmarks.json"), mix)
    check = dict(Spec(REPO).cell("nyc-knn.transform")["check"],
                 sample_landmarks=128)
    _write(os.path.join(tree, "workloads", CELL + ".json"), {"check": check})
    path = os.path.join(root, "BENCHMARK.json")
    with open(path, encoding="utf-8") as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-knn", "source": "test fixture",
        "file": "benchmark/configs/tiny-knn.json", "reduced": [],
        "why": "test fixture",
    })
    bench["workloads"].append({
        "name": CELL, "config": "tiny-knn", "traffic": "tiny-landmarks",
        "chips": 1, "why": "test fixture",
    })
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "nyc-knn.transform" in m.get("workloads", []):
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


@pytest.mark.parametrize("seed", [41, 4_000_000_777])
def test_sound_run_is_correct_and_f32_control_is_not(root, seed, capsys):
    line = _run(root, seed)
    said = capsys.readouterr().out
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"batch_rows_per_s", "setup_s"}
    assert line["attempted"] >= 256
    # a head and a tail in one call: some landmark walked on past ring 3
    win = next(s for s in said.splitlines() if "[bench] knn_window:" in s)
    field = lambda name: json.loads(  # noqa: E731
        win.split(name + "=", 1)[1].split("]", 1)[0] + "]")
    assert set(field("unrested")) == {0} and max(field("iterations")) >= 4
    assert min(field("pairs")) > 256 * 5
    control = _run(root, seed, control=True)
    assert control["correct"] is False


def test_answers_altered_where_they_are_produced_are_caught(root, monkeypatch):
    """The merge inside the timed call hands every landmark's second
    neighbour the id of the third: distances still read true for the id
    returned, the rank comparison with the reference catches it."""
    from mosaic_tpu.knn import engine

    real = engine.fold_heads

    def altered(*a, **kw):
        fd, fi = real(*a, **kw)
        fi[:, 1] = np.where(fi[:, 2] >= 0, fi[:, 2], fi[:, 1])
        return fd, fi

    monkeypatch.setattr(engine, "fold_heads", altered)
    line = _run(root, 43)
    assert line["correct"] is False and line["attempted"] > 0


def test_a_program_without_the_ring_engine_is_refused_at_once(root, monkeypatch):
    """The parent commit with these files: the builder raises before a
    candidate is made and before anything compiles."""
    import importlib.util

    gen = Spec(root).module("generators", "points")
    monkeypatch.setattr(gen, "make_generator",
                        lambda *a, **k: pytest.fail("candidates made"))
    real = importlib.util.find_spec
    monkeypatch.setattr(
        importlib.util, "find_spec",
        lambda name, *a: None if name == "mosaic_tpu.knn.engine" else real(name, *a),
    )
    t0 = time.perf_counter()
    with pytest.raises(RuntimeError, match="ring engine"):
        _run(root, 44)
    assert time.perf_counter() - t0 < 5.0


def test_reference_is_a_plain_sort_with_the_id_tie_rule():
    ref = Spec(REPO).module("references", "knn_bruteforce")
    rng = np.random.default_rng(3)
    cand = rng.uniform(0, 1, (500, 2))
    cand[100] = cand[7]  # an exact tie: the smaller id ranks first
    land = np.concatenate([rng.uniform(0, 1, (150, 2)), cand[7:8]])
    ids, dist = ref.answers(land, cand, 4)
    d = np.sqrt(((land[:, None] - cand[None]) ** 2).sum(-1))
    want = np.lexsort(
        (np.broadcast_to(np.arange(500), d.shape), d), axis=1)[:, :4]
    assert np.array_equal(ids, want)
    assert np.array_equal(dist, np.take_along_axis(d, want, 1))
    assert ids[-1, :2].tolist() == [7, 100] and dist[-1, 1] == 0.0
    assert np.array_equal(ref.distances(land, cand, ids), dist)
    # fewer candidates than k: the rest of the row stays empty
    ids, dist = ref.answers(land[:3], cand[:2], 4)
    assert (ids[:, 2:] == -1).all() and np.isinf(dist[:, 2:]).all()
    assert np.isinf(ref.distances(land[:3], cand[:2], ids)[:, 2:]).all()


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_metric_reads_nothing_on_an_empty_run(name):
    spec = Spec(REPO)
    entry = next(m for m in spec.benchmark["per_layer"] if m["name"] == name)
    assert "nyc-knn.transform" in entry["workloads"]
    check_entry(spec, name)
    # nor on a run of a program whose transform has no span and no counter
    desc = spec.data("layer_metrics", name)
    ctx = _ctx(spec, events=[{"event": "span", "name": "join.pip",
                              "seconds": 0.1, "ts_mono": 1.0}])
    assert spec.module("readers", desc["reader"]).read(
        ctx, desc["params"]) is None


def test_the_cell_reads_its_own_entries_and_the_shared_ones():
    spec = Spec(REPO)
    mine = {m["name"] for m in spec.per_layer("nyc-knn.transform")}
    assert mine >= set(NEW_METRICS) | set(SHARED_METRICS) | {
        "index_build_s", "warmup_s", "slabs_per_call.knn"}
    # the three that reckon the point block kernel: the footprint cell, which
    # runs the edge kernel, is not in their lists
    by = {m["name"]: m for m in spec.benchmark["per_layer"]}
    for name in ("pair_hbm_share.knn", "pairs_per_landmark.knn",
                 "pair_occupancy.knn"):
        assert "nyc-knn.transform" in by[name]["workloads"]
        assert "nyc-knn-buildings.transform" not in by[name]["workloads"]


def test_pair_hbm_share_arithmetic(monkeypatch):
    from types import SimpleNamespace

    spec = Spec(REPO)
    mod = spec.module("readers", "pair_hbm_share")
    assert mod.pair_bytes(8) == 44 and mod.pair_bytes(4) == 24
    ctx = _ctx(
        spec, counters={"traced_steps": 2, "traced_pairs": 200_000_000},
        deployment=SimpleNamespace(index=SimpleNamespace(dtype=np.dtype("f8"))),
        device={"kind": "TPU v5 lite"},
    )
    busy = spec.module("readers", "trace_stage_busy")
    asked = []

    def fake(ctx, p):
        asked.append(p)
        return 250.0  # ms a traced call

    monkeypatch.setattr(busy, "read", fake)
    want = 100.0 * (200_000_000 * 44 / 819e9) / 0.5
    assert mod.read(ctx, {}) == pytest.approx(want)
    assert asked[0]["stage"] == ["knn.gather", "knn.distance", "knn.topk"]
    # no traced call, no share
    ctx.counters["traced_pairs"] = 0
    assert mod.read(ctx, {}) is None
