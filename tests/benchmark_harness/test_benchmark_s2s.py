"""The ship-to-ship cell (`ais-s2s.join`) rehearsed on the CPU at a small
size: a temporary copy of the benchmark to which a tiny deployment is ADDED
as new files and appended entries (the real configuration's builder,
reference, traffic kind, generator and metric; 192 vessels a window in a
box of a third of a degree, 2 tables of 3 windows). The cell's files
resolve, the sound run reads correct, both controls and a broken path do
not, a program without the distance join is refused at once, and the
metric this cell brought reads a hand-made span and returns None where
there is nothing to read."""

import json
import os
import shutil
import time

import pytest

from bh_fixtures import REPO, _snapshot, _write

from benchmark.harness.run_cell import run_cell
from benchmark.harness.spec import Spec
from test_benchmark_program_spans import _ctx, check_entry
from test_benchmark_shared_entries import check_no_twins

CELL, REAL = "tiny.s2s", "ais-s2s.join"
NEW_METRICS = ["segpair_hbm_share.s2s"]
HOST_FED_METRICS = ["device_idle.batch", "compiles_in_window.batch",
                    "pool_build_s.batch", "call_warmup_s.batch",
                    "call_max_ms.batch", "device_busy_ms_per_call.overlay"]
#: a small fleet in a small box: every kind of vessel, dense enough to meet
FLEET = {
    "vessels": 192, "box": [-90.5, 28.0, -90.2, 28.2],
    "moored": {"places": 3, "sigma_km": [0.4, 1.0]},
    "lanes": {"count": 2, "min_length_deg": 0.1},
    "transfers": {"pairs": 3, "windows": [1, 2], "offset_km": [1, 2]},
    "layout_seed": 7,
}
#: fast free vessels that report twice or thrice a window under a narrow
#: buffer: pairs of tracks that cross far from their ends
CROSSING = {
    "buffer_m": 10, "pings": [2, 3],
    "moored": {"share": 0.0}, "lanes": {"share": 0.0},
    "free": {"speed_kn": [25, 35]},
}


def _merged(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merged(base[k], v) if isinstance(v, dict) else v
    return out


def make_copy(tmp, fleet=None) -> str:
    root = os.path.join(str(tmp), "copy")
    os.makedirs(root)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    shutil.copytree(
        os.path.join(REPO, "benchmark"), os.path.join(root, "benchmark"),
        ignore=shutil.ignore_patterns("__pycache__", ".traces", ".cache"),
    )
    before = _snapshot(root)
    tree = os.path.join(root, "benchmark")
    real = Spec(REPO).config("ais-s2s-h3r9")
    _write(os.path.join(tree, "configs", "tiny-s2s.json"), {
        "source": "test fixture", "rehearsal": True, "row": real["row"],
        "deployment": real["deployment"], "reference": real["reference"],
        "index_system": real["index_system"], "resolution": real["resolution"],
        "fleet": _merged(_merged(real["fleet"], FLEET), fleet or {}),
        "batch_rows_per_chip": 3 * 192, "chips": 1, "mesh": None,
        "reduced": {},
    })
    mix = Spec(REPO).traffic("tracks-host")
    mix.pop("name")
    _write(os.path.join(tree, "traffic", "tiny-tracks.json"),
           dict(mix, windows_per_table=3))
    _write(os.path.join(tree, "workloads", CELL + ".json"),
           {"check": Spec(REPO).cell(REAL)["check"]})
    path = os.path.join(root, "BENCHMARK.json")
    with open(path, encoding="utf-8") as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-s2s", "source": "test fixture",
        "file": "benchmark/configs/tiny-s2s.json", "reduced": [],
        "why": "test fixture",
    })
    bench["workloads"].append({
        "name": CELL, "config": "tiny-s2s", "traffic": "tiny-tracks",
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
    assert cell["chips"] == 1 and cell["traffic"] == "tracks-host"
    assert cell["config"] == "ais-s2s-h3r9"
    entry = next(c for c in spec.benchmark["configs"]
                 if c["name"] == "ais-s2s-h3r9")
    assert entry["reduced"] == ["days"] and len(entry["source"]) <= 200
    cfg = spec.config(cell["config"])
    assert cfg["index_system"] == "H3" and cfg["resolution"] == 9
    assert cfg["mesh"] is None and list(cfg["reduced"]) == ["days"]
    assert cfg["source"] == entry["source"]
    for word in ("Ship2ShipTransfers", "03 Line Aggregation",
                 "BASELINE.json config 5"):
        assert word in cfg["source"]
    for key in ("assumed", "precision", "guarantees", "entry_point_arguments"):
        assert cfg[key]
    assumed = " ".join(cfg["assumed"])
    for word in ("0.48%", "radius rule", "the fleet", "the box", "the mixture",
                 "time-overlap", "harbour mask", "one_metre"):
        assert word in assumed, word
    assert cfg["guarantees"]["overflow_rows"] == 0
    assert cfg["guarantees"]["forbidden_events"] == 0
    # the fleet as ISSUE 48 names it
    f = cfg["fleet"]
    assert f["vessels"] == 4096 and f["window_minutes"] == 15
    assert f["box"] == [-97.0, 26.0, -82.0, 30.5] and f["pings"] == [5, 15]
    assert f["one_metre_deg"] == pytest.approx(0.00001 - 0.000001)
    assert f["buffer_m"] == 200
    m, lanes, tr = f["moored"], f["lanes"], f["transfers"]
    assert (m["share"], m["places"], m["zipf_s"]) == (0.40, 24, 1.1)
    assert m["sigma_km"] == [0.4, 2.5] and m["jitter_m"] == [10, 30]
    assert m["speed_kn"][1] == 0.5
    assert (lanes["share"], lanes["count"], lanes["speed_kn"]) == (0.45, 6, [8, 16])
    assert lanes["lateral_sigma_m"] == 300 and f["free"]["speed_kn"] == [5, 14]
    assert tr["pairs"] == 32 and tr["windows"] == [4, 8] and tr["gap_m"][1] < 100
    assert m["share"] + lanes["share"] == pytest.approx(0.85)
    mix = spec.traffic(cell["traffic"])
    assert mix["kind"] == "dwithin_join_loop" and mix["pool_tables"] == 2
    assert mix["windows_per_table"] * f["vessels"] == cfg["batch_rows_per_chip"]
    assert mix["windows_per_table"] in (8, 16)
    assert mix["control"]["kinds"] == ["float32_input", "float32_frame"]
    for registry, name in (
        ("deployments", cfg["deployment"]), ("references", cfg["reference"]),
        ("traffic_kinds", mix["kind"]), ("generators", "ais_tracks"),
        ("readers", "segpair_hbm_share"),
    ):
        assert spec.module(registry, name)
    assert [m["name"] for m in spec.end_to_end(REAL)] == \
        ["setup_s", "batch_rows_per_s"]
    mine = {m["name"] for m in spec.per_layer(REAL)}
    assert mine == set(NEW_METRICS + HOST_FED_METRICS)
    limits = cell["check"]
    # every window of each table's first timed answer is held to the reference
    assert limits["sample_windows"] == mix["windows_per_table"]
    assert limits["rel_tol"] == 1e-12
    assert limits["why"]
    # the reference imports nothing of the program
    with open(os.path.join(REPO, "benchmark", "references",
                           "dwithin_bruteforce.py"), encoding="utf-8") as fh:
        assert "mosaic_tpu" not in fh.read().split('"""', 2)[2]
    check_no_twins(spec)


def test_sound_run_is_correct_and_both_controls_are_not(root, capsys):
    line = _run(root, 41)
    said = capsys.readouterr().out
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"batch_rows_per_s", "setup_s"}
    assert line["attempted"] >= 3 * 192
    for name in ("s2s_pairs_missing", "s2s_pairs_spurious",
                 "s2s_planted_pairs_missing", "s2s_overflow_rows",
                 "s2s_answers_unlike_first_pass", "forbidden_events"):
        assert line["checks"][name] == {"value": 0.0, "limit": 0.0}, name
    ready = next(s for s in said.splitlines() if "[bench] s2s_ready:" in s)
    assert "control=None" in ready and "pool=[576, 576]" in ready
    win = next(s for s in said.splitlines() if "[bench] s2s_window:" in s)
    assert "tessellated=[0" in win and "degraded_calls=0" in win
    ref = next(s for s in said.splitlines() if "[bench] reference:" in s)
    assert int(ref.split("pairs=")[1].split()[0]) > 500
    # an even seed rounds the input to float32 as it is, an odd one in one
    # frame for the table
    f32 = _run(root, 42, control=True)
    assert "control=float32_input" in capsys.readouterr().out
    assert f32["correct"] is False
    wrong = (f32["checks"]["s2s_pairs_missing"]["value"]
             + f32["checks"]["s2s_pairs_spurious"]["value"])
    assert wrong >= 1
    # (a box of a third of a degree holds float32 steps of a millimetre
    # about its centre: on this tiny fleet the second control may well
    # read sound. It must run, and find every planted pair)
    frame = _run(root, 43, control=True)
    assert "control=float32_frame" in capsys.readouterr().out
    assert frame["checks"]["s2s_planted_pairs_missing"]["value"] == 0


def test_the_crossing_test_taken_out_of_the_kernel_is_caught(tmp_path, monkeypatch):
    """Tracks that only cross — fast vessels reporting twice a window,
    under a narrow buffer — are 0 apart; a kernel without the crossing test
    reads their ends' distance and misses the pair."""
    from mosaic_tpu.kernels import proximity as kernel
    from mosaic_tpu.sql import proximity

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    root = make_copy(tmp_path, CROSSING)
    sound = _run(root, 44)
    assert sound["correct"] is True
    real = kernel.piece_distance

    def no_crossing(*a, **kw):
        d2, crosses = real(*a, **kw)
        return d2, crosses & False

    monkeypatch.setattr(kernel, "piece_distance", no_crossing)
    proximity._segpair_program.cache_clear()
    try:
        line = _run(root, 44)
    finally:
        proximity._segpair_program.cache_clear()
    assert line["correct"] is False and line["attempted"] > 0
    assert line["checks"]["s2s_pairs_missing"]["value"] >= 1
    assert line["checks"]["s2s_pairs_spurious"]["value"] == 0


def test_a_program_without_the_distance_join_is_refused_at_once(
        root, monkeypatch):
    """The parent commit with these files: the builder raises before a
    track is made and before anything compiles."""
    import importlib.util

    spec = Spec(root)
    monkeypatch.setattr(
        spec.module("generators", "ais_tracks"), "table",
        lambda *a, **k: pytest.fail("a table was made"))
    real = importlib.util.find_spec
    monkeypatch.setattr(
        importlib.util, "find_spec",
        lambda name, *a: None if name == "mosaic_tpu.sql.proximity"
        else real(name, *a))
    t0 = time.perf_counter()
    with pytest.raises(RuntimeError, match="no distance join"):
        _run(root, 45)
    assert time.perf_counter() - t0 < 5.0


# -------------------------------------------------------------- the metrics

@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_metric_is_this_cells_and_reads_nothing_on_an_empty_run(name):
    spec = Spec(REPO)
    entry = next(m for m in spec.benchmark["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [REAL] and entry["layer"] == "kernels"
    assert entry["moves"] == "batch_rows_per_s" and entry["unit"] == "%"
    check_entry(spec, name)
    # nor on a run of the overlay cell: its calls hold no such counter
    desc = spec.data("layer_metrics", name)
    ctx = _ctx(spec, counters={"traced_steps": 2},
               series={"traced_calls": [{"clip_rows": 10, "vpad": 8,
                                         "acc": "float32"}]})
    assert spec.module("readers", desc["reader"]).read(
        ctx, desc["params"]) is None


@pytest.mark.parametrize("name", HOST_FED_METRICS)
def test_host_fed_entries_list_the_cell(name):
    spec = Spec(REPO)
    entry = next(m for m in spec.benchmark["per_layer"] if m["name"] == name)
    assert entry["workloads"][-1] == REAL
    assert entry["moves"] in ("batch_rows_per_s", "setup_s")
    check_entry(spec, name)


def test_segpair_hbm_share_arithmetic(monkeypatch):
    spec = Spec(REPO)
    mod = spec.module("readers", "segpair_hbm_share")
    assert mod.row_bytes(16, 4) == 272 and mod.row_bytes(16, 8) == 528
    assert mod.STAGES == ["proximity.gather", "proximity.segpairs",
                          "proximity.fold"]
    calls = [
        {"raw_candidates": 3_000_000, "vpad": 16, "acc": "float32"},
        {"raw_candidates": 1_000_000, "vpad": 16, "acc": "float32"},
    ]
    ctx = _ctx(spec, counters={"traced_steps": 2},
               series={"traced_calls": calls}, device={"kind": "TPU v5 lite"})
    busy = spec.module("readers", "trace_stage_busy")
    # 20 ms of the three scopes a call
    monkeypatch.setattr(busy, "read", lambda c, p: 20.0 if p == {
        "stage": mod.STAGES, "steps": "traced_steps"} else None)
    share = mod.read(ctx, {})
    assert share == pytest.approx(100 * (4e6 * 272 / 819e9) / 0.040)
    said = dict(ctx.said)["segpair_bytes"]
    assert said["bytes"] == [816_000_000, 272_000_000]
    # no stage table (a program without the scopes): nothing to read
    monkeypatch.setattr(busy, "read", lambda c, p: None)
    assert mod.read(ctx, {}) is None
    # a device that is not in the table of peaks is an error, not a default
    monkeypatch.setattr(busy, "read", lambda c, p: 20.0)
    ctx.device = {"kind": "TPU v9"}
    with pytest.raises(KeyError, match="peaks"):
        mod.read(ctx, {})
