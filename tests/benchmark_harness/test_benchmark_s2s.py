"""The ship-to-ship cell (`ais-s2s.join`) rehearsed on the CPU at a small
size: a temporary copy of the benchmark to which a tiny deployment is ADDED
as new files and appended entries (the real configuration's builder,
reference, traffic kind, generator and metric; 192 vessels a window in a
box of a third of a degree, 2 tables of 3 windows). The cell's files
resolve, the sound run reads correct, both controls and a broken path do
not, a program without the distance join is refused at once, and the
metrics this cell brought — the predicate's share of the HBM roofline, the
call and its host pieces grouped by table, the candidate rows a track, the
predicate's device time — each resolve for the tiny cell, read a hand-made
`proximity.*` span tree to the value worked out by hand and return None
where there is nothing to read. The tiny cell traced on the CPU reads every
host one as a number.

What holds the real file's entries is membership, never equality: the cell
reads AT LEAST these names and stands IN these lists, so a later PR may
append an entry for the cell, or a second distance-join cell to the lists
(`check_s2s_entries`, which `test_benchmark_additive.py` runs on a copy
with both appended)."""

import json
import os
import shutil
import time

import pytest

from bh_fixtures import REPO, _snapshot, _write

from benchmark.harness.run_cell import run_cell
from benchmark.harness.spec import Spec
from test_benchmark_program_spans import _ctx, _span, _with_trace, check_entry
from test_benchmark_shared_entries import check_no_twins

CELL, REAL = "tiny.s2s", "ais-s2s.join"
#: the cell's own entries: name -> (layer, unit, source)
NEW_METRICS = {
    "segpair_hbm_share.s2s": ("kernels", "%", "device_trace"),
    "call_p50_ms.s2s": ("frontends", "ms", "program_span"),
    "cover_ms_per_call.s2s": ("proximity join", "ms", "program_span"),
    "candidates_ms_per_call.s2s": ("dispatch core", "ms", "program_span"),
    "launch_pull_ms_per_call.s2s": ("dispatch core", "ms", "program_span"),
    "glue_ms_per_call.s2s": ("proximity join", "ms", "program_span"),
    "candidate_rows_per_track.s2s": ("proximity join", "count",
                                     "program_counter"),
    "segpairs_device_ms_per_call.s2s": ("proximity join", "ms",
                                        "device_trace"),
}
#: the four pieces of a call the host spends, and what they are pieces of
HOST_PIECES = ["cover_ms_per_call.s2s", "candidates_ms_per_call.s2s",
               "launch_pull_ms_per_call.s2s", "glue_ms_per_call.s2s"]
#: the lists that were there and the cell joined
HOST_FED_METRICS = ["device_idle.batch", "compiles_in_window.batch",
                    "pool_build_s.batch", "call_warmup_s.batch",
                    "call_max_ms.batch", "device_busy_ms_per_call.overlay",
                    "candidates_device_ms_per_call.overlay"]
#: the two that list no cells: every cell reads them
START_UP_METRICS = ["index_build_s", "warmup_s"]
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
    check_s2s_entries(spec)
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

def check_new_entry(spec, name) -> None:
    entry = next(m for m in spec.benchmark["per_layer"] if m["name"] == name)
    # membership: a second distance-join cell joins the list behind this one
    assert REAL in entry["workloads"]
    assert entry["moves"] == "batch_rows_per_s"
    assert (entry["layer"], entry["unit"], entry["source"]) == NEW_METRICS[name]
    check_entry(spec, name)


def check_host_fed_entry_lists_the_cell(spec, name) -> None:
    entry = next(m for m in spec.benchmark["per_layer"] if m["name"] == name)
    # membership: the next host-fed cell joins the list behind this one
    assert REAL in entry["workloads"] and len(entry["workloads"]) > 1
    assert entry["moves"] in ("batch_rows_per_s", "setup_s")
    check_entry(spec, name)


def check_s2s_entries(spec) -> None:
    """What the real file's entries for this cell are held to, on the real
    file and on a copy to which a PR appended: the cell reads at least its
    own entries, the shared ones it joined and the start-up two."""
    mine = {m["name"] for m in spec.per_layer(REAL)}
    assert mine >= (set(NEW_METRICS) | set(HOST_FED_METRICS)
                    | set(START_UP_METRICS))
    for name in NEW_METRICS:
        check_new_entry(spec, name)
    for name in HOST_FED_METRICS:
        check_host_fed_entry_lists_the_cell(spec, name)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_metric_is_this_cells_and_reads_nothing_on_an_empty_run(name):
    spec = Spec(REPO)
    check_new_entry(spec, name)
    # nor on a run of the overlay cell: its calls hold no such span or counter
    desc = spec.data("layer_metrics", name)
    overlay = [dict(_span("overlay.call", "o", None, 0.5, 10.0),
                    right_rows=384, raw_candidates=10, clip_rows=3),
               _span("overlay.glue", "o1", "o", 0.1, 9.9)]
    ctx = _ctx(spec, events=overlay, counters={"traced_steps": 2},
               series={"traced_calls": [{"clip_rows": 10, "vpad": 8,
                                         "acc": "float32"}]})
    assert spec.module("readers", desc["reader"]).read(
        ctx, desc["params"]) is None


@pytest.fixture(scope="module")
def tiny_spec(tmp_path_factory):
    return Spec(make_copy(tmp_path_factory.mktemp("s2s")))


@pytest.mark.parametrize(
    "name", list(NEW_METRICS) + HOST_FED_METRICS + START_UP_METRICS)
def test_metric_resolves_for_the_tiny_cell(tiny_spec, name):
    assert name in [m["name"] for m in tiny_spec.per_layer(CELL)]
    check_entry(tiny_spec, name)


@pytest.mark.parametrize("name", HOST_FED_METRICS)
def test_host_fed_entries_list_the_cell(name):
    check_host_fed_entry_lists_the_cell(Spec(REPO), name)


@pytest.mark.parametrize("name", START_UP_METRICS)
def test_the_cell_reads_the_start_up_entries(name):
    spec = Spec(REPO)
    entry = next(m for m in spec.per_layer(REAL) if m["name"] == name)
    assert entry["moves"] == "setup_s" and "workloads" not in entry
    check_entry(spec, name)


def _proximity_calls():
    """Five calls in the window over a pool of two tables of unlike
    ``cover_rows`` — an ODD number: table A three times, table B twice —
    and one call outside it. Per call the children `dwithin_join` records:
    one cover, count, pull, glue and host band, an emit and a launch a
    slice (three slices on A, four on B); an overlay call beside them."""
    events = []
    calls = [  # (cover_rows, raw_candidates, ts, call, cover, count, pull, glue)
        (1000, 3_000_000, 10.0, 0.60, 0.20, 0.017, 0.040, 0.10),
        (1200, 3_300_000, 20.0, 0.80, 0.30, 0.019, 0.060, 0.15),
        (1000, 3_000_000, 30.0, 0.70, 0.27, 0.017, 0.050, 0.125),
        (1200, 3_300_000, 40.0, 0.90, 0.34, 0.019, 0.070, 0.13),
        (1000, 3_000_000, 50.0, 0.64, 0.22, 0.017, 0.045, 0.11),
        (1000, 9_000_000, 300.0, 9.0, 5.0, 1.0, 1.0, 1.0),  # after the window
    ]
    for c, (rows, cand, ts, call, cover, count, pull, glue) in enumerate(calls):
        root = f"c{c}"
        events.append(dict(_span("proximity.call", root, None, call, ts),
                           cover_rows=rows, tracks=32768, raw_candidates=cand))
        events.append(_span("proximity.cover", root + ".v", root, cover, ts - 0.5))
        events.append(_span("proximity.count", root + ".n", root, count, ts - 0.4))
        for k in range(3 if rows == 1000 else 4):
            events.append(_span("proximity.emit", f"{root}.e{k}", root, 0.001,
                                ts - 0.35))
            events.append(_span("proximity.launch", f"{root}.l{k}", root, 0.001,
                                ts - 0.34))
        events.append(_span("proximity.pull", root + ".p", root, pull, ts - 0.2))
        events.append(_span("proximity.glue", root + ".g", root, glue, ts - 0.1))
        events.append(_span("proximity.host_band", root + ".b", root, 0.007, ts))
    events.append(dict(_span("overlay.call", "o", None, 0.5, 15.0),
                       right_rows=384, raw_candidates=77))
    events.append(_span("overlay.glue", "o1", "o", 0.3, 14.9))
    return events


#: worked out by hand from `_proximity_calls`: p50 by nearest rank inside
#: each table (A's middle of three, B's lower of two), the mean of the two
HAND_MADE = {
    "call_p50_ms.s2s": (0.64 + 0.80) / 2 * 1000,
    "cover_ms_per_call.s2s": (0.22 + 0.30) / 2 * 1000,
    "candidates_ms_per_call.s2s": ((0.017 + 3 * 0.001)
                                   + (0.019 + 4 * 0.001)) / 2 * 1000,
    "launch_pull_ms_per_call.s2s": ((0.045 + 3 * 0.001)
                                    + (0.060 + 4 * 0.001)) / 2 * 1000,
    "glue_ms_per_call.s2s": (0.11 + 0.13) / 2 * 1000,
    "candidate_rows_per_track.s2s": (3 * 3_000_000 + 2 * 3_300_000)
                                    / (5 * 32768),
    # (0.130 + 0.080 + 0.002) s of the three scopes over two traced calls
    "segpairs_device_ms_per_call.s2s": 106.0,
    # the overlay's two scopes in this cell's calls: (0.146 + 0.030) s / 2
    "candidates_device_ms_per_call.overlay": 88.0,
}
STAGES = {"proximity.gather": 0.130, "proximity.segpairs": 0.080,
          "proximity.fold": 0.002, "overlay.emit": 0.146,
          "overlay.spans": 0.030, "unscoped": 0.004}


@pytest.mark.parametrize("name", HAND_MADE)
def test_metric_reads_a_hand_made_span_tree(monkeypatch, name):
    spec = Spec(REPO)
    desc = spec.data("layer_metrics", name)
    reader = spec.module("readers", desc["reader"])
    _with_trace(spec, monkeypatch, {"devices": {}})
    ctx = _ctx(spec, events=_proximity_calls(), counters={"traced_steps": 2},
               device_by_stage=dict(STAGES))
    assert reader.read(ctx, desc["params"]) == pytest.approx(HAND_MADE[name])
    if desc["reader"] != "span_child_by_group":
        return
    # each table's p50 is said once a metric, beside its count of calls: an
    # odd number of calls, and the reading is neither table's alone
    said = dict(ctx.said)["span_by_group"]
    assert said["by"] == "cover_rows"
    assert said["1000"].endswith("/3") and said["1200"].endswith("/2")


def test_the_four_host_pieces_lie_inside_the_hand_made_call():
    spec = Spec(REPO)
    ctx = _ctx(spec, events=_proximity_calls())

    def read(name):
        desc = spec.data("layer_metrics", name)
        return spec.module("readers", desc["reader"]).read(ctx, desc["params"])

    pieces = sum(read(n) for n in HOST_PIECES)
    assert pieces == pytest.approx(260.0 + 21.5 + 56.0 + 120.0)
    # 457.5 of 720 ms: the band's 7 and what no child span holds are the rest
    assert 0.6 < pieces / read("call_p50_ms.s2s") < 0.65


def test_traced_tiny_cell_reads_every_host_metric_as_a_number(
        root, capfd, monkeypatch):
    # the profiler starts with the window's first call here, not its second:
    # on a loaded machine one interpreted call can outlast the whole window,
    # and a traced run must find its trace
    kind = Spec(root).module("traffic_kinds", "dwithin_join_loop")
    monkeypatch.setattr(kind, "TRACE_FROM_CALL", 0)
    line = run_cell(root, CELL, 4_000_000_517, 0.5, True,
                    t_start=time.perf_counter(), rehearsal=True)
    assert line["correct"] is True and line["failed"] == 0
    m = line["metrics"]
    host = [n for n, (_l, _u, source) in NEW_METRICS.items()
            if source != "device_trace"]
    assert len(host) == 6
    # (`call_max_ms.batch` reads the unprofiled calls' walls: there may be none)
    for name in host + ["index_build_s", "warmup_s", "pool_build_s.batch",
                        "call_warmup_s.batch"]:
        assert name in m and m[name]["value"] > 0.0, name
    # the pieces are inside the call they are pieces of
    assert sum(m[n]["value"] for n in HOST_PIECES) < m["call_p50_ms.s2s"]["value"]
    # the CPU has no device trace: the three stage metrics read nothing
    assert not {"segpair_hbm_share.s2s", "segpairs_device_ms_per_call.s2s",
                "candidates_device_ms_per_call.overlay",
                "device_busy_ms_per_call.overlay", "device_idle.batch"} & set(m)
    out = capfd.readouterr().out
    # each grouped metric says its tables' p50s once, by cover rows (two
    # tables of unlike cover rows: two groups once both were called)
    by_group = [ln for ln in out.splitlines()
                if ln.startswith("[bench] span_by_group: ")]
    assert len(by_group) == 5
    assert all("by=cover_rows" in ln and 1 <= ln.count("/") <= 2
               for ln in by_group)


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
