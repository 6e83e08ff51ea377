"""The durable-stream cell (`taxi.stream-durable`, PR 52) rehearsed on the CPU
at a small size: a temporary copy of the benchmark to which a tiny durable
deployment is ADDED as new files and appended entries (the real cell's
traffic kind, deployment builder, reference and metrics; 2,048-row batches
on a coarse custom grid, jobs of 8 batches in segments of 2, killed at the
dispatch of the third). The cell's files resolve, the sound run reads
correct with every job killed and resumed on a fresh `StreamJoin`, both
controls and a broken fold do not, the rate counts a replayed batch once,
the four entries this cell brought are no twins and have nothing to read on
an empty run, and read the spans of PR 52 where a run recorded them. A CPU
run asserts answers and counts; it never states a device number."""

import json
import os
import time

import pytest

from bh_fixtures import REPO, TINY_POINTS, _write, append_as_a_pr, make_copy

from benchmark.harness.run_cell import run_cell
from benchmark.harness.spec import Spec
from test_benchmark_program_spans import _ctx, check_entry
from test_benchmark_shared_entries import check_no_twins

CELL, REAL = "tiny.stream-durable", "taxi.stream-durable"
CONFIG, MIX = "taxi-zones-h3r9-durable", "pickups-hotspot-durable"
NEW_METRICS = [
    "snapshot_stall_ms.durable", "resume_s.durable", "fingerprint_ms.durable",
    "segment_device_ms.durable",
]
SHARED_METRICS = ["device_idle.batch", "compiles_in_window.batch",
                  "index_build_s", "warmup_s"]
NB, EVERY, KILL_AFTER = 8, 2, 2  # jobs of 8 batches, killed at batch 4


def add_durable_cell(root: str, **mix_change) -> None:
    """``tiny.stream-durable`` on ``tiny-zones``' sizes: a configuration
    file, a mix file, a workloads file and two appended entries; its name
    joins every list the real cell stands in."""
    def add(tree, bench):
        spec = Spec(root)
        real = spec.config(CONFIG)
        cfg = dict(spec.config("tiny-zones"))
        cfg.pop("name")
        cfg.update(
            job_batches=NB,
            durable=dict(real["durable"], snapshot_every=EVERY),
            guarantees=dict(real["guarantees"]), reduced={},
        )
        _write(os.path.join(tree, "configs", "tiny-durable.json"), cfg)
        mix = spec.traffic(MIX)
        mix.pop("name")
        mix.update(ring_slots=2, points=TINY_POINTS,
                   kill=dict(mix["kill"], skip_first=KILL_AFTER))
        mix.update(mix_change)
        _write(os.path.join(tree, "traffic", "tiny-durable.json"), mix)
        _write(os.path.join(tree, "workloads", CELL + ".json"),
               {"check": {"sample_rows": 4096}})
        bench["configs"].append({
            "name": "tiny-durable", "source": "test fixture (durable)",
            "file": "benchmark/configs/tiny-durable.json", "reduced": [],
            "why": "test fixture",
        })
        bench["workloads"].append({
            "name": CELL, "config": "tiny-durable", "traffic": "tiny-durable",
            "chips": 1, "why": "test fixture"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if REAL in m.get("workloads", []):
                m["workloads"].append(CELL)

    append_as_a_pr(root, add)


@pytest.fixture()
def root(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("TMPDIR", str(tmp_path))
    monkeypatch.setattr("tempfile.tempdir", None)
    monkeypatch.delenv("MOSAIC_STREAM_PIPELINE", raising=False)
    root = make_copy(tmp_path)
    add_durable_cell(root)
    return root


def _run(root, seed, seconds=0.3, trace=False, **kw):
    return run_cell(root, CELL, seed, seconds, trace,
                    t_start=time.perf_counter(), rehearsal=True, **kw)


def _said(text: str, what: str) -> dict:
    """The last ``[bench] <what>:`` line's ``key=value`` words (values that
    hold no space)."""
    line = [ln for ln in text.splitlines()
            if ln.startswith(f"[bench] {what}:")][-1]
    return dict(w.split("=", 1) for w in line.split()[2:] if "=" in w)


# ------------------------------------------------------------ the real files

def test_the_cells_files_resolve():
    spec = Spec(REPO)
    cell = spec.cell(REAL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, MIX, 1)
    assert cell["check"] == {"sample_rows": 262144}
    assert [m["name"] for m in spec.end_to_end(REAL)] == \
        ["setup_s", "batch_rows_per_s"]
    mix = spec.traffic(MIX)
    assert mix["kind"] == "device_ring_durable" and mix["ring_slots"] == 8
    assert mix["points"] == spec.traffic("pickups-hotspot")["points"]
    assert mix["kill"]["skip_first"] == 5 and mix["kill"]["fail_first"] >= 3
    assert mix["kill"]["sites"] == ["stream.scan_step"]
    assert spec.module("traffic_kinds", mix["kind"]).KILLED == "device lost"


def test_the_configuration_is_the_taxi_zones_plus_a_durable_block():
    spec = Spec(REPO)
    cfg, base = spec.config(CONFIG), spec.config("taxi-zones-h3r9")
    for key in ("row", "deployment", "reference", "index_system",
                "resolution", "zones", "batch_rows_per_chip", "precision",
                "chips", "mesh"):
        assert cfg[key] == base[key], key
    assert cfg["job_batches"] == 64 and cfg["durable"]["snapshot_every"] == 8
    assert set(cfg["reduced"]) == {"trip_rows", "job_batches"}
    assert cfg["reduced"]["trip_rows"] == base["reduced"]["trip_rows"]
    for key in ("pipeline", "window"):
        assert "package default" in cfg["durable"][key]
    g = cfg["guarantees"]
    assert g["stream_max_disagreement"] == \
        base["guarantees"]["stream_max_disagreement"] == 0.001
    assert g["fold_max_mismatches"] == g["bounded_loss_max_violations"] == 0
    for key, word in (("exactly_once", "bit for bit"),
                      ("bounded_loss", "newest valid snapshot"),
                      ("snapshot_survives", "fsync")):
        assert word in g[key], key
    assert len(cfg["assumed"]) >= len(base["assumed"]) + 4
    assert "deployment_stood_for" in cfg and len(cfg["source"]) <= 200
    entry = next(c for c in spec.benchmark["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"]
    assert "Recovering from Failures with Checkpointing" in cfg["source"]


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_entry_moves_the_batch_rate_and_has_nothing_to_read_when_empty(name):
    spec = Spec(REPO)
    check_entry(spec, name)
    entry = next(m for m in spec.benchmark["per_layer"] if m["name"] == name)
    assert entry["moves"] == "batch_rows_per_s"
    assert entry["layer"] == "durability" and REAL in entry["workloads"]
    desc = spec.data("layer_metrics", name)
    assert desc["reader"] in ("span_seconds", "span_child_percentile",
                              "event_percentile", "event_ratio",
                              "trace_busy_in_span")


def test_the_four_entries_are_no_twins_and_the_cell_joined_the_shared_ones():
    spec = Spec(REPO)
    check_no_twins(spec)
    names = {m["name"] for m in spec.per_layer(REAL)}
    assert names == set(NEW_METRICS) | set(SHARED_METRICS)
    # `test_benchmark_additive.py` appends three probe entries to a copy of
    # the real file and holds the copy to the contract's 128: the real file
    # has room for 125, which is why this cell brought four entries and
    # `replayed_batches` stays a counter on the span and a printed line
    assert len(spec.benchmark["per_layer"]) + 3 <= 128
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    # appended, each at the end of its list
    assert bench["configs"][-1]["name"] == CONFIG
    assert bench["workloads"][-1]["name"] == REAL
    assert [m["name"] for m in bench["per_layer"][-4:]] == NEW_METRICS
    for m in bench["end_to_end"] + bench["per_layer"]:
        if REAL in m.get("workloads", []) and len(m["workloads"]) > 1:
            assert m["workloads"][-1] == REAL, m["name"]


# ------------------------------------------------------ the CPU rehearsal

@pytest.mark.parametrize("seed", [31, 4_000_000_778])
def test_rehearsal_is_sound_and_every_job_is_killed_and_resumed(
        root, seed, capfd):
    line = _run(root, seed)
    said = "".join(capfd.readouterr())
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"batch_rows_per_s", "setup_s"}
    for name in ("durable_fold_mismatches", "durable_boundary_violations",
                 "durable_overflow_rows", "durable_resumed_rows_differing",
                 "forbidden_events"):
        assert f"[check] {name}: value=0.0 limit=0.0 ok" in said, name
    assert "limit=0.001 ok" in said.split(
        "[check] stream_disagreement_share:")[1].splitlines()[0]
    ready, win = _said(said, "stream_ready"), _said(said, "durable_window")
    assert ready["pipelined"] == "False" and ready["control"] == "None"
    jobs = int(win["jobs"])
    assert jobs >= 1 and line["attempted"] == jobs * NB * 2048
    # every job died at the dispatch of its third segment and resumed there
    at = str([KILL_AFTER * EVERY] * jobs)
    assert f"resumed_from={at} cursor_at_kill={at}" in said
    assert f"replayed_batches={[0] * jobs}" in said
    assert line["checks"]["durable_fold_mismatches"] == \
        {"value": 0.0, "limit": 0.0}


def test_the_rate_counts_a_replayed_batch_once(root, capfd):
    """The rows are job_batches x batch x jobs whatever was replayed: the
    durability control's jobs fold a segment twice and run NB + EVERY
    batches, and neither ``attempted`` nor the rate's numerator grows."""
    line = _run(root, 33, control=True)  # odd seed: the snapshot moved back
    win = _said("".join(capfd.readouterr()), "durable_window")
    jobs = int(win["jobs"])
    assert line["attempted"] == jobs * NB * 2048
    assert line["metrics"]["batch_rows_per_s"]["value"] == pytest.approx(
        line["attempted"] / float(win["window_s"]), rel=1e-3)


@pytest.mark.parametrize("seed, control", [(32, "bfloat16_cells"),
                                           (33, "snapshot_moved_back")])
def test_both_controls_fail(root, seed, control, capfd):
    line = _run(root, seed, control=True)
    said = "".join(capfd.readouterr())
    assert _said(said, "stream_ready")["control"] == control
    assert line["correct"] is False
    checks = line["checks"]
    if control == "bfloat16_cells":
        # the fold is the control's own: only the reference catches it
        assert checks["durable_fold_mismatches"]["value"] == 0
        assert checks["stream_disagreement_share"]["value"] > 0.001
    else:
        # a segment folded twice: every job's fold differs, and the resume
        # started a segment early, inside the bounded-loss guarantee
        jobs = int(_said(said, "durable_window")["jobs"])
        assert checks["durable_fold_mismatches"]["value"] == jobs + 1
        assert checks["durable_boundary_violations"]["value"] == 0
        assert checks["stream_disagreement_share"]["value"] <= 0.001


def test_a_job_that_is_not_killed_is_an_error(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("TMPDIR", str(tmp_path))
    monkeypatch.setattr("tempfile.tempdir", None)
    root = make_copy(tmp_path)
    add_durable_cell(root, kill={"skip_first": 99, "fail_first": 1,
                                 "sites": ["stream.scan_step"]})
    with pytest.raises(RuntimeError, match="killed no segment"):
        _run(root, 5)
    assert not [d for d in os.listdir(tmp_path) if d.startswith("mosaic-")]


def test_the_pipelined_loop_runs_through_the_same_kind(root, capfd, monkeypatch):
    """`MOSAIC_STREAM_PIPELINE=1`, the program's own knob: how the builder
    read both loops on the chip."""
    monkeypatch.setenv("MOSAIC_STREAM_PIPELINE", "1")
    line = _run(root, 35)
    said = "".join(capfd.readouterr())
    assert line["correct"] is True and line["failed"] == 0
    assert _said(said, "stream_ready")["pipelined"] == "True"


def test_traced_rehearsal_reads_the_spans_pr_52_added(root, capfd):
    """A `--trace 1` run on the CPU: no device plane, so the two trace
    readers have nothing to read; the span metrics read PR 52's spans."""
    line = _run(root, 37, seconds=0.2, trace=True)
    said = "".join(capfd.readouterr())
    assert line["correct"] is True
    got = line["metrics"]
    assert set(got) >= {"snapshot_stall_ms.durable", "resume_s.durable",
                        "fingerprint_ms.durable",
                        "compiles_in_window.batch", "index_build_s",
                        "warmup_s"}
    assert got["compiles_in_window.batch"]["value"] == 0.0
    assert got["resume_s.durable"]["value"] > 0.0
    assert "segment_device_ms.durable" not in got  # no device plane here
    assert "[bench] nothing_to_read: metric=segment_device_ms.durable" in said
    per_job = _said(said, "job_breakdown")
    assert per_job  # the line is there


def test_readers_on_hand_made_spans():
    spec = Spec(REPO)

    def span(name, seconds, ts, **kw):
        return dict({"event": "span", "name": name, "seconds": seconds,
                     "ts_mono": ts}, **kw)

    events = [
        span("stream.snapshot", 0.10, 1.0), span("stream.snapshot", 0.30, 2.0),
        span("stream.snapshot", 0.20, 3.0),
        span("stream.fingerprint", 1.5, 4.0, nbytes=512_000_000),
        span("stream.fingerprint", 2.5, 5.0, nbytes=512_000_000),
        span("stream.resume", 9.0, 6.0, ready_s=3.25),
        span("stream.durable_run", 5.0, 7.0, resumed_from=None),
        span("stream.durable_run", 4.0, 8.0, resumed_from=40,
             replayed_batches=8),
        span("stream.snapshot", 9.9, 500.0),  # outside the window
    ]
    ctx = _ctx(spec, events=events, window=(0.0, 100.0))

    def read(name):
        desc = spec.data("layer_metrics", name)
        return spec.module("readers", desc["reader"]).read(ctx, desc["params"])

    assert read("snapshot_stall_ms.durable") == pytest.approx(200.0)
    assert read("fingerprint_ms.durable") == pytest.approx(1500.0)
    assert read("resume_s.durable") == pytest.approx(3.25)
    assert read("segment_device_ms.durable") is None
