"""The batch cells' inside (PR 35): the reader `span_cover_share` on
hand-made events, a tiny traced `host_batch_join` cell on the CPU that
reads every new span metric as a number, and the stage and idle metrics
read from four traced calls of `taxi.batch` recorded on the TPU v5e
(``benchmark/fixtures/taxi_batch_v5e/``, written by
``benchmark/tools/record_trace_fixture.py``). A CPU run states counts and
names, never a device number.

Two entries appended after those read what they leave unread:
`probes_per_call.batch` (the field ``probes`` on `join.pip`) and
`compact_device_ms.batch` (the recheck's scope `pip.compact`). Each resolves
for the tiny cells, reads hand-made events to the value worked out by hand
and reads nothing on an empty run; the batch cells are IN their lists, by
membership, so a later batch cell joins them
(`check_later_entry`, which `test_benchmark_additive.py` runs on a copy)."""

import gzip
import json
import os
import time
from types import SimpleNamespace

import pytest

from bh_fixtures import REPO, make_copy

from benchmark.harness.spec import Spec
from test_benchmark_host_batch import add_batch_cells
from test_benchmark_program_spans import (
    _ctx, _read, _span, _with_trace, check_entry,
)

FIXTURE = os.path.join(REPO, "benchmark", "fixtures", "taxi_batch_v5e")
SPAN_METRICS = [
    "put_p50_ms.batch", "counts_sync_p50_ms.batch", "host_shift_p50_ms.batch",
    "put_shifted_p50_ms.batch", "launch_p50_ms.batch", "pull_p50_ms.batch",
    "span_coverage_p50.batch", "call_max_covered_share.batch",
]
EXACT_SPAN_METRICS = [
    "recheck_band_p50_ms.batch", "recheck_host_p50_ms.batch",
    "recheck_host_row_share.batch",
]
STAGE_METRICS = [
    "cells_device_ms.batch", "counts_device_ms.batch", "probe_device_ms.batch",
    "tier1_device_ms.batch", "writeback_device_ms.batch",
    "unscoped_device_share.batch",
]
#: appended by PR 39 after the twenty: the host work under the count sync
UNDER_SYNC = "host_under_sync_p50_ms.batch"
IDLE_METRICS = [
    "idle_host_shift_share.batch", "idle_put_share.batch",
    "idle_sync_share.batch",
]
#: appended after the twenty: name -> (source, unit, the cells that read it)
LATER_ENTRIES = {
    "probes_per_call.batch": ("program_counter", "count",
                              ["taxi.batch", "taxi.batch-exact"]),
    "compact_device_ms.batch": ("device_trace", "ms", ["taxi.batch-exact"]),
}


@pytest.fixture(scope="module")
def spec():
    return Spec(REPO)


def test_the_twenty_entries_are_the_batch_cells_and_move_their_rate(spec):
    names = SPAN_METRICS + EXACT_SPAN_METRICS + STAGE_METRICS + IDLE_METRICS
    assert len(names) == len(set(names)) == 20
    entries = {m["name"]: m for m in spec.benchmark["per_layer"]}
    # all twenty are in the list, in the order PR 35 appended them among
    # themselves, whatever a later PR appended after them or put between
    # (order, not position: `[-20:]` here refused every later metric)
    order = (SPAN_METRICS[:6] + EXACT_SPAN_METRICS + SPAN_METRICS[6:]
             + STAGE_METRICS + IDLE_METRICS)
    assert [m["name"] for m in spec.benchmark["per_layer"]
            if m["name"] in names] == order
    for n in names:
        e = entries[n]
        assert e["moves"] == "batch_rows_per_s"
        # membership: a later batch cell appends its name to these lists
        assert "taxi.batch-exact" in e["workloads"]
        # (the recheck's spans are in no call of the default cell)
        assert ("taxi.batch" in e["workloads"]) == (n not in EXACT_SPAN_METRICS)
        assert e["source"] == ("device_trace" if n in STAGE_METRICS + IDLE_METRICS
                               else "program_span")


# ------------------------------------------------------ span_cover_share

def _calls():
    """Three calls in the window and one outside it; a grandchild and
    another root's child do not count as a call's direct children."""
    return [
        _span("join.pip", "a", None, 1.0, 10.0),
        _span("join.put", "a1", "a", 0.2, 9.3),
        _span("join.pull", "a2", "a", 0.7, 9.9),
        _span("dispatch.guard.handoff", "a3", "a2", 0.6, 9.8),
        _span("join.pip", "b", None, 3.0, 20.0),        # the stalled call
        _span("join.put", "b1", "b", 0.3, 17.5),
        _span("join.recheck.band", "b2", "b", 0.3, 19.5),
        _span("join.pip", "c", None, 2.0, 30.0),
        _span("join.put", "c1", "c", 1.0, 28.5),
        _span("join.put", "c2", "c", 0.5, 29.5),        # a second chunk's
        _span("join.pip", "d", None, 9.0, 300.0),       # outside the window
        _span("join.put", "d1", "d", 9.0, 299.0),
        _span("serve.batch", "e", None, 5.0, 40.0),
        _span("serve.pad", "e1", "e", 5.0, 39.0),
    ]


def test_span_cover_share_p50_and_slowest(spec):
    ctx = _ctx(spec, events=_calls())
    p = {"root": "join.pip", "pick": "p50"}
    # shares of the calls in the window: 90%, 20%, 75% -> nearest rank 75%
    assert _read(spec, "span_cover_share", ctx, p) == pytest.approx(75.0)
    assert _read(spec, "span_cover_share", ctx, dict(p, pick="slowest")) == \
        pytest.approx(20.0)
    # both lines once a run, however many metrics read the reader
    said = dict(ctx.said)
    assert [w for w, _ in ctx.said] == ["slowest_call", "p50_call"]
    assert said["slowest_call"] == {
        "seconds": 3.0, "calls": 3, "put": 300.0, "recheck_band": 300.0,
        "uncovered": 2400.0}
    assert said["p50_call"] == {
        "seconds": 2.0, "calls": 3, "put": 1500.0, "uncovered": 500.0}


def test_span_cover_share_has_nothing_to_read_without_child_spans(spec):
    """The parent's program: one `join.pip` a call and nothing under it."""
    roots = [e for e in _calls() if e["name"] == "join.pip"]
    for pick in ("p50", "slowest"):
        ctx = _ctx(spec, events=roots)
        assert _read(spec, "span_cover_share", ctx,
                     {"root": "join.pip", "pick": pick}) is None
        assert ctx.said == []
    assert _read(spec, "span_cover_share", _ctx(spec),
                 {"root": "join.pip", "pick": "p50"}) is None


# ------------------------------------ the probes a call, the compaction

def _pip_calls(probes):
    """One `join.pip` root a call in the window carrying ``probes``, one
    more outside it, and another frontend's span with the field."""
    events = [dict(_span("join.pip", f"j{i}", None, 0.14, 10.0 + i), probes=n)
              for i, n in enumerate(probes)]
    events.append(dict(_span("join.pip", "late", None, 0.14, 300.0), probes=9))
    events.append(dict(_span("serve.batch", "s", None, 0.01, 12.0), probes=7))
    return events


def check_later_entry(spec, name) -> None:
    """What holds the entry on the real file, and on a copy to which a PR
    appended a batch cell's name."""
    source, unit, cells = LATER_ENTRIES[name]
    entry = next(m for m in spec.benchmark["per_layer"] if m["name"] == name)
    # membership: a later batch cell appends its name to the list
    assert set(cells) <= set(entry["workloads"])
    assert (entry["moves"], entry["layer"], entry["better"]) == (
        "batch_rows_per_s", "join stages", "lower")
    assert (entry["source"], entry["unit"]) == (source, unit)
    check_entry(spec, name)  # an empty run: nothing to read
    # after the twenty of PR 35 and PR 39's one, whatever else came between
    names = [m["name"] for m in spec.benchmark["per_layer"]]
    assert names.index(name) > names.index(UNDER_SYNC)


@pytest.mark.parametrize("name", LATER_ENTRIES)
def test_later_entry_is_the_batch_cells_and_resolves_for_the_tiny_ones(
        spec, tmp_path, name):
    cells = LATER_ENTRIES[name][2]
    check_later_entry(spec, name)
    # the tiny cells join the lists the real ones stand in
    root = make_copy(tmp_path)
    add_batch_cells(root)
    tiny = Spec(root)
    for cell in cells:
        assert name in [m["name"] for m in tiny.per_layer(
            cell.replace("taxi", "tiny"))]
    check_entry(tiny, name)


#: the exact cell's window: 2 of the pool's 4 batches run `alt_rejoin`'s count
#: — an odd number of calls, in either parity
EXACT_ODD, EXACT_OTHER_PARITY = [1, 1, 2, 2, 1, 1, 2], [2, 2, 1, 1, 2, 2, 1]


@pytest.mark.parametrize("probes, reads", [
    ([1] * 7, 1),                    # the join handed its slots: one probe
    ([2] * 7, 2),                    # the count's probe and the join's
    (EXACT_ODD, 1),
    (EXACT_OTHER_PARITY, 1),
    ([2, 2, 3, 3, 2, 2], 2),         # -exact with two probes a call
], ids=["one-probe", "two-probes", "exact-odd", "exact-other-parity",
        "exact-two-probes"])
def test_probes_per_call_reads_the_call_with_an_empty_band(spec, probes, reads):
    desc = spec.data("layer_metrics", "probes_per_call.batch")
    ctx = _ctx(spec, events=_pip_calls(probes))
    assert _read(spec, desc["reader"], ctx, desc["params"]) == reads
    # a program whose `join.pip` carries no such field: nothing, never a 0
    bare = [_span("join.pip", "a", None, 0.14, 10.0)]
    assert _read(spec, desc["reader"], _ctx(spec, events=bare),
                 desc["params"]) is None


def test_a_median_of_probes_would_follow_the_call_counts_parity(spec):
    """Why the entry reads the lower quartile: a populated cell band's
    `alt_rejoin` adds a probe to half of the exact cell's calls."""
    desc = spec.data("layer_metrics", "probes_per_call.batch")
    assert desc["params"]["q"] == 0.25
    median = dict(desc["params"], q=0.5)
    assert [_read(spec, desc["reader"], _ctx(spec, events=_pip_calls(p)), median)
            for p in (EXACT_ODD, EXACT_OTHER_PARITY)] == [1, 2]


def test_compact_device_ms_reads_the_rechecks_scope_per_traced_call(
        spec, monkeypatch):
    desc = spec.data("layer_metrics", "compact_device_ms.batch")
    assert desc["params"] == {"stage": "pip.compact", "steps": "traced_steps"}
    # the same reader and parameters as the stream's entry, another moved
    # metric: no twin (`check_no_twins` holds the real file to it)
    assert desc["params"] == spec.data(
        "layer_metrics", "compact_device_ms.stream")["params"]
    _with_trace(spec, monkeypatch, {"devices": {}})
    table = {"pip.compact": 0.0394, "pip.tier1": 0.268, "pip.cells": 0.164}
    ctx = _ctx(spec, counters={"traced_steps": 4}, device_by_stage=table)
    # the two populated calls' 19.7 ms each, over the four traced calls
    assert _read(spec, desc["reader"], ctx, desc["params"]) == \
        pytest.approx(9.85)
    # no trace (a CPU run, an untraced one): nothing to read
    _with_trace(spec, monkeypatch, None)
    assert _read(spec, desc["reader"], ctx, desc["params"]) is None


# ------------------------------------------- a tiny traced cell on the CPU

@pytest.fixture()
def root(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    root = make_copy(tmp_path)
    add_batch_cells(root)
    return root


@pytest.mark.parametrize("cell", ["tiny.batch", "tiny.batch-exact"])
def test_traced_batch_cell_reads_every_span_metric_as_a_number(
        root, cell, capfd):
    from benchmark.harness.run_cell import run_cell

    line = run_cell(root, cell, 4_000_000_511, 0.5, True,
                    t_start=time.perf_counter(), rehearsal=True)
    assert line["correct"] is True and line["failed"] == 0
    m = line["metrics"]
    exact = cell.endswith("-exact")
    for name in SPAN_METRICS + (EXACT_SPAN_METRICS if exact else []):
        assert name in m, name
        assert m[name]["value"] >= 0.0
    if not exact:
        assert not set(EXACT_SPAN_METRICS) & set(m)
    else:
        assert 0.0 <= m["recheck_host_row_share.batch"]["value"] < 100.0
    # PR 36's counter, appended after the twenty (PR 39): call by call the
    # host work hidden under the count sync holds the shift, so p50 by p50
    assert m[UNDER_SYNC]["value"] >= m["host_shift_p50_ms.batch"]["value"] > 0.0
    # one program holds a hash probe in a call whose cell band is empty,
    # under either mode
    assert m["probes_per_call.batch"]["value"] == 1.0
    assert "compact_device_ms.batch" not in m  # a device number: no trace here
    # the pieces are inside the call they are pieces of
    pieces = sum(m[n]["value"] for n in SPAN_METRICS[:6])
    assert 0.0 < pieces and 0.0 < m["span_coverage_p50.batch"]["value"] <= 100.0
    assert 0.0 < m["call_max_covered_share.batch"]["value"] <= 100.0
    # the CPU has no device trace: stage and idle metrics read nothing
    assert not set(STAGE_METRICS + IDLE_METRICS) & set(m)
    out = capfd.readouterr().out
    slowest = [ln for ln in out.splitlines()
               if ln.startswith("[bench] slowest_call: ")]
    assert len(slowest) == 1
    for piece in ("put=", "cells=", "counts=", "shift=", "put_shifted=",
                  "launch=", "pull=", "uncovered="):
        assert piece in slowest[0]
    assert ("recheck_band=" in slowest[0]) is exact
    assert ("recheck_host=" in slowest[0]) is exact
    assert out.count("[bench] p50_call: ") == 1


# --------------------------------------------- the trace recorded on the chip

@pytest.fixture(scope="module")
def recorded(spec):
    with open(os.path.join(FIXTURE, "result.json"), encoding="utf-8") as f:
        result = json.load(f)
    with gzip.open(os.path.join(FIXTURE, "events.jsonl.gz"), "rt",
                   encoding="utf-8") as f:
        events = [json.loads(line) for line in f]
    with open(os.path.join(FIXTURE, "stage_tables.json"), encoding="utf-8") as f:
        tables = json.load(f)
    tr = spec.module("readers", "_trace").load(
        os.path.join(FIXTURE, "trace.xplane.pb.gz"))
    return SimpleNamespace(result=result, events=events, tables=tables, tr=tr)


def _recorded_ctx(spec, recorded, monkeypatch):
    from mosaic_tpu.obs import stages

    _with_trace(spec, monkeypatch, recorded.tr)
    monkeypatch.setattr(stages, "tables", lambda modules, rows: recorded.tables)
    kind = spec.module("traffic_kinds", "host_batch_join")
    return _ctx(spec, events=recorded.events,
                window=tuple(recorded.result["window"]),
                counters={"traced_steps": kind.TRACE_CALLS})


def test_recorded_calls_run_three_programs_under_the_calls_spans(spec, recorded):
    tr = recorded.tr
    assert list(tr["devices"]) == ["/device:TPU:0"]
    kind = spec.module("traffic_kinds", "host_batch_join")
    names = [p[0] for p in tr["program"]]
    for piece in ("join.pip", "join.put", "join.cells", "join.counts",
                  "join.shift", "join.put_shifted", "join.launch", "join.pull"):
        assert names.count(piece) == kind.TRACE_CALLS, piece
    # every piece lies inside its call's annotation, on one clock
    calls = [(s, e) for n, s, e, _t in tr["program"] if n == "join.pip"]
    for n, s, e, t in tr["program"]:
        if n.startswith("join.") and n != "join.pip":
            assert any(a <= s and e <= b for a, b in calls), n
        assert t is not None
    dev = tr["devices"]["/device:TPU:0"]
    runs = [m[0].split("(")[0] for m in dev["modules"]]
    assert set(runs) == {"jit_cells", "jit__probe_counts",
                         "jit_pip_join_points"} <= set(recorded.tables)
    for module in set(runs):
        assert runs.count(module) == kind.TRACE_CALLS
    assert set(recorded.tables["jit__probe_counts"].values()) == {"pip.counts"}


@pytest.mark.parametrize("name", STAGE_METRICS + IDLE_METRICS)
def test_recorded_calls_read_every_stage_and_idle_metric(
        spec, recorded, monkeypatch, name):
    ctx = _recorded_ctx(spec, recorded, monkeypatch)
    desc = spec.data("layer_metrics", name)
    value = _read(spec, desc["reader"], ctx, desc["params"])
    assert value is not None and value >= 0.0
    unit = next(m["unit"] for m in spec.benchmark["per_layer"]
                if m["name"] == name)
    assert value <= (100.0 if unit == "%" else 120.0)
    # the run that recorded the fixture read the same number from the
    # same trace
    then = recorded.result["line"]["metrics"][name]
    assert value == pytest.approx(then["value"], rel=1e-6)


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_recorded_events_read_every_span_metric(spec, recorded, monkeypatch, name):
    """The fixture's events are the window's last half second: one whole
    unprofiled call of the chip's, read as the run's own readers read it."""
    ctx = _recorded_ctx(spec, recorded, monkeypatch)
    desc = spec.data("layer_metrics", name)
    value = _read(spec, desc["reader"], ctx, desc["params"])
    unit = next(m["unit"] for m in spec.benchmark["per_layer"]
                if m["name"] == name)
    if unit == "%":
        assert 95.0 <= value <= 100.0, "the pieces are the call"
    else:
        assert 0.0 < value < 250.0, "milliseconds of one 4M-row call's piece"


def test_recorded_events_of_pr35_hold_nothing_for_the_under_sync_metric(
        spec, recorded, monkeypatch):
    """The fixture was recorded at PR 35, whose `join.counts` spans carry no
    `hidden_s`: the parent's case, nothing to read — never a 0."""
    ctx = _recorded_ctx(spec, recorded, monkeypatch)
    desc = spec.data("layer_metrics", UNDER_SYNC)
    assert any(e.get("name") == "join.counts" for e in recorded.events)
    assert _read(spec, desc["reader"], ctx, desc["params"]) is None
    entry = next(m for m in spec.benchmark["per_layer"]
                 if m["name"] == UNDER_SYNC)
    assert (entry["moves"], entry["source"], entry["layer"]) == (
        "batch_rows_per_s", "program_span", "dispatch core")
    assert {"taxi.batch", "taxi.batch-exact"} <= set(entry["workloads"])


def test_recorded_stages_are_the_calls_device_time(spec, recorded, monkeypatch):
    """The five stages sum to the device's busy time a call (the programs
    run one after another, nothing overlaps), next to nothing is unscoped,
    and the counts probe reads apart from the probe that answers."""
    ctx = _recorded_ctx(spec, recorded, monkeypatch)

    def read(name):
        desc = spec.data("layer_metrics", name)
        return _read(spec, desc["reader"], ctx, desc["params"])

    stages_ms = {n: read(n) for n in STAGE_METRICS[:5]}
    busy = read("device_busy_ms_per_call.batch")
    assert sum(stages_ms.values()) == pytest.approx(busy, rel=0.03)
    assert read("unscoped_device_share.batch") <= 2.0
    assert stages_ms["counts_device_ms.batch"] > 0.0
    assert stages_ms["probe_device_ms.batch"] > 0.0
    idle = [read(n) for n in IDLE_METRICS]
    assert 0.0 < sum(idle) <= 100.0 + 1e-6
    said = next(kv for what, kv in ctx.said if what == "idle_by_program_span")
    assert said["under_program_spans"] >= 0.9
