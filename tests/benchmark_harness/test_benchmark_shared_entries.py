"""The rule `per_layer` follows: ONE entry per (reader, parameters, moved
end-to-end metric), with every cell that reads it in its ``workloads``. A
later PR that adds a cell appends the cell's name to the lists that are
there and mints no twin (the six `.knn-buildings` names,
`compiles_in_window.*` and `device_idle.*` by cell, `tessellate_s.overlay`,
`landmark_pool_build_s.knn` were such twins and are gone). The limit on the
list is the contract's 128 and nothing under it: what keeps it from filling
with copies is the rule above, not a count (``PERF.md`` says how many places
are left and who spent which).

The two start-up entries carry NO ``workloads`` key: by the contract, and by
`Spec.per_layer`, such an entry is read by every cell that reports what it
moves, and every cell reports `setup_s`. So a PR that adds a cell has no
list to join for them.

Every (shared entry, cell that reads it) pair is a case: the entry resolves
for that cell, its reader has nothing to read on an empty run, and where the
repo has a recording of that cell on the chip (``benchmark/fixtures/``) the
reader reads the number the recording's own run read."""

import gzip
import json
import os

import pytest

from bh_fixtures import REPO

from benchmark.harness import xplane
from benchmark.harness.spec import Spec
from test_benchmark_program_spans import _ctx, _with_trace, check_entry

LIMIT = 128  # the contract's, `test_benchmark_contract.py`
#: the entries every cell of the benchmark reads
START_UP = ("index_build_s", "warmup_s")
#: the cells the repo holds a recording of, made on the TPU v5e by
#: `benchmark/tools/record_trace_fixture.py`
FIXTURES = {"taxi.batch": "taxi_batch_v5e", "taxi.serve": "taxi_serve_v5e",
            "modis-zonal.scan": "modis_zonal_v5e"}
#: under which name a recording's run read what a merged entry reads now
RECORDED_AS = {"device_idle.batch": "device_idle.zonal",
               "compiles_in_window.batch": "compiles_in_window.zonal"}
#: readers of what only the live harness holds (its own spans, the traffic
#: kind's series): a recording cannot feed them
LIVE_ONLY = {"span_seconds", "series_percentile"}
RETIRED = {
    "dispatch_p50_ms.serve": ["dispatch_handoff_p50_ms.serve",
                              "dispatch_h2d_p50_ms.serve",
                              "dispatch_launch_p50_ms.serve",
                              "dispatch_d2h_p50_ms.serve"],
    "loop_ms_per_step.stream": ["launch_ms_per_dispatch.stream",
                                "device_idle.stream"],
    "join_device_ms.stream": ["cells_device_ms.stream",
                              "probe_device_ms.stream",
                              "tier1_device_ms.stream",
                              "writeback_device_ms.stream",
                              "tier2_device_ms.stream",
                              "unscoped_device_share.stream"],
    "call_p95_ms.batch": ["call_p50_ms.batch", "call_max_ms.batch",
                          "call_max_covered_share.batch"],
}
MERGED = {
    "compiles_in_window.batch": ["compiles_in_window.zonal",
                                 "compiles_in_window.knn",
                                 "compiles_in_window.overlay",
                                 "compiles_in_window.knn-buildings"],
    "device_idle.batch": ["device_idle.zonal", "device_idle.knn",
                          "device_idle.overlay", "device_idle.knn-buildings"],
    "tessellate_s.build": ["tessellate_s.overlay"],
    "pool_build_s.batch": ["landmark_pool_build_s.knn"],
    "call_p50_ms.knn": ["call_p50_ms.knn-buildings"],
    "expand_ms_per_call.knn": ["expand_ms_per_call.knn-buildings"],
    "distance_ms_per_call.knn": ["distance_ms_per_call.knn-buildings"],
    "device_busy_ms_per_call.knn": ["device_busy_ms_per_call.knn-buildings"],
}
HOST_FED = ["taxi.batch", "taxi.batch-exact", "modis-zonal.scan",
            "nyc-knn.transform", "bng-parcels.overlay",
            "nyc-knn-buildings.transform"]


@pytest.fixture(scope="module")
def spec():
    return Spec(REPO)


def key_of(spec, entry) -> tuple:
    desc = spec.data("layer_metrics", entry["name"])
    return (desc["reader"], json.dumps(desc["params"], sort_keys=True),
            entry["moves"])


def check_no_twins(spec) -> None:
    """No two entries share (reader, parameters, ``moves``): what the
    additivity test holds a copy with appended entries to as well."""
    seen: dict = {}
    for entry in spec.benchmark["per_layer"]:
        seen.setdefault(key_of(spec, entry), []).append(entry["name"])
    twins = {k: v for k, v in seen.items() if len(v) > 1}
    assert not twins, (
        f"one entry per (reader, parameters, moves): append the cell to the "
        f"first entry's workloads instead of a second entry: {twins}")


def test_no_two_entries_share_reader_parameters_and_moved_metric(spec):
    check_no_twins(spec)


def test_a_twin_is_caught(spec, tmp_path):
    """The rule fails on a copy to which a PR appends `device_idle.batch`'s
    reader under a new name for a new cell's sake."""
    from bh_fixtures import _write, append_as_a_pr, make_copy

    root = make_copy(tmp_path)
    check_no_twins(Spec(root))  # the tiny cells joined lists, minted none

    def mint(tree, bench):
        _write(os.path.join(tree, "layer_metrics", "device_idle.twin.json"),
               {"what": "a twin", "reader": "trace_idle_share", "params": {}})
        by = {m["name"]: m for m in bench["per_layer"]}
        bench["per_layer"].append(dict(
            by["device_idle.batch"], name="device_idle.twin",
            workloads=["bng-parcels.overlay"]))

    append_as_a_pr(root, mint)
    with pytest.raises(AssertionError, match="device_idle.twin"):
        check_no_twins(Spec(root))


def check_limit(spec) -> None:
    assert len(spec.benchmark["per_layer"]) <= LIMIT


def test_the_list_is_held_to_the_contracts_limit_and_to_nothing_under_it(spec):
    check_limit(spec)
    with open(os.path.join(REPO, "tests", "benchmark_harness",
                           "test_benchmark_contract.py"), encoding="utf-8") as f:
        assert f'len(bench["per_layer"]) <= {LIMIT}' in f.read()


def check_start_up_entries(spec) -> None:
    """`index_build_s` and `warmup_s` name no cells: every cell that reports
    `setup_s` reads both, and `setup_s` names none either, so every cell —
    whichever PR appended it — does."""
    by = {m["name"]: m for m in spec.benchmark["per_layer"]}
    moved = next(m for m in spec.benchmark["end_to_end"]
                 if m["name"] == "setup_s")
    assert "workloads" not in moved
    for name in START_UP:
        assert "workloads" not in by[name], (
            f"{name} lists no cells: every cell reads it")
        assert by[name]["moves"] == "setup_s"
    for w in spec.benchmark["workloads"]:
        assert set(START_UP) <= {m["name"] for m in spec.per_layer(w["name"])}


def test_the_start_up_entries_list_no_cells_and_every_cell_reads_them(spec):
    check_start_up_entries(spec)


def test_no_twin_name_and_no_retired_name_is_left(spec):
    names = {m["name"] for m in spec.benchmark["per_layer"]}
    files = {f[:-len(".json")] for f in os.listdir(
        os.path.join(REPO, "benchmark", "layer_metrics"))}
    assert files == names, files ^ names
    assert not [n for n in names if n.endswith(".knn-buildings")]
    for survivor, twins in MERGED.items():
        assert survivor in names and not names & set(twins)
    for name, successors in RETIRED.items():
        assert name not in names and set(successors) <= names, name


SHARED_BY_THE_HOST_FED = ["compiles_in_window.batch", "device_idle.batch"]
#: the cells whose set-up opens the span, by the entry that reads it
SPAN_LISTS = {
    "tessellate_s.build": ("tessellate",
                           {"osm-buildings.join", "bng-parcels.overlay"}),
    "pool_build_s.batch": ("pool_build",
                           {"taxi.batch", "taxi.batch-exact",
                            "nyc-knn.transform",
                            "nyc-knn-buildings.transform"}),
}


def check_host_fed_entry(spec, name) -> None:
    """The six host-fed cells are IN the one entry's list — membership, so
    that the next host-fed cell appends its name (the additivity test holds
    a copy with one appended to this)."""
    by = {m["name"]: m for m in spec.benchmark["per_layer"]}
    assert set(HOST_FED) <= set(by[name]["workloads"])
    assert by[name]["moves"] == "batch_rows_per_s"
    # `.stream` and `.serve` move other end-to-end metrics and stay
    stem = name[: -len(".batch")]
    assert {by[stem + ".stream"]["moves"], by[stem + ".serve"]["moves"]} == \
        {"rows_per_s", "latency_p95_ms"}
    # the survivor says what differs by cell: how much each one traces
    what = spec.data("layer_metrics", name)["what"]
    for word in (("host-fed",) if stem == "compiles_in_window" else
                 ("modis-zonal.scan", "nyc-knn", "bng-parcels.overlay",
                  "profiler")):
        assert word in what, word


def check_span_lists(spec) -> None:
    """`tessellate_s.build` and `pool_build_s.batch` list the cells that
    have the span (and whichever a later PR appends), and every listed
    cell's traffic kind or deployment opens it."""
    by = {m["name"]: m for m in spec.benchmark["per_layer"]}
    for name, (span, cells) in SPAN_LISTS.items():
        assert cells <= set(by[name]["workloads"]), name
        assert spec.data("layer_metrics", name)["params"] == {"span": span}
        for cell in by[name]["workloads"]:
            cfg = spec.config(spec.cell(cell)["config"])
            kind = spec.traffic(spec.cell(cell)["traffic"])["kind"]
            sources = [
                os.path.join(spec.tree, "traffic_kinds", kind + ".py"),
                os.path.join(spec.tree, "deployments",
                             cfg["deployment"] + ".py")]
            said = "".join(open(p, encoding="utf-8").read() for p in sources)
            assert f'span("{span}")' in said, (cell, span)


@pytest.mark.parametrize("name", SHARED_BY_THE_HOST_FED)
def test_the_host_fed_cells_share_one_entry(spec, name):
    check_host_fed_entry(spec, name)


def test_tessellate_and_pool_build_list_every_cell_that_has_the_span(spec):
    check_span_lists(spec)


# ------------------------------- every (shared entry, cell) pair is a case

def _pairs():
    """(entry, cell) for every entry more than one cell reads: the cells it
    lists, or every cell where it lists none."""
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    return [(m["name"], cell) for m in bench["per_layer"]
            if len(m.get("workloads", cells)) > 1
            for cell in m.get("workloads", cells)]


@pytest.fixture(scope="module")
def recordings(spec):
    """``{cell: (result, events, stage tables, trace, reduction)}``."""
    out = {}
    for cell, name in FIXTURES.items():
        d = os.path.join(REPO, "benchmark", "fixtures", name)
        with open(os.path.join(d, "result.json"), encoding="utf-8") as f:
            result = json.load(f)
        with gzip.open(os.path.join(d, "events.jsonl.gz"), "rt",
                       encoding="utf-8") as f:
            events = [json.loads(line) for line in f]
        with open(os.path.join(d, "stage_tables.json"), encoding="utf-8") as f:
            tables = json.load(f)
        path = os.path.join(d, "trace.xplane.pb.gz")
        red = xplane.reduce_planes(
            xplane.read_planes(path), result["tracer_window_s"])
        out[cell] = (result, events, tables, path, red)
    return out


@pytest.mark.parametrize("name, cell", _pairs())
def test_shared_entry_resolves_and_reads_for_each_of_its_cells(
        spec, recordings, monkeypatch, name, cell):
    check_entry(spec, name)
    entry = next(m for m in spec.per_layer(cell) if m["name"] == name)
    moved = next(m for m in spec.benchmark["end_to_end"]
                 if m["name"] == entry["moves"])
    assert cell in moved.get("workloads", [cell])
    desc = spec.data("layer_metrics", name)
    reader = spec.module("readers", desc["reader"])
    assert reader.read(_ctx(spec), desc["params"]) is None
    if cell not in recordings:
        return
    # the recording of this cell: the reader reads what its own run read
    from mosaic_tpu.obs import stages

    result, events, tables, path, red = recordings[cell]
    then = result["line"]["metrics"]
    was = then.get(name) or then.get(RECORDED_AS.get(name, ""))
    _with_trace(spec, monkeypatch,
                spec.module("readers", "_trace").load(path))
    monkeypatch.setattr(stages, "tables", lambda modules, rows: tables)
    kind = spec.module(
        "traffic_kinds", spec.traffic(spec.cell(cell)["traffic"])["kind"])
    ctx = _ctx(
        spec, events=events, window=tuple(result["window"]),
        trace_reduction=red, device={"kind": result["line"]["device"]["kind"]},
        counters={"traced_steps": getattr(kind, "TRACE_CALLS", None),
                  "compiles_in_window": 0})
    value = reader.read(ctx, desc["params"])
    if was is None or desc["reader"] in LIVE_ONLY:
        assert value is None
    else:
        assert value == pytest.approx(was["value"], rel=1e-6)
