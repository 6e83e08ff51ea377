"""`harness/spec.py`'s promise run against the REAL ``BENCHMARK.json``: a
later PR appends a per-layer metric and a cell as new files and entries —
the cell's name joining the ``workloads`` of metrics that are there — edits
no file of the benchmark, and every check of the contract and of each
per-layer entry still holds on the result. A second "PR" then appends one
more metric after the first's, and both still pass: the case an assertion
on the LAST entries of ``per_layer`` refused until PR 39. A third appends a
host-fed KNN cell that brings NO entry: its name joins the lists PR 47
merged (`device_idle.batch`, `compiles_in_window.batch`, `pool_build_s.batch`,
the `.knn` entries both KNN cells share), and the very checks that hold
those lists on the real file hold them on the copy — the case a list pinned
with ``==`` refused until PR 47, and the reason twins were minted.

The copy is held to the WHOLE-FILE assertions of the real file too, not
only to the per-entry ones: no twin, the contract's limit on ``per_layer``
(and nothing under it), and the two start-up entries, which list no cells so
that every cell — the appended ones as well — reads them. A count under the
limit and a pin on a list's last name were exactly the assertions this proof
did not run, and each cost a PR its metrics. Their failing sides are proved
as well: a start-up entry given a list, a list one past the limit, and (in
`test_benchmark_shared_entries.py`) a twin.

It is held, too, to what each cell's own test module holds the real file to
for that cell (`check_s2s_entries`, `check_later_entry`, the KNN siblings,
the host-fed lists): a fourth "PR" appends an entry that lists
`ais-s2s.join`, a fifth a second distance-join cell whose name joins every
list that cell stands in — its own `.s2s` entries among them — and a sixth a
batch cell that joins `taxi.batch`'s. An ``==`` on the cell's set of names
or on an entry's list refused each of them.

The names below are RESERVED for this test and mean nothing else: no cell,
mix or metric of the real benchmark, and none of ``PERF.md``'s queue, may
take them (the first cell of that queue once had this test's names, and
could not go in until the test let go of them)."""

import os
import shutil

import pytest

from bh_fixtures import REPO, _write, append_as_a_pr

from benchmark.harness.spec import Spec
from test_benchmark_batch_spans import (
    LATER_ENTRIES,
    check_later_entry,
    test_the_twenty_entries_are_the_batch_cells_and_move_their_rate
    as check_batch_entries_keep_their_order,
)
from test_benchmark_contract import check_cells, check_configs, check_metrics
from test_benchmark_knn_buildings import SIBLING_METRICS, check_sibling_entry
from test_benchmark_knn_slabs import check_both_entries as check_slab_entries
from test_benchmark_program_spans import check_entry
from test_benchmark_s2s import check_s2s_entries
from test_benchmark_shared_entries import (
    LIMIT,
    SHARED_BY_THE_HOST_FED,
    START_UP,
    check_host_fed_entry,
    check_limit,
    check_no_twins,
    check_span_lists,
    check_start_up_entries,
)

CELL, MIX = "additive-probe.cell", "additive-probe-mix"
METRIC = "additive_probe_dispatches.stream"
SECOND_METRIC = "additive_probe_calls.batch"
BATCH_CELLS = ["taxi.batch", "taxi.batch-exact"]
KNN_CELL, KNN_MIX = "additive-probe.knn-cell", "additive-probe-knn-mix"
KNN_CELLS = {"nyc-knn.transform", "nyc-knn-buildings.transform"}
S2S_CELL, S2S_MIX = "additive-probe.s2s-cell", "additive-probe-s2s-mix"
S2S_METRIC = "additive_probe_band_ms.s2s"
BATCH_CELL, BATCH_MIX = "additive-probe.batch-cell", "additive-probe-batch-mix"
PROBE_CELLS = (CELL, KNN_CELL, S2S_CELL, BATCH_CELL)
PROBE_METRICS = (METRIC, SECOND_METRIC, S2S_METRIC)


def _copy(tmp) -> str:
    root = os.path.join(str(tmp), "copy")
    os.makedirs(root)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    shutil.copytree(
        os.path.join(REPO, "benchmark"), os.path.join(root, "benchmark"),
        ignore=shutil.ignore_patterns("__pycache__", ".traces", ".cache"),
    )
    return root


def _first_pr(tree: str, bench: dict) -> None:
    """A mix file, a workloads file, a ``layer_metrics`` file; one
    ``workloads`` entry, one ``per_layer`` entry, the cell's name joined to
    lists that are there."""
    mix = Spec(os.path.dirname(tree)).traffic("pickups-hotspot")
    mix.pop("name")
    mix["points"] = dict(mix["points"], hotspot_share=0.5)
    _write(os.path.join(tree, "traffic", MIX + ".json"), mix)
    _write(os.path.join(tree, "workloads", CELL + ".json"),
           {"check": {"sample_rows": 262144}})
    _write(os.path.join(tree, "layer_metrics", METRIC + ".json"), {
        "what": "dispatches the window made", "reader": "counter",
        "params": {"name": "dispatches"},
    })
    bench["workloads"].append({
        "name": CELL, "config": "taxi-zones-h3r9", "traffic": MIX,
        "chips": 1, "why": "reserved for the additivity test: half hotspot",
    })
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ("rows_per_s", "tier1_device_ms.stream"):
            m["workloads"].append(CELL)
    bench["per_layer"].append({
        "name": METRIC, "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "frontends",
        "moves": "rows_per_s", "workloads": [CELL],
    })


def _second_pr(tree: str, bench: dict) -> None:
    """One more metric, of the batch cells, after whatever is last."""
    _write(os.path.join(tree, "layer_metrics", SECOND_METRIC + ".json"), {
        "what": "calls the window made", "reader": "counter",
        "params": {"name": "calls"},
    })
    bench["per_layer"].append({
        "name": SECOND_METRIC, "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "frontends",
        "moves": "batch_rows_per_s", "workloads": list(BATCH_CELLS),
    })


def _knn_pr(tree: str, bench: dict) -> None:
    """A host-fed KNN cell on the point cell's configuration under a mix of
    its own: two data files, one ``workloads`` entry and NO ``per_layer``
    entry — its name joins every list both KNN cells stand in."""
    mix = Spec(os.path.dirname(tree)).traffic("landmarks-host")
    mix.pop("name")
    mix["pool_tables"] = 3
    _write(os.path.join(tree, "traffic", KNN_MIX + ".json"), mix)
    check = Spec(os.path.dirname(tree)).cell("nyc-knn.transform")["check"]
    _write(os.path.join(tree, "workloads", KNN_CELL + ".json"),
           {"check": check})
    bench["workloads"].append({
        "name": KNN_CELL, "config": "nyc-knn-h3r10", "traffic": KNN_MIX,
        "chips": 1, "why": "reserved for the additivity test: three tables",
    })
    for m in bench["end_to_end"] + bench["per_layer"]:
        if KNN_CELLS <= set(m.get("workloads", [])):
            m["workloads"].append(KNN_CELL)


def _joins_the_lists_of(tree: str, bench: dict, real: str, cell: str,
                        mix_of: str, mix: str, change: dict) -> None:
    """A cell on ``real``'s configuration under a mix of its own (``mix_of``
    with ``change``): two data files, one ``workloads`` entry and NO
    ``per_layer`` entry — its name joins every list ``real`` stands in."""
    spec = Spec(os.path.dirname(tree))
    data = spec.traffic(mix_of)
    data.pop("name")
    data.update(change)
    _write(os.path.join(tree, "traffic", mix + ".json"), data)
    _write(os.path.join(tree, "workloads", cell + ".json"),
           {"check": spec.cell(real)["check"]})
    bench["workloads"].append({
        "name": cell, "config": spec.cell(real)["config"], "traffic": mix,
        "chips": 1, "why": "reserved for the additivity test",
    })
    for m in bench["end_to_end"] + bench["per_layer"]:
        if real in m.get("workloads", []):
            m["workloads"].append(cell)


def _s2s_metric_pr(tree: str, bench: dict) -> None:
    """One more metric of `ais-s2s.join`'s spans, after whatever is last:
    what a `tracing` PR that reads one more child of `proximity.call`
    brings."""
    _write(os.path.join(tree, "layer_metrics", S2S_METRIC + ".json"), {
        "what": "per proximity.call, its proximity.host_band span",
        "reader": "span_child_by_group",
        "params": {"root": "proximity.call", "child": "proximity.host_band",
                   "by": "cover_rows", "q": 0.5, "scale": 1000},
    })
    bench["per_layer"].append({
        "name": S2S_METRIC, "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "proximity join",
        "moves": "batch_rows_per_s", "workloads": ["ais-s2s.join"],
    })


def _s2s_cell_pr(tree: str, bench: dict) -> None:
    """A second distance-join cell: it joins the `.s2s` lists and the shared
    ones `ais-s2s.join` stands in, and mints nothing."""
    _joins_the_lists_of(tree, bench, "ais-s2s.join", S2S_CELL,
                        "tracks-host", S2S_MIX, {"pool_tables": 3})


def _batch_cell_pr(tree: str, bench: dict) -> None:
    """A third batch cell on the default call: it joins every list
    `taxi.batch` stands in."""
    _joins_the_lists_of(tree, bench, "taxi.batch", BATCH_CELL,
                        "pickups-hotspot-host", BATCH_MIX, {"pool_batches": 6})


def _every_check(root: str) -> Spec:
    check_configs(root)
    check_cells(root)
    check_metrics(root)
    spec = Spec(root)
    for m in spec.benchmark["per_layer"]:
        check_entry(spec, m["name"])
    check_batch_entries_keep_their_order(spec)
    # the whole-file assertions the real file is held to: a cell appended
    # to a shared list mints no twin, the list is inside the contract's
    # limit, and every cell (the appended ones too) starts up under both
    # start-up entries
    check_no_twins(spec)
    check_limit(spec)
    check_start_up_entries(spec)
    # what holds the merged lists on the real file holds them on the copy
    for name in SHARED_BY_THE_HOST_FED:
        check_host_fed_entry(spec, name)
    check_span_lists(spec)
    for name in SIBLING_METRICS:
        check_sibling_entry(spec, name)
    check_slab_entries(spec)
    # and what each cell's own module holds the real file's entries to
    check_s2s_entries(spec)
    for name in LATER_ENTRIES:
        check_later_entry(spec, name)
    return spec


def _accepted_entries_are_the_real_files(spec: Spec) -> None:
    """Entry for entry the real file's, but for the appended cells' names."""
    real = Spec(REPO).benchmark
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        assert len(spec.benchmark[section]) >= len(real[section])
        for was, now in zip(real[section], spec.benchmark[section]):
            now = dict(now)
            if "workloads" in now:
                now["workloads"] = [c for c in now["workloads"]
                                    if c not in PROBE_CELLS]
            assert was == now


def test_the_names_are_reserved_for_this_test():
    real = Spec(REPO).benchmark
    taken = {e["name"] for s in ("workloads", "end_to_end", "per_layer")
             for e in real[s]} | {w["traffic"] for w in real["workloads"]}
    assert not taken & ({MIX, KNN_MIX, S2S_MIX, BATCH_MIX} | set(PROBE_CELLS)
                        | set(PROBE_METRICS)), (
        "these names are reserved for tests/benchmark_harness/"
        "test_benchmark_additive.py; give the real entry another")
    for kind, name in (("traffic", MIX), ("workloads", CELL),
                       ("traffic", KNN_MIX), ("workloads", KNN_CELL),
                       ("traffic", S2S_MIX), ("workloads", S2S_CELL),
                       ("traffic", BATCH_MIX), ("workloads", BATCH_CELL),
                       ("layer_metrics", METRIC),
                       ("layer_metrics", SECOND_METRIC),
                       ("layer_metrics", S2S_METRIC)):
        assert not os.path.exists(
            os.path.join(REPO, "benchmark", kind, name + ".json"))


def test_appended_metric_and_cell_pass_every_check_of_the_real_file(tmp_path):
    root = _copy(tmp_path)
    append_as_a_pr(root, _first_pr)
    spec = _every_check(root)
    # the new cell reads what it was listed under, old and new
    assert [m["name"] for m in spec.end_to_end(CELL)] == ["rows_per_s", "setup_s"]
    assert {m["name"] for m in spec.per_layer(CELL)} == \
        {"tier1_device_ms.stream", METRIC} | set(START_UP)
    assert spec.traffic(spec.cell(CELL)["traffic"])["kind"] == "device_ring_stream"
    _accepted_entries_are_the_real_files(spec)
    # no cell of the real file reads anything more or less than before
    real = Spec(REPO)
    for w in real.benchmark["workloads"]:
        for reads in (Spec.end_to_end, Spec.per_layer):
            assert [m["name"] for m in reads(spec, w["name"])] == \
                [m["name"] for m in reads(real, w["name"])]


@pytest.mark.parametrize("listed", START_UP)
def test_a_start_up_entry_given_a_list_is_caught(tmp_path, listed):
    """The failing side of the start-up rule: a PR that gives one of the two
    entries a ``workloads`` list (and so a duty to every later cell)."""
    root = _copy(tmp_path)

    def lists(tree, bench):
        _first_pr(tree, bench)
        entry = next(m for m in bench["per_layer"] if m["name"] == listed)
        entry["workloads"] = [w["name"] for w in bench["workloads"]]

    append_as_a_pr(root, lists)
    with pytest.raises(AssertionError, match=f"{listed} lists no cells"):
        _every_check(root)
    # and it is that rule alone which fails: every other check holds
    spec = Spec(root)
    check_no_twins(spec)
    check_limit(spec)
    for m in spec.benchmark["per_layer"]:
        check_entry(spec, m["name"])


def test_a_list_past_the_contracts_limit_is_caught(tmp_path):
    """The limit's failing side: entries of distinct keys appended until
    the list is one past the contract's 128."""
    root = _copy(tmp_path)
    have = len(Spec(root).benchmark["per_layer"])

    def flood(tree, bench):
        for n in range(have, LIMIT + 1):  # to one past the limit
            name = f"additive_probe_flood_{n}.batch"
            _write(os.path.join(tree, "layer_metrics", name + ".json"), {
                "what": "one of too many", "reader": "counter",
                "params": {"name": f"flood_{n}"},
            })
            bench["per_layer"].append({
                "name": name, "unit": "count", "better": "higher",
                "source": "program_counter", "layer": "frontends",
                "moves": "batch_rows_per_s", "workloads": list(BATCH_CELLS),
            })

    append_as_a_pr(root, flood)
    spec = Spec(root)
    check_no_twins(spec)  # distinct parameters: no twin, only too many
    with pytest.raises(AssertionError):
        check_limit(spec)


@pytest.mark.parametrize("before", [(), (_first_pr, _second_pr)],
                         ids=["alone", "after-two-prs"])
def test_a_host_fed_cell_joins_the_merged_lists_and_mints_no_entry(
        tmp_path, before):
    """The cell PR 47's rule is for: its name appended to `device_idle.batch`,
    `compiles_in_window.batch`, `pool_build_s.batch` and the shared `.knn`
    entries, not one entry or file of metrics added — and the checks of those
    lists, of every entry and of the contract hold on the copy."""
    root = _copy(tmp_path)
    for add in before:
        append_as_a_pr(root, add)
    append_as_a_pr(root, _knn_pr)
    spec = _every_check(root)
    real = Spec(REPO)
    assert len(spec.benchmark["per_layer"]) == \
        len(real.benchmark["per_layer"]) + len(before)
    _accepted_entries_are_the_real_files(spec)
    # the new cell reads what both KNN cells read, and nothing it has no
    # span, counter or kernel for
    mine = [m["name"] for m in spec.per_layer(KNN_CELL)]
    both = [m["name"] for m in real.per_layer("nyc-knn.transform")
            if m in real.per_layer("nyc-knn-buildings.transform")]
    assert mine == both
    assert set(mine) >= set(SIBLING_METRICS) | set(SHARED_BY_THE_HOST_FED) | {
        "pool_build_s.batch", "index_build_s", "warmup_s"}
    assert not set(mine) & {"pair_hbm_share.knn", "edge_pair_hbm_share.knn"}
    assert [m["name"] for m in spec.end_to_end(KNN_CELL)] == \
        ["setup_s", "batch_rows_per_s"]
    assert spec.traffic(spec.cell(KNN_CELL)["traffic"])["kind"] == \
        "knn_transform"
    # no cell of the real file reads anything more or less than before
    # (but the metric an earlier probe PR brought it)
    for w in real.benchmark["workloads"]:
        for reads in (Spec.end_to_end, Spec.per_layer):
            assert [m["name"] for m in reads(spec, w["name"])
                    if m["name"] not in (METRIC, SECOND_METRIC)] == \
                [m["name"] for m in reads(real, w["name"])]


@pytest.mark.parametrize("order", [(_first_pr, _second_pr),
                                   (_second_pr, _first_pr)],
                         ids=["stream-then-batch", "batch-then-stream"])
def test_two_prs_append_a_metric_each_and_both_pass(tmp_path, order):
    """One metric appended after the accepted list, then one more after
    it, each by a "PR" of its own: every check holds after each."""
    root = _copy(tmp_path)
    real = Spec(REPO)
    for n, add in enumerate(order, start=1):
        append_as_a_pr(root, add)
        spec = _every_check(root)
        _accepted_entries_are_the_real_files(spec)
        assert len(spec.benchmark["per_layer"]) == \
            len(real.benchmark["per_layer"]) + n
    last_two = [m["name"] for m in spec.benchmark["per_layer"][-2:]]
    assert sorted(last_two) == sorted([METRIC, SECOND_METRIC])
    # the batch cells read the second PR's metric last, after PR 35's twenty
    for cell in BATCH_CELLS:
        names = [m["name"] for m in spec.per_layer(cell)]
        assert names[-1] == SECOND_METRIC
        assert names[:-1] == [m["name"] for m in real.per_layer(cell)]


# ------------------------- an existing cell's entries take a PR's append too

@pytest.mark.parametrize("before", [(), (_first_pr, _second_pr)],
                         ids=["alone", "after-two-prs"])
def test_an_entry_appended_for_the_s2s_cell_passes_its_own_modules_checks(
        tmp_path, before):
    """A `tracing` PR's shape: one entry, one file, `workloads`
    ``["ais-s2s.join"]`` — and everything `test_benchmark_s2s.py` holds the
    real file to for that cell holds on the copy."""
    root = _copy(tmp_path)
    for add in before + (_s2s_metric_pr,):
        append_as_a_pr(root, add)
    spec = _every_check(root)
    real = Spec(REPO)
    _accepted_entries_are_the_real_files(spec)
    assert spec.benchmark["per_layer"][-1]["name"] == S2S_METRIC
    # the cell reads what it read, and the new entry last
    names = [m["name"] for m in spec.per_layer("ais-s2s.join")]
    assert names == [m["name"] for m in real.per_layer("ais-s2s.join")] + \
        [S2S_METRIC]
    # no other cell of the real file reads anything more or less than before
    for w in real.benchmark["workloads"]:
        if w["name"] == "ais-s2s.join":
            continue
        assert [m["name"] for m in spec.per_layer(w["name"])
                if m["name"] not in PROBE_METRICS] == \
            [m["name"] for m in real.per_layer(w["name"])]


@pytest.mark.parametrize("real_cell, add, cell, kind", [
    ("ais-s2s.join", _s2s_cell_pr, S2S_CELL, "dwithin_join_loop"),
    ("taxi.batch", _batch_cell_pr, BATCH_CELL, "host_batch_join"),
], ids=["s2s", "batch"])
@pytest.mark.parametrize("before", [(), (_s2s_metric_pr,)],
                         ids=["alone", "after-an-s2s-entry"])
def test_a_second_cell_joins_the_lists_of_a_cell_with_entries_of_its_own(
        tmp_path, before, real_cell, add, cell, kind):
    """The lists a cell's own test module holds (`.s2s`, the two later
    `.batch` entries) take a second cell's name: membership, so the cell
    mints no copy of them — which `check_no_twins` would refuse."""
    root = _copy(tmp_path)
    for a in before + (add,):
        append_as_a_pr(root, a)
    spec = _every_check(root)
    real = Spec(REPO)
    assert len(spec.benchmark["per_layer"]) == \
        len(real.benchmark["per_layer"]) + len(before)
    _accepted_entries_are_the_real_files(spec)
    # the new cell reads, entry for entry, what the cell it stands beside does
    assert [m["name"] for m in spec.per_layer(cell)] == \
        [m["name"] for m in spec.per_layer(real_cell)]
    assert [m["name"] for m in spec.end_to_end(cell)] == \
        [m["name"] for m in real.end_to_end(real_cell)]
    assert spec.traffic(spec.cell(cell)["traffic"])["kind"] == kind
    # no cell of the real file reads anything more or less than before
    for w in real.benchmark["workloads"]:
        assert [m["name"] for m in spec.per_layer(w["name"])
                if m["name"] not in PROBE_METRICS] == \
            [m["name"] for m in real.per_layer(w["name"])]
