"""The three metrics PR 41 appended for `nyc-knn.transform` — what a call
spends enqueueing block launches, what it spends in the blocking pull of
their answers, and how many answer rows it pulled: each is a new file and
an appended entry read by a reader the benchmark had; each reads nothing
on an empty run and on a program that lacks its span or counter (the
parent commit records no ``rows_pulled``), and the right number on
hand-made events."""

import pytest

from bh_fixtures import REPO

from benchmark.harness.spec import Spec
from test_benchmark_program_spans import _ctx, _span, check_entry

SPAN_METRICS = {"pull_ms_per_call.knn": "knn.pull",
                "enqueue_ms_per_call.knn": "knn.blocks"}
ROWS = "pulled_rows_per_call.knn"
NEW_METRICS = [*SPAN_METRICS, ROWS]


@pytest.fixture(scope="module")
def spec():
    return Spec(REPO)


def _read(spec, name, ctx):
    desc = spec.data("layer_metrics", name)
    return spec.module("readers", desc["reader"]).read(ctx, desc["params"])


@pytest.mark.parametrize("name", NEW_METRICS)
def test_entry_is_the_knn_cells_and_reads_nothing_on_an_empty_run(spec, name):
    entry = next(m for m in spec.benchmark["per_layer"] if m["name"] == name)
    assert "nyc-knn.transform" in entry["workloads"]
    assert entry["layer"] == "knn ring engine"
    assert entry["moves"] == "batch_rows_per_s" and entry["better"] == "lower"
    assert entry["source"] == (
        "program_counter" if name == ROWS else "program_span")
    check_entry(spec, name)
    # nor on a run of a program whose transform has no such span or counter
    ctx = _ctx(spec, events=[
        dict(_span("knn.transform", "t", None, 1.0, 5.0), launches=40),
        _span("knn.distance", "d", "t", 0.6, 4.0),
    ])
    assert _read(spec, name, ctx) is None


def _calls(rows=True):
    """Three calls in the window and one before it; a call's launches and
    pulls hang under its ``knn.distance`` spans, one an iteration."""
    events = []
    for c, (ts, blocks, pulls, n) in enumerate([
        (10.0, [0.002, 0.001, 0.001], [0.200, 0.010], 300_000),
        (20.0, [0.003, 0.002], [0.150, 0.020], 250_000),
        (30.0, [0.004], [0.300], 500_000),
        (-5.0, [9.0], [9.0], 9_000_000),  # ended before the window
    ]):
        root = dict(_span("knn.transform", f"t{c}", None, 1.0, ts), launches=40)
        if rows:
            root["rows_pulled"] = n
        events.append(root)
        events.append(_span("knn.distance", f"d{c}", f"t{c}", 0.5, ts - 0.1))
        events += [_span("knn.blocks", f"b{c}.{i}", f"d{c}", s, ts - 0.2)
                   for i, s in enumerate(blocks)]
        events += [_span("knn.pull", f"p{c}.{i}", f"d{c}", s, ts - 0.1)
                   for i, s in enumerate(pulls)]
    return events


def test_span_metrics_sum_a_calls_launches_and_pulls(spec):
    ctx = _ctx(spec, events=_calls())
    # per call 210 / 170 / 300 ms of pulls and 4 / 5 / 4 ms of launches;
    # the nearest-rank p50 of three is the middle one
    assert _read(spec, "pull_ms_per_call.knn", ctx) == pytest.approx(210.0)
    assert _read(spec, "enqueue_ms_per_call.knn", ctx) == pytest.approx(4.0)


def test_pulled_rows_is_the_p50_of_the_calls_counter(spec):
    assert _read(spec, ROWS, _ctx(spec, events=_calls())) == 300_000
    # the parent's spans carry launches and no rows_pulled: nothing to read,
    # while the two span metrics still read its knn.pull and knn.blocks
    parent = _ctx(spec, events=_calls(rows=False))
    assert _read(spec, ROWS, parent) is None
    assert _read(spec, "pull_ms_per_call.knn", parent) == pytest.approx(210.0)
