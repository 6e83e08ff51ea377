"""The metric PR 45 appended for `nyc-knn.transform` — how much of a call's
host work ran under queued device work: a new data file and an appended
entry read by a reader the benchmark had (`event_percentile` over the
``knn.transform`` span, as `launches_per_call.knn`); it reads nothing on an
empty run and on a program whose span lacks the field (the parent commit),
and the right number on hand-made events and on a span the program itself
recorded, beside which ``slabs`` is read by the same reader (no entry takes
it: `per_layer` is full at 128). A slabbed call of a warmed model compiles
nothing."""

import numpy as np
import pytest

from bh_fixtures import REPO

from benchmark.harness.spec import Spec
from test_benchmark_program_spans import _ctx, _span, check_entry

NAME = "overlap_ms_per_call.knn"


@pytest.fixture(scope="module")
def spec():
    return Spec(REPO)


def _read(spec, ctx, **params):
    desc = spec.data("layer_metrics", NAME)
    return spec.module("readers", desc["reader"]).read(
        ctx, {**desc["params"], **params})


def test_entry_is_the_point_cells_and_reads_nothing_without_the_field(spec):
    entry = spec.benchmark["per_layer"][-1]
    assert entry == {
        "name": NAME, "unit": "ms", "better": "higher",
        "source": "program_span", "layer": "knn ring engine",
        "moves": "batch_rows_per_s", "workloads": ["nyc-knn.transform"],
    }
    desc = spec.data("layer_metrics", NAME)
    assert desc["reader"] == "event_percentile"
    assert desc["params"] == {
        "event": "span", "where": {"name": "knn.transform"},
        "field": "hidden_s", "q": 0.5, "scale": 1000,
    }
    check_entry(spec, NAME)
    # the parent's transform span has launches and rows_pulled, nothing more
    ctx = _ctx(spec, events=[
        dict(_span("knn.transform", "t", None, 1.0, 5.0), launches=40,
             rows_pulled=562_176),
        _span("knn.pull", "p", "t", 0.26, 4.0),
    ])
    assert _read(spec, ctx) is None and _read(spec, ctx, field="slabs") is None


def test_it_reads_the_p50_of_the_calls_in_the_window(spec):
    events = [
        dict(_span("knn.transform", f"t{c}", None, 0.7, ts), slabs=slabs,
             hidden_s=hidden, iterations=9)
        for c, (ts, slabs, hidden) in enumerate([
            (10.0, 47, 0.180), (20.0, 49, 0.210), (30.0, 47, 0.150),
            (-5.0, 900, 9.0),  # ended before the window
        ])
    ]
    ctx = _ctx(spec, events=events)
    assert _read(spec, ctx) == pytest.approx(180.0)
    assert _read(spec, ctx, field="slabs", scale=1) == 47


def test_a_recorded_call_is_read_and_a_slabbed_call_compiles_nothing(
    spec, monkeypatch
):
    """The program's own span through the file: a call whose early
    iterations are cut into slabs, on a warmed model."""
    from mosaic_tpu.core.index.h3 import H3IndexSystem
    from mosaic_tpu.dispatch import BucketLadder
    from mosaic_tpu.knn import build_knn_index, engine
    from mosaic_tpu.knn import frontend as knn_frontend
    from mosaic_tpu.models import SpatialKNN
    from mosaic_tpu.runtime import telemetry

    rng = np.random.default_rng(45)
    centre = np.array([-73.98, 40.75])
    cand = np.concatenate([centre + rng.normal(0, 0.002, (1500, 2)),
                           centre + rng.uniform(-0.02, 0.02, (900, 2))])
    land = centre + rng.uniform(-0.02, 0.02, (1200, 2))
    monkeypatch.setattr(knn_frontend, "BLOCK_LADDER", BucketLadder(16, 64, growth=4))
    monkeypatch.setattr(engine, "SLAB_KEYS", 2000)
    h3 = H3IndexSystem()
    kx = build_knn_index(cand, h3, 10)
    m = SpatialKNN(index=h3, resolution=10, k_neighbours=5, approximate=False,
                   max_iterations=32)
    m.warmup(kx)
    fe = m._frontend[1]
    warmed = fe.signature_count()
    with telemetry.capture() as events:
        res = m.transform(land, kx)
    assert fe.signature_count() == warmed and fe.cold_compiles == 0
    assert res.metrics["unrested_landmarks"] == 0
    (root,) = [e for e in events
               if e.get("event") == "span" and e["name"] == "knn.transform"]
    assert root["slabs"] > root["iterations"] and root["hidden_s"] > 0
    ctx = _ctx(spec, events=[dict(root, ts_mono=5.0)])
    assert _read(spec, ctx) == pytest.approx(1000 * root["hidden_s"])
    assert _read(spec, ctx, field="slabs", scale=1) == root["slabs"]
