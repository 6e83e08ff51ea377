"""The metric PR 45 appended for `nyc-knn.transform` — how much of a call's
host work ran under queued device work — and, since PR 47 made room,
`slabs_per_call.knn` beside it: each a data file and an entry read by a
reader the benchmark had (`event_percentile` over the ``knn.transform``
span, as `launches_per_call.knn`), on both KNN cells; each reads nothing on
an empty run and on a program whose span lacks the field (the parent
commit), and the right number on hand-made events and on a span the program
itself recorded. A slabbed call of a warmed model compiles nothing."""

import numpy as np
import pytest

from bh_fixtures import REPO

from benchmark.harness.spec import Spec
from test_benchmark_program_spans import _ctx, _span, check_entry

NAME = "overlap_ms_per_call.knn"
SLABS = "slabs_per_call.knn"
KNN_CELLS = ["nyc-knn.transform", "nyc-knn-buildings.transform"]


@pytest.fixture(scope="module")
def spec():
    return Spec(REPO)


def _read(spec, ctx, name=NAME):
    desc = spec.data("layer_metrics", name)
    return spec.module("readers", desc["reader"]).read(ctx, desc["params"])


def check_both_entries(spec) -> None:
    """Each entry as it was appended, both KNN cells IN its list
    (membership: a later KNN cell appends its name; the additivity test
    holds a copy with one appended to this)."""
    by = {m["name"]: dict(m) for m in spec.benchmark["per_layer"]}
    for name in (NAME, SLABS):
        assert set(KNN_CELLS) <= set(by[name].pop("workloads"))
    assert by[NAME] == {
        "name": NAME, "unit": "ms", "better": "higher",
        "source": "program_span", "layer": "knn ring engine",
        "moves": "batch_rows_per_s",
    }
    assert by[SLABS] == {
        "name": SLABS, "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "knn ring engine",
        "moves": "batch_rows_per_s",
    }
    span = {"event": "span", "where": {"name": "knn.transform"}, "q": 0.5}
    for name, more in ((NAME, {"field": "hidden_s", "scale": 1000}),
                       (SLABS, {"field": "slabs"})):
        desc = spec.data("layer_metrics", name)
        assert desc["reader"] == "event_percentile"
        assert desc["params"] == {**span, **more}
        check_entry(spec, name)


def test_both_entries_are_the_knn_cells_and_read_nothing_without_the_field(spec):
    check_both_entries(spec)
    # the parent's transform span has launches and rows_pulled, nothing more
    ctx = _ctx(spec, events=[
        dict(_span("knn.transform", "t", None, 1.0, 5.0), launches=40,
             rows_pulled=562_176),
        _span("knn.pull", "p", "t", 0.26, 4.0),
    ])
    assert _read(spec, ctx) is None and _read(spec, ctx, SLABS) is None


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
    assert _read(spec, ctx, SLABS) == 47


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
    assert _read(spec, ctx, SLABS) == root["slabs"]
