"""Traffic kind ``host_batch_join`` on the CPU: a tiny batch cell and its
``recheck=True`` twin, added as files to a temporary copy of the benchmark
and run through the unchanged harness. A CPU run asserts answers, counts
and the result line's shape; it never states a device number.

The lower-precision control reads not correct, and a run whose timed path
is broken underneath (an answer altered where it is produced, in every call
or in one timed call only; a device path that degrades to the host oracle)
reads ``correct: false`` or counts in ``failed``. The exact cell's control is
the program's own lower path, ``recheck`` off, which only the rows at a
zone's edge can tell from the sound run."""

import json
import os
import time

import numpy as np
import pytest

from bh_fixtures import TINY_POINTS, _write, make_copy

from benchmark.harness.run_cell import run_cell

BATCH = 2048  # the tiny configuration's batch_rows_per_chip
POOL = 3
#: the tiny exact cell counts rows unlike the reference this near a zone's
#: edge apart (the tiny zones are ~15 degrees wide)
EDGE_SLACK_DEG = 0.25


def add_batch_cells(root: str) -> None:
    """``tiny.batch`` and ``tiny.batch-exact`` on the tiny zones, as new
    files and appended entries; their names join the ``workloads`` of
    whatever the real batch cells are listed under."""
    tree = os.path.join(root, "benchmark")
    for suffix, arguments, check, control in (
        ("", {}, {"sample_rows": BATCH, "max_disagreement": 0.0001},
         {"cell_dtype": "bfloat16"}),
        ("-exact", {"recheck": True},
         {"sample_rows": POOL * BATCH, "max_disagreement": 0.0,
          "edge_within_deg": EDGE_SLACK_DEG, "max_edge_rows": 0},
         {"arguments": {}}),
    ):
        _write(os.path.join(tree, "traffic", f"tiny-host{suffix}.json"), {
            "kind": "host_batch_join", "pool_batches": POOL,
            "points": TINY_POINTS, "arguments": arguments, "control": control,
        })
        _write(os.path.join(tree, "workloads", f"tiny.batch{suffix}.json"),
               {"check": check})
    path = os.path.join(root, "BENCHMARK.json")
    with open(path, encoding="utf-8") as f:
        bench = json.load(f)
    bench["workloads"] += [
        {"name": f"tiny.batch{s}", "config": "tiny-zones",
         "traffic": f"tiny-host{s}", "chips": 1, "why": "test fixture"}
        for s in ("", "-exact")
    ]
    for m in bench["end_to_end"] + bench["per_layer"]:
        for s in ("", "-exact"):
            if f"taxi.batch{s}" in m.get("workloads", []):
                m["workloads"].append(f"tiny.batch{s}")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(bench, f, indent=1)


@pytest.fixture()
def root(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    root = make_copy(tmp_path)
    add_batch_cells(root)
    return root


def _run(root, cell, seed, *, trace=False, **kw):
    return run_cell(root, cell, seed, kw.pop("seconds", 0.5), trace,
                    t_start=time.perf_counter(), rehearsal=True, **kw)


@pytest.mark.parametrize("cell", ["tiny.batch", "tiny.batch-exact"])
def test_batch_cell_untraced(root, cell, capfd):
    line = _run(root, cell, 4_000_000_411)
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device",
                         "checks"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["attempted"] % BATCH == 0
    assert set(line["metrics"]) == {"batch_rows_per_s", "setup_s"}
    for v in line["metrics"].values():
        assert set(v) == {"value", "unit"} and v["value"] > 0
    out = capfd.readouterr().out
    assert "[check] batch_rows_unlike_repeat: value=0.0 limit=0.0 ok" in out
    assert "[check] batch_disagreement_share: value=" in out
    assert "compiles_in_window=0" in out
    assert f"pool=({POOL}, {BATCH}, 2) dtype=float64" in out


def test_batch_cell_traced_reads_the_programs_call_span(root):
    line = _run(root, "tiny.batch-exact", 51, trace=True)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    m = line["metrics"]
    assert "batch_rows_per_s" not in m and "setup_s" not in m
    assert m["call_p50_ms.batch"]["value"] > 0
    assert m["compiles_in_window.batch"] == {"value": 0.0, "unit": "count"}
    assert m["pool_build_s.batch"]["value"] > 0
    assert m["call_warmup_s.batch"]["value"] > 0
    assert m["index_build_s"]["value"] > 0 and m["warmup_s"]["value"] > 0
    # the device-trace metrics find nothing to read on the CPU
    assert "device_idle.batch" not in m
    assert "device_busy_ms_per_call.batch" not in m
    # the tails of the calls made with the profiler off
    assert m["call_max_ms.batch"]["value"] >= m["call_p50_ms.batch"]["value"] > 0


def test_the_result_line_takes_the_stage_table_from_the_harness(
        root, monkeypatch):
    """The breakdown's ``device_stages`` is what `harness/stage_table.py`
    hands `run_cell`, under a key of its own beside the two rankings (on the
    CPU there is no device plane to build one from: the table is stood in
    for here, and no reader of the cell is asked for it)."""
    from benchmark.harness import stage_table

    built = []

    def of_run(ctx):
        built.append(len(built))
        return {"pip.tier1": 0.5, "unscoped": 0.125, "pip.cells": 0.25}

    monkeypatch.setattr(stage_table, "of_run", of_run)
    line = _run(root, "tiny.batch", 53, trace=True)
    assert line["correct"] is True and built[0] == 0
    assert list(line["breakdown"]) == ["device_ops", "idle_gaps",
                                       "device_stages"]
    assert line["breakdown"]["device_stages"] == [
        ["pip.tier1", 0.5], ["pip.cells", 0.25], ["unscoped", 0.125]]


def test_call_metrics_read_the_unprofiled_calls():
    """`call_p50_ms.batch` reads the program's span over the calls after the
    profiler stopped; `call_max_ms.batch` reads the benchmark's own series of
    those calls, where one stalled call shows (`call_p95_ms.batch`, between
    the two, went with PR 47: `series_percentile` still takes any ``q``)."""
    from types import SimpleNamespace

    from bh_fixtures import REPO
    from benchmark.harness.spec import Spec

    spec = Spec(REPO)
    call = lambda t, s: {"event": "span", "name": "join.pip",  # noqa: E731
                         "ts_mono": t, "seconds": s}
    # two profiled calls (0.9 s each, before the window the kind sets),
    # three unprofiled ones; another span's events do not count
    events = [call(1.0, 0.9), call(2.0, 0.9), call(11.0, 0.5),
              call(12.0, 0.4), call(13.0, 0.6),
              {"event": "span", "name": "serve.batch", "ts_mono": 12.5,
               "seconds": 7.0}]
    ctx = SimpleNamespace(spec=spec, events=events, window=(10.0, 20.0),
                          series={"call_s": [0.4] * 38 + [0.52, 10.9]})

    def read(name):
        desc = spec.data("layer_metrics", name)
        return spec.module("readers", desc["reader"]).read(ctx, desc["params"])

    assert read("call_p50_ms.batch") == pytest.approx(500.0)
    assert read("call_max_ms.batch") == pytest.approx(10900.0)
    desc = spec.data("layer_metrics", "call_max_ms.batch")
    tail = spec.module("readers", desc["reader"]).read
    assert tail(ctx, dict(desc["params"], q=0.95)) == pytest.approx(400.0)
    ctx.series = {}
    assert read("call_max_ms.batch") is None
    assert tail(ctx, dict(desc["params"], q=0.95)) is None


def test_the_benchmarks_span_wraps_every_call(root, monkeypatch):
    from benchmark.harness.context import SpanLog

    logs = []
    real = SpanLog.__init__

    def init(self):
        real(self)
        logs.append(self)

    monkeypatch.setattr(SpanLog, "__init__", init)
    line = _run(root, "tiny.batch", 52)
    calls = [s for s in logs[0].spans if s[0] == "batch.call"]
    assert len(calls) * BATCH == line["attempted"]


@pytest.mark.parametrize("seed", [61, 62, 4_000_000_613])
def test_sound_run_is_correct_and_bf16_control_is_not(root, seed):
    assert _run(root, "tiny.batch", seed)["correct"] is True
    # the mix's control: cell assignment in bfloat16 through pip_join's own
    # cell_dtype
    assert _run(root, "tiny.batch", seed, control=True)["correct"] is False


@pytest.mark.parametrize("seed", [63, 64, 4_000_000_615])
def test_exact_cell_tells_recheck_off_from_on(root, seed, monkeypatch, capfd):
    """The exact cell's control is the program's own lower path: `pip_join`
    without ``recheck``. On the chip its float32 edge probe misses 5-11 of a
    pool's 16M rows, all within 2e-8 degrees of a zone's edge (PERF.md
    section 2); 6,144 rows hold none, so here a `pip_join` called without
    ``recheck`` answers as an edge test with EDGE_SLACK_DEG / 2 of slack
    does. The rows at the edges fail the run; the share of all rows alone
    would have let it pass."""
    from bh_fixtures import tiny_config
    from benchmark.harness.spec import Spec
    from mosaic_tpu.sql import join

    spec = Spec(root)
    kind = spec.module("traffic_kinds", "host_batch_join")
    z = tiny_config()["zones"]
    rings = spec.module("generators", "zones").star_lattice(
        z["nx"], z["ny"], tuple(z["bbox"]), seed=z["seed"], verts=z["verts"],
        jitter=z["jitter"])
    real, lowered = join.pip_join, [0]

    def coarse_without_recheck(points, *a, **kw):
        out = np.array(real(points, *a, **kw))
        if not kw.get("recheck"):
            near = np.array([kind._edge_distance_deg(rings, p)
                             < EDGE_SLACK_DEG / 2 for p in points])
            out[near & (out >= 0)] = -1
            lowered[0] += 1
        return out

    monkeypatch.setattr(join, "pip_join", coarse_without_recheck)
    # the cell's own limit of the share is 0: give the control's run room
    # there, so that the edge rows alone decide
    path = os.path.join(root, "benchmark", "workloads", "tiny.batch-exact.json")
    with open(path, encoding="utf-8") as f:
        cell = json.load(f)
    cell["check"]["max_disagreement"] = 0.5
    with open(path, "w", encoding="utf-8") as f:
        json.dump(cell, f)
    assert _run(root, "tiny.batch-exact", seed)["correct"] is True
    assert lowered[0] == 0  # recheck=True reached every call of the sound run
    capfd.readouterr()
    assert _run(root, "tiny.batch-exact", seed, control=True)["correct"] is False
    out = capfd.readouterr().out
    assert lowered[0] > 0 and "arguments={} control={'arguments': {}}" in out
    edge = [ln for ln in out.splitlines()
            if ln.startswith("[check] batch_edge_rows_unlike_reference")]
    assert len(edge) == 1 and "FAILED" in edge[0]
    share = [ln for ln in out.splitlines()
             if ln.startswith("[check] batch_disagreement_share")]
    assert len(share) == 1 and " ok " in share[0]


def _altering(monkeypatch, which):
    """`pip_join` answers the next zone for every seventh matched row of
    the calls ``which(n)`` picks (n counts calls from 1)."""
    from mosaic_tpu.sql import join

    real, n = join.pip_join, [0]

    def altered(points, *a, **kw):
        out = np.array(real(points, *a, **kw))
        n[0] += 1
        if which(n[0]):
            out[::7] = np.where(out[::7] >= 0, out[::7] + 1, out[::7])
        return out

    monkeypatch.setattr(join, "pip_join", altered)


def test_answers_altered_in_every_call_fail_the_reference(root, monkeypatch, capfd):
    _altering(monkeypatch, lambda n: True)
    line = _run(root, "tiny.batch", 71)
    assert line["correct"] is False and line["attempted"] > 0
    out = capfd.readouterr().out
    # timed and repeated calls are wrong alike; the plain reference is not
    assert "[check] batch_rows_unlike_repeat: value=0.0 limit=0.0 ok" in out
    assert "batch_disagreement_share" in out and "FAILED" in out


def test_one_timed_answer_altered_differs_from_the_repeat(root, monkeypatch, capfd):
    # calls 1..POOL are the warm-up's; POOL + 2 is the window's second
    _altering(monkeypatch, lambda n: n == POOL + 2)
    line = _run(root, "tiny.batch-exact", 72)
    assert line["correct"] is False
    out = capfd.readouterr().out
    assert "[check] batch_rows_unlike_repeat: value=" in out
    assert "batch_rows_unlike_repeat: value=0.0" not in out


def test_a_degraded_call_counts_in_failed(root, monkeypatch):
    """Transient device failures past the retry budget inside the window:
    the call answers from the f64 host oracle (right answers, wrong path),
    which counts its rows in ``failed`` and is a forbidden event."""
    from mosaic_tpu.runtime import faults

    monkeypatch.setenv("MOSAIC_RETRY_BASE_S", "0.001")
    # the warm-up's calls (one a pool batch) pass; every later one fails
    with faults.transient_errors(
        10_000, sites=("pip_join.device",), skip_first=POOL
    ):
        line = _run(root, "tiny.batch", 73)
    assert line["correct"] is False
    assert line["failed"] >= BATCH


# --- a pool that is the same work on every seed (PR 39, after the refusal) ---

@pytest.mark.parametrize("bands, k, banded, max_draws, want", [
    # the first two banded and the first two unbanded, in the order drawn
    ([1, 2, 0, 3, 0, 0, 1], 4, 2, 16, ([0, 1, 2, 4], 5, True)),
    # a seed whose first draws are the composition already draws no more
    ([0, 1, 0, 2], 4, 2, 16, ([0, 1, 2, 3], 4, True)),
    # every batch unbanded where none may be banded
    ([0, 0, 0, 0, 0], 4, 0, 16, ([0, 1, 2, 3], 4, True)),
    # past max_draws whatever comes is kept, and the pool says it is not composed
    ([1, 2, 1, 3, 1, 1, 1, 0, 0], 4, 2, 6, ([0, 1, 6, 7], 8, False)),
    # a call that records no event (recheck off) keeps the first draws
    ([None] * 6, 4, 2, 16, ([0, 1, 2, 3], 4, False)),
    # one class wanted only: three banded of three
    ([0, 1, 0, 1, 2, 0], 3, 3, 16, ([1, 3, 4], 5, True)),
])
def test_compose_pool_keeps_the_first_of_each_class(bands, k, banded,
                                                    max_draws, want):
    from benchmark.traffic_kinds.host_batch_join import compose_pool

    kept, got, draws, composed = compose_pool(
        lambda i: (i, bands[i]), k, banded, max_draws)
    assert (kept, draws, composed) == want
    assert got == [bands[i] for i in kept]


def test_draw_i_is_slot_i_of_the_one_call_pool():
    """The composed pool's candidates continue the pool a mix without the
    parameter gets: slot i of the one-call generator is draw i."""
    import jax

    from bh_fixtures import REPO
    from benchmark.harness.spec import Spec

    points = Spec(REPO).module("generators", "points")
    bbox = (-25.0, -25.0, 35.0, 20.0)
    key = points.seed_key(4_000_000_617)
    pool = np.asarray(points.make_generator(TINY_POINTS, bbox, 64, slots=3)(key))
    one = points.make_generator(TINY_POINTS, bbox, 64)
    for i in range(3):
        assert np.array_equal(pool[i], np.asarray(one(jax.random.fold_in(key, i))))


def _composed_cell(root, banded, max_draws):
    """`tiny.batch-exact`'s twin with a composed pool, as new files."""
    tree = os.path.join(root, "benchmark")
    with open(os.path.join(tree, "traffic", "tiny-host-exact.json"),
              encoding="utf-8") as f:
        mix = json.load(f)
    mix.update(pool_banded_batches=banded, pool_max_draws=max_draws)
    _write(os.path.join(tree, "traffic", "tiny-host-composed.json"), mix)
    _write(os.path.join(tree, "workloads", "tiny.batch-composed.json"),
           {"check": {"sample_rows": POOL * BATCH, "max_disagreement": 0.0,
                      "edge_within_deg": EDGE_SLACK_DEG, "max_edge_rows": 0}})
    path = os.path.join(root, "BENCHMARK.json")
    with open(path, encoding="utf-8") as f:
        bench = json.load(f)
    bench["workloads"].append(
        {"name": "tiny.batch-composed", "config": "tiny-zones",
         "traffic": "tiny-host-composed", "chips": 1, "why": "test fixture"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "tiny.batch-exact" in m.get("workloads", []):
            m["workloads"].append("tiny.batch-composed")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(bench, f, indent=1)


def _ready(out: str) -> str:
    return [ln for ln in out.splitlines()
            if ln.startswith("[bench] batch_ready")][-1]


@pytest.mark.parametrize("seed", [81, 4_000_000_619])
def test_composed_pool_holds_as_many_banded_batches_on_every_seed(
        root, seed, monkeypatch, capfd):
    """The tiny grid's 2,048-row batches hold no cell-band row, so a wrapper
    records one more `recheck_narrow` event, with a row in the band, for
    every second call of set-up: the pool keeps the one banded batch it may
    and two unbanded ones, draws past the banded ones it has no room for,
    and the run is correct as any other."""
    from mosaic_tpu.runtime import telemetry
    from mosaic_tpu.sql import join

    _composed_cell(root, banded=1, max_draws=16)
    real, n = join.pip_join, [0]

    def banded_every_second_call(points, *a, **kw):
        out = real(points, *a, **kw)
        n[0] += 1
        if n[0] % 2 == 1 and n[0] <= 5:  # set-up's calls 1, 3, 5
            telemetry.record("recheck_narrow", n=len(points), seconds=0.0,
                             band=2, cap=2, ties=0, mode="alt_rejoin")
        return out

    monkeypatch.setattr(join, "pip_join", banded_every_second_call)
    line = _run(root, "tiny.batch-composed", seed)
    assert line["correct"] is True and line["failed"] == 0
    ready = _ready(capfd.readouterr().out)
    # draws 0 (banded), 1, 2 (banded: no room), 3: four draws, three kept
    assert "pool_draws=4 pool_band_rows=[2, 0, 0] pool_composed=True" in ready
    assert f"pool=({POOL}, {BATCH}, 2) dtype=float64" in ready


def test_a_pool_that_cannot_be_composed_says_so_and_still_runs(root, capfd):
    # no tiny batch holds a band row: after two draws whatever comes is kept
    _composed_cell(root, banded=2, max_draws=2)
    line = _run(root, "tiny.batch-composed", 82)
    assert line["correct"] is True
    ready = _ready(capfd.readouterr().out)
    assert "pool_draws=4 pool_band_rows=[0, 0, 0] pool_composed=False" in ready


def test_the_control_without_recheck_keeps_the_first_draws(root, capfd):
    # recheck off records no `recheck_narrow` event: nothing to compose by
    _composed_cell(root, banded=1, max_draws=16)
    _run(root, "tiny.batch-composed", 83, control=True)
    ready = _ready(capfd.readouterr().out)
    assert ("pool_draws=3 pool_band_rows=[None, None, None] "
            "pool_composed=False") in ready


def test_the_exact_mix_composes_its_pool_and_the_default_mix_does_not():
    from bh_fixtures import REPO
    from benchmark.harness.spec import Spec

    spec = Spec(REPO)
    exact = spec.traffic("pickups-hotspot-host-exact")
    assert exact["pool_batches"] == 4 and exact["pool_banded_batches"] == 2
    assert exact["pool_max_draws"] >= 4 * exact["pool_batches"]
    assert "pool_banded_batches" not in spec.traffic("pickups-hotspot-host")
