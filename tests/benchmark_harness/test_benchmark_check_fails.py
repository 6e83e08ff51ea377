"""The check fails when it should: the lower-precision control reads not
correct, and a run whose timed path is broken underneath prints
``correct: false``. Driven in this process through `run_cell` (which skips
nothing but the look for a chip: ``rehearsal`` admits the CPU) on the tiny
fixtures of a temporary copy."""

import numpy as np
import pytest

from bh_fixtures import make_copy
from test_benchmark_uniform import add_uniform_cell

from benchmark.harness.run_cell import run_cell


@pytest.fixture()
def root(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    root = make_copy(tmp_path)
    add_uniform_cell(root)
    return root


def _run(root, cell, seed, **kw):
    import time

    return run_cell(root, cell, seed, kw.pop("seconds", 0.5), False,
                    t_start=time.perf_counter(), rehearsal=True, **kw)


@pytest.mark.parametrize("seed", [11, 12, 4_000_000_123])
def test_stream_sound_run_is_correct_and_bf16_control_is_not(root, seed):
    assert _run(root, "tiny.stream", seed)["correct"] is True
    # cell assignment in bfloat16, through StreamJoin's own cell_dtype
    assert _run(root, "tiny.stream", seed, control=True)["correct"] is False


@pytest.mark.parametrize("seed", [21, 22, 4_000_000_321])
def test_serve_sound_run_is_correct_and_bf16_control_is_not(root, seed):
    assert _run(root, "tiny.serve", seed, seconds=1.0)["correct"] is True
    # coordinates rounded to bfloat16 before submit
    line = _run(root, "tiny.serve", seed, seconds=1.0, control=True)
    assert line["correct"] is False


@pytest.mark.parametrize("cell", ["tiny.stream", "tiny.stream-uniform"])
def test_stream_with_answers_altered_where_they_are_produced(
        root, monkeypatch, cell):
    """The join inside the timed loop answers the next zone for every
    seventh matched row: timed and collected folds still agree (both are
    wrong alike), and the sample against the plain reference catches it."""
    import jax.numpy as jnp

    from mosaic_tpu import dispatch
    from mosaic_tpu.sql import stream

    real = stream.pip_join_points

    def altered(shifted, cells, index, **kw):
        out = real(shifted, cells, index, **kw)
        seventh = (jnp.arange(out.shape[0]) % 7) == 0
        return jnp.where(seventh & (out >= 0), out + 1, out)

    dispatch.clear_caches()
    monkeypatch.setattr(stream, "pip_join_points", altered)
    try:
        line = _run(root, cell, 31)
    finally:
        monkeypatch.undo()
        dispatch.clear_caches()
    assert line["correct"] is False and line["attempted"] > 0
    # the line says which number failed, beside its limit, and which held
    said = line["checks"]
    assert said["stream_disagreement_share"]["value"] > \
        said["stream_disagreement_share"]["limit"] == 0.001
    assert said["stream_fold_mismatches"] == {"value": 0.0, "limit": 0.0}


def test_stream_whose_timed_loop_differs_from_the_collected_run(root, monkeypatch):
    """A timed dispatch that folds something else than the collected run
    (here: its checksum off by one) is caught exactly."""
    from mosaic_tpu.sql.stream import StreamJoin

    real = StreamJoin.run

    def run(self, ring, n_batches, *, collect=False):
        res = real(self, ring, n_batches, collect=collect)
        if not collect:
            res.checksum += 1
        return res

    monkeypatch.setattr(StreamJoin, "run", run)
    assert _run(root, "tiny.stream", 32)["correct"] is False


def test_serve_with_an_answer_altered_where_it_is_produced(root, monkeypatch):
    from mosaic_tpu.serve import ServeEngine

    real = ServeEngine._dispatch_resilient

    def altered(self, core, padded, deadline_hint):
        out = np.array(real(self, core, padded, deadline_hint))
        out[::5] = np.where(out[::5] >= 0, out[::5] + 1, out[::5])
        return out

    monkeypatch.setattr(ServeEngine, "_dispatch_resilient", altered)
    line = _run(root, "tiny.serve", 33, seconds=1.0)
    assert line["correct"] is False and line["failed"] == 0


def test_a_shed_request_counts_in_failed(root, monkeypatch):
    """A request the engine sheds has no latency and counts in failed."""
    from mosaic_tpu.runtime.errors import Overloaded
    from mosaic_tpu.serve import ServeEngine

    real = ServeEngine.submit
    n = [0]

    def submit(self, points, **kw):
        n[0] += 1
        if n[0] % 10 == 0:
            raise Overloaded("queue full", reason="queue_full")
        return real(self, points, **kw)

    monkeypatch.setattr(ServeEngine, "submit", submit)
    line = _run(root, "tiny.serve", 34, seconds=1.0)
    assert line["failed"] >= 3 and line["attempted"] == 40


def _add_stall_cell(root, name, serve_engine):
    """A serve cell of its own, added as files: 800 requests/s on the tiny
    zones, with or without the configuration's ``serve_engine`` group."""
    import json
    import os

    from bh_fixtures import TINY_POINTS, _write, tiny_config

    tree = os.path.join(root, "benchmark")
    cfg = tiny_config()
    if serve_engine:
        cfg["serve_engine"] = dict(serve_engine, why="test fixture")
    _write(os.path.join(tree, "configs", f"{name}-zones.json"), cfg)
    _write(os.path.join(tree, "traffic", f"{name}-open.json"), {
        "kind": "open_loop_requests", "rate_per_s": 800.0,
        "size_rows": {"median": 4, "sigma": 0.5, "min": 1, "max": 32},
        "pool_rows": 16384, "points": TINY_POINTS, "schedule_seed": 5,
    })
    _write(os.path.join(tree, "workloads", f"{name}.serve.json"),
           {"check": {"sample_rows": 2000}})
    path = os.path.join(root, "BENCHMARK.json")
    with open(path, encoding="utf-8") as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": f"{name}-zones", "source": "test fixture",
        "file": f"benchmark/configs/{name}-zones.json", "reduced": [],
        "why": "test fixture",
    })
    bench["workloads"].append({
        "name": f"{name}.serve", "config": f"{name}-zones",
        "traffic": f"{name}-open", "chips": 1, "why": "test fixture",
    })
    for m in bench["end_to_end"]:
        if "tiny.serve" in m.get("workloads", []):
            m["workloads"].append(f"{name}.serve")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(bench, f, indent=1)


@pytest.mark.parametrize("serve_engine", [
    None, {"queue_capacity": 8192, "default_deadline_s": 10.0},
], ids=["package_defaults_shed", "configured_queue_rides_it_out"])
def test_a_dispatch_stall_sheds_only_under_the_default_queue(
    root, monkeypatch, serve_engine
):
    """One 0.6 s stall of the dispatch thread at 800 requests/s queues
    ~480 requests. The package's 256-request queue refuses the rest (what
    the driver's first check read in `taxi.serve`); the queue the
    configuration's ``serve_engine`` group sets holds them, no request
    fails, and the wait shows in the latency tail instead."""
    import time

    from benchmark.harness.context import Ctx
    from mosaic_tpu.serve import ServeEngine

    _add_stall_cell(root, "stall", serve_engine)
    armed, real_say = [False], Ctx.say

    def say(self, what, **kv):
        if what == "serve_ready":
            armed[0] = True
        return real_say(self, what, **kv)

    real = ServeEngine._dispatch_resilient

    def stalled(self, core, padded, deadline_hint):
        if armed[0]:
            armed[0] = False
            time.sleep(0.6)
        return real(self, core, padded, deadline_hint)

    monkeypatch.setattr(Ctx, "say", say)
    monkeypatch.setattr(ServeEngine, "_dispatch_resilient", stalled)
    line = _run(root, "stall.serve", 35, seconds=1.5)
    assert line["attempted"] == 1200
    if serve_engine is None:
        assert line["failed"] >= 50
    else:
        assert line["failed"] == 0 and line["correct"] is True
        assert line["metrics"]["latency_p95_ms"]["value"] > 300
