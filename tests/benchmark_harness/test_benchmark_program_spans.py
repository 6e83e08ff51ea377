"""The readers of the program's own spans and stage names: exact on
hand-made events and traces, and against one short trace of the serve cell
recorded on the TPU v5e with its span events and stage tables
(``benchmark/fixtures/taxi_serve_v5e/``, written by
``benchmark/tools/record_trace_fixture.py``). Each reader's "nothing to
read" path returns None and raises nothing: the parent commit's program
has no such span, annotation or stage table."""

import gzip
import json
import os
import statistics
import time
from types import SimpleNamespace

import pytest

from bh_fixtures import REPO, make_copy

from benchmark.harness.spec import Spec

FIXTURE = os.path.join(REPO, "benchmark", "fixtures", "taxi_serve_v5e")
#: the serve metrics the recorded fixture's run read (its ``result.json``)
RECORDED_SERVE_METRICS = [
    "linger_p50_ms.serve", "batch_pad_p50_ms.serve",
    "dispatch_handoff_p50_ms.serve", "dispatch_h2d_p50_ms.serve",
    "dispatch_launch_p50_ms.serve", "dispatch_d2h_p50_ms.serve",
    "deliver_p50_ms.serve", "dispatch_device_p50_ms.serve",
    "probe_device_share.serve", "idle_no_work_share.serve",
    "idle_linger_share.serve", "idle_host_dispatch_share.serve",
]
S = 1e9


@pytest.fixture(scope="module")
def spec():
    return Spec(REPO)


def _ctx(spec, **kw):
    from benchmark.harness.context import SpanLog

    said = []
    ctx = SimpleNamespace(
        spec=spec, events=[], window=(0.0, 100.0), counters={}, series={},
        spans=SpanLog(), trace_reduction=None,
        tracer=SimpleNamespace(log_dir=None),
    )
    ctx.say = lambda what, **kv: said.append((what, kv))
    ctx.said = said
    for k, v in kw.items():
        setattr(ctx, k, v)
    return ctx


def _read(spec, reader, ctx, params):
    return spec.module("readers", reader).read(ctx, params)


def _with_trace(spec, monkeypatch, tr):
    monkeypatch.setattr(
        spec.module("readers", "_trace"), "of_run", lambda ctx: tr)


# ------------------------------------------- every entry of ``per_layer``

def _per_layer_names():
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        return [m["name"] for m in json.load(f)["per_layer"]]


def check_entry(spec, name):
    """What holds of any per-layer entry, whichever PR appended it (the
    additivity test runs it against a copy with one more)."""
    entry = next(m for m in spec.benchmark["per_layer"] if m["name"] == name)
    cells = {w["name"] for w in spec.benchmark["workloads"]}
    # an entry lists the cells that read it, or carries no ``workloads`` key
    # and is read by every cell that reports what it moves (`Spec.per_layer`)
    listed = entry.get("workloads", cells)
    assert listed and set(listed) <= cells
    desc = spec.data("layer_metrics", name)
    assert desc["what"] and set(desc) == {"what", "reader", "params"}
    reader = spec.module("readers", desc["reader"])
    # on a run with no trace and no such event, span, series or counter the
    # reader has nothing to read, whatever its parameters: the case of a
    # parent commit whose program lacks what the metric reads
    assert reader.read(_ctx(spec), desc["params"]) is None


@pytest.mark.parametrize("name", _per_layer_names())
def test_entry_has_its_files_and_a_reader_that_takes_its_params(spec, name):
    check_entry(spec, name)


# ------------------------------------------------- span_child_percentile

def _span(name, sid, parent, seconds, ts):
    return {"event": "span", "name": name, "span_id": sid,
            "parent_id": parent, "seconds": seconds, "ts_mono": ts}


def test_span_child_percentile_sums_descendants_per_root(spec):
    events = [
        _span("serve.batch", "b1", "r1", 5.0, 10.0),
        _span("serve.dispatch", "d1", "b1", 4.0, 9.9),
        _span("dispatch.launch", "l1", "d1", 0.1, 9.0),
        _span("dispatch.launch", "l2", "d1", 0.3, 9.5),
        _span("serve.deliver", "v1", "b1", 0.2, 10.1),
        _span("serve.batch", "b2", "r2", 6.0, 20.0),
        _span("serve.dispatch", "d2", "b2", 5.0, 19.9),
        _span("dispatch.launch", "l3", "d2", 1.0, 19.0),
        _span("serve.batch", "b3", "r3", 6.0, 200.0),   # outside the window
        _span("dispatch.launch", "l4", "b3", 9.0, 199.0),
        {"event": "serve_stage", "stage": "dispatch", "seconds": 1.0},
    ]
    ctx = _ctx(spec, events=events)
    p = {"root": "serve.batch", "child": ["dispatch.launch"], "q": 0.5,
         "scale": 1000}
    # roots in the window: 0.4 and 1.0 -> nearest-rank p50 = 0.4
    assert _read(spec, "span_child_percentile", ctx, p) == pytest.approx(400.0)
    p["q"] = 1.0
    assert _read(spec, "span_child_percentile", ctx, p) == pytest.approx(1000.0)
    both = dict(p, child=["dispatch.launch", "serve.deliver"], q=0.5)
    assert _read(spec, "span_child_percentile", ctx, both) == pytest.approx(600.0)
    own = {"root": "serve.batch", "child": "serve.batch", "q": 1.0}
    assert _read(spec, "span_child_percentile", ctx, own) == 6.0
    none = dict(p, child=["no.such.span"])
    assert _read(spec, "span_child_percentile", ctx, none) is None


# ----------------------------------------------------- the trace readers

def _tr():
    """One device, two module runs; the program's spans on two threads."""
    ops = [
        ("%fusion.1 = f32[64]{0} fusion()", 10 * S, 11 * S),
        ("%fusion.2 = s32[64]{0} fusion()", 11 * S, 11.5 * S),
        ("%copy.3 = u32[8,2]{1,0} copy()", 12 * S, 12.25 * S),
        ("%fusion.1 = f32[64]{0} fusion()", 20 * S, 21 * S),
        ("%fusion.9 = f32[64]{0} fusion()", 30 * S, 30.5 * S),  # no module
    ]
    modules = [
        ("jit_join(123)", 10 * S, 12.5 * S),
        ("jit_join(123)", 20 * S, 21 * S),
    ]
    program = sorted([
        # name, start, end, t
        ("serve.wait", 0 * S, 9 * S, 1000),
        ("serve.linger", 9 * S, 9.5 * S, None),
        ("serve.batch", 9.5 * S, 13 * S, None),
        ("serve.dispatch", 9.8 * S, 12.9 * S, None),
        ("dispatch.transfer.d2h", 11.4 * S, 12.8 * S, None),  # other thread
        ("serve.wait", 13 * S, 19 * S, int(13 * S) + 1000),
        ("serve.batch", 19.5 * S, 22 * S, None),
        ("serve.dispatch", 19.8 * S, 21.9 * S, None),
        ("stream.launch", 9.9 * S, 9.95 * S, None),
        ("stream.launch", 19.0 * S, 19.1 * S, None),
    ], key=lambda p: p[1])
    other = {
        "ops": [("%fusion.1 = f32[64]{0} fusion()", 10.5 * S, 11 * S)],
        "modules": [("jit_join(123)", 10.5 * S, 11 * S),
                    ("jit_join(123)", 19.5 * S, 20 * S)],
    }
    return {
        "devices": {"/device:TPU:0": {"ops": ops, "modules": modules},
                    "/device:TPU:1": other},
        "program": program,
    }


def test_trace_busy_in_span(spec, monkeypatch):
    _with_trace(spec, monkeypatch, _tr())
    ctx = _ctx(spec)
    p = {"span": "serve.dispatch", "q": 0.5, "scale": 1000}
    # device 0 busy inside the two dispatches: 1.75 s and 1.0 s
    assert _read(spec, "trace_busy_in_span", ctx, p) == pytest.approx(1000.0)
    assert _read(spec, "trace_busy_in_span", ctx, dict(p, q=1.0)) == \
        pytest.approx(1750.0)
    # a span that cuts an op counts the part inside it
    cut = {"span": "dispatch.transfer.d2h", "q": 1.0, "scale": 1}
    assert _read(spec, "trace_busy_in_span", ctx, cut) == pytest.approx(0.1 + 0.25)
    assert _read(spec, "trace_busy_in_span", ctx, dict(p, span="nope")) is None
    # launch -> the next module run's start, the latest chip:
    # launch 9.9: chip 0 at 10 (+0.1), chip 1 at 10.5 (+0.6) -> 0.6;
    # launch 19.0: chip 0 at 20 (+1.0), chip 1 at 19.5 (+0.5) -> 1.0
    d = {"span": "stream.launch", "measure": "first_op_delay", "q": 0.5,
         "scale": 1}
    assert _read(spec, "trace_busy_in_span", ctx, d) == pytest.approx(0.6)
    assert _read(spec, "trace_busy_in_span", ctx, dict(d, q=1.0)) == \
        pytest.approx(1.0)
    said = [kv for what, kv in ctx.said if what == "launch_to_device"][0]
    assert (said["0"], said["1"]) == (100.0, 500.0)  # per-chip p50, ms
    # a launch with no later module run on some chip has nothing to read
    tr = _tr()
    tr["program"] = [("stream.launch", 19.8 * S, 19.9 * S, None)]
    _with_trace(spec, monkeypatch, tr)
    assert _read(spec, "trace_busy_in_span", _ctx(spec), d) is None


def test_trace_idle_in_span_shares_and_the_printed_attribution(spec, monkeypatch):
    _with_trace(spec, monkeypatch, _tr())
    ctx = _ctx(spec)
    # gaps of device 0: 11.5-12, 12.25-20 and 21-30, cut at every
    # annotation's start and end; each piece by the span that covers it
    idle = 0.5 + 7.75 + 9.0
    read = lambda spans: _read(  # noqa: E731
        spec, "trace_idle_in_span", ctx, {"spans": spans})
    assert read(["serve.wait"]) == pytest.approx(100 * 6.0 / idle)
    in_batch = 0.5 + (0.55 + 0.1 + 0.1) + (0.3 + 0.2) + (0.9 + 0.1)
    assert read(["serve.batch", "serve.deliver"]) == \
        pytest.approx(100 * in_batch / idle)
    assert read(["serve.linger"]) == 0.0
    said = [kv for what, kv in ctx.said if what == "idle_by_program_span"]
    assert len(said) == 1  # once a run, however many metrics read it
    assert said[0]["idle_s"] == pytest.approx(idle)
    # innermost wins: the pull on the worker thread, inside the dispatch
    assert said[0]["dispatch.transfer.d2h"] == pytest.approx(0.5 + 0.55)
    assert said[0]["serve.dispatch"] == pytest.approx(0.1 + 0.2 + 0.9)
    assert said[0]["serve.batch"] == pytest.approx(0.1 + 0.3 + 0.1)
    assert said[0]["serve.wait"] == pytest.approx(6.0)
    assert said[0]["stream.launch"] == pytest.approx(0.1)
    assert said[0]["none"] == pytest.approx(0.4 + 8.0)
    assert said[0]["under_program_spans"] == pytest.approx(1 - 8.4 / idle, abs=1e-4)


def test_trace_stage_busy_by_module_and_label(spec, monkeypatch):
    from mosaic_tpu.obs import stages

    _with_trace(spec, monkeypatch, _tr())
    asked = []

    def tables(modules, rows):
        asked.append((set(modules), set(rows)))
        return {"jit_join": {"fusion.1 f32[64]": "pip.tier1",
                             "fusion.2 s32[64]": "pip.hash_probe",
                             "fusion.9 f32[64]": "pip.cells"}}

    monkeypatch.setattr(stages, "tables", tables)
    ctx = _ctx(spec, counters={"traced_steps": 2})
    # device 0: tier1 2.0, probe 0.5, copy.3 (no entry) 0.25 and fusion.9
    # (outside any module run) 0.5 unscoped; device 1: tier1 0.5; mean of 2
    per_step = {"stage": "pip.tier1", "steps": "traced_steps"}
    assert _read(spec, "trace_stage_busy", ctx, per_step) == \
        pytest.approx(1000 * (2.0 + 0.5) / 2 / 2)
    share = {"stage": "unscoped", "share": True}
    assert _read(spec, "trace_stage_busy", ctx, share) == \
        pytest.approx(100 * 0.75 / (2.5 + 0.5 + 0.75))
    two = {"stage": ["pip.tier1", "pip.hash_probe"], "share": True}
    assert _read(spec, "trace_stage_busy", ctx, two) == \
        pytest.approx(100 * 3.0 / 3.75)
    assert asked[0] == ({"jit_join"}, {64, 8, 2})
    assert len(asked) == 2  # once per device, then kept for the run
    assert [w for w, _ in ctx.said].count("device_by_stage") == 1
    no_steps = _ctx(spec, device_by_stage={"pip.tier1": 1.0})
    assert _read(spec, "trace_stage_busy", no_steps, per_step) is None


def test_the_harness_builds_the_stage_table_and_the_readers_take_it(
        spec, monkeypatch):
    """`harness/stage_table.py` builds the table with no reader's help
    (`run_cell` calls it before the first reader, for the breakdown's
    ``device_stages``); a stage reader after it lowers nothing again."""
    from mosaic_tpu.obs import stages

    from benchmark.harness import stage_table

    asked = []

    def tables(modules, rows):
        asked.append(set(modules))
        return {"jit_join": {"fusion.1 f32[64]": "pip.tier1"}}

    monkeypatch.setattr(stages, "tables", tables)
    bare = _ctx(spec)  # no trace: nothing to read, nothing lowered
    assert stage_table.of_run(bare) is None and not asked
    _with_trace(spec, monkeypatch, _tr())
    ctx = _ctx(spec, counters={"traced_steps": 2})
    table = stage_table.of_run(ctx)
    assert table == pytest.approx({"pip.tier1": 1.25, "unscoped": 0.625})
    assert ctx.device_by_stage is table and len(asked) == 2
    assert [w for w, _ in ctx.said] == ["stage_tables", "device_by_stage"]
    assert stage_table.of_run(ctx) is table
    assert _read(spec, "trace_stage_busy", ctx,
                 {"stage": "pip.tier1", "steps": "traced_steps"}) == \
        pytest.approx(1000 * 1.25 / 2)
    assert len(asked) == 2 and len(ctx.said) == 2


def test_a_trace_without_devices_or_program_spans_is_nothing_to_read(
        spec, tmp_path):
    """The CPU rehearsal's trace (no device plane) and the parent's (no
    ``mosaic.*`` annotation): `of_run` is None and every reader with it."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.stream.run"):
        time.sleep(0.001)
    jax.profiler.stop_trace()
    ctx = _ctx(spec, tracer=SimpleNamespace(log_dir=str(tmp_path)))
    tr_mod = spec.module("readers", "_trace")
    assert tr_mod.trace_path(ctx) is not None
    assert tr_mod.of_run(ctx) is None
    for reader, params in [
        ("trace_busy_in_span", {"span": "serve.dispatch", "q": 0.5, "scale": 1}),
        ("trace_idle_in_span", {"spans": ["serve.wait"]}),
        ("trace_stage_busy", {"stage": "pip.tier1", "share": True}),
    ]:
        assert _read(spec, reader, ctx, params) is None


# ------------------------------------- an untraced run lowers nothing

def test_untraced_rehearsal_runs_lower_nothing_through_stages(
        tmp_path, monkeypatch):
    from benchmark.harness.run_cell import run_cell
    from mosaic_tpu.obs import stages

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    root = make_copy(tmp_path)
    n0 = stages.lowerings()
    for cell in ("tiny.stream", "tiny.serve"):
        line = run_cell(root, cell, 4_000_000_777, 0.5, False,
                        t_start=time.perf_counter(), rehearsal=True)
        assert line["correct"] is True and line["failed"] == 0
    assert stages.lowerings() == n0
    registered = {m for m, _rows in stages.registered()}
    assert {"jit_loop", "jit_cells", "jit_pip_join_points"} <= registered


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


def test_recorded_trace_has_the_programs_spans_on_the_device_clock(spec, recorded):
    tr = recorded.tr
    assert list(tr["devices"]) == ["/device:TPU:0"]
    names = {p[0] for p in tr["program"]}
    assert {"serve.wait", "serve.linger", "serve.batch", "serve.dispatch",
            "serve.pad", "serve.deliver", "dispatch.launch",
            "dispatch.transfer.h2d", "dispatch.transfer.d2h"} <= names
    # one clock: every annotation that carries ``t`` gives the same offset
    # within a millisecond, and a span event placed by it lands on its own
    # annotation
    diffs = [s - t for _n, s, _e, t in tr["program"] if t is not None]
    offset = statistics.median(diffs)
    assert len(diffs) > 100 and max(abs(d - offset) for d in diffs) < 1e6
    # every module run of the window is a registered program's
    modules = {m[0].split("(")[0] for m in tr["devices"]["/device:TPU:0"]["modules"]}
    assert modules == {"jit_cells", "jit_pip_join_points"}
    assert modules <= set(recorded.tables)


@pytest.mark.parametrize("name", RECORDED_SERVE_METRICS)
def test_recorded_serve_window_reads_every_new_serve_metric(
        spec, recorded, monkeypatch, name):
    from mosaic_tpu.obs import stages

    _with_trace(spec, monkeypatch, recorded.tr)
    monkeypatch.setattr(stages, "tables", lambda modules, rows: recorded.tables)
    desc = spec.data("layer_metrics", name)
    ctx = _ctx(spec, events=recorded.events,
               window=tuple(recorded.result["window"]))
    value = _read(spec, desc["reader"], ctx, desc["params"])
    assert value is not None and value >= 0.0
    unit = next(m["unit"] for m in spec.benchmark["per_layer"]
                if m["name"] == name)
    if unit == "%":
        assert value <= 100.0
    else:
        assert value < 50.0, "milliseconds of one dispatch's piece"
    # the run that recorded the fixture read the same number from the
    # same trace (the span metrics read the events of its own window)
    if desc["reader"].startswith("trace_"):
        then = recorded.result["line"]["metrics"][name]
        assert value == pytest.approx(then["value"], rel=1e-6)


def test_recorded_idle_is_accounted_for_under_program_spans(
        spec, recorded, monkeypatch):
    _with_trace(spec, monkeypatch, recorded.tr)
    ctx = _ctx(spec)
    shares = [
        _read(spec, "trace_idle_in_span", ctx, {"spans": s})
        for s in (["serve.wait"], ["serve.linger"],
                  ["serve.batch", "serve.deliver"])
    ]
    said = next(kv for what, kv in ctx.said if what == "idle_by_program_span")
    assert said["under_program_spans"] >= 0.95
    assert sum(shares) >= 95.0 and sum(shares) <= 100.0 + 1e-6
