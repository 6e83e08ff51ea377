"""One clock and named stages (tier-1, CPU): the program's spans enter the
profiler's trace as ``mosaic.*`` annotations that carry their monotonic
start; the spans and counts of the serve dispatch and the stream launch
are recorded where the work happens; `obs.stages` maps a compiled
program's instructions to the join's stage names and lowers nothing until
a trace reader asks."""

from __future__ import annotations

import glob
import statistics
import threading
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mosaic_tpu.core.geometry import wkt
from mosaic_tpu.core.index import CustomIndexSystem, GridConf
from mosaic_tpu.core.tessellate import tessellate
from mosaic_tpu.dispatch import BucketLadder, core as dispatch_core
from mosaic_tpu.obs import stages, trace
from mosaic_tpu.runtime import telemetry, watchdog
from mosaic_tpu.serve import ServeEngine
from mosaic_tpu.sql.join import build_chip_index
from mosaic_tpu.sql.stream import StreamJoin, ring_from_host

BBOX = (-25.0, -25.0, 35.0, 20.0)
RES = 3
JOIN_STAGES = {
    "pip.cells", "pip.recentre", "pip.hash_probe", "pip.compact",
    "pip.tier1", "pip.writeback", "stream.slot", "stream.fold",
}


@pytest.fixture(scope="module")
def grid():
    return CustomIndexSystem(GridConf(-180, 180, -90, 90, 2, 10.0, 10.0))


def _chip_table(grid):
    col = wkt.from_wkt([
        "POLYGON ((1 1, 13 2, 12 11, 6 14, 2 9, 1 1))",
        "POLYGON ((-20 -20, -5 -20, -5 -5, -20 -5, -20 -20))",
        "POLYGON ((20 -10, 30 -10, 30 5, 20 5, 20 -10))",
    ])
    return tessellate(col, grid, RES, keep_core_geoms=False)


@pytest.fixture(scope="module")
def index(grid):
    return build_chip_index(_chip_table(grid))


def _points(seed, n):
    return np.random.default_rng(seed).uniform(BBOX[:2], BBOX[2:], (n, 2))


def _engine(index, grid, **kw):
    kw.setdefault("ladder", BucketLadder(64, 1024))
    kw.setdefault("bounds", BBOX)
    return ServeEngine(index, grid, RES, **kw)


def _program_annotations(log_dir):
    from jax.profiler import ProfileData

    path = sorted(glob.glob(f"{log_dir}/plugins/profile/*/*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("mosaic."):
                    out.append((ev.name, float(ev.start_ns), dict(ev.stats)))
    return out


# ------------------------------------------------------------- one clock

@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """Spans from two threads, and one detached, inside a profiler
    session on the CPU backend: (annotations, span events)."""
    log_dir = str(tmp_path_factory.mktemp("profile"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2

    def work(tag):
        telemetry.adopt_sinks(sinks)
        for i in range(5):
            with trace.span(f"clock.{tag}", i=i):
                with trace.span("clock.inner"):
                    time.sleep(0.002)

    with telemetry.capture() as events:
        sinks = telemetry.current_sinks()
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            other = threading.Thread(target=work, args=("b",))  # lint: thread-context-adoption-ok (a second ROOT trace on its own thread is the case under test; only the sinks are shared)
            other.start()
            work("a")
            other.join(30)
            assert not other.is_alive()
            trace.start_span("clock.detached", detached=True).end()
            quiet = trace.start_span("clock.dropped")
            quiet.drop()
        finally:
            jax.profiler.stop_trace()
    return _program_annotations(log_dir), [
        e for e in events if e["event"] == "span"
    ]


def test_spans_enter_the_trace_with_their_monotonic_start(profiled):
    anns, spans = profiled
    by_id = {e["span_id"]: e for e in spans}
    named = [a for a in anns if a[2].get("span_id") in by_id]
    assert {a[0] for a in named} == {
        "mosaic.clock.a", "mosaic.clock.b", "mosaic.clock.inner"}
    # 5 spans a thread and 10 inner ones (the profiler may lose an event
    # on a loaded host: it promises no delivery)
    assert 17 <= len(named) <= 20
    for name, _start, stats in named:
        e = by_id[stats["span_id"]]
        assert name == "mosaic." + e["name"]
        # ``t`` is the span's own ``start_mono``, in nanoseconds
        assert stats["t"] == int(round(e["start_mono"] * 1e9))


@pytest.mark.parametrize("thread", ["a", "b"])
def test_every_annotation_is_an_anchor_within_a_millisecond(profiled, thread):
    """``start_ns - t`` is ONE offset: taken from any annotation of
    either thread it places every span's ``start_mono`` on the trace's
    clock within 1 ms."""
    anns, _spans = profiled
    diffs = [start - stats["t"] for _n, start, stats in anns if "t" in stats]
    offset = statistics.median(diffs)
    mine = [
        start - stats["t"] for name, start, stats in anns
        if name == f"mosaic.clock.{thread}"
    ]
    assert 3 <= len(mine) <= 5
    assert max(abs(d - offset) for d in mine) < 1e6
    assert max(abs(d - offset) for d in diffs) < 1e6


def test_detached_spans_emit_no_annotation_and_dropped_ones_no_event(profiled):
    anns, spans = profiled
    names = {a[0] for a in anns}
    assert "mosaic.clock.detached" not in names
    assert "clock.detached" in {e["name"] for e in spans}
    # a dropped span is the reverse: annotation yes, event no
    assert "mosaic.clock.dropped" in names
    assert "clock.dropped" not in {e["name"] for e in spans}


def test_a_span_ended_on_another_thread_still_records_once():
    sp = trace.start_span("clock.crossed")
    with telemetry.capture() as events:
        sinks = telemetry.current_sinks()

        def end():
            telemetry.adopt_sinks(sinks)
            sp.end()

        t = threading.Thread(target=end)  # lint: thread-context-adoption-ok (the thread only ends a span the caller started)
        t.start()
        t.join(30)
        assert not t.is_alive()
        assert sp.end() is None  # idempotent
    assert [e["name"] for e in events if e["event"] == "span"] == [
        "clock.crossed"]
    assert trace.current_context() is None  # and left this thread's stack


# ------------------------------------------- spans where the work happens

@pytest.fixture(scope="module")
def served(index, grid):
    """One guarded request through a warmed engine: (events, metrics
    before, metrics after, answer)."""
    with _engine(index, grid, default_deadline_s=30.0) as eng:
        eng.warmup()
        m0 = eng.metrics()
        with telemetry.capture() as events:
            out = eng.join(_points(3, 100), deadline_s=30.0)
        time.sleep(0.15)  # a few idle ticks of the batcher
        m1 = eng.metrics()
    return events, m0, m1, np.asarray(out)


SERVE_PARENTS = [
    ("serve.wait", "serve.request"),
    ("serve.linger", "serve.request"),
    ("serve.batch", "serve.request"),
    ("serve.concat", "serve.batch"),
    ("serve.pad", "serve.batch"),
    ("serve.dispatch", "serve.batch"),
    ("serve.deliver", "serve.batch"),
    ("dispatch.guard.handoff", "serve.dispatch"),
    ("dispatch.transfer.h2d", "serve.dispatch"),
    ("dispatch.launch", "serve.dispatch"),
    ("dispatch.transfer.d2h", "serve.dispatch"),
]


@pytest.mark.parametrize("name,parent", SERVE_PARENTS)
def test_serve_span_is_a_child_of_the_span_that_causes_it(served, name, parent):
    events = served[0]
    spans = [e for e in events if e["event"] == "span"]
    by_id = {e["span_id"]: e for e in spans}
    mine = [e for e in spans if e["name"] == name]
    assert mine, f"no {name} span"
    for e in mine:
        assert by_id[e["parent_id"]]["name"] == parent
        assert e["seconds"] >= 0 and "start_mono" in e


def test_serve_span_counts_and_attributes(served):
    events = served[0]
    spans = [e for e in events if e["event"] == "span"]
    count = lambda n: sum(e["name"] == n for e in spans)  # noqa: E731
    # one event each, no `telemetry.timed` twin for the new stages
    assert count("serve.linger") == count("serve.pad") == 1
    assert count("serve.concat") == count("serve.deliver") == 1
    assert count("dispatch.launch") == count("dispatch.transfer.h2d") == 2
    assert count("dispatch.guard.handoff") == 2
    assert {e.get("leg") for e in spans
            if e["name"] == "dispatch.guard.handoff"} == {"out", "back"}
    assert {e.get("program") for e in spans
            if e["name"] == "dispatch.launch"} == {"cells", "join"}
    linger = next(e for e in spans if e["name"] == "serve.linger")
    assert linger["closed_by"] == "window"
    assert linger["requests"] == 1 and linger["rows"] == 100
    pad = next(e for e in spans if e["name"] == "serve.pad")
    assert (pad["rows"], pad["bucket"]) == (100, 128)
    stages_twice = [e for e in events if e.get("stage") in
                    ("linger", "pad", "concat", "deliver", "launch")]
    assert not stages_twice


def test_an_idle_tick_records_no_event(index, grid):
    """`serve.wait` is an annotation on every tick and an event only when
    a request ends it."""
    seen: list = []
    obs = lambda e: seen.append(e)  # noqa: E731
    with _engine(index, grid) as eng:
        eng.warmup()
        eng.batcher.idle_tick_s = 0.005
        time.sleep(0.05)
        telemetry.add_observer(obs)
        try:
            time.sleep(0.2)  # ~40 idle ticks
            quiet = list(seen)
            eng.join(_points(5, 10), deadline_s=30.0)
        finally:
            telemetry.remove_observer(obs)
    assert not [e for e in quiet if e.get("name") == "serve.wait"]
    assert not [e for e in quiet if e["event"] == "span"]
    assert sum(e.get("name") == "serve.wait" for e in seen) == 1


COUNTERS = [
    ("dispatches", 1), ("padded_rows", 128), ("batches", 1),
    ("batched_rows", 100), ("linger_closed_by_window", 1),
    ("linger_closed_by_rows", 0), ("linger_closed_by_put_back", 0),
    ("batched_requests", 1),
    # two puts (f64 points, f32 shifted points) and one pull (s32 rows)
    ("h2d_bytes", 128 * 2 * 8 + 128 * 2 * 4), ("d2h_bytes", 128 * 4),
]


@pytest.mark.parametrize("name,delta", COUNTERS)
def test_engine_metrics_count_where_the_work_happens(served, name, delta):
    _events, m0, m1, _out = served
    assert m1[name] - m0[name] == delta


def test_linger_closed_by_rows_and_put_back(index, grid):
    """Two 40-row requests against a 64-row batch budget: the second
    overshoots, goes back and leads the next batch."""
    with _engine(index, grid, max_batch_rows=64, max_wait_s=0.25,
                 default_deadline_s=None) as eng:
        eng.warmup()
        futs = [eng.submit(_points(s, 40)) for s in (1, 2)]
        for f in futs:
            f.result(timeout=60)
        first = eng.metrics()
        eng.join(_points(3, 64), timeout=60)
        m = eng.metrics()
    assert first["linger_closed_by_put_back"] == 1
    assert m["linger_closed_by_rows"] == 1
    assert sum(m["linger_closed_by_" + k]
               for k in ("rows", "window", "put_back")) == m["batches"]


def test_guard_hands_off_only_under_a_deadline():
    """No deadline: the guard runs inline, no thread and no hand-off.
    With one: two legs, each naming the guarded site."""
    with telemetry.capture() as events:
        assert watchdog.guard("test.site", lambda: 7) == 7
    assert not [e for e in events if e.get("name") == "dispatch.guard.handoff"]
    with telemetry.capture() as events:
        assert watchdog.guard("test.site", lambda: 8, default_s=5.0) == 8
    legs = [e for e in events if e.get("name") == "dispatch.guard.handoff"]
    assert sorted(e["leg"] for e in legs) == ["back", "out"]
    assert {e["site"] for e in legs} == {"test.site"}


@pytest.mark.parametrize("broken", ["start", "end"])
def test_a_failing_tracer_neither_fails_nor_stalls_the_guard(
        monkeypatch, broken):
    """Tracing is not the dispatch: a span that cannot start or end costs
    its leg, never the answer or the caller's wake-up."""

    class Span:
        def end(self):
            raise RuntimeError("tracer down")

    def start_span(*_a, **_k):
        if broken == "start":
            raise RuntimeError("tracer down")
        return Span()

    monkeypatch.setattr(telemetry, "start_span", start_span)
    t0 = time.monotonic()
    assert watchdog.guard("test.site", lambda: 9, default_s=5.0) == 9
    assert time.monotonic() - t0 < 2.0  # woken by the worker, not the deadline


@pytest.fixture(scope="module")
def stream(index, grid):
    rng = np.random.default_rng(11)
    ring = ring_from_host(
        [rng.uniform(BBOX[:2], BBOX[2:], (256, 2)) for _ in range(2)]
    )
    sj = StreamJoin(index, grid, RES)
    return sj, ring


def test_stream_launch_and_pull_are_children_of_the_run(stream):
    sj, ring = stream
    sj.compile(ring, 2)
    with telemetry.capture() as events:
        res = sj.run(ring, 2)
    spans = {e["name"]: e for e in events if e["event"] == "span"}
    run = spans["stream.run"]
    for name in ("stream.launch", "stream.pull"):
        assert spans[name]["parent_id"] == run["span_id"]
    assert spans["stream.launch"]["start_mono"] <= spans["stream.pull"]["start_mono"]
    assert res.n_points == 512
    loop = [e for e in events if e.get("stage") == "join_loop"]
    assert len(loop) == 1  # the timed twin stays, undoubled


# ------------------------------------- the rule's verdict, where it is read

@pytest.mark.parametrize("probe,compacted", [
    ("scatter", False),   # full-bucket caps cut no rows
    ("adaptive", True),
])
def test_engine_metrics_say_whether_tier1_compacts(
        index, grid, probe, compacted):
    with _engine(index, grid, probe=probe) as eng:
        assert eng.metrics()["compacted"] is compacted


@pytest.mark.parametrize("found_cap,compacted", [
    (None, False), (256, False), (255, True),
])
def test_stream_result_says_whether_tier1_compacts(
        stream, index, grid, found_cap, compacted):
    _sj, ring = stream  # 256 rows a slot
    res = StreamJoin(index, grid, RES, found_cap=found_cap).run(ring, 2)
    assert res.metrics["compacted"] is compacted
    assert res.overflow == 0


@pytest.mark.parametrize("dense,kw,compacted", [
    # most rows found: the cap sized from the count is the whole batch
    (True, {}, False),
    # most rows miss: the cap is under the batch, tier 1 compacts
    (False, {}, True),
    (False, {"writeback": "direct"}, False),
    (True, {"probe": "adaptive"}, True),
    # any chunk's verdict; the mesh lane's full per-shard caps
    (True, {"batch_size": 500}, False),
    (False, {"mesh": 2}, False),
])
def test_join_pip_span_says_whether_tier1_compacts(
        index, grid, dense, kw, compacted):
    from mosaic_tpu.sql.join import pip_join

    box = (1.0, 1.0, 12.0, 11.0) if dense else (40.0, 40.0, 170.0, 80.0)
    pts = np.random.default_rng(4).uniform(box[:2], box[2:], (2048, 2))
    with telemetry.capture() as events:
        out = pip_join(pts, None, grid, RES, chip_index=index,
                       recheck=False, **kw)
    (span,) = [e for e in events
               if e["event"] == "span" and e["name"] == "join.pip"]
    assert span["compacted"] is compacted
    assert ((np.asarray(out) >= 0).mean() > 0.5) == dense


# ------------------------------------------- the same, one tier down

@pytest.fixture(scope="module")
def heavy_index(grid):
    """The same three polygons at an edge cap of 4: 9 heavy cells."""
    index = build_chip_index(_chip_table(grid), edge_cap=4)
    assert index.num_heavy_cells == 9
    return index


@pytest.mark.parametrize("probe", ["scatter", "adaptive"])
def test_engine_metrics_say_whether_tier2_compacts(
        index, heavy_index, grid, probe):
    """Full-bucket caps cut no rows of either tier's."""
    for ix in (index, heavy_index):
        with _engine(ix, grid, probe=probe) as eng:
            assert eng.metrics()["tier2_compacted"] is False
            assert not eng.core.tier2_compacted(64)


@pytest.mark.parametrize("heavy,found_cap,heavy_cap,compacted", [
    (True, None, None, False), (True, None, 256, False),
    (True, None, 255, True), (True, 128, 128, False), (True, 128, 64, True),
    (False, None, 64, False),   # no heavy cell: no tier 2 to compact
])
def test_stream_result_and_span_say_whether_tier2_compacts(
        stream, index, heavy_index, grid, heavy, found_cap, heavy_cap,
        compacted):
    _sj, ring = stream  # 256 rows a slot
    sj = StreamJoin(heavy_index if heavy else index, grid, RES,
                    found_cap=found_cap, heavy_cap=heavy_cap)
    with telemetry.capture() as events:
        res = sj.run(ring, 2)
    assert res.metrics["tier2_compacted"] is compacted
    (span,) = [e for e in events
               if e["event"] == "span" and e["name"] == "stream.run"]
    assert span["tier2_compacted"] is compacted
    assert span["compacted"] is res.metrics["compacted"] is bool(found_cap)


@pytest.mark.parametrize("heavy,kw,compacted", [
    # few rows in heavy cells: the cap sized from their count cuts rows
    (True, {}, True),
    (True, {"writeback": "direct"}, True),
    (True, {"probe": "adaptive"}, True),
    # the mesh lane's full per-shard caps
    (True, {"mesh": 2}, False),
    (False, {}, False),
])
def test_join_pip_span_says_whether_tier2_compacts(
        index, heavy_index, grid, heavy, kw, compacted):
    from mosaic_tpu.sql.join import pip_join

    ix = heavy_index if heavy else index
    pts = np.random.default_rng(4).uniform((1.0, 1.0), (12.0, 11.0), (2048, 2))
    with telemetry.capture() as events:
        got = pip_join(pts, None, grid, RES, chip_index=ix, recheck=False,
                       **kw)
    (span,) = [e for e in events
               if e["event"] == "span" and e["name"] == "join.pip"]
    assert span["tier2_compacted"] is compacted
    want = pip_join(pts, None, grid, RES, chip_index=index, recheck=False)
    assert (np.asarray(got) == np.asarray(want)).all()


# --------------------------------------------------------- stage tables

def test_no_lowering_until_a_reader_asks(stream):
    sj, ring = stream
    stages.clear()
    n0 = stages.lowerings()
    sj._stages_seen.clear()
    sj.compile(ring, 2)
    sj.run(ring, 2)
    sj.run(ring, 2)
    assert stages.registered() == [("jit_loop", 256)]
    assert stages.lowerings() == n0
    stages.tables({"jit_other"})
    stages.tables({"jit_loop"}, rows={999})
    assert stages.lowerings() == n0  # filtered by module and by rows
    table = stages.tables({"jit_loop(123456789)"}, rows={256, 2})
    assert stages.lowerings() == n0 + 1 and set(table) == {"jit_loop"}
    stages.tables({"jit_loop"})
    assert stages.lowerings() == n0 + 1  # parsed once


def test_tables_lower_the_jaxpr_the_programs_own_call_traced(
        index, grid, monkeypatch):
    """The table's lowering reuses the jaxpr the program's own call
    traced: the stream's loop is not traced a second time."""
    from mosaic_tpu.sql import stream as stream_mod

    traces = []
    real = stream_mod.fold_stats
    monkeypatch.setattr(
        stream_mod, "fold_stats", lambda out: traces.append(1) or real(out))
    dispatch_core.clear_caches()
    stages.clear()
    try:
        sj = StreamJoin(index, grid, RES)
        ring = ring_from_host([_points(s, 128) for s in (1, 2)])
        sj.compile(ring, 2)
        sj.run(ring, 2)
        traced = len(traces)
        assert traced >= 1
        assert set(stages.tables()) == {"jit_loop"}
        assert len(traces) == traced
    finally:
        dispatch_core.clear_caches()
        stages.clear()


@pytest.mark.parametrize("found_cap", [None, 128])
def test_stream_loop_maps_every_instruction_to_a_stage(
        stream, index, grid, found_cap):
    """Every stage of the join shows in the loop's table; `pip.compact`
    only where the cap cuts rows (128 of the ring's 256 a slot)."""
    sj, ring = stream
    if found_cap:
        sj = StreamJoin(index, grid, RES, found_cap=found_cap)
    stages.clear()
    sj._stages_seen.clear()
    sj.compile(ring, 2)
    with telemetry.capture() as events:
        table = stages.tables({"jit_loop"})["jit_loop"]
    # a fresh compile keeps every scope the lowering has: nothing here
    # looks like an executable from before a scope changed
    assert not [e for e in events if e["event"] == "stages_stale_executable"]
    found = set(table.values())
    assert JOIN_STAGES - {"pip.compact"} <= found
    assert ("pip.compact" in found) == bool(found_cap)
    assert found <= JOIN_STAGES | {stages.UNSCOPED}
    # what is left without a scope: the entry's parameters and constants
    unscoped = [k for k, v in table.items() if v == stages.UNSCOPED]
    assert len(unscoped) <= 0.05 * len(table), unscoped


def test_join_program_maps_every_instruction_to_a_stage(index, grid):
    core = dispatch_core.DispatchCore(
        index, grid, RES, ladder=BucketLadder(64, 64))
    stages.clear()
    want = np.asarray(core.execute_padded(_points(9, 64)))
    assert sorted(stages.registered()) == [
        ("jit_cells", 64), ("jit_pip_join_points", 64)]
    with telemetry.capture() as events:
        tables = stages.tables()
    assert not [e for e in events if e["event"] == "stages_stale_executable"]
    assert set(tables["jit_cells"].values()) <= {"pip.cells", stages.UNSCOPED}
    join = set(tables["jit_pip_join_points"].values())
    # a full-bucket cap cuts no rows: the program has no compaction stage
    assert not core.compacted(64)
    assert {"pip.hash_probe", "pip.tier1", "pip.writeback"} <= join
    assert "pip.compact" not in join
    assert join <= JOIN_STAGES | {stages.UNSCOPED}
    # an adaptive core's program compacts, and names the stage
    adaptive = dispatch_core.DispatchCore(
        index, grid, RES, ladder=BucketLadder(64, 64), probe="adaptive")
    stages.clear()
    got = np.asarray(adaptive.execute_padded(_points(9, 64)))
    assert adaptive.compacted(64) and (got == want).all()
    join = set(stages.tables()["jit_pip_join_points"].values())
    assert {"pip.hash_probe", "pip.compact", "pip.tier1",
            "pip.writeback"} <= join
    # scopes are metadata: the answers are those of the unscoped function
    from mosaic_tpu.sql.join import host_join

    host = host_join(_points(9, 64), index.host, grid, RES)
    assert (want == np.asarray(host)).all()


def test_parse_hlo_rules():
    """Own scope; else the fused computation's commonest; else what
    consumes it; else what it consumes; else the caller; else unscoped."""
    text = """HloModule jit_f

%fused_computation (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %a = f32[8]{0} sine(%p), metadata={op_name="jit(f)/pip.tier1/sin"}
  ROOT %b = f32[8]{0} negate(%a), metadata={op_name="jit(f)/pip.tier1/pip.compact/neg"}
}

%fused_computation.1 (q: f32[8]) -> f32[8] {
  %q = f32[8]{0} parameter(0)
  ROOT %c = f32[8]{0} negate(%q)
}

ENTRY %main (x: s64[8]) -> f32[8] {
  %x = s64[8]{0} parameter(0)
  %custom-call.1 = u32[8]{0:T(128)} custom-call(%x), custom_call_target="X64SplitLow"
  %fusion.1 = f32[8]{0:T(128)} fusion(%custom-call.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/pip.hash_probe/mul"}
  %fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation
  %copy.3 = f32[8]{0} copy(%fusion.2)
  %fusion.4 = f32[8]{0} fusion(%copy.3), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(f)/stream.fold/add"}
  %lonely.5 = f32[] constant(0)
  ROOT %out = f32[8]{0} copy(%fusion.4), metadata={op_name="jit(f)/copy"}
}
"""
    t = stages.parse_hlo(text)
    assert t["fusion.1 f32[8]"] == "pip.hash_probe"        # its own scope
    assert t["custom-call.1 u32[8]"] == "pip.hash_probe"   # its consumer's
    assert t["fusion.2 f32[8]"] in ("pip.tier1", "pip.compact")  # fused body
    assert t["copy.3 f32[8]"] == "stream.fold"             # its consumer's
    assert t["out f32[8]"] == "stream.fold"                # what it consumes
    assert t["c f32[8]"] == "stream.fold"                  # its caller's
    assert t["b f32[8]"] == "pip.compact"                  # innermost wins
    assert t["lonely.5 f32[]"] == stages.UNSCOPED
    assert stages.stage_of("jit(f)/while/body/pip.tier2/pip.compact/x") == "pip.compact"
    assert stages.stage_of("jit(f)/while/body/add") is None


def test_op_key_is_the_benchmarks_label():
    from benchmark.harness import xplane

    for text in (
        "%fusion.504 = f32[4000000,153]{0,1:T(8,128)} fusion(f32[] %a), kind=kLoop",
        "%custom-call.1 = u32[262144,2]{1,0:T(8,128)} custom-call(s64[262144,2] %t)",
        "%copy-start.1 = (f32[20]{0:T(128)S(1)}, f32[20]{0:T(128)}, u32[]{:S(2)}) copy-start(%c)",
        "all-reduce.3",
    ):
        assert stages.op_key(text) == xplane.op_label(text)


@pytest.fixture
def persistent_cache(tmp_path):
    """The persistent compile cache, on (the tests run without it) and in
    a directory of its own."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    names = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {n: getattr(jax.config, n) for n in names}
    cc.reset_cache()
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    yield tmp_path
    for n, v in saved.items():
        jax.config.update(n, v)
    cc.reset_cache()


def test_a_cached_executable_from_before_a_scope_changed_is_compiled_again(
        persistent_cache):
    """The cache's key leaves metadata out, so a program that differs
    from a cached one only in its scope names is answered with the old
    executable and its old names. `tables` sees that the names are not
    the lowering's and compiles under a key that adds them — once: the
    next reader finds that entry, from whatever line it asks (the key
    holds op names, not source locations)."""

    def program(scope):
        def renamed(x):
            with jax.named_scope(scope):
                y = jnp.cumsum(x * 2.0)
            return y + 1.0

        return jax.jit(renamed)

    x = jax.ShapeDtypeStruct((1024,), jnp.float32)
    program("pip.before").lower(x).compile()
    entries = len(list(persistent_cache.iterdir()))
    assert entries == 1
    # the hazard itself: equal HLO but for metadata is a cache hit
    assert "pip.before" in program("pip.after").lower(x).compile().as_text()
    assert len(list(persistent_cache.iterdir())) == entries

    def read():
        stages.clear()
        stages.register(program("pip.after"), (x,), rows=1024)
        with telemetry.capture() as events:
            found = set(stages.tables()["jit_renamed"].values())
        return found, [e["event"] for e in events]

    n0 = stages.recompiled()
    first = read()
    again = read()  # another call site: other source locations
    for found, events in (first, again):
        assert "pip.after" in found and "pip.before" not in found
        assert events == ["stages_stale_executable"]
    assert stages.recompiled() == n0 + 2
    # compiled by the first reader, found by the next
    assert len(list(persistent_cache.iterdir())) == entries + 1
    stages.clear()


def test_a_program_that_no_longer_lowers_is_left_out():
    stages.clear()

    def broken(x):
        raise RuntimeError("cannot trace")

    stages.register(jax.jit(broken), (jax.ShapeDtypeStruct((4,), jnp.float32),))
    with telemetry.capture() as events:
        assert stages.tables() == {}
    assert [e["event"] for e in events] == ["stages_lowering_failed"]
    stages.clear()


# ------------------------------------------------------- the raster scan

ZONAL_STAGES = {
    "zonal.centers", "pip.cells", "pip.recentre", "pip.hash_probe",
    "pip.compact", "pip.tier1", "pip.writeback",
}


@pytest.fixture(scope="module")
def scanned(index, grid):
    from mosaic_tpu.raster import Raster
    from mosaic_tpu.sql import RasterStream

    rng = np.random.default_rng(5)
    data = rng.integers(0, 10_000, (1, 50, 60)).astype(np.int16)
    raster = Raster(data=data, gt=(-25.3, 1.0, 0.0, 20.2, 0.0, -0.9),
                    srid=0, nodata=32767)
    rs = RasterStream(index, grid, RES)
    stages.clear()
    with telemetry.capture() as events:
        result = rs.scan(raster, tile=(32, 32))
    return SimpleNamespace(rs=rs, events=events, result=result)


def test_a_scanned_tile_holds_probe_patch_and_fold_spans(scanned):
    spans = [e for e in scanned.events if e["event"] == "span"]
    by_id = {e["span_id"]: e for e in spans}
    scan = [e for e in spans if e["name"] == "raster.scan"]
    tiles = [e for e in spans if e["name"] == "raster.zonal"]
    assert len(scan) == 1 and len(tiles) == scanned.result.ntiles == 4
    assert all(t["parent_id"] == scan[0]["span_id"] and t["pipelined"]
               for t in tiles)
    for name in ("raster.probe", "raster.patch", "raster.fold"):
        mine = [e for e in spans if e["name"] == name]
        assert sorted(e["tile"] for e in mine) == [0, 1, 2, 3]
        assert all(by_id[e["parent_id"]]["name"] == "raster.zonal"
                   and by_id[e["parent_id"]]["step"] == e["tile"] for e in mine)
    # within a tile: probe, then patch, then fold
    for t in range(4):
        order = [e["name"] for e in spans
                 if e.get("tile") == t and e["name"].startswith("raster.")]
        assert order == ["raster.probe", "raster.patch", "raster.fold"]
    # the tile's pull is the pipeline's drain, beside the tiles
    drains = [e for e in spans if e["name"] == "stream.pipeline.drain"]
    assert len(drains) == 4
    assert all(d["site"] == "raster.pipeline.drain"
               and d["parent_id"] == scan[0]["span_id"] for d in drains)


def test_a_scan_records_one_raster_scan_event(scanned):
    evts = [e for e in scanned.events if e["event"] == "raster_scan"]
    assert len(evts) == 1
    e = evts[0]
    assert (e["tiles"], e["pixels"], e["valid_pixels"]) == (4, 3000, 3000)
    patched = sum(s["rows"] for s in scanned.events
                  if s["event"] == "span" and s["name"] == "raster.patch")
    assert e["patched_pixels"] == patched and e["degraded_tiles"] == 0
    assert e["window"] == 4 and e["seconds"] > 0


def test_both_zonal_programs_are_registered_and_map_to_stages(scanned):
    assert sorted(stages.registered()) == [
        ("jit_zones_fold", 1024), ("jit_zones_probe", 1024)]
    tables = stages.tables()
    fold = set(tables["jit_zones_fold"].values())
    assert fold == {"zonal.fold"}
    probe = set(tables["jit_zones_probe"].values())
    assert ZONAL_STAGES <= probe
    unscoped = [k for k, v in tables["jit_zones_probe"].items()
                if v == stages.UNSCOPED]
    assert len(unscoped) <= 0.05 * len(tables["jit_zones_probe"]), unscoped
    assert stages.stage_of("jit(f)/zonal.fold/scatter-add") == "zonal.fold"
