"""Observability subsystem contract (PR 5): spans + cross-thread
propagation, typed metrics + event bridge, exporters (JSONL / Chrome
trace / Prometheus), the telemetry satellites (hot-path logging guard,
timed() error stamping, nearest-rank percentiles), the durable-stream
single-trace contract, and the perf regression gate."""

import json
import logging
import re
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from mosaic_tpu import obs
from mosaic_tpu.obs import metrics as obs_metrics
from mosaic_tpu.runtime import faults, telemetry

REPO = Path(__file__).parent.parent
sys.path.insert(0, str(REPO / "tools"))


# ------------------------------------------------------------------ spans


class TestSpans:
    def test_ids_nesting_and_parent_links(self):
        with telemetry.capture() as events:
            with obs.span("outer", a=1):
                with obs.span("inner"):
                    pass
        spans = {e["name"]: e for e in events if e["event"] == "span"}
        outer, inner = spans["outer"], spans["inner"]
        assert len(outer["trace_id"]) == 32
        assert len(outer["span_id"]) == 16
        assert inner["trace_id"] == outer["trace_id"]
        assert inner["parent_id"] == outer["span_id"]
        assert outer["parent_id"] is None
        assert outer["a"] == 1
        assert outer["seconds"] >= inner["seconds"] >= 0.0
        # inner ends before outer: the trail is ordered by seq
        assert inner["seq"] < outer["seq"]

    def test_exception_stamps_error_and_reraises(self):
        with telemetry.capture() as events:
            with pytest.raises(ValueError):
                with obs.span("doomed"):
                    raise ValueError("boom")
        (s,) = [e for e in events if e["event"] == "span"]
        assert s["name"] == "doomed" and s["error"] == "ValueError"

    def test_event_stamping_inside_and_outside(self):
        with telemetry.capture() as events:
            telemetry.record("before")
            with obs.span("scope") as sp:
                telemetry.record("inside")
                telemetry.record("explicit", trace_id="mine")
            telemetry.record("after")
        by = {e["event"]: e for e in events if e["event"] != "span"}
        assert "trace_id" not in by["before"]
        assert by["inside"]["trace_id"] == sp.context.trace_id
        assert by["inside"]["span_id"] == sp.context.span_id
        # explicitly passed ids win over the ambient span
        assert by["explicit"]["trace_id"] == "mine"
        assert "trace_id" not in by["after"]

    def test_detached_span_does_not_become_ambient_parent(self):
        with telemetry.capture() as events:
            root = obs.start_span("request", detached=True)
            with obs.span("sibling"):
                pass
            root.end()
        spans = {e["name"]: e for e in events if e["event"] == "span"}
        # the detached root never occupied the stack: the sibling is its
        # own fresh trace, not a child
        assert spans["sibling"]["trace_id"] != spans["request"]["trace_id"]
        assert spans["sibling"]["parent_id"] is None

    def test_end_is_idempotent(self):
        with telemetry.capture() as events:
            sp = obs.start_span("once", detached=True)
            assert sp.end() is not None
            assert sp.end() is None
        assert sum(e["event"] == "span" for e in events) == 1

    def test_cross_thread_adoption_joins_the_trace(self):
        """A worker thread that adopts the caller's context emits spans
        and events into the SAME trace, with valid parent links."""
        with telemetry.capture() as events:
            sinks = telemetry.current_sinks()
            with obs.span("caller") as sp:
                ctx = obs.current_context()

                def work():
                    telemetry.adopt_sinks(sinks)
                    obs.adopt_context(ctx)
                    telemetry.record("worker_event")
                    with obs.span("worker_span"):
                        pass

                t = threading.Thread(target=work)  # lint: thread-context-adoption-ok (this IS the adoption test fixture; no fault plans in scope)
                t.start()
                t.join()
        spans = {e["name"]: e for e in events if e["event"] == "span"}
        ev = next(e for e in events if e["event"] == "worker_event")
        assert ev["trace_id"] == sp.context.trace_id
        assert spans["worker_span"]["trace_id"] == sp.context.trace_id
        assert spans["worker_span"]["parent_id"] == sp.context.span_id
        summ = obs.trace_summary(events)
        assert len(summ) == 1
        (t_sum,) = summ.values()
        assert t_sum["roots"] == 1 and not t_sum["orphans"]

    def test_watchdog_worker_inherits_the_span(self):
        """Events recorded inside a watchdog-guarded callable (which
        runs on a worker thread) attach to the caller's span."""
        from mosaic_tpu.runtime import watchdog

        with telemetry.capture() as events:
            with obs.span("guarded") as sp:
                with faults.stalls(0.0, n=1, sites=("unit.site",)):
                    watchdog.guard(
                        "unit.site",
                        lambda: telemetry.record("from_worker"),
                        default_s=30.0,
                    )
        ev = next(e for e in events if e["event"] == "from_worker")
        assert ev["trace_id"] == sp.context.trace_id


# ---------------------------------------------------------------- metrics


class TestMetrics:
    def test_counter_gauge_histogram(self):
        reg = obs_metrics.Registry()
        c = reg.counter("c.requests", "reqs")
        c.inc()
        c.inc(2, reason="deadline")
        assert c.value() == 1
        assert c.value(reason="deadline") == 2
        g = reg.gauge("g.depth")
        g.set(7)
        g.set(3)
        assert g.value() == 3.0
        h = reg.histogram("h.lat", buckets=(0.01, 0.1, 1.0))
        for v in (0.005, 0.05, 0.5, 5.0):
            h.observe(v)
        hv = h.value()
        assert hv["counts"] == [1, 1, 1, 1]  # one per bucket + overflow
        assert hv["count"] == 4
        assert hv["sum"] == pytest.approx(5.555)

    def test_kind_conflict_raises(self):
        reg = obs_metrics.Registry()
        reg.counter("x")
        with pytest.raises(TypeError, match="already registered"):
            reg.gauge("x")

    def test_snapshot_is_json_clean(self):
        reg = obs_metrics.Registry()
        reg.counter("a").inc(site="s1")
        reg.histogram("b", buckets=(1.0,)).observe(0.5)
        snap = {
            name: m.snapshot() for name, m in reg._metrics.items()
        }
        parsed = json.loads(json.dumps(snap))
        assert parsed["a"]["series"][0]["labels"] == {"site": "s1"}
        assert parsed["b"]["series"][0]["value"]["buckets"] == [1.0]

    def test_event_bridge_counts_runtime_events(self):
        """The telemetry→metrics bridge folds well-known events into
        the standard registry without touching their emitters."""
        before = obs.counter("join.cap_overflows").value(stage="unit_t")
        shed_before = obs.counter("serve.requests_shed").value(
            reason="unit_reason"
        )
        telemetry.record("capacity_overflow", stage="unit_t", attempt=1)
        telemetry.record("serve_shed", reason="unit_reason")
        assert (
            obs.counter("join.cap_overflows").value(stage="unit_t")
            == before + 1
        )
        assert (
            obs.counter("serve.requests_shed").value(reason="unit_reason")
            == shed_before + 1
        )

    def test_prometheus_exposition(self):
        reg = obs_metrics.Registry()
        reg.counter("serve.requests_shed", "shed requests").inc(
            3, reason="deadline"
        )
        reg.gauge("queue.depth").set(2)
        reg.histogram("lat", buckets=(0.1, 1.0)).observe(0.05)
        snap = {
            name: m.snapshot() for name, m in reg._metrics.items()
        }
        text = obs.prometheus_text(snap)
        assert "# TYPE serve_requests_shed counter" in text
        assert "# HELP serve_requests_shed shed requests" in text
        assert 'serve_requests_shed{reason="deadline"} 3' in text
        assert "queue_depth 2.0" in text
        assert 'lat_bucket{le="0.1"} 1' in text
        assert 'lat_bucket{le="+Inf"} 1' in text
        assert "lat_count 1" in text
        assert text.endswith("\n")


# --------------------------------------------------------------- exporters


def _span_evt(name, trace, span_id, parent, seconds=0.25, **attrs):
    return {
        "event": "span", "seq": 0, "ts_mono": 100.0 + seconds,
        "name": name, "trace_id": trace, "span_id": span_id,
        "parent_id": parent, "seconds": seconds,
        "start_mono": 100.0, **attrs,
    }


class TestExport:
    def test_jsonl_roundtrip(self, tmp_path):
        events = [
            {"event": "a", "seq": 0, "ts_mono": 1.0, "x": 1},
            _span_evt("s", "t1", "a1", None),
        ]
        p = tmp_path / "trail.jsonl"
        # +1: write_jsonl opens the trail with an incarnation meta line
        # (the fleet_report stitching anchor)
        assert obs.write_jsonl(events, str(p)) == 3
        rows = obs.read_trail(str(p))
        assert rows[0]["event"] == "incarnation"
        assert rows[0]["incarnation"] == telemetry.INCARNATION
        assert rows[1:] == events
        # an already-stamped trail is NOT double-stamped on re-write
        assert obs.write_jsonl(rows, str(p)) == 3

    def test_read_trail_accepts_bench_artifact(self, tmp_path):
        stages = [{"event": "stream_stage", "stage": "x", "seconds": 1.0}]
        artifact = {"metric": "m", "value": 1, "detail": {"stages": stages}}
        p = tmp_path / "bench.json"
        p.write_text(json.dumps(artifact) + "\n")
        assert obs.read_trail(str(p)) == stages

    def test_chrome_trace_shape(self):
        events = [
            _span_evt("root", "t1", "a1", None, seconds=0.5),
            _span_evt("child", "t1", "b2", "a1", seconds=0.2),
            {"event": "transient_retry", "seq": 2, "ts_mono": 100.1,
             "trace_id": "t1", "span_id": "b2", "label": "x"},
        ]
        doc = obs.chrome_trace(events)
        json.loads(json.dumps(doc))  # loads cleanly
        evs = doc["traceEvents"]
        xs = [e for e in evs if e["ph"] == "X"]
        inst = [e for e in evs if e["ph"] == "i"]
        assert len(xs) == 2 and len(inst) == 1
        root = next(e for e in xs if e["name"] == "root")
        child = next(e for e in xs if e["name"] == "child")
        assert root["ts"] == pytest.approx(100.0 * 1e6)
        assert root["dur"] == pytest.approx(0.5 * 1e6)
        # same trace -> same timeline row; args carry the linkage
        assert root["tid"] == child["tid"] == inst[0]["tid"]
        assert child["args"]["parent_id"] == "a1"
        for e in xs:
            assert {"name", "ph", "ts", "dur", "pid", "tid"} <= set(e)

    def test_trace_summary_flags_orphans_and_roots(self):
        events = [
            _span_evt("root", "t1", "a1", None),
            _span_evt("ok_child", "t1", "b2", "a1"),
            _span_evt("orphan", "t1", "c3", "missing"),
            _span_evt("other_root", "t2", "d4", None),
        ]
        summ = obs.trace_summary(events)
        assert summ["t1"]["spans"] == 3
        assert summ["t1"]["roots"] == 1
        assert summ["t1"]["orphans"] == ["orphan"]
        assert summ["t2"]["roots"] == 1 and not summ["t2"]["orphans"]


# ------------------------------------------------- telemetry satellites


class _FormatProbe:
    def __init__(self):
        self.formats = 0

    def __repr__(self):
        self.formats += 1
        return "probe"

    __str__ = __repr__


class TestRecordHotPath:
    def test_disabled_logging_does_no_formatting(self):
        """With no sinks and the runtime logger quiet, record() must not
        format anything — and must NOT force-install a handler the way
        utils.get_logger does (the old hot-path tax)."""
        logger = logging.getLogger("mosaic_tpu.runtime")
        saved = (logger.level, logger.handlers[:])
        logger.handlers[:] = []
        logger.setLevel(logging.WARNING)
        try:
            probe = _FormatProbe()
            evt = telemetry.record("hot_path_unit", payload=probe)
            assert evt["payload"] is probe
            assert probe.formats == 0
            assert logger.handlers == []  # record() never configures it
            assert logger.level == logging.WARNING
        finally:
            logger.setLevel(saved[0])
            logger.handlers[:] = saved[1]

    def test_enabled_logging_still_formats(self):
        import io

        logger = logging.getLogger("mosaic_tpu.runtime")
        saved = (logger.level, logger.handlers[:], logger.propagate)
        buf = io.StringIO()
        logger.handlers[:] = [logging.StreamHandler(buf)]
        logger.setLevel(logging.INFO)
        logger.propagate = False
        try:
            probe = _FormatProbe()
            telemetry.record("hot_path_unit", payload=probe)
            assert probe.formats >= 1
            assert "hot_path_unit" in buf.getvalue()
        finally:
            logger.setLevel(saved[0])
            logger.handlers[:] = saved[1]
            logger.propagate = saved[2]

    def test_micro_benchmark_disabled_record_is_cheap(self):
        """20k no-sink, logging-off events well under a second — the
        guard keeps record() out of the formatting business entirely."""
        logger = logging.getLogger("mosaic_tpu.runtime")
        saved = logger.level
        logger.setLevel(logging.ERROR)
        try:
            t0 = time.perf_counter()
            for _ in range(20_000):
                telemetry.record("hot_path_bench", a=1, b="x")
            elapsed = time.perf_counter() - t0
        finally:
            logger.setLevel(saved)
        assert elapsed < 2.0, f"record() too slow: {elapsed:.3f}s / 20k"


class TestTimedErrorStamp:
    def test_exception_stamps_error_type_and_reraises(self):
        with telemetry.capture() as events:
            with pytest.raises(KeyError):
                with telemetry.timed("stage_unit", stage="s"):
                    raise KeyError("gone")
        (e,) = [x for x in events if x["event"] == "stage_unit"]
        assert e["error"] == "KeyError"
        assert e["seconds"] >= 0.0

    def test_success_has_no_error_field(self):
        with telemetry.capture() as events:
            with telemetry.timed("stage_unit", stage="s"):
                pass
        (e,) = [x for x in events if x["event"] == "stage_unit"]
        assert "error" not in e


class TestSummarizeNearestRank:
    """Exact nearest-rank (ceil(q*n)-1) values — the old banker's-
    rounding spelling drifted p50 at n=4 (to the 3rd value) and n=100
    (to the 51st)."""

    @pytest.mark.parametrize(
        "n,p50,p90,p99",
        [
            (1, 1.0, 1.0, 1.0),
            (2, 1.0, 2.0, 2.0),
            (3, 2.0, 3.0, 3.0),
            (10, 5.0, 9.0, 10.0),
            (100, 50.0, 90.0, 99.0),
        ],
    )
    def test_exact_ranks(self, n, p50, p90, p99):
        events = [
            {"event": "e", "seconds": float(v)} for v in range(1, n + 1)
        ]
        s = telemetry.summarize(events, event="e")
        assert s["count"] == n
        assert (s["p50"], s["p90"], s["p99"]) == (p50, p90, p99)
        assert s["max"] == float(n)

    def test_n4_regression_pin(self):
        # banker's rounding gave index round(1.5)=2 (the 3rd value);
        # nearest-rank gives ceil(2)-1=1 (the 2nd)
        s = telemetry.summarize(
            [{"event": "e", "seconds": float(v)} for v in (1, 2, 3, 4)],
            event="e",
        )
        assert s["p50"] == 2.0


# --------------------------------------------- durable stream: one trace


@pytest.fixture(scope="module")
def stream_setup():
    from mosaic_tpu.core.geometry import wkt
    from mosaic_tpu.core.index import CustomIndexSystem, GridConf
    from mosaic_tpu.core.tessellate import tessellate
    from mosaic_tpu.sql.join import build_chip_index
    from mosaic_tpu.sql.stream import StreamJoin, ring_from_host

    grid = CustomIndexSystem(GridConf(-180, 180, -90, 90, 2, 10.0, 10.0))
    col = wkt.from_wkt(["POLYGON ((1 1, 13 2, 12 11, 6 14, 2 9, 1 1))"])
    index = build_chip_index(
        tessellate(col, grid, 3, keep_core_geoms=False)
    )
    rng = np.random.default_rng(0)
    sj = StreamJoin(index, grid, 3, prefetch=True)
    ring = ring_from_host(
        [rng.uniform((-25, -25), (35, 20), (2048, 2)) for _ in range(3)]
    )
    return sj, ring


class TestDurableStreamTrace:
    def test_run_durable_is_one_connected_trace(self, stream_setup, tmp_path):
        sj, ring = stream_setup
        with telemetry.capture() as events:
            sj.run_durable(ring, 6, run_dir=str(tmp_path), snapshot_every=2)
        spans = [e for e in events if e["event"] == "span"]
        summ = obs.trace_summary(events)
        assert len(summ) == 1, summ
        ((tid, t),) = summ.items()
        assert t["roots"] == 1 and not t["orphans"], t
        names = t["names"]
        assert "stream.durable_run" in names
        assert names.count("stream.segment") == 3
        assert names.count("stream.snapshot") == 3
        # snapshot_saved events attach to their snapshot span's trace
        saved = [e for e in events if e["event"] == "snapshot_saved"]
        assert saved and all(e["trace_id"] == tid for e in saved)
        root = next(
            s for s in spans if s["name"] == "stream.durable_run"
        )
        segs = [s for s in spans if s["name"] == "stream.segment"]
        assert all(s["parent_id"] == root["span_id"] for s in segs)

    def test_kill_and_resume_join_one_trace(self, stream_setup, tmp_path):
        """A killed durable run and its resume read as ONE trace: the
        resume's root parents to the interrupted run's root (persisted
        through the snapshot sidecar), and the stats stay bit-identical
        to the clean run."""
        sj, ring = stream_setup
        clean = sj.run(ring, 9)
        d = str(tmp_path / "run")
        with telemetry.capture() as events:
            with pytest.raises(RuntimeError):
                with faults.inject(
                    fail_first=99, skip_first=2,
                    sites=("stream.scan_step",),
                    exc_factory=lambda s: RuntimeError("device loss"),
                ):
                    sj.run_durable(ring, 9, run_dir=d, snapshot_every=2)
            r = sj.resume(d, ring)
        assert (r.checksum, r.matches, r.overflow) == (
            clean.checksum, clean.matches, clean.overflow
        )
        roots = [
            e for e in events
            if e["event"] == "span" and e["name"] == "stream.durable_run"
        ]
        assert len(roots) == 2
        killed, resumed = roots
        assert killed["error"] == "RuntimeError"
        assert resumed["trace_id"] == killed["trace_id"]
        assert resumed["parent_id"] == killed["span_id"]
        assert resumed["resumed_from"] == 4
        summ = obs.trace_summary(events)
        assert len(summ) == 1
        (t,) = summ.values()
        assert t["roots"] == 1 and not t["orphans"], t


# ----------------------------------------------------------- trace report


def _mk_trail(tmp_path, name, stages):
    """stages: {stage_name: (seconds, count)} -> trail file path."""
    events = []
    for stage, (seconds, count) in stages.items():
        for _ in range(count):
            events.append({
                "event": "bench_stage", "stage": stage,
                "seconds": seconds / count, "seq": 0, "ts_mono": 0.0,
            })
    p = tmp_path / name
    obs.write_jsonl(events, str(p))
    return str(p)


BASE_STAGES = {
    "compile": (4.0, 2),
    "join_loop": (2.0, 2),
    "dispatch": (0.5, 10),
}


class TestTraceReport:
    def test_stage_keys(self):
        import trace_report

        assert trace_report.stage_key(
            {"event": "stream_stage", "stage": "x", "seconds": 1.0}
        ) == "stream_stage.x"
        assert trace_report.stage_key(
            {"event": "span", "name": "serve.request", "seconds": 1.0}
        ) == "span.serve.request"
        assert trace_report.stage_key(
            {"event": "serve_request", "seconds": 1.0}
        ) == "serve_request"
        assert trace_report.stage_key({"event": "no_seconds"}) is None

    def test_cli_report_and_diff(self, tmp_path, monkeypatch, capsys):
        import trace_report

        a = _mk_trail(tmp_path, "a.jsonl", BASE_STAGES)
        b = _mk_trail(tmp_path, "b.jsonl", {
            k: (s * 2 if k == "compile" else s, c)
            for k, (s, c) in BASE_STAGES.items()
        })
        monkeypatch.setattr(sys, "argv", ["trace_report.py", a])
        trace_report.main()
        out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert out["metric"] == "trace_report"
        assert out["stages"]["bench_stage.compile"]["count"] == 2
        assert sum(
            s["share"] for s in out["stages"].values()
        ) == pytest.approx(1.0, abs=0.01)
        monkeypatch.setattr(
            sys, "argv", ["trace_report.py", b, "--against", a]
        )
        trace_report.main()
        out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        d = out["diff"]["bench_stage.compile"]
        assert d["total_ratio"] == pytest.approx(2.0, abs=0.01)
        assert d["share_delta"] > 0

    def test_diff_tolerates_one_sided_stages(self, tmp_path, monkeypatch,
                                             capsys):
        """New lanes (e.g. the adaptive probe's probe_stage.* keys) diff
        cleanly against a historical trail that never emitted them: no
        throw, null deltas, and an explicit only_in tag each way."""
        import trace_report

        old = _mk_trail(tmp_path, "old.jsonl", BASE_STAGES)
        new = _mk_trail(tmp_path, "new.jsonl", {
            **BASE_STAGES,
            "probe_light": (0.2, 1),
            "probe_heavy": (0.4, 1),
        })
        monkeypatch.setattr(
            sys, "argv", ["trace_report.py", new, "--against", old]
        )
        trace_report.main()
        out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        d = out["diff"]["bench_stage.probe_heavy"]
        assert d["only_in"] == "fresh"
        assert d["share_delta"] is None and d["total_ratio"] is None
        # and the reverse direction: a stage that vanished
        monkeypatch.setattr(
            sys, "argv", ["trace_report.py", old, "--against", new]
        )
        trace_report.main()
        out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        d = out["diff"]["bench_stage.probe_heavy"]
        assert d["only_in"] == "base"
        assert out["diff"]["bench_stage.compile"].get("only_in") is None

    def test_diff_against_summary_only_artifact(self, tmp_path,
                                                monkeypatch, capsys):
        """A bench artifact whose detail.stages is a DICT of per-stage
        summaries ({stage_key: {total_s, count}}) must yield a real base
        breakdown, not a silently-empty one."""
        import trace_report

        fresh = _mk_trail(tmp_path, "fresh.jsonl", BASE_STAGES)
        art = tmp_path / "hist.json"
        art.write_text(json.dumps({
            "metric": "m", "value": 1,
            "detail": {"stages": {
                "bench_stage.compile": {"total_s": 2.0, "count": 2},
            }},
        }) + "\n")
        monkeypatch.setattr(
            sys, "argv", ["trace_report.py", fresh, "--against", str(art)]
        )
        trace_report.main()
        out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        d = out["diff"]["bench_stage.compile"]
        assert d.get("only_in") is None
        assert d["total_ratio"] == pytest.approx(2.0, abs=0.01)

    def test_stage_key_skips_non_dict_and_non_numeric(self):
        import trace_report

        assert trace_report.stage_key("bench_stage.seconds") is None
        assert trace_report.stage_key({"seconds": None}) is None
        assert trace_report.stage_key(
            {"stage_key": "x", "seconds": 1.0}
        ) == "x"


# ------------------------------------- prometheus label-value escaping


_LABEL_RE = re.compile(r'="((?:[^"\\]|\\.)*)"')


def _unescape_label(v: str) -> str:
    # inverse of the exposition-format escaping, applied left to right
    out, i = [], 0
    while i < len(v):
        c = v[i]
        if c == "\\" and i + 1 < len(v):
            nxt = v[i + 1]
            out.append({"\\": "\\", '"': '"', "n": "\n"}.get(nxt, nxt))
            i += 2
        else:
            out.append(c)
            i += 1
    return "".join(out)


class TestPrometheusLabelEscaping:
    HOSTILE = [
        'a\\b"c\nd',                      # all three escapes at once
        "C:\\temp\\trail.jsonl",          # windows path (backslashes)
        'say "hi"',                       # embedded quotes
        "line1\nline2",                   # embedded newline
        "\\n",                            # literal backslash-n, NOT \n
        'trailing\\',                     # trailing backslash
    ]

    @pytest.mark.parametrize("value", HOSTILE)
    def test_hostile_value_round_trips(self, value):
        reg = obs_metrics.Registry()
        reg.counter("hostile").inc(site=value)
        snap = {n: m.snapshot() for n, m in reg._metrics.items()}
        text = obs.prometheus_text(snap)
        line = next(
            ln for ln in text.splitlines() if ln.startswith("hostile{")
        )
        # exactly one series line, one value capture, lossless inverse
        (escaped,) = _LABEL_RE.findall(line)
        assert "\n" not in line
        assert _unescape_label(escaped) == value

    def test_distinct_hostile_values_stay_distinct(self):
        # the raw f-string rendering collapsed 'a\nb' and 'a\\nb' into
        # ambiguous text; escaped rendering must keep them apart
        reg = obs_metrics.Registry()
        reg.counter("h2").inc(site="a\nb")
        reg.counter("h2").inc(2, site="a\\nb")
        snap = {n: m.snapshot() for n, m in reg._metrics.items()}
        text = obs.prometheus_text(snap)
        lines = [
            ln for ln in text.splitlines() if ln.startswith("h2{")
        ]
        assert len(lines) == 2
        vals = {
            _unescape_label(_LABEL_RE.findall(ln)[0]) for ln in lines
        }
        assert vals == {"a\nb", "a\\nb"}


# ------------------------------------------ chrome trace class tracks


class TestChromeTraceClassTracks:
    def test_classified_spans_land_on_named_tracks(self):
        events = [
            _span_evt(
                "dispatch.transfer.h2d", "t1", "a1", None,
                seconds=0.1, nbytes=4096,
            ),
            _span_evt("stream.segment", "t1", "b2", None, seconds=0.5),
            {"event": "serve_stage", "stage": "queue_wait",
             "seconds": 0.02, "ts_mono": 100.5, "seq": 3,
             "trace_id": "t1"},
        ]
        doc = obs.chrome_trace(events)
        evs = doc["traceEvents"]
        track = [e for e in evs if e.get("cat") == "mosaic.timeline"]
        # transfer span + queue_wait interval get track rows; the
        # device-class segment stays on its trace row only
        assert {e["args"]["class"] for e in track} == {
            "transfer", "queue_wait",
        }
        xfer = next(e for e in track if e["args"]["class"] == "transfer")
        assert xfer["ph"] == "X" and xfer["tid"] == 1002
        qw = next(e for e in track if e["args"]["class"] == "queue_wait")
        assert qw["ph"] == "X" and qw["tid"] == 1003
        # the flat interval is anchored at ts_mono - seconds
        assert qw["ts"] == pytest.approx((100.5 - 0.02) * 1e6)
        names = {
            (e["tid"], e["args"]["name"]) for e in evs
            if e.get("ph") == "M" and e["name"] == "thread_name"
        }
        assert (1002, "mosaic:transfer") in names
        assert (1003, "mosaic:queue_wait") in names
        # the original trace rows are still intact alongside
        assert any(
            e["ph"] == "X" and e.get("cat") == "mosaic"
            and e["name"] == "dispatch.transfer.h2d"
            for e in evs
        )
        json.loads(json.dumps(doc))

    def test_unclassified_trails_emit_no_tracks(self):
        doc = obs.chrome_trace(
            [_span_evt("custom.thing", "t1", "a1", None)]
        )
        assert not [
            e for e in doc["traceEvents"]
            if e.get("cat") == "mosaic.timeline" or e.get("ph") == "M"
        ]
