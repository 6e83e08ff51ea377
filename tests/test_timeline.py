"""Timeline attribution: interval reconstruction, the priority sweep's
exact-partition arithmetic, stall classification, and the stall_report
CLI over a REAL durable stream run.

The load-bearing invariant everything downstream trusts
(`tools/stall_report.py`'s ``sum_ok``, the CI ±5% lane): `flatten` is
a PARTITION — every instant of the window has exactly one owner class,
so the per-class seconds sum to the wall exactly, whatever the input
intervals overlap like.
"""

from __future__ import annotations

import json
import pathlib
import sys

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "tools"))

from mosaic_tpu.obs import timeline
from mosaic_tpu.runtime import telemetry


def _span(name, start, seconds, seq=0, **attrs):
    return {
        "event": "span", "name": name, "start_mono": start,
        "seconds": seconds, "seq": seq, "ts_mono": start + seconds,
        **attrs,
    }


class TestKeysAndClasses:
    def test_event_key_conventions(self):
        assert timeline.event_key(
            {"event": "span", "name": "stream.segment", "seconds": 1}
        ) == "span.stream.segment"
        assert timeline.event_key(
            {"event": "serve_stage", "stage": "queue_wait", "seconds": 1}
        ) == "serve_stage.queue_wait"
        assert timeline.event_key(
            {"stage_key": "span.x", "seconds": 1}
        ) == "span.x"
        assert timeline.event_key(
            {"event": "recheck_narrow", "seconds": 0.1}
        ) == "recheck_narrow"
        assert timeline.event_key({"event": "snapshot_saved"}) is None

    @pytest.mark.parametrize("key,cls", [
        ("span.dispatch.transfer.h2d", "transfer"),
        ("span.dispatch.transfer.d2h", "transfer"),
        ("span.stream.ring_build", "transfer"),
        ("span.dispatch.compile", "compile"),
        ("stream_stage.compile", "compile"),
        ("stream_stage.gen_compile", "compile"),
        ("serve_stage.queue_wait", "queue_wait"),
        ("span.stream.snapshot", "host_callback"),
        ("span.raster.snapshot", "host_callback"),
        ("span.stream.segment", "device"),
        ("span.stream.pull", "device"),
        ("span.serve.linger", "queue_wait"),
        ("span.serve.concat", "host_callback"),
        ("span.serve.pad", "host_callback"),
        ("span.serve.deliver", "host_callback"),
        ("span.dispatch.guard.handoff", "host_callback"),
        ("span.dispatch.launch", "host_callback"),
        ("span.stream.launch", "host_callback"),
        ("span.join.probe.scatter", "device"),
        ("span.join.pip", "device"),
        ("span.join.put", "transfer"),
        ("span.join.put_shifted", "transfer"),
        ("span.join.pull", "transfer"),
        ("span.join.counts", "transfer"),
        ("span.join.cells", "host_callback"),
        ("span.join.launch", "host_callback"),
        ("span.join.counts_launch", "host_callback"),
        ("span.join.shift", "host_callback"),
        ("span.join.recheck.band", "host_callback"),
        ("span.join.recheck.host", "host_callback"),
        ("probe_stage.heavy", "device"),
        ("raster_stage.zonal", "device"),
        ("span.stream.pipeline.drain", "device"),
        ("stream_stage.pipeline_drain", "device"),
        ("span.stream.pipeline.flush", "host_callback"),
        ("stream_stage.pipeline_flush", "host_callback"),
    ])
    def test_classifier_table(self, key, cls):
        assert timeline.classify_key(key) == cls

    def test_containers_and_unknowns_stay_unclassified(self):
        for key in (
            "span.stream.durable_run", "stream_stage.durable_loop",
            "span.serve.request",
            "stream_stage.single_batch", "no_such_key", None,
            # host intervals around a dispatch: containers of the spans
            # that say what the host did, not device time
            "span.serve.dispatch", "span.serve.batch",
            "serve_stage.dispatch", "serve_stage.batch",
            "span.serve.wait",
        ):
            assert timeline.classify_key(key) is None


class TestIntervals:
    def test_span_uses_start_mono(self):
        iv = timeline.interval_of(_span("x", 10.0, 2.5))
        assert iv == (10.0, 12.5)

    def test_flat_timed_event_ends_at_ts_mono(self):
        iv = timeline.interval_of(
            {"event": "serve_stage", "stage": "queue_wait",
             "seconds": 0.5, "ts_mono": 4.0}
        )
        assert iv == (3.5, 4.0)

    def test_instants_and_negative_seconds_are_skipped(self):
        assert timeline.interval_of({"event": "x", "ts_mono": 1.0}) is None
        assert timeline.interval_of(
            {"event": "x", "seconds": -1, "ts_mono": 1.0}
        ) is None


class TestFlattenPartition:
    def test_partition_sums_to_window_exactly(self):
        evts = [
            _span("stream.segment", 0.0, 1.0, seq=1),
            _span("dispatch.transfer.h2d", 0.4, 0.2, seq=2),
            _span("stream.snapshot", 1.1, 0.3, seq=3),
        ]
        segs = timeline.flatten(timeline.intervals(evts), (0.0, 2.0))
        total = sum(s["end"] - s["start"] for s in segs)
        assert total == pytest.approx(2.0, abs=1e-9)
        by_cls = {}
        for s in segs:
            by_cls[s["cls"]] = by_cls.get(s["cls"], 0.0) + (
                s["end"] - s["start"]
            )
        # transfer outranks the device span it nests inside
        assert by_cls["transfer"] == pytest.approx(0.2)
        assert by_cls["device"] == pytest.approx(0.8)
        assert by_cls["host_callback"] == pytest.approx(0.3)
        assert by_cls["idle"] == pytest.approx(0.7)

    def test_priority_order_under_total_overlap(self):
        evts = [
            _span("stream.segment", 0.0, 1.0, seq=1),
            _span("stream.snapshot", 0.0, 1.0, seq=2),
            _span("dispatch.transfer.h2d", 0.0, 1.0, seq=3),
            _span("dispatch.compile", 0.0, 1.0, seq=4),
        ]
        segs = timeline.flatten(timeline.intervals(evts), (0.0, 1.0))
        assert len(segs) == 1 and segs[0]["cls"] == "compile"

    def test_intervals_clip_to_window(self):
        evts = [_span("stream.segment", -1.0, 4.0)]
        segs = timeline.flatten(timeline.intervals(evts), (0.0, 2.0))
        assert segs == [{"start": 0.0, "end": 2.0, "cls": "device"}]

    def test_empty_window_returns_nothing(self):
        assert timeline.flatten([], (1.0, 1.0)) == []


class TestAttribute:
    def test_durable_loop_event_picks_the_window(self):
        evts = [
            _span("stream.segment", 0.5, 1.0, seq=1),
            {"event": "stream_stage", "stage": "durable_loop",
             "seconds": 2.0, "ts_mono": 2.0, "seq": 2},
        ]
        rep = timeline.attribute(evts)
        assert rep["window"]["source"] == "stream_stage.durable_loop"
        assert rep["wall_s"] == pytest.approx(2.0)
        assert rep["sum_s"] == pytest.approx(rep["wall_s"], abs=1e-6)
        assert rep["classes"]["device"]["seconds"] == pytest.approx(1.0)
        assert rep["classes"]["idle"]["seconds"] == pytest.approx(1.0)

    def test_envelope_fallback_without_loop_events(self):
        evts = [
            _span("stream.segment", 1.0, 0.5, seq=1),
            _span("stream.segment", 2.0, 0.5, seq=2),
        ]
        rep = timeline.attribute(evts)
        assert rep["window"]["source"] == "envelope"
        assert rep["wall_s"] == pytest.approx(1.5)
        assert rep["classes"]["idle"]["seconds"] == pytest.approx(0.5)

    def test_device_time_of_a_serve_dispatch_comes_from_the_trace(self):
        """A serve dispatch's host interval is a container; the chip's
        own intervals (from a trace, on the monotonic clock) are the
        device class, and the host pieces keep their own."""
        evts = [
            _span("serve.dispatch", 1.0, 1.0, seq=1),
            _span("dispatch.transfer.h2d", 1.0, 0.1, seq=2),
            _span("dispatch.launch", 1.1, 0.2, seq=3),
            _span("dispatch.transfer.d2h", 1.3, 0.6, seq=4),
        ]
        rep = timeline.attribute(evts, window=(1.0, 2.0))
        assert rep["classes"]["device"]["seconds"] == 0.0
        rep = timeline.attribute(
            evts, window=(1.0, 2.0), device_intervals=[(1.15, 1.25), (1.9, 2.5)]
        )
        c = {k: v["seconds"] for k, v in rep["classes"].items()}
        # 1.15-1.25 lies under the launch (host_callback outranks
        # device); the pull (transfer) ends at 1.9, so device owns 1.9-2.0
        assert c["transfer"] == pytest.approx(0.1 + 0.6)
        assert c["host_callback"] == pytest.approx(0.2)
        assert c["device"] == pytest.approx(0.1)
        assert rep["sum_s"] == pytest.approx(1.0, abs=1e-6)

    def test_no_intervals_returns_none(self):
        assert timeline.attribute([{"event": "x", "ts_mono": 1.0}]) is None


class TestTracks:
    def test_tracks_merge_and_gap(self):
        evts = [
            _span("stream.segment", 0.0, 1.0, seq=1),
            _span("stream.segment", 1.5, 1.0, seq=2),
            _span("stream.segment", 1.6, 0.2, seq=3),
        ]
        tr = timeline.build_tracks(evts)["span.stream.segment"]
        assert tr["count"] == 3
        assert tr["intervals"] == [(0.0, 1.0), (1.5, 2.5)]
        assert tr["busy_s"] == pytest.approx(2.0)
        assert tr["gap_s"] == pytest.approx(0.5)

    def test_overlap_measures_pipeline_hiding(self):
        a = [(0.0, 1.0), (2.0, 3.0)]
        b = [(0.5, 2.5)]
        assert timeline.overlap_s(a, b) == pytest.approx(1.0)
        assert timeline.overlap_s(a, [(5.0, 6.0)]) == 0.0


class TestOverlappedTimelines:
    """The pipelined executor's claim as interval arithmetic: snapshot
    ``host_callback`` intervals that genuinely OVERLAP ``device``
    intervals (the writer thread runs while the next segments compute)
    must still flatten to an exact partition, and the pinned
    ``overlap_fraction`` helper turns "off the critical path" into a
    number the bench and CI lanes can gate."""

    def test_overlapping_snapshot_partition_still_exact(self):
        # device busy 0..2 (two back-to-back segments); the async
        # snapshot write covers 0.5..1.5 ENTIRELY inside device time —
        # the pipelined shape a synchronous loop can never produce
        evts = [
            _span("stream.segment", 0.0, 1.0, seq=1),
            _span("stream.segment", 1.0, 1.0, seq=2),
            _span("stream.snapshot", 0.5, 1.0, seq=3, mode="async"),
        ]
        segs = timeline.flatten(timeline.intervals(evts), (0.0, 2.0))
        total = sum(s["end"] - s["start"] for s in segs)
        assert total == pytest.approx(2.0, abs=1e-9)
        by_cls = {}
        for s in segs:
            by_cls[s["cls"]] = by_cls.get(s["cls"], 0.0) + (
                s["end"] - s["start"]
            )
        # host_callback outranks device for the overlapped second;
        # nothing is double-counted and nothing leaks to idle
        assert by_cls["host_callback"] == pytest.approx(1.0)
        assert by_cls["device"] == pytest.approx(1.0)
        assert "idle" not in by_cls

    def test_drain_and_flush_classes_sweep_exactly(self):
        # drain (device: the window's one blocking pull) overlapping
        # the writer's flush barrier (host_callback) at the run tail
        evts = [
            _span("stream.pipeline.drain", 0.0, 1.0, seq=1),
            _span("stream.pipeline.flush", 0.8, 0.6, seq=2),
        ]
        segs = timeline.flatten(timeline.intervals(evts), (0.0, 1.5))
        total = sum(s["end"] - s["start"] for s in segs)
        assert total == pytest.approx(1.5, abs=1e-9)
        by_cls = {
            s["cls"]: sum(
                x["end"] - x["start"] for x in segs
                if x["cls"] == s["cls"]
            )
            for s in segs
        }
        assert by_cls["device"] == pytest.approx(0.8)
        assert by_cls["host_callback"] == pytest.approx(0.6)
        assert by_cls.get("idle", 0.1) == pytest.approx(0.1)

    def test_overlap_fraction_pinned(self):
        dev = [(0.0, 1.0), (2.0, 3.0)]
        # fully hidden under device -> 1.0
        assert timeline.overlap_fraction([(0.2, 0.8)], dev) == 1.0
        # serialized after device (the synchronous loop) -> 0.0
        assert timeline.overlap_fraction([(1.0, 2.0)], dev) == 0.0
        # half in, half out
        assert timeline.overlap_fraction(
            [(0.5, 1.5)], dev
        ) == pytest.approx(0.5)
        # empty snapshot track -> 0.0, never a ZeroDivisionError
        assert timeline.overlap_fraction([], dev) == 0.0

    def test_pipelined_run_emits_drain_intervals(self, tmp_path):
        from mosaic_tpu.core.geometry import wkt
        from mosaic_tpu.core.index import CustomIndexSystem, GridConf
        from mosaic_tpu.core.tessellate import tessellate
        from mosaic_tpu.sql.join import build_chip_index
        from mosaic_tpu.sql.stream import StreamJoin, ring_from_host

        grid = CustomIndexSystem(
            GridConf(-180, 180, -90, 90, 2, 10.0, 10.0)
        )
        col = wkt.from_wkt(
            ["POLYGON ((1 1, 13 2, 12 11, 6 14, 2 9, 1 1))"]
        )
        index = build_chip_index(
            tessellate(col, grid, 3, keep_core_geoms=False)
        )
        rng = np.random.default_rng(0)
        sj = StreamJoin(index, grid, 3, prefetch=True)
        ring = ring_from_host(
            [rng.uniform((-25, -25), (35, 20), (2048, 2))
             for _ in range(3)]
        )
        with telemetry.capture() as events:
            sj.run_durable(
                ring, 6, run_dir=str(tmp_path), snapshot_every=2,
                pipeline=True,
            )
        rep = timeline.attribute(events)
        assert rep["window"]["source"] == "stream_stage.durable_loop"
        # the partition invariant holds for a REAL overlapped trail
        # (writer-thread snapshot spans + main-thread drain spans)
        assert abs(rep["sum_s"] - rep["wall_s"]) <= 0.05 * rep["wall_s"]
        tracks = timeline.build_tracks(events)
        assert "span.stream.pipeline.drain" in tracks
        assert tracks["span.stream.pipeline.drain"]["count"] == 3
        assert "span.stream.snapshot" in tracks
        # the helper runs end to end on real tracks (the value itself
        # is timing-dependent on CPU; the bench pins the A/B claim)
        frac = timeline.overlap_fraction(
            tracks["span.stream.snapshot"]["intervals"],
            tracks["span.stream.pipeline.drain"]["intervals"]
            + tracks["span.stream.segment"]["intervals"],
        )
        assert 0.0 <= frac <= 1.0


# ------------------------------------------------ real durable stream


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


class TestRealDurableRunAttribution:
    def test_attribution_partitions_a_real_run(
        self, stream_setup, tmp_path
    ):
        sj, ring = stream_setup
        with telemetry.capture() as events:
            sj.run_durable(
                ring, 6, run_dir=str(tmp_path), snapshot_every=2
            )
        rep = timeline.attribute(events)
        assert rep["window"]["source"] == "stream_stage.durable_loop"
        assert abs(rep["sum_s"] - rep["wall_s"]) <= 0.05 * rep["wall_s"]
        # segments dominate a healthy CPU run; the snapshot D2H spans
        # (prefetch=True pulls cells) show up as transfer time
        assert rep["classes"]["device"]["seconds"] > 0
        assert rep["classes"]["transfer"]["seconds"] > 0
        assert rep["classes"]["host_callback"]["seconds"] > 0
        tracks = timeline.build_tracks(events)
        assert "span.stream.segment" in tracks
        assert tracks["span.stream.segment"]["count"] == 3
        assert "span.dispatch.transfer.d2h" in tracks

    def test_stall_report_cli_on_a_real_trail(
        self, stream_setup, tmp_path, monkeypatch, capsys
    ):
        import stall_report

        from mosaic_tpu.obs import export

        sj, ring = stream_setup
        with telemetry.capture() as events:
            sj.run_durable(
                ring, 6, run_dir=str(tmp_path / "run"), snapshot_every=2
            )
            # a single-batch rate, recorded by the caller
            telemetry.record(
                "stream_stage", stage="single_batch", seconds=0.001,
                batch=2048, points_per_sec=2048 / 0.001,
            )
        trail = str(tmp_path / "t.jsonl")
        export.write_jsonl(events, trail)
        out = str(tmp_path / "stall.json")
        monkeypatch.setattr(
            "sys.argv", ["stall_report.py", trail, "--out", out]
        )
        assert stall_report.main() == 0
        last = capsys.readouterr().out.strip().splitlines()[-1]
        rep = json.loads(last)
        assert rep["metric"] == "stall_report"
        assert rep["sum_ok"] is True
        assert rep["loss"]["sustained_frac"] > 0
        lc = rep["loss"]["loss_classes"]
        assert abs(
            sum(lc.values()) + rep["loss"]["ideal_s"] - rep["wall_s"]
        ) <= 0.05 * rep["wall_s"]
        with open(out) as f:
            assert json.load(f)["metric"] == "stall_report"

    def test_injected_slowdown_lands_in_the_right_class(
        self, stream_setup, tmp_path, monkeypatch, capsys
    ):
        import stall_report

        from mosaic_tpu.obs import export

        sj, ring = stream_setup
        with telemetry.capture() as events:
            sj.run_durable(
                ring, 6, run_dir=str(tmp_path / "run"), snapshot_every=2
            )
        trail = str(tmp_path / "t.jsonl")
        export.write_jsonl(events, trail)

        def run(extra):
            monkeypatch.setattr(
                "sys.argv", ["stall_report.py", trail, *extra]
            )
            assert stall_report.main() == 0
            return json.loads(
                capsys.readouterr().out.strip().splitlines()[-1]
            )

        base = run([])
        slow = run(["--inject-slowdown", "span.stream.snapshot:25"])
        b = base["classes"]["host_callback"]
        s = slow["classes"]["host_callback"]
        # the stall must grow in ITS class: 5x the seconds, or — on a
        # warm tiny window — saturate most of the wall
        assert (
            s["seconds"] > 5 * max(b["seconds"], 1e-9)
            or s["share"] > 0.6
        ), (b, s)
        assert s["share"] > b["share"], (b, s)
        assert slow["sum_ok"] is True

    def test_diff_against_itself_is_zero(
        self, stream_setup, tmp_path, monkeypatch, capsys
    ):
        import stall_report

        from mosaic_tpu.obs import export

        sj, ring = stream_setup
        with telemetry.capture() as events:
            sj.run_durable(
                ring, 6, run_dir=str(tmp_path / "run"), snapshot_every=3
            )
        trail = str(tmp_path / "t.jsonl")
        export.write_jsonl(events, trail)
        monkeypatch.setattr(
            "sys.argv", ["stall_report.py", trail, "--against", trail]
        )
        assert stall_report.main() == 0
        rep = json.loads(
            capsys.readouterr().out.strip().splitlines()[-1]
        )
        assert all(
            v["seconds"] == 0 and v["share"] == 0
            for v in rep["diff"].values()
        )


# ------------------------------- the chip's own intervals from a trace

SERVE_FIXTURE = (
    pathlib.Path(__file__).resolve().parent.parent
    / "benchmark" / "fixtures" / "taxi_serve_v5e"
)


class TestDeviceClassFromATrace:
    """A serve trail recorded on the chip (the benchmark's fixture: the
    program's span events and the profiler trace of the same window)."""

    @pytest.fixture(scope="class")
    def recorded(self):
        import gzip

        from mosaic_tpu.obs import trace

        runs = trace.device_intervals(str(SERVE_FIXTURE / "trace.xplane.pb.gz"))
        lo, hi = runs[0][0] - 0.001, runs[-1][1] + 0.001
        with gzip.open(SERVE_FIXTURE / "events.jsonl.gz", "rt") as f:
            events = [json.loads(line) for line in f]
        inside = []
        for e in events:
            iv = timeline.interval_of(e)
            if iv is not None and lo <= iv[0] and iv[1] <= hi:
                inside.append(e)
        return runs, inside

    def test_module_runs_land_inside_their_dispatch_spans(self, recorded):
        """One clock: placed by the annotations' ``t``, every module run
        of the trace lies inside a ``serve.dispatch`` span of the trail
        (their two clocks agree within a millisecond)."""
        runs, events = recorded
        assert len(runs) == 46 and all(b > a for a, b in runs)
        dispatches = [
            timeline.interval_of(e) for e in events
            if e.get("name") == "serve.dispatch"
        ]
        assert len(dispatches) >= 20
        homeless = [
            r for r in runs
            if not any(a - 1e-3 <= r[0] and r[1] <= b + 1e-3
                       for a, b in dispatches)
        ]
        # two programs a dispatch: the first and the last pair belong to
        # dispatches whose spans the window cut
        assert len(homeless) <= 4

    def test_stall_report_xplane_gives_a_serve_trail_its_device_class(
        self, recorded, tmp_path, monkeypatch, capsys
    ):
        import stall_report

        from mosaic_tpu.obs import export

        _runs, events = recorded
        trail = str(tmp_path / "serve.jsonl")
        export.write_jsonl(events, trail)
        reps = []
        for extra in ([], ["--xplane", str(SERVE_FIXTURE / "trace.xplane.pb.gz")]):
            monkeypatch.setattr(
                "sys.argv", ["stall_report.py", trail, *extra]
            )
            assert stall_report.main() == 0
            reps.append(json.loads(
                capsys.readouterr().out.strip().splitlines()[-1]
            ))
        host_only, with_trace = reps
        # the host spans of a dispatch only bound the chip's work
        assert host_only["classes"]["device"]["seconds"] == 0.0
        assert with_trace["classes"]["device"]["seconds"] > 0.0
        assert with_trace["sum_ok"] is True
        # what the device class gained, idle lost: the other classes
        # outrank it and keep their time
        for c in ("transfer", "queue_wait", "host_callback"):
            assert with_trace["classes"][c] == host_only["classes"][c]

    def test_a_trace_without_program_annotations_places_nothing(self):
        from mosaic_tpu.obs import trace

        stream = SERVE_FIXTURE.parent / "taxi_stream_v5e.xplane.pb.gz"
        assert trace.device_intervals(str(stream)) == []


# -------------------------------------------- seg-loop compile hoist


def _fresh_stream(found_cap):
    """A NOVEL static spec (unique found_cap) so the process-wide
    stream_programs cache misses and the seg_loop is genuinely cold."""
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
    rng = np.random.default_rng(1)
    sj = StreamJoin(index, grid, 3, prefetch=True, found_cap=found_cap)
    ring = ring_from_host(
        [rng.uniform((-25, -25), (35, 20), (512, 2)) for _ in range(3)]
    )
    return sj, ring


class TestSegLoopCompileHoist:
    """Satellite of ISSUE 13: a round-12 CPU run booked 1.95 s of a 2.28 s
    durable run inside stream.segment[0] — the seg_loop trace+compile,
    misattributed as device time. The hoist compiles BEFORE the segment
    loop under a ``dispatch.compile`` span, so segment[0]'s device
    excess collapses to actual replay time."""

    def test_segment0_compile_hoisted(self, tmp_path):
        sj, ring = _fresh_stream(found_cap=251)
        with telemetry.capture() as events:
            sj.run_durable(
                ring, 5, run_dir=str(tmp_path), snapshot_every=2
            )
        spans = [e for e in events if e["event"] == "span"]
        comp = [
            e for e in spans
            if e["name"] == "dispatch.compile"
            and e.get("site") == "stream.seg_loop"
        ]
        assert len(comp) == 1
        assert comp[0]["backend_compiles"] >= 1
        # both static nb signatures warmed: snapshot_every=2 and the
        # tail remainder 1
        assert comp[0]["sizes"] == "[1, 2]"
        segs = sorted(
            (e for e in spans if e["name"] == "stream.segment"),
            key=lambda e: e["start_mono"],
        )
        assert segs
        # the compile ended before segment[0] began ...
        assert comp[0]["ts_mono"] <= segs[0]["start_mono"] + 1e-6
        # ... and segment[0] is now pure replay: its wall is a fraction
        # of the compile it used to contain
        assert segs[0]["seconds"] < comp[0]["seconds"]
        # timeline classifies the hoisted span as compile
        assert (
            timeline.classify_key("span.dispatch.compile") == "compile"
        )
        # second run on the same stream: everything warm, no new
        # compile span, bit-identical stats
        with telemetry.capture() as ev2:
            sj.run_durable(
                ring, 5, run_dir=str(tmp_path / "b"), snapshot_every=2
            )
        assert not [
            e for e in ev2
            if e["event"] == "span" and e["name"] == "dispatch.compile"
        ]

    def test_warmup_knob_disables_hoist(self, tmp_path, monkeypatch):
        monkeypatch.setenv("MOSAIC_STREAM_NO_SEG_WARMUP", "1")
        sj, ring = _fresh_stream(found_cap=253)
        with telemetry.capture() as events:
            res = sj.run_durable(
                ring, 4, run_dir=str(tmp_path), snapshot_every=2
            )
        assert not [
            e for e in events
            if e["event"] == "span" and e["name"] == "dispatch.compile"
            and e.get("site") == "stream.seg_loop"
        ]
        # and the run itself still converges (compile just lands back
        # inside segment[0], as before the hoist)
        monkeypatch.delenv("MOSAIC_STREAM_NO_SEG_WARMUP")
        want = sj.run(ring, 4)
        assert (res.checksum, res.matches, res.overflow) == (
            want.checksum, want.matches, want.overflow
        )
