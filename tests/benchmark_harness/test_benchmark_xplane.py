"""The reduction from a profiler trace to numbers: on hand-made planes
(exact), and on one small trace recorded on the TPU v5e by this benchmark
(kept beside the harness: ``benchmark/fixtures/``)."""

import glob
import os

import pytest

from bh_fixtures import REPO

from benchmark.harness import xplane


def test_union_merges_overlaps_and_keeps_gaps():
    busy, merged = xplane.union_seconds(
        [(0, 10e9), (5e9, 12e9), (20e9, 21e9), (20.5e9, 20.7e9)])
    assert busy == 13.0
    assert merged == [[0, 12e9], [20e9, 21e9]]


def test_op_name_is_the_hlo_instructions_own():
    assert xplane.op_name(
        "%fusion.11 = s32[917505]{0:T(1024)S(1)} fusion(s32[4000000] %x)"
    ) == "fusion.11"
    assert xplane.op_name("all-reduce.3") == "all-reduce.3"
    assert xplane.op_label(
        "%fusion.504 = f32[4000000,153]{0,1:T(8,128)} fusion(f32[] %a)"
    ) == "fusion.504 f32[4000000,153]"
    assert xplane.CONTAINER_OPS.match("while.7")
    assert not xplane.CONTAINER_OPS.match("fusion.7")


def test_reduction_busy_idle_collectives_and_gap_attribution():
    s = 1e9
    planes = {
        "devices": {
            "/device:TPU:0": [
                ("%fusion.1 = f32[] fusion()", 0 * s, 2 * s),
                ("%all-reduce.7 = s32[3] all-reduce()", 2 * s, 2.5 * s),
                ("%while.3 = (s32[]) while()", 0 * s, 7 * s),  # a container
                ("%fusion.1 = f32[] fusion()", 4 * s, 5 * s),
                ("%copy.2 = f32[] copy()", 5.5 * s, 6 * s),
            ],
            "/device:TPU:1": [
                ("%fusion.1 = f32[] fusion()", 0 * s, 1 * s),
            ],
        },
        "host": [
            ("bench.stream.run", 0 * s, 3.2 * s),
            ("bench.between", 2.4 * s, 4.1 * s),   # innermost over 2.5..4
            ("bench.stream.run", 4.1 * s, 7 * s),
        ],
    }
    red = xplane.reduce_planes(planes, window_s=8.0)
    assert red["devices"] == 2
    assert red["busy_by_device"]["/device:TPU:0"] == 4.0
    assert red["busy_s"] == (4.0 + 1.0) / 2
    assert red["collective_s"] == 0.5 and red["first_device_busy_s"] == 4.0
    assert red["op_seconds"]["fusion.1 f32[]"] == 3.0
    assert red["device_ops"][0] == ["fusion.1 f32[]", 3.0]
    # gaps on the first device: 2.5..4 (midpoint 3.25: bench.between) and
    # 5..5.5 (midpoint 5.25: bench.stream.run)
    assert dict(map(tuple, red["idle_gaps"])) == {
        "bench.between": 1.5, "bench.stream.run": 0.5}
    # idle share as the reader computes it: 1 - 2.5/8
    assert 1.0 - red["busy_s"] / red["window_s"] == pytest.approx(0.6875)


def test_gaps_are_cut_at_annotation_edges_and_go_to_the_programs_spans():
    """One gap under two of the program's spans and a stretch only the
    benchmark's span covers: each piece to the innermost ``mosaic.*`` span,
    then ``bench.*``, then ``host:other``."""
    s = 1e9
    planes = {
        "devices": {"/device:TPU:0": [
            ("%fusion.1 = f32[] fusion()", 0 * s, 1 * s),
            ("%fusion.1 = f32[] fusion()", 9 * s, 10 * s),
            ("%fusion.2 = f32[] fusion()", 12 * s, 13 * s),
        ]},
        "host": [("bench.knn.call", 0.5 * s, 10.5 * s)],
        "program": [
            ("mosaic.knn.transform", 0.6 * s, 8 * s),
            ("mosaic.knn.expand", 1.5 * s, 4 * s),
            ("mosaic.knn.pull", 6 * s, 7.5 * s),
            ("mosaic.knn.expand", 7.6 * s, 7.9 * s),
        ],
    }
    red = xplane.reduce_planes(planes, window_s=13.0)
    gaps = dict(map(tuple, red["idle_gaps"]))
    assert gaps == pytest.approx({
        "mosaic.knn.expand": 2.5 + 0.3,
        "mosaic.knn.pull": 1.5,
        # 1..1.5, 4..6, 7.5..7.6, 7.9..8: under the call's root span alone
        "mosaic.knn.transform": 0.5 + 2.0 + 0.1 + 0.1,
        "bench.knn.call": 1.0 + 0.5,  # 8..9, 10..10.5: the benchmark's alone
        "host:other": 1.5,            # 10.5..12: between two calls
    })
    assert sum(gaps.values()) == pytest.approx(13.0 - red["busy_s"])
    assert red["idle_gaps"][0][0] == "mosaic.knn.expand"  # largest first
    # planes without the key (an older caller): the benchmark's spans alone
    planes.pop("program")
    old = dict(map(tuple, xplane.reduce_planes(planes, 13.0)["idle_gaps"]))
    assert old == pytest.approx({"bench.knn.call": 8.5, "host:other": 1.5})


def test_breakdown_keeps_the_op_ranking_whole_and_the_stages_apart():
    """A stage's seconds contain its ops': the stage table has a key of its
    own beside the ten-op ranking, in the ranking's shape."""
    red = {"device_ops": [[f"fusion.{i} f32[8]", 10.0 - i] for i in range(10)],
           "idle_gaps": [[f"mosaic.span{i}", 1.0] for i in range(12)]}
    stage_s = {"knn.topk": 0.17, "knn.gather": 0.35, "knn.distance": 0.03,
               "knn.heads": 0.013, "pip.cells": 0.002, "unscoped": 0.001,
               "knn.nothing": 0.0}
    bd = xplane.breakdown(red, stage_s)
    assert set(bd) == {"device_ops", "idle_gaps", "device_stages"}
    assert bd["device_ops"] == red["device_ops"]  # all ten, no stage row
    assert len(bd["idle_gaps"]) == 10
    assert bd["device_stages"] == [
        ["knn.gather", 0.35], ["knn.topk", 0.17], ["knn.distance", 0.03],
        ["knn.heads", 0.013], ["pip.cells", 0.002], ["unscoped", 0.001]]
    many = {f"pip.s{i}": 1.0 + i for i in range(14)}
    assert len(xplane.breakdown(red, many)["device_stages"]) == 10
    # a run with no stage table (no trace of a device, a program without
    # scopes): the two rankings alone
    assert set(xplane.breakdown(red)) == {"device_ops", "idle_gaps"}
    assert set(xplane.breakdown(red, {"a": 0.0})) == {"device_ops", "idle_gaps"}


#: the recordings of three cells on the chip and program spans that hold
#: idle time in each (what one ``bench.*.call`` or ``host:other`` held until
#: PR 47)
RECORDED_GAPS = {
    "taxi_batch_v5e": {"mosaic.join.counts", "mosaic.join.shift",
                       "mosaic.join.pull"},
    "taxi_serve_v5e": {"mosaic.serve.linger", "mosaic.dispatch.transfer.d2h",
                       "mosaic.serve.dispatch"},
    "modis_zonal_v5e": {"mosaic.raster.probe", "mosaic.raster.patch",
                        "mosaic.raster.fold"},
}


@pytest.mark.parametrize("fixture", sorted(RECORDED_GAPS))
def test_recorded_idle_gaps_name_the_programs_spans(fixture):
    import json

    d = os.path.join(REPO, "benchmark", "fixtures", fixture)
    with open(os.path.join(d, "result.json"), encoding="utf-8") as f:
        result = json.load(f)
    planes = xplane.read_planes(os.path.join(d, "trace.xplane.pb.gz"))
    assert all(n.startswith("mosaic.") for n, _s, _e in planes["program"])
    red = xplane.reduce_planes(planes, result["tracer_window_s"])
    bd = xplane.breakdown(red, result["device_by_stage"])
    names = [n for n, _ in bd["idle_gaps"]]
    assert RECORDED_GAPS[fixture] <= set(names[:5]), names
    # the same idle time as the recording's own line read under one name
    was = sum(v for _n, v in result["line"]["breakdown"]["idle_gaps"])
    assert sum(v for _n, v in red["idle_gaps"]) == pytest.approx(was, rel=0.02)
    # the recording's own op ranking, whole; the stages under their own key
    assert bd["device_ops"] == result["line"]["breakdown"]["device_ops"]
    stages = bd["device_stages"]
    assert 1 <= len(stages) <= 10
    assert stages == sorted(stages, key=lambda kv: -kv[1])
    assert dict(map(tuple, stages)).items() <= \
        result["device_by_stage"].items()


def test_no_device_plane_reduces_to_nothing_to_read():
    red = xplane.reduce_planes({"devices": {}, "host": []}, window_s=1.0)
    assert red["devices"] == 0 and red["busy_s"] == 0.0


def test_recorded_tpu_trace():
    """One small trace of this benchmark's stream cell on the chip."""
    files = glob.glob(
        os.path.join(REPO, "benchmark", "fixtures", "*.xplane.pb.gz"))
    assert files, "the recorded check trace is missing"
    planes = xplane.read_planes(files[0])
    assert planes["devices"], "no /device:TPU plane in the recorded trace"
    red = xplane.reduce_planes(planes, window_s=RECORDED["window_s"])
    assert red["devices"] == RECORDED["devices"]
    assert red["busy_s"] == pytest.approx(RECORDED["busy_s"], rel=1e-9)
    assert 0.0 < red["busy_s"] < red["window_s"]
    assert red["device_ops"] and len(red["device_ops"]) <= 10
    assert red["device_ops"][0][0] == RECORDED["top_op"]
    names = {n for n, _ in red["idle_gaps"]}
    assert names & RECORDED["gap_names"], names
    assert any(n.startswith("bench.") for n, _s, _e in planes["host"])


#: what the recorded trace reduces to (my chip run, PR 23); filled in when
#: the trace was recorded
RECORDED = {
    "window_s": 7.079102272, "devices": 1, "busy_s": 7.075407125,
    "top_op": "fusion.504 f32[4000000,153]",
    "gap_names": {"bench.stream.run"},
}
