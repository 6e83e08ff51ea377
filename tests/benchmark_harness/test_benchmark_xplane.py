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
