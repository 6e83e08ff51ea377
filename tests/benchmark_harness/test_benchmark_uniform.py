"""The cell ``taxi.stream-uniform`` (PR 39): its mix is ``pickups-hotspot``
but for ``hotspot_share``, the generator at share 0 draws every point
inside the zone layer's box, and a tiny uniform stream cell on the CPU —
added as files to a temporary copy, run through the unchanged harness — is
sound and its bfloat16 control is not (the run with the timed path broken
underneath is `test_benchmark_check_fails.py`'s, on this fixture too). A
CPU run asserts answers and counts; it never states a device number."""

import os
import time

import numpy as np
import pytest

from bh_fixtures import (
    REPO, TINY_POINTS, _write, append_as_a_pr, make_copy,
)

from benchmark.generators import points, zones
from benchmark.harness.run_cell import run_cell
from benchmark.harness.spec import Spec

CELL = "taxi.stream-uniform"


@pytest.fixture(scope="module")
def spec():
    return Spec(REPO)


def test_the_mix_is_pickups_hotspot_but_for_the_share(spec):
    uniform, hotspot = spec.traffic("pickups-uniform"), spec.traffic("pickups-hotspot")
    assert uniform["points"]["hotspot_share"] == 0.0
    assert hotspot["points"]["hotspot_share"] == 0.9
    words = ("name", "what", "assumed")  # what the two say of themselves
    assert set(uniform) == set(hotspot)
    for key in set(uniform) - set(words):
        if key == "points":
            assert dict(uniform[key], hotspot_share=0.9) == hotspot[key]
        else:
            assert uniform[key] == hotspot[key], key
    assert all(uniform[w] != hotspot[w] for w in words)


def test_the_cell_is_taxi_streams_twin(spec):
    """One configuration, one chip, the same check, and every end-to-end
    and ``.stream`` metric `taxi.stream` reads on one chip."""
    twin, cell = spec.cell("taxi.stream"), spec.cell(CELL)
    assert (cell["config"], cell["chips"]) == (twin["config"], 1)
    assert cell["traffic"] == "pickups-uniform"
    assert cell["check"] == twin["check"] == {"sample_rows": 262144}
    for reads in (spec.end_to_end, spec.per_layer):
        assert [m["name"] for m in reads(CELL)] == \
            [m["name"] for m in reads("taxi.stream")]
    assert "launch_to_device_ms.stream" not in \
        {m["name"] for m in spec.per_layer(CELL)}


@pytest.mark.parametrize("seed", [1, 4_000_000_123])
def test_share_zero_draws_every_point_inside_the_box(spec, seed):
    params = spec.traffic("pickups-uniform")["points"]
    z = spec.config("taxi-zones-h3r9")["zones"]
    bbox = zones.rings_bbox(zones.star_lattice(
        z["nx"], z["ny"], tuple(z["bbox"]), seed=z["seed"],
        verts=z["verts"], jitter=z["jitter"]))
    lay = points.layout(params, bbox)
    assert lay["share"] == 0.0 and lay["centres"].shape == (64, 2)
    n = 100_000
    ring = np.asarray(points.make_generator(params, bbox, n, slots=2)(
        points.seed_key(seed)))
    assert ring.shape == (2, n, 2) and ring.dtype == np.float64
    assert not np.array_equal(ring[0], ring[1])
    lo, hi = np.asarray(bbox[:2]), np.asarray(bbox[2:])
    assert (ring >= lo).all() and (ring <= hi).all()
    # uniform: each quarter of each axis holds a quarter of the points
    unit = (ring.reshape(-1, 2) - lo) / (hi - lo)
    for axis in (0, 1):
        share = np.histogram(unit[:, axis], bins=4, range=(0, 1))[0] / (2 * n)
        assert share == pytest.approx([0.25] * 4, abs=0.005)
    # f32 draws widened to f64: each coordinate is lo + u * span for an f32 u
    u = unit.astype(np.float32).astype(np.float64)
    assert np.allclose(lo + u * (hi - lo), ring.reshape(-1, 2),
                       rtol=0.0, atol=1e-12)


# ------------------------------------------------------ the CPU rehearsal

def add_uniform_cell(root: str) -> None:
    """``tiny.stream-uniform``: ``tiny-ring`` with ``hotspot_share`` 0, as a
    mix file, a workloads file and an entry whose name joins whatever lists
    ``tiny.stream``."""
    def add(tree, bench):
        mix = Spec(root).traffic("tiny-ring")
        mix.pop("name")
        mix["points"] = dict(TINY_POINTS, hotspot_share=0.0)
        _write(os.path.join(tree, "traffic", "tiny-ring-uniform.json"), mix)
        _write(os.path.join(tree, "workloads", "tiny.stream-uniform.json"),
               {"check": {"sample_rows": 2048}})
        bench["workloads"].append({
            "name": "tiny.stream-uniform", "config": "tiny-zones",
            "traffic": "tiny-ring-uniform", "chips": 1, "why": "test fixture"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if "tiny.stream" in m.get("workloads", []):
                m["workloads"].append("tiny.stream-uniform")

    append_as_a_pr(root, add)


@pytest.fixture()
def root(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    root = make_copy(tmp_path)
    add_uniform_cell(root)
    return root


@pytest.mark.parametrize("seed", [31, 4_000_000_777])
def test_uniform_rehearsal_is_sound_and_its_bf16_control_is_not(
        root, seed, capfd):
    def run(**kw):
        return run_cell(root, "tiny.stream-uniform", seed, 0.5, False,
                        t_start=time.perf_counter(), rehearsal=True, **kw)

    line = run()
    said = "".join(capfd.readouterr())
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"rows_per_s", "setup_s"}
    # the configuration's own limits, each printed beside its number
    for name, limit in (("stream_disagreement_share", "0.001"),
                        ("stream_fold_mismatches", "0.0"),
                        ("stream_overflow_rows", "0.0"),
                        ("forbidden_events", "0.0")):
        assert f"[check] {name}: value=" in said
        assert f"limit={limit} ok" in said.split(f"[check] {name}:")[1]
    # every point lies in the box, some outside every zone: misses, not
    # failures (the tiny zones cover part of their lattice's box)
    share = float(said.split("match_share=")[1].split()[0])
    assert 0.05 < share < 0.95
    # cell assignment in bfloat16, through StreamJoin's own cell_dtype
    assert run(control=True)["correct"] is False

