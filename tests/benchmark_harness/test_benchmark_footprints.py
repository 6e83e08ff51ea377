"""The building-footprint cell (`osm-buildings.join`) rehearsed on the CPU
at a small size: a temporary copy of the benchmark to which a tiny
building deployment is ADDED as new files and appended entries (the real
configuration's builder, reference, traffic kind and metrics; a custom grid
whose cells are the size of H3 res 11's, so the index has heavy cells and
the stream's rule picks f64 cells). The sound run reads correct, the
lower-precision control and a broken path do not, the reference equals
`pip_bruteforce` where both apply, and every reader this cell brought
returns None where there is nothing to read."""

import json
import os
import shutil
import time

import numpy as np
import pytest

from bh_fixtures import REPO, _snapshot, _write

from benchmark.harness.run_cell import run_cell
from benchmark.harness.spec import Spec
from test_benchmark_program_spans import _ctx, check_entry

CELL = "tiny.buildings"
NEW_METRICS = [
    "tier2_device_ms.stream", "tier2_hbm_share.stream",
    "heavy_row_share.stream", "tessellate_s.build",
]
#: at resolution 11 cells of 4.9e-4 degrees (41 x 54 m)
GRID, RES = "CUSTOM(-75,-73,40,42,2,1,1)", 11


def make_copy(tmp) -> str:
    root = os.path.join(str(tmp), "copy")
    os.makedirs(root)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    shutil.copytree(
        os.path.join(REPO, "benchmark"), os.path.join(root, "benchmark"),
        ignore=shutil.ignore_patterns("__pycache__", ".traces", ".cache"),
    )
    before = _snapshot(root)
    tree = os.path.join(root, "benchmark")
    real = Spec(REPO).config("osm-buildings-h3r11")
    _write(os.path.join(tree, "configs", "tiny-buildings.json"), {
        "source": "test fixture", "rehearsal": True, "row": "point",
        "deployment": real["deployment"], "reference": real["reference"],
        "index_system": GRID, "resolution": RES,
        "buildings": dict(real["buildings"], count=400),
        "batch_rows_per_chip": 4096, "chips": 1, "mesh": None,
        "guarantees": {"stream_max_disagreement": 0.001},
        "reduced": {},
    })
    mix = Spec(REPO).traffic("pings-venues")
    mix.pop("name")
    mix.update(ring_slots=2, steps_per_dispatch=2, trace_from_dispatch=0,
               trace_dispatches=1)
    mix["points"] = dict(mix["points"], hotspots=6)
    _write(os.path.join(tree, "traffic", "tiny-pings.json"), mix)
    _write(os.path.join(tree, "workloads", CELL + ".json"),
           {"check": {"sample_rows": 8192}})
    path = os.path.join(root, "BENCHMARK.json")
    with open(path, encoding="utf-8") as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-buildings", "source": "test fixture",
        "file": "benchmark/configs/tiny-buildings.json", "reduced": [],
        "why": "test fixture",
    })
    bench["workloads"].append({
        "name": CELL, "config": "tiny-buildings", "traffic": "tiny-pings",
        "chips": 1, "why": "test fixture",
    })
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "osm-buildings.join" in m.get("workloads", []):
            m["workloads"].append(CELL)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(bench, f, indent=1)
    after = _snapshot(root)
    changed = [p for p, h in before.items()
               if p != "BENCHMARK.json" and after.get(p) != h]
    assert not changed, f"the fixture edited existing files: {changed}"
    return root


@pytest.fixture()
def root(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    return make_copy(tmp_path)


def _run(root, seed, **kw):
    return run_cell(root, CELL, seed, 0.3, False,
                    t_start=time.perf_counter(), rehearsal=True, **kw)


@pytest.mark.parametrize("seed", [41, 42, 4_000_000_777])
def test_sound_run_is_correct_and_bf16_control_is_not(root, seed, capsys):
    line = _run(root, seed)
    said = capsys.readouterr().out
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"rows_per_s", "setup_s"}
    # the deployment this PR is about: heavy cells, and the rule's f64 cells
    dep = next(s for s in said.splitlines() if "[bench] deployment:" in s)
    fields = dict(f.split("=", 1) for f in dep.split()[2:])
    assert int(fields["footprints"]) == 400
    assert float(fields["heavy_share"]) >= 0.2 and int(fields["M2"]) >= 2
    assert "the stream assigns cells" in said
    assert _run(root, seed, control=True)["correct"] is False


def test_the_rule_chose_f64_cells_and_the_fold_counts_heavy_rows(root):
    """Driven as the traffic kind drives it: package defaults."""
    from benchmark.harness.context import Ctx, SpanLog, TraceSession

    spec = Spec(root)
    cell = spec.cell(CELL)
    spans = SpanLog()
    ctx = Ctx(spec=spec, cell=cell, config=spec.config(cell["config"]),
              traffic=spec.traffic(cell["traffic"]), seed=5, seconds=0.2,
              trace=False, rehearsal=True, spans=spans,
              tracer=TraceSession(False, "", spans))
    ctx.deployment = spec.module("deployments", "building_join").build(ctx)
    kind = spec.module("traffic_kinds", "device_ring_stream")
    st = kind.prepare(ctx)
    res = st["sj"].run(st["ring"], st["nb"], collect=True)
    assert st["sj"].cell_dtype == "float64"
    assert res.metrics["cell_dtype"] == "float64"
    # heavy rows by the host's tables: every found row whose cell is heavy
    host = ctx.deployment.index.host
    pts = np.concatenate([np.asarray(st["ring"][i % st["k"]])
                          for i in range(st["nb"])])
    cells = np.asarray(ctx.deployment.grid.point_to_cell(pts, RES))
    u = np.clip(np.searchsorted(host.cells, cells), 0, len(host.cells) - 1)
    heavy = (host.cells[u] == cells) & (host.cell_heavy[u] >= 0)
    assert res.metrics["heavy_rows"] == int(heavy.sum()) > 0
    assert spans.seconds("tessellate") <= spans.seconds("index_build")
    kind.close(ctx, st)


def test_answers_altered_where_they_are_produced_are_caught(root, monkeypatch):
    """The join inside the timed loop answers the next footprint for every
    seventh matched row: folds still agree, the reference catches it."""
    import jax.numpy as jnp

    from mosaic_tpu import dispatch
    from mosaic_tpu.sql import stream

    real = stream.pip_join_points_heavy

    def altered(shifted, cells, index, **kw):
        out, heavy = real(shifted, cells, index, **kw)
        seventh = (jnp.arange(out.shape[0]) % 7) == 0
        return jnp.where(seventh & (out >= 0), out + 1, out), heavy

    dispatch.clear_caches()
    monkeypatch.setattr(stream, "pip_join_points_heavy", altered)
    try:
        line = _run(root, 43)
    finally:
        monkeypatch.undo()
        dispatch.clear_caches()
    assert line["correct"] is False and line["attempted"] > 0


def test_a_program_without_the_rule_is_refused_at_once(root, monkeypatch):
    """The parent commit with these files: the builder raises before the
    layer is made and before anything compiles."""
    from mosaic_tpu.sql import stream

    gen = Spec(root).module("generators", "buildings")
    monkeypatch.setattr(gen, "fabric", lambda p: pytest.fail("layer made"))
    monkeypatch.delattr(stream, "stream_cell_dtype")
    t0 = time.perf_counter()
    with pytest.raises(RuntimeError, match="cell_dtype"):
        _run(root, 44)
    assert time.perf_counter() - t0 < 5.0


def _layers():
    spec = Spec(REPO)
    zones = spec.module("generators", "zones")
    fabric = spec.module("generators", "buildings").fabric(
        dict(spec.config("osm-buildings-h3r11")["buildings"], count=600))[0]
    return {
        "zones": zones.star_lattice(4, 4, (-74.3, 40.4, -73.6, 41.0)),
        # outer rings alone: `pip_bruteforce` takes one ring a zone
        "footprints": [f[0] for f in fabric],
    }


@pytest.mark.parametrize("layer", ["zones", "footprints"])
def test_reference_equals_pip_bruteforce_where_both_apply(layer):
    spec = Spec(REPO)
    rings = _layers()[layer]
    allp = np.concatenate(rings)
    lo, hi = allp.min(axis=0), allp.max(axis=0)
    rng = np.random.default_rng(7)
    pts = lo + rng.uniform(-0.05, 1.05, (40000, 2)) * (hi - lo)
    # and points exactly on vertices and on bbox borders
    some = allp[:500]
    pts = np.concatenate([pts, some, np.column_stack(
        [some[:, 0], some[::-1, 1]])])
    got = spec.module("references", "pip_footprints").answers(rings, pts)
    want = spec.module("references", "pip_bruteforce").answers(rings, pts)
    assert got.dtype == want.dtype == np.int32
    assert np.array_equal(got, want) and (got >= 0).mean() > 0.1


def test_reference_counts_a_courtyard_as_outside():
    ref = Spec(REPO).module("references", "pip_footprints")
    outer = np.array([[0.0, 0.0], [4.0, 0.0], [4.0, 4.0], [0.0, 4.0]])
    yard = np.array([[1.0, 1.0], [1.0, 3.0], [3.0, 3.0], [3.0, 1.0]])
    inner = np.array([[1.5, 1.5], [2.5, 1.5], [2.5, 2.5], [1.5, 2.5]])
    pts = np.array([[0.5, 0.5], [2.0, 2.0], [2.0, 1.2], [5.0, 5.0]])
    # footprint 1 stands in footprint 0's courtyard
    got = ref.answers([[outer, yard], [inner]], pts)
    assert got.tolist() == [0, 1, -1, -1]


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_metric_reads_nothing_on_an_empty_run(name):
    spec = Spec(REPO)
    entry = next(m for m in spec.benchmark["per_layer"] if m["name"] == name)
    assert "osm-buildings.join" in entry["workloads"]
    check_entry(spec, name)
    # nor on a run of a program without the counter or the scope
    desc = spec.data("layer_metrics", name)
    ctx = _ctx(spec, events=[{"event": "span", "name": "stream.run",
                              "n_batches": 4, "ts_mono": 1.0}],
               counters={"traced_steps": 8})
    assert spec.module("readers", desc["reader"]).read(
        ctx, desc["params"]) is None


def test_tier2_hbm_share_and_heavy_row_share_arithmetic(monkeypatch):
    from types import SimpleNamespace

    spec = Spec(REPO)
    index = SimpleNamespace(
        heavy_edges=np.zeros((7, 80, 4), np.float32),
        heavy_slot_geom=np.zeros((7, 19), np.int32))
    runs = [{"event": "span", "name": "stream.run", "n_batches": 4,
             "rows": 16_000_000, "heavy_rows": 4_000_000, "ts_mono": t}
            for t in (1.0, 2.0)]
    ctx = _ctx(spec, events=runs, counters={"traced_steps": 8}, chips=1,
               deployment=SimpleNamespace(index=index),
               device={"kind": "TPU v5 lite"})
    busy = spec.module("readers", "trace_stage_busy")
    monkeypatch.setattr(busy, "read", lambda ctx, p: 50.0)  # ms a step
    per_row = 80 * (4 * 4 + 4) + 19 * 4 + 8
    mod = spec.module("readers", "tier2_hbm_share")
    assert mod.tier2_bytes_per_row(index) == per_row == 1684
    want = 100.0 * (1_000_000 * per_row / 819e9) / 0.050
    assert mod.read(ctx, {}) == pytest.approx(want)
    desc = spec.data("layer_metrics", "heavy_row_share.stream")
    assert spec.module("readers", desc["reader"]).read(
        ctx, desc["params"]) == pytest.approx(25.0)
    # an index without heavy cells has no tier 2 to share out
    index.heavy_edges = np.zeros((0, 8, 4), np.float32)
    assert mod.read(ctx, {}) is None
