"""The batch join probes once (ISSUE 50): `_probe_counts` returns the slot
column it counted on beside the counts, `pip_join_points(slots=)` reads it
where it would have probed, and `pip_join` hands each chunk's column from
its count sync to every join it launches for the chunk. Answers are the
probing program's bit for bit and the f64 host oracle's row for row;
`join.launch` says `slots="handed"` or `"probed"`, `join.pip` counts the
`probes`, and `join.counts` carries a convex count only where a lane reads
it. Counts, names and answers only: a CPU run states no time."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mosaic_tpu.core.geometry import wkt
from mosaic_tpu.core.index import CustomIndexSystem
from mosaic_tpu.core.tessellate import tessellate
from mosaic_tpu.dispatch import core as dispatch
from mosaic_tpu.runtime import faults, telemetry
from mosaic_tpu.sql import join as join_mod
from mosaic_tpu.sql.join import (
    OVERFLOW, build_chip_index, host_join, pip_join, pip_join_points,
)

# the grid, the zones and their box are the span tests' own
from test_join_spans import BBOX, CUSTOM, RES, ZONES

N = 1024


class Runnerup(CustomIndexSystem):
    """The grid with a cell band: every eighth row is borderline (none near
    a corner) and its runner-up cell is the one a step to the east."""

    def point_to_cell_margin(self, xy, resolution):
        cells = self.point_to_cell(xy, resolution)
        first = jnp.where(jnp.arange(xy.shape[0]) % 8 == 0, 0.0, 1.0)
        return cells, jnp.stack([first, jnp.ones_like(first)], axis=1)

    def point_to_cell_alt(self, xy, resolution):
        return self.point_to_cell(xy + jnp.asarray([0.3, 0.0]), resolution)


@pytest.fixture(scope="module")
def table():
    return tessellate(wkt.from_wkt(ZONES), CUSTOM, RES, keep_core_geoms=False)


@pytest.fixture(scope="module")
def indexes(table):
    zone, heavy = build_chip_index(table), build_chip_index(table, edge_cap=2)
    assert zone.num_heavy_cells == 0 and zone.num_convex_cells > 0
    assert heavy.num_heavy_cells > 0
    return {"zone": zone, "heavy": heavy}


@pytest.fixture(scope="module")
def points():
    return np.random.default_rng(7).uniform(BBOX[:2], BBOX[2:], (N, 2))


def _staged(points, idx):
    """What `pip_join` stages for a chunk: shifted rows, cells, slots."""
    shift = np.asarray(idx.host.shift, dtype=np.float64)
    shifted = jnp.asarray(points - shift, dtype=idx.border.verts.dtype)
    cells = CUSTOM.point_to_cell(jnp.asarray(points), RES)
    return shifted, cells, join_mod._probe_slot(cells, idx)


# ------------------------------ (a) the program handed a slot column

@pytest.mark.parametrize("cut", [False, True], ids=["nocap", "cap-cuts"])
@pytest.mark.parametrize("which", ["zone", "heavy"])
@pytest.mark.parametrize("banded", [False, True], ids=["plain", "banded"])
@pytest.mark.parametrize("writeback", ["scatter", "gather", "direct"])
def test_handed_slots_join_the_probing_programs_bits(
        points, indexes, writeback, banded, which, cut):
    idx = indexes[which]
    shifted, cells, u = _staged(points, idx)
    kw = {"writeback": writeback}
    if cut:
        kw["found_cap"] = 64
        if idx.num_heavy_cells:
            kw["heavy_cap"] = 8
    if banded:
        kw["edge_eps2"] = jnp.asarray(1e-6, shifted.dtype)
    probed = pip_join_points(shifted, cells, idx, **kw)
    handed = pip_join_points(shifted, cells, idx, slots=u, **kw)
    no_cells = pip_join_points(shifted, None, idx, slots=u, **kw)
    for a, b, c in zip(*(r if banded else (r,)
                         for r in (probed, handed, no_cells))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
    out = np.asarray(probed[0] if banded else probed)
    cuts = cut and (writeback != "direct" or idx.num_heavy_cells)
    assert bool((out == OVERFLOW).any()) == bool(cuts)
    assert (out >= 0).any()


@pytest.mark.parametrize("which", ["zone", "heavy"])
@pytest.mark.parametrize("probe", ["adaptive", "adaptive-light"])
def test_handed_slots_under_an_adaptive_probe(points, indexes, which, probe):
    idx = indexes[which]
    shifted, cells, u = _staged(points, idx)
    probed = pip_join_points(shifted, cells, idx, probe=probe)
    handed = pip_join_points(shifted, None, idx, probe=probe, slots=u)
    np.testing.assert_array_equal(np.asarray(probed), np.asarray(handed))
    np.testing.assert_array_equal(
        np.asarray(probed), host_join(points, idx.host, CUSTOM, RES))


def test_cells_or_slots_one_of_them(points, indexes):
    shifted, _cells, _u = _staged(points, indexes["zone"])
    with pytest.raises(ValueError, match="pcells to probe, or slots"):
        pip_join_points(shifted, None, indexes["zone"])


# ------------------------------------------ the counts program's pair

@pytest.mark.parametrize("which", ["zone", "heavy"])
@pytest.mark.parametrize(
    "probe", ["scatter", "adaptive", "adaptive-light", "adaptive-convex"])
def test_counts_program_returns_the_counts_and_the_column(
        points, indexes, which, probe):
    idx = indexes[which]
    _shifted, cells, u = _staged(points, idx)
    counts, slots = dispatch.jit_counts()(cells, idx, probe=probe)
    assert counts.shape == (3,) and slots.shape == (N,)
    assert slots.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(slots), np.asarray(u))
    un = np.asarray(u)
    found = un >= 0
    nf, nh, nc = (int(v) for v in np.asarray(counts))
    assert nf == int(found.sum()) > 0
    heavy = np.asarray(idx.cell_heavy)[np.maximum(un, 0)] >= 0
    assert nh == int((found & heavy).sum())
    assert (nh > 0) == bool(idx.num_heavy_cells)
    convex = np.asarray(idx.cell_convex)[np.maximum(un, 0)] >= 0
    want = int((found & convex).sum()) if idx.num_convex_cells else 0
    assert nc == (0 if probe == "scatter" else want)
    # the default is the scatter program's: one cache entry, not two
    again, _ = dispatch.jit_counts()(cells, idx)
    if probe == "scatter":
        np.testing.assert_array_equal(np.asarray(again), np.asarray(counts))


# -------------------------------------- (b) `pip_join` probes once a chunk

#: lane -> (pip_join keywords, index, what the call's spans should say:
#: counts launches, `probes`, the `slots` of its join launches)
LANES = {
    "default": ({}, "zone", 1, 1, {"handed"}),
    "gather": ({"writeback": "gather"}, "zone", 1, 1, {"handed"}),
    "heavy": ({}, "heavy", 1, 1, {"handed"}),
    "chunks": ({"batch_size": 256}, "zone", 4, 4, {"handed"}),
    "recheck-empty-band": ({"recheck": True}, "zone", 1, 1, {"handed"}),
    "recheck-band": ({"recheck": True, "grid": True}, "zone", 2, 2, {"handed"}),
    "recheck-band-heavy": (
        {"recheck": True, "grid": True}, "heavy", 2, 2, {"handed"}),
    "recheck-band-chunks": (
        {"recheck": True, "grid": True, "batch_size": 512}, "zone", 4, 4,
        {"handed"}),
    "escalates": ({"shrink": {"found_cap": 16}}, "zone", 1, 1, {"handed"}),
    "escalates-heavy": (
        {"shrink": {"found_cap": 32, "heavy_cap": 8}}, "heavy", 1, 1,
        {"handed"}),
    "adaptive": ({"probe": "adaptive"}, "zone", 1, 1, {"handed"}),
    "adaptive-heavy": ({"probe": "adaptive"}, "heavy", 1, 1, {"handed"}),
    "direct-no-sync": ({"writeback": "direct"}, "zone", 0, 1, {"probed"}),
    "direct-heavy": ({"writeback": "direct"}, "heavy", 1, 1, {"handed"}),
}


@pytest.mark.parametrize("lane", sorted(LANES))
def test_pip_join_probes_once_a_chunk_and_answers_as_the_oracle(
        points, indexes, monkeypatch, lane):
    kw, which, want_counts, want_probes, want_slots = LANES[lane]
    kw = dict(kw)
    idx = indexes[which]
    grid = Runnerup(CUSTOM.conf) if kw.pop("grid", False) else CUSTOM
    shrink = kw.pop("shrink", None)
    counted, joined = [], []
    real_counts, real_join = dispatch.jit_counts(), dispatch.jit_join()

    def counts_spy(cells, index_, **k):
        res = real_counts(cells, index_, **k)
        counted.append((cells.shape[0], k, res[1]))
        return res

    def join_spy(shifted, cells, index_, **k):
        joined.append((shifted.shape[0], cells, k))
        return real_join(shifted, cells, index_, **k)

    monkeypatch.setattr(dispatch, "jit_counts", lambda: counts_spy)
    monkeypatch.setattr(dispatch, "jit_join", lambda: join_spy)
    monkeypatch.setattr(join_mod, "_register_stages", lambda *a, **k: None)
    with telemetry.capture() as events:
        if shrink:
            with faults.shrink_caps(**shrink):
                got = pip_join(points, None, grid, RES, chip_index=idx, **kw)
        else:
            got = pip_join(points, None, grid, RES, chip_index=idx, **kw)
    np.testing.assert_array_equal(
        got, host_join(points, idx.host, CUSTOM, RES))
    assert not isinstance(got, join_mod.DegradedResult)
    spans = [e for e in events if e["event"] == "span"]
    root = next(s for s in spans if s["name"] == "join.pip")
    launches = [s for s in spans if s["name"] == "join.launch"]
    assert len(counted) == want_counts
    assert root["probes"] == want_probes
    assert {s["slots"] for s in launches} == want_slots
    # one blocking sync a chunk: the band's own count is not a `join.counts`
    chunks = N // kw.get("batch_size", N)
    assert sum(s["name"] == "join.counts" for s in spans) == (
        chunks if want_counts else 0)
    probe = kw.get("probe", "scatter")
    main = [c for c in counted if c[0] == N // chunks]
    assert [c[1] for c in main] == [{"probe": probe}] * len(main)
    if want_slots == {"handed"}:
        # every join of a chunk — each escalation attempt, and the narrow
        # re-join with its own — is handed a counts program's column, the
        # array itself, and no cells
        columns = [id(c[2]) for c in counted]
        for rows, cells, k in joined:
            assert cells is None and id(k["slots"]) in columns
            assert k["slots"].shape == (rows,)
        assert len(main) == chunks
    else:
        assert all("slots" not in k and cells is not None
                   for _rows, cells, k in joined)
    band = [s for s in spans if s["name"] == "join.recheck.band"]
    if grid is not CUSTOM:
        assert {s["mode"] for s in band} == {"alt_rejoin"}
        assert all(s["band"] == (N // chunks) // 8 for s in band)
        # the band's count runs the scatter program: no convex count
        narrow = [c for c in counted if c[0] != N // chunks]
        assert len(narrow) == chunks
        assert all(c[1] == {"probe": "scatter"} for c in narrow)
        assert len(joined) == 2 * chunks
    if shrink:
        assert len(launches) >= 2  # the fault's cap overflowed: it grew
        caps = [s["found_cap"] for s in launches]
        assert caps == sorted(caps) and caps[0] == shrink["found_cap"]
        assert len({id(k["slots"]) for _r, _c, k in joined}) == 1
    assert not [e for e in events if e["event"] in ("degraded", "retry_exhausted")]


def test_the_mesh_lane_probes_in_its_own_program(points, indexes, devices):
    with telemetry.capture() as events:
        got = pip_join(points, None, CUSTOM, RES, chip_index=indexes["zone"],
                       mesh=2, recheck=False)
    np.testing.assert_array_equal(
        got, host_join(points, indexes["zone"].host, CUSTOM, RES))
    spans = [e for e in events if e["event"] == "span"]
    root = next(s for s in spans if s["name"] == "join.pip")
    assert root["probes"] == 1
    assert not [s for s in spans if s["name"] in ("join.counts", "join.launch")]


# ------------------- (c) the convex count, where a lane reads it

@pytest.mark.parametrize("probe, taken", [
    ("scatter", False), ("adaptive", True), ("adaptive-light", True),
    ("adaptive-heavy", True), ("adaptive-convex", True),
])
def test_the_convex_count_is_taken_under_an_adaptive_probe_only(
        points, indexes, probe, taken):
    idx = indexes["zone"]
    with telemetry.capture() as events:
        got = pip_join(points, None, CUSTOM, RES, chip_index=idx, probe=probe)
    np.testing.assert_array_equal(
        got, host_join(points, idx.host, CUSTOM, RES))
    c = next(e for e in events
             if e["event"] == "span" and e["name"] == "join.counts")
    assert "convex" in c and "convex_cap" in c
    if not taken:
        assert c["convex"] is None and c["convex_cap"] is None
        assert not [e for e in events if e["event"] == "probe_route"]
        return
    _shifted, _cells, u = _staged(points, idx)
    un = np.asarray(u)
    want = int(((un >= 0)
                & (np.asarray(idx.cell_convex)[np.maximum(un, 0)] >= 0)).sum())
    assert c["convex"] == want > 0
    assert c["convex_cap"] == min(join_mod._next_pow2(want + 1), N)
    route = next(e for e in events if e["event"] == "probe_route")
    assert route["convex"] == want and route["light"] == c["found"] - want


def test_the_join_stays_one_cache_keyed_on_what_it_is_called_with(
        points, indexes):
    """`jit_join` is one wrapper: the handed and the probing signature are
    two entries of its one cache, and a second call of either adds none."""
    idx = indexes["zone"]
    shifted, cells, u = _staged(points, idx)
    prog = dispatch.jit_join()
    assert prog is dispatch.jit_join()
    prog(shifted, cells, idx)
    prog(shifted, None, idx, slots=u)
    n = prog._cache_size()
    prog(shifted, cells, idx)
    prog(shifted, None, idx, slots=u)
    assert prog._cache_size() == n
    assert isinstance(u, jax.Array)
