"""The batch join's inside (ISSUE 35): every piece of a `pip_join` call is
a child span of its `join.pip`, the three programs it launches register
with `obs.stages` once a signature, `_probe_counts` reads under its own
scope `pip.counts`, and splitting the shift from its put changed no
answer. Since ISSUE 36 the counts program is launched before the host's
subtract and pulled after the shifted put: the order of the pieces, what
`hidden_s` holds, one counts launch a chunk, and every lane's answer.
Since ISSUE 50 the counts program hands the join the slot column it
counted on (`slots="handed"` on `join.launch`, `probes` on `join.pip`), and
counts convex rows only under an adaptive probe.
Counts, names, orders and answers only: a CPU run states no time."""

import numpy as np
import pytest

import jax.numpy as jnp

from mosaic_tpu.core.geometry import wkt
from mosaic_tpu.core.index import CustomIndexSystem, GridConf
from mosaic_tpu.core.tessellate import tessellate
from mosaic_tpu.dispatch import core as dispatch
from mosaic_tpu.obs import stages
from mosaic_tpu.runtime import telemetry
from mosaic_tpu.sql import join as join_mod
from mosaic_tpu.sql.join import build_chip_index, host_join, pip_join

CUSTOM = CustomIndexSystem(GridConf(-180, 180, -90, 90, 2, 10.0, 10.0))
RES = 3
BBOX = (-25.0, -25.0, 35.0, 20.0)
ZONES = [
    "POLYGON ((1 1, 13 2, 12 11, 6 14, 2 9, 1 1), "
    "(5 5, 5 8, 8 8, 8 5, 5 5))",
    "POLYGON ((20 0, 30 0, 30 10, 25 4, 20 10, 20 0))",
    "MULTIPOLYGON (((-20 -20, -12 -20, -12 -12, -20 -12, -20 -20)), "
    "((-8 -8, -2 -8, -2 -2, -8 -2, -8 -8)))",
]
#: the pieces of a default call, in the order they run
DEFAULT_PIECES = [
    "join.put", "join.cells", "join.counts_launch", "join.shift",
    "join.put_shifted", "join.counts", "join.launch", "join.pull",
]
#: the two that exist only where the call syncs on its counts
SYNC_PIECES = ("join.counts_launch", "join.counts")
RECHECK_PIECES = DEFAULT_PIECES + ["join.recheck.band", "join.recheck.host"]
#: what each span carries beside its identity and seconds
ATTRIBUTES = {
    "join.put": {"rows", "nbytes", "dtype"},
    "join.cells": {"variant"},
    "join.counts_launch": set(),
    "join.counts": {"found", "heavy", "convex", "found_cap", "heavy_cap",
                    "convex_cap", "hidden_s"},
    "join.shift": {"rows"},
    "join.put_shifted": {"nbytes"},
    "join.launch": {"banded", "found_cap", "heavy_cap", "convex_cap", "slots"},
    "join.pull": {"nbytes"},
    "join.recheck.band": {"band", "cap", "ties", "mode"},
    "join.recheck.host": {"rows", "n"},
}


@pytest.fixture(scope="module")
def table():
    return tessellate(wkt.from_wkt(ZONES), CUSTOM, RES, keep_core_geoms=False)


@pytest.fixture(scope="module")
def index(table):
    return build_chip_index(table)


@pytest.fixture(scope="module")
def heavy_index(table):
    idx = build_chip_index(table, edge_cap=2)
    assert idx.num_heavy_cells > 0
    return idx


@pytest.fixture(scope="module")
def points():
    return np.random.default_rng(7).uniform(BBOX[:2], BBOX[2:], (1024, 2))


def _call(points, index, **kw):
    with telemetry.capture() as events:
        got = pip_join(points, None, CUSTOM, RES, chip_index=index, **kw)
    spans = [e for e in events if e["event"] == "span"]
    return got, spans, events


def _pieces(spans):
    """(the call's root, its direct children in the order they ended)."""
    roots = [s for s in spans if s["name"] == "join.pip"]
    assert len(roots) == 1
    return roots[0], [s for s in spans if s["parent_id"] == roots[0]["span_id"]]


# ------------------------------------------------------------ (a) spans

@pytest.mark.parametrize("recheck", [False, True])
def test_every_piece_is_one_child_of_the_calls_span(points, index, recheck):
    got, spans, events = _call(points, index, recheck=recheck)
    root, kids = _pieces(spans)
    assert [k["name"] for k in kids] == (
        RECHECK_PIECES if recheck else DEFAULT_PIECES)
    assert len(spans) == len(kids) + 1  # nothing else, nothing deeper
    for k in kids:
        assert k["trace_id"] == root["trace_id"]
        assert ATTRIBUTES[k["name"]] <= set(k), k["name"]
        assert root["start_mono"] <= k["start_mono"]
    assert sum(k["seconds"] for k in kids) <= root["seconds"]
    by = {k["name"]: k for k in kids}
    n = points.shape[0]
    assert by["join.put"]["rows"] == n == by["join.shift"]["rows"]
    assert by["join.put"]["nbytes"] == points.nbytes
    assert by["join.put"]["dtype"] == "float64"
    assert by["join.cells"]["variant"] == ("margin" if recheck else "cells")
    assert by["join.put_shifted"]["nbytes"] == n * 2 * 4  # an f32 index
    assert by["join.launch"]["banded"] is recheck
    # the sync's own numbers beside the caps it sized, and the launch's
    # caps as dispatched
    c = by["join.counts"]
    assert n >= c["found"] >= int((got >= 0).sum()) > 0  # cells found >= rows matched
    assert c["found_cap"] == min(join_mod._next_pow2(c["found"] + 1), n)
    assert by["join.launch"]["found_cap"] == c["found_cap"]
    assert by["join.pull"]["nbytes"] == n * (5 if recheck else 4)
    narrow = [e for e in events if e["event"] == "recheck_narrow"]
    assert len(narrow) == int(recheck)
    if recheck:
        band = by["join.recheck.band"]
        # recorded from inside the band's span, on the span's clock
        assert narrow[0]["span_id"] == band["span_id"]
        assert narrow[0]["seconds"] <= band["seconds"]
        assert {k: narrow[0][k] for k in ("band", "cap", "ties", "mode")} == \
            {k: band[k] for k in ("band", "cap", "ties", "mode")}
        assert band["mode"] == "empty" and band["band"] == 0
        host = by["join.recheck.host"]
        assert host["n"] == n and 0 <= host["rows"] < n
    np.testing.assert_array_equal(
        got, host_join(points, index.host, CUSTOM, RES))


def test_children_repeat_per_chunk_under_one_root(points, index):
    _got, spans, events = _call(points, index, batch_size=256, recheck=True)
    root, kids = _pieces(spans)
    chunks = points.shape[0] // 256
    names = [k["name"] for k in kids]
    assert names == RECHECK_PIECES * chunks
    assert {k["rows"] for k in kids if k["name"] == "join.put"} == {256}
    assert sum(k["seconds"] for k in kids) <= root["seconds"]
    narrow = [e for e in events if e["event"] == "recheck_narrow"]
    assert len(narrow) == chunks and {e["n"] for e in narrow} == {256}
    assert {e["mode"] for e in narrow} == {"empty"}


def test_direct_writeback_syncs_only_where_the_index_has_heavy_cells(
        points, index, heavy_index):
    assert index.num_heavy_cells == 0
    got, spans, _ = _call(points, index, writeback="direct")
    _root, kids = _pieces(spans)
    assert [k["name"] for k in kids] == [
        p for p in DEFAULT_PIECES if p not in SYNC_PIECES]
    assert not any("hidden_s" in k for k in kids)  # no sync, nothing under it
    assert {k["found_cap"] for k in kids if k["name"] == "join.launch"} == {None}
    got_h, spans, _ = _call(points, heavy_index, writeback="direct")
    _root, kids = _pieces(spans)
    assert [k["name"] for k in kids] == DEFAULT_PIECES
    c = next(k for k in kids if k["name"] == "join.counts")
    assert c["found_cap"] is None and c["heavy_cap"] >= 1
    np.testing.assert_array_equal(got, got_h)


def test_an_escalation_attempt_is_one_more_launch_and_pull(points, index):
    from mosaic_tpu.runtime import faults

    with faults.shrink_caps(found_cap=16):
        got, spans, events = _call(points, index)
    _root, kids = _pieces(spans)
    launches = [k for k in kids if k["name"] == "join.launch"]
    pulls = [k for k in kids if k["name"] == "join.pull"]
    assert len(launches) == len(pulls) >= 2
    caps = [k["found_cap"] for k in launches]
    assert caps[0] == 16 and caps == sorted(caps) and len(set(caps)) == len(caps)
    assert [k["name"] for k in kids if k["name"] not in
            ("join.launch", "join.pull")] == DEFAULT_PIECES[:6]
    np.testing.assert_array_equal(
        got, host_join(points, index.host, CUSTOM, RES))


# ------------------- the subtract under the count sync (ISSUE 36)

LANES = {
    "default": {},
    "recheck": {"recheck": True},
    "chunks": {"batch_size": 256},
    "chunks-recheck": {"batch_size": 256, "recheck": True},
}


def _chunks(kids):
    """The direct children, one list a chunk (a chunk opens with its put)."""
    out = []
    for k in kids:
        if k["name"] == "join.put":
            out.append([])
        out[-1].append(k)
    return out


@pytest.mark.parametrize("lane", sorted(LANES))
def test_the_subtract_and_its_put_lie_between_the_counts_launch_and_its_pull(
        points, index, lane):
    _got, spans, _ = _call(points, index, **LANES[lane])
    _root, kids = _pieces(spans)
    chunks = _chunks(kids)
    rows = LANES[lane].get("batch_size", points.shape[0])
    assert len(chunks) == points.shape[0] // rows
    for chunk in chunks:
        by = {k["name"]: k for k in chunk}
        launch, shift, put, pull = (
            by["join.counts_launch"], by["join.shift"],
            by["join.put_shifted"], by["join.counts"])
        # one thread, no nesting: a piece starts after the one before it ended
        starts = [k["start_mono"] for k in (
            by["join.cells"], launch, shift, put, pull, by["join.launch"])]
        assert starts == sorted(starts)
        # (start_mono is rounded to the microsecond)
        assert launch["start_mono"] + launch["seconds"] <= shift["start_mono"] + 2e-6
        # what the host did while the device had the sync's work queued
        assert pull["hidden_s"] >= shift["seconds"] + put["seconds"]
        assert pull["hidden_s"] <= pull["start_mono"] - launch["start_mono"] + 2e-6


@pytest.mark.parametrize("lane", sorted(LANES))
def test_the_counts_program_is_launched_once_a_chunk(
        points, index, monkeypatch, lane):
    launched = []
    real = dispatch.jit_counts()

    def counting(cells, idx, **kw):
        launched.append((cells.shape[0], kw))
        return real(cells, idx, **kw)

    monkeypatch.setattr(dispatch, "jit_counts", lambda: counting)
    monkeypatch.setattr(join_mod, "_register_stages", lambda *a, **k: None)
    _got, spans, _ = _call(points, index, **LANES[lane])
    root, kids = _pieces(spans)
    rows = LANES[lane].get("batch_size", points.shape[0])
    assert launched == [(rows, {"probe": "scatter"})] * (points.shape[0] // rows)
    assert len(launched) == sum(k["name"] == "join.counts" for k in kids)
    assert len(launched) == sum(k["name"] == "join.counts_launch" for k in kids)
    # and its probe is the chunk's only one: every join is handed the slots
    assert root["probes"] == len(launched)
    assert {k["slots"] for k in kids if k["name"] == "join.launch"} == {"handed"}


def test_with_no_sync_the_shift_still_follows_the_cells_launch(
        points, index, monkeypatch):
    """`writeback="direct"` on an index with no heavy cell launches no
    counts program, records neither counts span, and subtracts right after
    the cells launch."""
    def refuse():
        raise AssertionError("the counts program has no caller here")

    monkeypatch.setattr(dispatch, "jit_counts", refuse)
    got, spans, _ = _call(points, index, writeback="direct")
    _root, kids = _pieces(spans)
    names = [k["name"] for k in kids]
    assert not set(SYNC_PIECES) & set(names)
    assert names.index("join.shift") == names.index("join.cells") + 1
    by = {k["name"]: k for k in kids}
    # nobody probed for the join: it does, once
    assert by["join.launch"]["slots"] == "probed" and _root["probes"] == 1
    assert by["join.cells"]["start_mono"] <= by["join.shift"]["start_mono"]
    np.testing.assert_array_equal(
        got, host_join(points, index.host, CUSTOM, RES))


@pytest.mark.parametrize("writeback", ["scatter", "gather", "direct"])
def test_heavy_cap_as_dispatched_is_sized_from_the_late_pull(
        points, heavy_index, writeback):
    got, spans, _ = _call(points, heavy_index, writeback=writeback)
    _root, kids = _pieces(spans)
    assert [k["name"] for k in kids] == DEFAULT_PIECES
    by = {k["name"]: k for k in kids}
    c, n = by["join.counts"], points.shape[0]
    assert c["heavy"] > 0
    if writeback == "direct":
        want_found, want_heavy = None, min(join_mod._next_pow2(c["heavy"] + 1), n)
    else:
        want_found = min(join_mod._next_pow2(c["found"] + 1), n)
        want_heavy = min(join_mod._next_pow2(c["heavy"] + 1), want_found)
    assert (c["found_cap"], c["heavy_cap"]) == (want_found, want_heavy)
    assert by["join.launch"]["heavy_cap"] == want_heavy
    assert by["join.launch"]["found_cap"] == want_found
    np.testing.assert_array_equal(
        got, host_join(points, heavy_index.host, CUSTOM, RES))


ANSWER_LANES = {
    **LANES,
    "heavy": {"heavy": True},
    "heavy-recheck": {"heavy": True, "recheck": True},
    "adaptive": {"probe": "adaptive"},
    "heavy-adaptive": {"heavy": True, "probe": "adaptive"},
}


@pytest.mark.parametrize("lane", sorted(ANSWER_LANES))
def test_every_lane_answers_as_the_host_oracle_and_joins_the_same_bits(
        points, index, heavy_index, monkeypatch, lane):
    """The order of the sync and the subtract reaches no program: the join
    is handed the one-expression shift's bits, the slot column of the cells
    the counts saw (and no cells) and the caps a blocking count of those
    cells sizes, and every row is the f64 host oracle's."""
    kw = dict(ANSWER_LANES[lane])
    idx = heavy_index if kw.pop("heavy", False) else index
    seen = []
    real = dispatch.jit_join()

    def spy(shifted, cells, index_, **k):
        seen.append((shifted, cells, k))
        return real(shifted, cells, index_, **k)

    monkeypatch.setattr(dispatch, "jit_join", lambda: spy)
    monkeypatch.setattr(join_mod, "_register_stages", lambda *a, **k: None)
    got, spans, events = _call(points, idx, **kw)
    np.testing.assert_array_equal(
        got, host_join(points, idx.host, CUSTOM, RES))
    rows = kw.get("batch_size", points.shape[0])
    shift = np.asarray(idx.host.shift, dtype=np.float64)
    assert len(seen) == points.shape[0] // rows
    probe = kw.get("probe", "scatter")
    for i, (shifted, cells, k) in enumerate(seen):
        chunk = points[i * rows:(i + 1) * rows]
        before = jnp.asarray(chunk - shift, dtype=idx.border.verts.dtype)
        np.testing.assert_array_equal(
            np.asarray(shifted).view(np.uint32),
            np.asarray(before).view(np.uint32))
        # the slots are those of the chunk's cells, probed again here
        assert cells is None
        again = CUSTOM.point_to_cell(jnp.asarray(chunk), RES)
        counts, u = dispatch.jit_counts()(again, idx, probe=probe)
        np.testing.assert_array_equal(np.asarray(k["slots"]), np.asarray(u))
        nf, nh, nc = (int(v) for v in np.asarray(counts))
        fcap = min(join_mod._next_pow2(nf + 1), rows)
        assert k["found_cap"] == fcap
        assert k["heavy_cap"] == (
            min(join_mod._next_pow2(nh + 1), fcap)
            if idx.num_heavy_cells else None)
        assert k["convex_cap"] == (
            min(join_mod._next_pow2(nc + 1), rows)
            if "probe" in kw and idx.num_convex_cells else None)
    routes = [e for e in events if e["event"] == "probe_route"]
    assert len(routes) == (len(seen) if "probe" in kw else 0)
    for e, c in zip(routes, (s for s in spans if s["name"] == "join.counts")):
        # recorded after the late pull, from its numbers
        assert (e["found"], e["heavy"], e["convex"]) == (
            c["found"], c["heavy"], c["convex"])
    # (c) the convex count is taken where a lane reads it, and only there
    for c in (s for s in spans if s["name"] == "join.counts"):
        if "probe" in kw:
            assert c["convex"] is not None and 0 <= c["convex"] <= c["found"]
            assert (c["convex"] > 0) == bool(idx.num_convex_cells)
        else:
            assert c["convex"] is None


def test_a_band_with_rows_and_no_alternate_cells_goes_to_the_host(points, index):
    """A grid with margins and no runner-up cell: the flagged band goes to
    the host oracle whole (`mode="host_all"`), still one span and one
    event a chunk, and the host span counts the rows it re-joined."""
    class Margins(CustomIndexSystem):
        def point_to_cell_margin(self, xy, resolution):
            cells = self.point_to_cell(xy, resolution)
            # every eighth row borderline, none near a corner
            first = jnp.where(jnp.arange(xy.shape[0]) % 8 == 0, 0.0, 1.0)
            return cells, jnp.stack([first, jnp.ones_like(first)], axis=1)

    grid = Margins(CUSTOM.conf)
    with telemetry.capture() as events:
        got = pip_join(points, None, grid, RES, chip_index=index, recheck=True)
    spans = [e for e in events if e["event"] == "span"]
    _root, kids = _pieces(spans)
    by = {k["name"]: k for k in kids}
    n = points.shape[0]
    band = by["join.recheck.band"]
    assert (band["mode"], band["band"], band["ties"]) == ("host_all", n // 8, n // 8)
    assert band["cap"] == join_mod._next_pow2(n // 8)
    assert by["join.recheck.host"]["rows"] >= n // 8
    narrow = [e for e in events if e["event"] == "recheck_narrow"]
    assert len(narrow) == 1 and narrow[0]["mode"] == "host_all"
    np.testing.assert_array_equal(
        got, host_join(points, index.host, CUSTOM, RES))


# ------------------------------------- (b) the shift, split from its put

@pytest.mark.parametrize("recheck", [False, True])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_split_shift_hands_the_join_the_bits_it_had(
        points, table, monkeypatch, dtype, recheck):
    """`jnp.asarray(chunk - shift, dtype=dtype)` was one expression; it is
    now numpy's subtract-and-narrow, then a plain put. The join program is
    handed the same bits, so its answers are the same row for row: equal
    to the program run on the one-expression array, and (with the recheck)
    to the f64 host oracle."""
    idx = build_chip_index(table, dtype=dtype)
    assert idx.border.verts.dtype == dtype
    # coordinates whose f32 rounding is not trivial: the seeded points
    # and rows a hair either side of f32 ties around the shift
    shift = np.asarray(idx.host.shift, dtype=np.float64)
    ties = shift + np.float64(np.float32(3.0)) + np.array(
        [[2.0 ** -25, -(2.0 ** -25)], [2.0 ** -24, 2.0 ** -24 * 3]])
    pts = np.concatenate([points, ties])
    seen = []
    real = dispatch.jit_join()

    def spy(shifted, cells, index, **kw):
        seen.append((shifted, cells, kw))
        return real(shifted, cells, index, **kw)

    monkeypatch.setattr(dispatch, "jit_join", lambda: spy)
    monkeypatch.setattr(join_mod, "_register_stages", lambda *a, **k: None)
    got = pip_join(pts, None, CUSTOM, RES, chip_index=idx, recheck=recheck)
    shifted, cells, kw = seen[0]
    before = jnp.asarray(pts - shift, dtype=dtype)  # the one expression
    assert shifted.dtype == before.dtype == dtype
    width = np.uint32 if dtype == jnp.float32 else np.uint64
    np.testing.assert_array_equal(
        np.asarray(shifted).view(width), np.asarray(before).view(width))
    again = real(before, cells, idx, **kw)
    want = np.asarray(again[0] if recheck else again)
    if recheck:  # exact: every row is the f64 oracle's
        np.testing.assert_array_equal(
            got, host_join(pts, idx.host, CUSTOM, RES))
        assert int((got != want).sum()) <= len(pts) // 100  # band rows only
    else:
        np.testing.assert_array_equal(got, want)


# --------------------------------------------------- (c) the stage table

def test_programs_register_once_a_signature_and_counts_has_its_scope(index):
    rows = join_mod._JIT_CELLS_MIN  # the jitted cells program, on the CPU too
    pts = np.random.default_rng(11).uniform(BBOX[:2], BBOX[2:], (rows, 2))
    stages.clear()
    join_mod._STAGES_SEEN.clear()
    n0 = stages.lowerings()
    got = pip_join(pts, None, CUSTOM, RES, chip_index=index)
    first = sorted(stages.registered())
    assert first == [("jit__probe_counts", rows), ("jit_cells", rows),
                     ("jit_pip_join_points", rows)]
    seen = set(join_mod._STAGES_SEEN)
    again = pip_join(pts, None, CUSTOM, RES, chip_index=index)
    np.testing.assert_array_equal(got, again)
    assert sorted(stages.registered()) == first  # a second call adds nothing
    assert join_mod._STAGES_SEEN == seen
    assert stages.lowerings() == n0  # and nothing is lowered by a call
    tables = stages.tables(["jit__probe_counts"], [rows])
    assert set(tables) == {"jit__probe_counts"}
    # the one probe reads under its own name inside the counts program
    # (the innermost scope), the sums under `pip.counts`
    counts = tables["jit__probe_counts"]
    assert set(counts.values()) == {"pip.counts", "pip.hash_probe"}
    T, W = index.table_rows.shape
    probed = [k for k, v in counts.items() if v == "pip.hash_probe"]
    assert any(f"u32[{T},{W}]" in k for k in probed)  # the table, read here
    assert any(k.endswith(f"u32[{W},{rows}]") for k in probed)  # its rows
    assert any(k.startswith("reduce") for k, v in counts.items()
               if v == "pip.counts")
    # and the join, handed the slots, holds no table and no gathered rows
    join_table = stages.tables(["jit_pip_join_points"], [rows])
    join = join_table["jit_pip_join_points"]
    assert not [k for k in join if f"u32[{T}," in k or f"u32[{W},{rows}]" in k]
    assert "pip.counts" not in set(join.values())
    assert {"pip.tier1", "pip.writeback"} <= set(join.values())


def test_seen_signatures_are_bounded(index, monkeypatch):
    calls = []
    monkeypatch.setattr(stages, "register", lambda *a, **k: calls.append(k))
    monkeypatch.setattr(join_mod, "_STAGES_SEEN", set())
    monkeypatch.setattr(join_mod, "_STAGES_SEEN_MAX", 4)
    cells = jnp.zeros(8, jnp.int64)
    prog = dispatch.jit_counts()  # (any function: nothing is called here)
    for rows in (1, 2, 3, 4, 4, 3):
        join_mod._register_stages(prog, (cells, index), {}, rows)
    assert len(calls) == 4 and len(join_mod._STAGES_SEEN) == 4
    join_mod._register_stages(prog, (cells, index), {}, 5)  # full: starts over
    assert len(calls) == 5 and len(join_mod._STAGES_SEEN) == 1
    # a keyword that is an array counts by its dtype, a static by its value
    kw = {"edge_eps2": jnp.float32(1e-9), "found_cap": 16}
    join_mod._register_stages(prog, (cells, index), kw, 5)
    join_mod._register_stages(
        prog, (cells, index), dict(kw, edge_eps2=jnp.float32(2e-9)), 5)
    join_mod._register_stages(prog, (cells, index), dict(kw, found_cap=32), 5)
    assert len(calls) == 7
