"""`overlay_measures` against the plain reference on a British National
Grid fixture: 4,000 land parcels (rectangles, L, U and comb lots that share
their boundaries exactly) in a 2 x 2 km box at easting 530,000, overlaid at
100 m cells on a 15-district partition whose boundaries run along the
parcels' frontages, and on one river's three nested flood bands with islands
as hole rings. The device lane and ``lane="host"`` both agree with
`benchmark/references/overlay_bruteforce.py` on the WHOLE geometries; every
clip route runs (in place, swapped, fanned; a hole ring's negative sign);
touching pairs read exactly 0.0; the signed fan equals the convex clip where
the window is convex; the cell-local frame gives the same areas at easting
530,000 as at 0; and the band is sized from the arithmetic the device really
computes in.
"""

import numpy as np
import pytest

import mosaic_tpu
from mosaic_tpu import expr as E
from mosaic_tpu.core.tessellate import tessellate
from mosaic_tpu.core.types import GeometryBuilder, GeometryType
from mosaic_tpu.kernels import overlay as K
from mosaic_tpu.runtime import platform, telemetry
from mosaic_tpu.sql import overlay as ov
from mosaic_tpu.sql.join import EDGE_BAND_K

from benchmark.generators import parcels as parcel_gen
from benchmark.generators import themes as theme_gen
from benchmark.references import overlay_bruteforce as ref

RES = 4
COUNT = 4000


def _pack(polygons):
    b = GeometryBuilder()
    for rings in polygons:
        b.add_geometry(GeometryType.POLYGON, [rings], srid=27700)
    return b.build()


def _world(x0: float):
    """The fixture's layers, made with the box's west edge at easting 0 and
    moved east by ``x0`` (a multiple of 100 m, so the cells cut every layer
    alike wherever it stands; the move rounds a coordinate by 6e-11 m)."""
    grid = mosaic_tpu.enable_mosaic("BNG").index_system
    east = np.array([x0, 0.0])
    box = [0.0, 180000.0, 2000.0, 182000.0]
    rings, layout = parcel_gen.fabric({"count": COUNT, "box": box, "seed": 40})
    parcels = [[r + east] for r in rings]
    pcol = _pack(parcels)
    ptable = tessellate(pcol, grid, RES)
    d, _ = theme_gen.districts(layout, {"grid": [3, 5]}, 7)
    f, _ = theme_gen.flood(layout, {
        "rivers": 1, "islands_per_river": 3, "meander_m": [60.0, 120.0],
        "wavelength_m": [900.0, 1500.0],
    }, 7)
    themes = {}
    for name, polygons in (("districts", d), ("flood", f)):
        polygons = [[r + east for r in pg] for pg in polygons]
        col = _pack(polygons)
        table = tessellate(col, grid, RES)
        themes[name] = (
            polygons, col,
            ov.prepare_overlay(ptable, table, pcol, col, grid, RES),
        )
    return {"grid": grid, "parcels": parcels, "pcol": pcol, "themes": themes}


@pytest.fixture(scope="module")
def world():
    return _world(530000.0)


def _measures(world, name, lane):
    polygons, col, prep = world["themes"][name]
    with telemetry.capture() as events:
        ans = ov.overlay_measures(
            world["pcol"], col, world["grid"], RES, E.overlap_fraction(),
            prep=prep, lane=lane,
        )
    calls = [e for e in events
             if e.get("event") == "span" and e.get("name") == "overlay.call"]
    return ans, calls


def _against_reference(world, name, ans):
    """(reference areas, the answer's areas, its values, found) over every
    (parcel, polygon) pair whose boxes meet."""
    polygons = world["themes"][name][0]
    rp, rq, ra = ref.overlay(
        world["parcels"], np.arange(len(world["parcels"])), polygons
    )
    width = len(polygons) + 1
    key = ans.pairs[:, 0] * width + ans.pairs[:, 1]
    assert (np.diff(key) > 0).all()  # one row a pair, in (left, right) order
    rk = rp * width + rq
    pos = np.clip(np.searchsorted(key, rk), 0, key.shape[0] - 1)
    found = key[pos] == rk
    return rp, ra, np.where(found, ans.area[pos], 0.0), \
        np.where(found, ans.value[pos], 0.0), found


@pytest.mark.parametrize("lane", ["device", "host"])
@pytest.mark.parametrize("name", ["districts", "flood"])
def test_both_lanes_agree_with_the_plain_reference(world, name, lane):
    ans, calls = _measures(world, name, lane)
    assert ans.lane == lane and not ans.degraded and ans.overflow == 0
    rp, want, got, value, found = _against_reference(world, name, ans)
    # every pair whose interiors meet is returned
    assert found[want > 1e-9].all()
    # a pair that only touches (or lies apart in a shared cell) reads 0.0
    touching = found & (want < 1e-9)
    assert touching.sum() > 50 and (value[touching] == 0.0).all()
    assert (got[touching] == 0.0).all()
    # areas agree to the f64 tessellation's own rounding at easting 530,000
    assert np.abs(got - want).max() < 1e-7
    area = np.array([ref.area_of(p) for p in world["parcels"]])
    assert np.abs(value - want / area[rp]).max() < 1e-9
    if name == "districts":  # a partition: a parcel's shares sum to 1
        total = np.zeros(len(world["parcels"]))
        np.add.at(total, ans.pairs[:, 0], ans.value)
        assert np.abs(total - 1.0).max() < 1e-9
    if lane == "device":
        (call,) = calls
        assert call["clip_rows"] > 0 and call["swapped_rows"] > 0
        assert call["fan_rows"] > 0 and call["fan_triangles"] > call["fan_rows"]
        # a pair flagged for two reasons is overridden once
        assert call["host_overridden"] <= (
            call["host_band"] + call["host_shape"] + call["host_spill"]
            + call["host_cancel"]
        )
        assert call["pairs"] == ans.pairs.shape[0]


def test_the_two_lanes_are_one_answer_bit_for_bit(world):
    for name in world["themes"]:
        dev, _ = _measures(world, name, "device")
        host, _ = _measures(world, name, "host")
        for field in ("pairs", "value", "valid", "area", "sure"):
            assert np.asarray(getattr(dev, field)).tobytes() == \
                np.asarray(getattr(host, field)).tobytes(), (name, field)


def test_a_hole_ring_is_a_row_of_negative_sign(world):
    prep = world["themes"]["flood"][2]
    R = prep.right
    holes = (R.sign[: R.n] < 0)
    assert holes.sum() >= 3 and (R.chip_area[: R.n][holes] < 0).all()
    assert not R.core[: R.n][holes].any()
    # the ring rows of one chip sum to the chip's area
    assert (R.ring_len[: R.n] <= prep.vpad).all()


def test_span_children_are_the_call(world):
    _, (call,) = _measures(world, "districts", "device")
    polygons, col, prep = world["themes"]["districts"]
    with telemetry.capture() as events:
        ov.overlay_measures(world["pcol"], col, world["grid"], RES,
                            E.overlap_fraction(), prep=prep)
    spans = [e for e in events if e.get("event") == "span"]
    (root,) = [e for e in spans if e["name"] == "overlay.call"]
    # the root names its pair: a reader prices a clip row by the pad
    assert (root["right_rows"], root["vpad"], root["acc"]) == (
        prep.right.n, prep.vpad, prep.acc_name)
    kids = [e["name"] for e in spans if e.get("parent_id") == root["span_id"]]
    assert sorted(kids) == [
        "overlay.count", "overlay.emit", "overlay.glue",
        "overlay.host_override", "overlay.launch", "overlay.pull",
    ]
    # one vocabulary: the fault-site labels are no spans, and no call
    # records a stage event
    names = {e["name"] for e in spans}
    assert not names & {"overlay.device_candidates", "overlay.measures"}
    assert not [e for e in events if e.get("event") == "overlay_stage"]


def _ring(points):
    return np.asarray(points, dtype=np.float64)


def _padded(ring, V):
    out = np.repeat(ring[-1:], V, axis=0)
    out[: ring.shape[0]] = ring
    return out[None], np.array([ring.shape[0]], np.int32)


@pytest.mark.parametrize("xp_name", ["numpy", "jax"])
def test_fan_equals_the_convex_clip_on_convex_windows(xp_name):
    import jax.numpy as jnp

    xp = np if xp_name == "numpy" else jnp
    rng = np.random.default_rng(5)
    V = 8
    subs, wins, sl, wl = [], [], [], []
    for _ in range(64):
        n, m = rng.integers(3, V + 1, 2)
        th = np.sort(rng.uniform(0, 2 * np.pi, n))
        s = np.column_stack([50 + 30 * np.cos(th), 50 + 30 * np.sin(th)])
        th = np.sort(rng.uniform(0, 2 * np.pi, m))
        c = rng.uniform(20, 80, 2)
        w = np.column_stack([c[0] + 25 * np.cos(th), c[1] + 25 * np.sin(th)])
        a, la = _padded(s, V)
        b, lb = _padded(w, V)
        subs.append(a), wins.append(b), sl.append(la), wl.append(lb)
    subs, wins = np.concatenate(subs), np.concatenate(wins)
    sl, wl = np.concatenate(sl), np.concatenate(wl)
    clip, _n, spill = K.clip_area_convex(
        xp.asarray(subs), xp.asarray(sl), xp.asarray(wins), xp.asarray(wl), xp=xp)
    fan, terms, fspill = K.fan_area(
        xp.asarray(subs), xp.asarray(sl), xp.asarray(wins), xp.asarray(wl), xp=xp)
    assert not np.asarray(spill).any() and not np.asarray(fspill).any()
    np.testing.assert_allclose(np.asarray(fan), np.asarray(clip),
                               rtol=0, atol=1e-10)
    assert (np.asarray(clip) > 1.0).sum() > 20
    # symmetric: window against subject is the same area
    swapped, _n, _s = K.clip_area_convex(
        xp.asarray(wins), xp.asarray(wl), xp.asarray(subs), xp.asarray(sl), xp=xp)
    np.testing.assert_allclose(np.asarray(swapped), np.asarray(clip),
                               rtol=0, atol=1e-10)


def test_a_fan_only_pair_and_a_touch_pair():
    """A U against an L: neither convex, so only the fan can answer; and
    the same L pushed against the U's side: a touch along an edge."""
    V = 8
    u = _ring([[0, 0], [10, 0], [10, 8], [7, 8], [7, 3], [3, 3], [3, 8], [0, 8]])
    ell = _ring([[2, 1], [9, 1], [9, 6], [6, 6], [6, 2], [2, 2]])
    # area by hand: the L's bar [2,9]x[1,2] lies in the U's base (7); its
    # upright [6,9]x[2,6] meets the base [y<3] (3) and the right arm
    # [7,10]x[3,8] over [7,9]x[3,6] (6)
    want = 7.0 + 3.0 + 6.0
    assert ref.intersection_area(ref.edges_of([u]), ref.edges_of([ell])) == \
        pytest.approx(want)
    a, la = _padded(u, V)
    b, lb = _padded(ell, V)
    for subj, slen, win, wlen in ((a, la, b, lb), (b, lb, a, la)):
        got, terms, spill = K.fan_area(subj, slen, win, wlen, xp=np, width=40)
        assert got[0] == pytest.approx(want, abs=1e-12) and terms[0] >= 2
        assert not spill[0]
    touching = ell + np.array([8.0, 0.0])  # its west edge on the U's east
    t, lt = _padded(touching, V)
    area, host, _s = K.fan_rows(a, la, t, lt, np.array([False]),
                                np.array([1.0]), 1e-9, xp=np, width=40)
    assert area[0] == 0.0 and not host[0]
    area, host, _s = K.clip_rows(
        *_padded(_ring([[0, 0], [4, 0], [4, 4], [0, 4]]), 4),
        *_padded(_ring([[4, 1], [6, 1], [6, 3], [4, 3]]), 4),
        np.array([False]), np.array([1.0]), 1e-9, xp=np,
    )
    assert area[0] == 0.0 and not host[0]


def test_the_cell_local_frame_reads_the_same_at_easting_0(world):
    """The same layers 530 km to the west: every ring's cell-local
    coordinates are those of its twin up to the rounding of the f64
    tessellation at easting 530,000 (1e-10 m), so the areas are too — the
    frame holds nothing of where the data stands."""
    west = _world(0.0)
    for name in world["themes"]:
        here, _ = _measures(world, name, "device")
        there, _ = _measures(west, name, "device")
        assert np.array_equal(here.pairs, there.pairs)
        assert np.abs(here.area - there.area).max() < 1e-7
        assert ((here.value == 0.0) == (there.value == 0.0)).all()
        a, b = world["themes"][name][2], west["themes"][name][2]
        assert a.band == b.band and a.scale == b.scale == 100.0
        assert np.abs(a.right.verts).max() <= 100.0 + 1e-6
        assert np.abs(a.left.verts - b.left.verts).max() < 1e-6


def test_the_band_is_sized_from_the_arithmetic_the_device_computes_in():
    f32, f64 = np.finfo(np.float32).eps, np.finfo(np.float64).eps
    for plat in ("cpu", "gpu", "tpu"):
        assert platform.arithmetic_eps(np.float32, plat) == f32
        assert platform.arithmetic_eps("float32", plat) == f32
    assert platform.arithmetic_eps(np.float64, "cpu") == f64
    # the chip has no float64 unit: about 46 bits, 64 times numpy's step
    assert platform.arithmetic_eps(np.float64, "tpu") == 2.0 ** -46 == 64 * f64
    assert platform.arithmetic_eps("float64") == f64  # this process: the CPU
    assert ov.overlay_band("float32", 100.0, "tpu") == \
        pytest.approx(EDGE_BAND_K * f32 * 1e4)
    assert ov.overlay_band("float64", 100.0, "tpu") == \
        64 * ov.overlay_band("float64", 100.0, "cpu")
    # the rule: float32 on the chip, the oracle's float64 under x64 off it
    assert ov.overlay_acc_dtype("tpu") == "float32"
    assert ov.overlay_acc_dtype("cpu") == "float64"


def test_a_ring_over_the_pad_is_the_host_lanes_and_still_exact(monkeypatch):
    """With the device's pad cut to 8 vertices the districts' longer rings
    go to the f64 host lane, which packs them at their own length."""
    monkeypatch.setattr(ov, "MAX_CHIP_VERTS", 8)
    small = _world(530000.0)
    polygons, col, prep = small["themes"]["districts"]
    assert prep.vpad == 8 and (prep.right.ring_len > 8).any()
    ans, (call,) = _measures(small, "districts", "device")
    assert call["host_shape"] > 0 and ans.host_overridden >= call["host_shape"]
    _rp, want, got, _value, found = _against_reference(small, "districts", ans)
    assert found[want > 1e-9].all() and np.abs(got - want).max() < 1e-7


def test_the_programs_register_their_stages(world):
    """`obs/stages.py` can lower the three overlay programs again and
    names their ops by the scopes a device trace is read with."""
    from mosaic_tpu.obs import stages

    _measures(world, "districts", "device")
    tables = stages.tables(
        {"jit_overlay_count", "jit_overlay_emit", "jit_overlay_measure"}
    )
    assert set(tables["jit_overlay_count"].values()) == {"overlay.spans"}
    # the emission has its spans from the count program: it holds none
    emit = set(tables["jit_overlay_emit"].values())
    assert "overlay.emit" in emit and "overlay.spans" not in emit
    assert set(tables["jit_overlay_measure"].values()) >= {
        "overlay.gather", "overlay.clip", "overlay.fan", "overlay.fold"}


def _rank_form(lcells, rcells):
    """The dense-rank form of two sorted, sentinel-padded cell columns, as
    `prepare_overlay` lays it out: ranks among the pair's distinct cells,
    then the right sentinel, then the left; the right column as its runs'
    offsets over them."""
    real = np.concatenate([
        lcells[lcells != K.LEFT_PAD_CELL], rcells[rcells != K.RIGHT_PAD_CELL],
    ])
    ranked = np.concatenate(
        [np.unique(real), [K.RIGHT_PAD_CELL, K.LEFT_PAD_CELL]])
    rank = np.searchsorted(ranked, lcells).astype(np.int32)
    roff = K.run_offsets(
        np.searchsorted(ranked, rcells),
        ov.RANK_LADDER.bucket_for(ranked.shape[0] + 1),
    )
    return rank, roff


def _column(cells, bucket, pad):
    out = np.full(bucket, pad, np.int64)
    out[: len(cells)] = np.sort(np.asarray(cells, np.int64))
    return out


def _seeded_columns(case):
    """``(lcells, rcells, n_left)`` of one case of the span property."""
    rng = np.random.default_rng(43)
    big = 5_000_000_000_000  # cell ids past 32 bits, as BNG's are
    if case == "cells_on_one_side_only":  # evens left, odds right: no pair
        left, right = big + 2 * np.arange(70), big + 2 * np.arange(90) + 1
    elif case == "runs_of_duplicates":
        left = big + rng.integers(0, 40, 300)
        right = big + rng.integers(0, 40, 500)
    elif case == "an_empty_right_table":
        left, right = big + rng.integers(0, 40, 100), np.zeros(0, np.int64)
    elif case == "n_left_under_the_bucket":  # 3 rows of a bucket of 64
        left, right = big + np.array([5, 5, 9]), big + rng.integers(0, 12, 200)
    elif case == "full_buckets_no_pad_row":
        left, right = big + rng.integers(0, 30, 128), big + rng.integers(0, 30, 64)
    elif case == "a_random_mix":
        left = big + rng.integers(0, 4000, 3000)
        right = big + rng.integers(1000, 6000, 2000)
    else:
        raise ValueError(case)
    Lb = ov.TABLE_LADDER.bucket_for(max(len(left), 1))
    Rb = ov.TABLE_LADDER.bucket_for(max(len(right), 1))
    return (_column(left, Lb, K.LEFT_PAD_CELL),
            _column(right, Rb, K.RIGHT_PAD_CELL), len(left))


@pytest.mark.parametrize("xp_name", ["numpy", "jax"])
@pytest.mark.parametrize("case", [
    "cells_on_one_side_only", "runs_of_duplicates", "an_empty_right_table",
    "n_left_under_the_bucket", "full_buckets_no_pad_row", "a_random_mix",
    "districts", "flood",
])
def test_the_spans_read_are_the_spans_searched(world, case, xp_name):
    """`rank_spans` over the dense ranks and the right column's run offsets
    gives `pair_spans`' ``(lo, cnt)`` over the raw int64 ids, integer for
    integer, the pad rows' too (both sentinels have their rank)."""
    import jax.numpy as jnp

    if case in world["themes"]:  # the form as `prepare_overlay` made it
        prep = world["themes"][case][2]
        L, R = prep.left, prep.right
        lcells, rcells, n_left, rank, roff = L.cells, R.cells, L.n, L.rank, R.roff
        again = _rank_form(lcells, rcells)
        assert np.array_equal(rank, again[0]) and np.array_equal(roff, again[1])
        assert prep.ranks == np.unique(np.concatenate(
            [lcells[:L.n], rcells[:R.n]])).shape[0]
        assert rank.dtype == roff.dtype == np.int32 and L.roff is None
        assert set(L.dev) - set(R.dev) == {"rank"}
        assert set(R.dev) - set(L.dev) == {"roff"}
    else:
        lcells, rcells, n_left = _seeded_columns(case)
        rank, roff = _rank_form(lcells, rcells)
    want_lo, want_cnt = K.pair_spans(lcells, rcells, n_left, xp=np)
    xp = np if xp_name == "numpy" else jnp
    lo, cnt = K.rank_spans(xp.asarray(rank), xp.asarray(roff), n_left, xp=xp)
    assert np.array_equal(np.asarray(lo), want_lo)
    assert np.array_equal(np.asarray(cnt), want_cnt)
    assert (np.asarray(cnt)[n_left:] == 0).all()
    if case not in ("cells_on_one_side_only", "an_empty_right_table"):
        assert want_cnt.sum() > 0
    for n in (0, n_left // 2):  # fewer live rows than the column holds
        lo, cnt = K.rank_spans(rank, roff, n, xp=np)
        w_lo, w_cnt = K.pair_spans(lcells, rcells, n, xp=np)
        assert np.array_equal(lo, w_lo) and np.array_equal(cnt, w_cnt)


@pytest.mark.parametrize("pair_cap", [None, 1000])
@pytest.mark.parametrize("name", ["districts", "flood"])
def test_the_device_candidates_are_the_numpy_twins(world, name, pair_cap):
    """The device lane's two programs — spans read once, emission from
    them — give the twin's ``li, ri, valid`` (spans searched, on the raw
    ids), capped or not."""
    prep = world["themes"][name][2]
    L, R = prep.left, prep.right
    total, lo, cnt = ov._count_program()(L.dev["rank"], R.dev["roff"], L.n)
    assert int(total) == int(K.pair_count(L.cells, R.cells, L.n, xp=np)) > 1000
    Pb, emit_limit, overflow = ov.pair_plan(int(total), pair_cap)
    assert (overflow > 0) == (pair_cap is not None)
    li, ri, valid = ov._emit_program(Pb)(lo, cnt, emit_limit)
    w_li, w_ri, w_valid = K.emit_pairs(
        L.cells, R.cells, L.n, emit_limit, Pb, xp=np)
    for got, want in ((li, w_li), (ri, w_ri), (valid, w_valid)):
        got = np.asarray(got)
        assert got.dtype == want.dtype and np.array_equal(got, want)
    assert int(np.asarray(valid).sum()) == emit_limit


def test_a_call_reads_its_spans_once_and_the_emission_cannot_search(
        world, monkeypatch):
    """One count program a call, whose device arrays ``lo`` and ``cnt``
    are the emission's only tables: no cell column, no rank column, and
    nothing pulled to the host between the two but the total."""
    import functools
    import inspect

    import jax

    polygons, col, prep = world["themes"]["districts"]
    assert list(inspect.signature(ov._emit_program(64)).parameters) == [
        "lo", "cnt", "emit_limit", "start"]
    seen = {"count": [], "emit": []}

    def spy(kind, make):
        def made(*key):
            fn = make(*key)

            @functools.wraps(fn)
            def run(*args):
                out = fn(*args)
                seen[kind].append((args, out))
                return out

            run.lower = fn.lower  # `obs.stages` lowers what is registered
            return run
        return made

    monkeypatch.setattr(ov, "_count_program", spy("count", ov._count_program))
    monkeypatch.setattr(ov, "_emit_program", spy("emit", ov._emit_program))
    with telemetry.capture() as events:
        ans = ov.overlay_measures(world["pcol"], col, world["grid"], RES,
                                  E.overlap_fraction(), prep=prep)
    assert ans.lane == "device" and not ans.degraded
    ((count_args, (_total, lo, cnt)),) = seen["count"]
    ((emit_args, _rows),) = seen["emit"]
    assert count_args[0] is prep.left.dev["rank"]
    assert count_args[1] is prep.right.dev["roff"]
    assert emit_args[0] is lo and emit_args[1] is cnt
    assert isinstance(lo, jax.Array) and isinstance(cnt, jax.Array)
    assert isinstance(emit_args[2], int) and len(emit_args) == 4
    assert emit_args[3] == 0  # one bucket holds the stream: from rank 0
    (span,) = [e for e in events
               if e.get("event") == "span" and e.get("name") == "overlay.count"]
    assert span["spans"] == "rank" and span["ranks"] == prep.ranks
    (span,) = [e for e in events
               if e.get("event") == "span" and e.get("name") == "overlay.emit"]
    assert span["form"] == "marks"
    # the resident tables hold no int64 cell column any more
    for side in (prep.left, prep.right):
        assert "cells" not in side.dev
        assert all(np.dtype(a.dtype) != np.int64 for a in side.dev.values())


def test_rows_that_cancel_are_the_host_lanes(world, monkeypatch):
    """A parcel on an island is the shell's row less the hole's. Where the
    two roundings differ (on the chip they do: its division is not
    numpy's) the pair's sum is no area: it goes to the f64 host lane and
    reads exactly 0.0."""
    from mosaic_tpu.expr import host_oracle

    polygons, col, prep = world["themes"]["flood"]
    L, R = prep.left, prep.right
    li, ri, valid = K.emit_pairs(L.cells, R.cells, L.n, 1 << 20, 1 << 14, xp=np)
    uniq, seg, _sure, Sb, _a, _b = ov.pair_glue(prep, li, ri, valid)
    neg = (seg >= 0) & (L.sign[li] * R.sign[ri] < 0)
    assert neg.any()
    pair = int(seg[neg][0])
    folded = np.zeros(Sb)
    count = np.bincount(seg[seg >= 0], minlength=Sb)
    assert host_oracle.cancelled_pairs(prep, li, ri, seg, folded, count).size == 0
    folded[pair] = 0.25 * prep.band          # what two roundings leave
    got = host_oracle.cancelled_pairs(prep, li, ri, seg, folded, count)
    assert got.tolist() == [pair]
    folded[pair] = 5.0 * prep.band * count[pair]   # an area: the device's
    assert host_oracle.cancelled_pairs(prep, li, ri, seg, folded, count).size == 0
    # the re-answer sums the pair's rows in f64 and snaps what cancels
    over, rows = host_oracle.host_pair_override(prep, li, ri, seg, [pair])
    ans, _ = _measures(world, "flood", "host")
    at = np.nonzero((ans.pairs == uniq[pair]).all(axis=1))[0][0]
    assert rows == count[pair] and over[0] == ans.area[at]
