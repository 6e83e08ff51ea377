"""The distance join (`mosaic_tpu.sql.proximity.dwithin_join`) at small
size on the CPU, seeded: the frontend against the plain reference pair for
pair — LINESTRING and POINT rows, with and without a key, one table and
two, a scalar radius and one a row, the H3 lattice and a grid without one —
the host twin of the kernel against the device kernel under x64, the band,
the cap, the ladders, the degradation."""

import numpy as np
import pytest

from mosaic_tpu.core.index.bng import BNGIndexSystem
from mosaic_tpu.core.index.h3 import H3IndexSystem
from mosaic_tpu.dispatch import backend_compiles
from mosaic_tpu.kernels import overlay as K
from mosaic_tpu.kernels import proximity as KP
from mosaic_tpu.knn.index import points_column
from mosaic_tpu.runtime import telemetry
from mosaic_tpu.sql import proximity as P
from mosaic_tpu.sql.join import OVERFLOW
from mosaic_tpu.sql.proximity import dwithin_join, prepare_dwithin, warmup_dwithin

from test_proximity_reference import FLEET, GEN, REF, csr, lines

H3 = H3IndexSystem()


def fleet_table(vessels=128, windows=2, seed=17):
    t = GEN.table(dict(FLEET, vessels=vessels), windows, seed)
    return t, lines(t["xy"], t["offsets"])


def reference(t, rows=None, key=None, radius=None):
    r = t["radius"] if radius is None else radius
    return REF.within(t["xy"], t["offsets"], r, rows=rows, key=key)[0]


@pytest.fixture(scope="module")
def table():
    return fleet_table()


# ------------------------------------------------- frontend = reference


def test_self_join_with_key_and_a_radius_a_row(table):
    t, col = table
    spans = []
    telemetry.add_observer(spans.append)
    try:
        got = dwithin_join(col, radius=t["radius"], key=t["window"],
                           index_system=H3, resolution=9)
    finally:
        telemetry.remove_observer(spans.append)
    want = reference(t, key=t["window"])
    assert got.lane == "device" and not got.degraded and got.overflow == 0
    assert len(want) > 300 and np.array_equal(got.pairs, want)
    assert (got.pairs[:, 0] < got.pairs[:, 1]).all()
    m = got.metrics
    assert m["tracks"] == 256 and m["tessellated"] == 0 and m["vpad"] == 16
    assert m["segments"] == int((t["pings"] - 1).sum())
    assert m["hits"] == len(want) and m["raw_candidates"] >= m["hits"]
    assert m["rows_per_pair"] >= 1.0 and m["cover_rows"] > 256
    # every planted transfer is found
    p = t["planted"]
    planted = set(zip(p[:, 0] * 128 + p[:, 1], p[:, 0] * 128 + p[:, 2]))
    assert planted and planted <= set(map(tuple, got.pairs))
    # the call's spans: one root, every child under it
    names = [e["name"] for e in spans if e.get("event") == "span"]
    for child in ("cover", "count", "emit", "launch", "pull", "glue",
                  "host_band", "call"):
        assert "proximity." + child in names, child
    root = next(e for e in spans if e.get("name") == "proximity.call")
    assert root["hits"] == len(want) and root["vpad"] == 16
    emits = [e for e in spans if e.get("name") == "proximity.emit"]
    assert emits and all(e["form"] == "marks" for e in emits)


def test_no_key_and_a_scalar_radius(table):
    t, col = table
    got = dwithin_join(col, radius=2.5e-3, index_system=H3, resolution=9)
    want = reference(t, radius=2.5e-3)
    # with no key the two windows' tracks of one vessel meet too
    assert np.array_equal(got.pairs, want) and len(want) > 600


def test_point_rows_are_the_one_vertex_case(table):
    t, _ = table
    xy = t["xy"][t["offsets"][:-1]]  # every track's first ping
    off = np.arange(len(xy) + 1)
    got = dwithin_join(points_column(xy), radius=t["radius"], key=t["window"],
                       index_system=H3, resolution=9)
    want = REF.within(xy, off, t["radius"], key=t["window"])[0]
    assert len(want) > 200 and np.array_equal(got.pairs, want)
    assert got.metrics["segments"] == 0


def test_two_tables_lines_against_points(table):
    t, col = table
    rng = np.random.default_rng(5)
    at = t["xy"][rng.choice(t["xy"].shape[0], 300)] + rng.normal(0, 2e-3, (300, 2))
    pkey = rng.integers(0, 2, 300)
    got = dwithin_join(col, points_column(at), radius=(t["radius"], 1e-3),
                       key=(t["window"], pkey), index_system=H3, resolution=9)
    # the reference on the two tables laid end to end, left x right kept
    n = len(col)
    xy = np.concatenate([t["xy"], at])
    off = np.concatenate([t["offsets"], t["offsets"][-1] + 1 + np.arange(300)])
    both = REF.within(xy, off, np.concatenate([t["radius"], np.full(300, 1e-3)]),
                      key=np.concatenate([t["window"], pkey]))[0]
    want = both[(both[:, 0] < n) & (both[:, 1] >= n)] - [0, n]
    assert len(want) > 100 and np.array_equal(got.pairs, want)
    assert got.metrics["tracks"] == n + 300


def test_a_line_longer_than_a_piece_is_cut_and_answered_whole():
    rng = np.random.default_rng(3)
    tracks = []
    for _ in range(40):
        n = int(rng.integers(2, 60))
        start = np.array([-90.4, 28.1]) + rng.uniform(0, 0.05, 2)
        tracks.append(start + np.cumsum(rng.normal(0, 1.5e-3, (n, 2)), axis=0))
    xy, off = csr(tracks)
    got = dwithin_join(lines(xy, off), radius=1.5e-3, index_system=H3, resolution=9)
    want = REF.within(xy, off, 1.5e-3)[0]
    assert len(want) > 50 and np.array_equal(got.pairs, want)
    prep = prepare_dwithin(lines(xy, off), radius=1.5e-3, index_system=H3, resolution=9)
    assert prep.left.owner.shape[0] > 40  # more pieces than lines
    assert prep.left.length.max() == P.PIECE_VERTS


def test_a_grid_without_a_lattice_takes_the_polygon_path_counted():
    rng = np.random.default_rng(9)
    tracks = [np.array([400000.0, 300000.0]) + rng.uniform(0, 3000, 2)
              + np.cumsum(rng.normal(0, 150, (int(rng.integers(1, 7)), 2)), axis=0)
              for _ in range(30)]
    xy, off = csr(tracks)
    col = lines(xy, off)
    col.srid[:] = 27700
    got = dwithin_join(col, radius=250.0, index_system=BNGIndexSystem(), resolution=3)
    want = REF.within(xy, off, 250.0)[0]
    assert len(want) > 20 and np.array_equal(got.pairs, want)
    assert got.metrics["tessellated"] == 30


def test_input_is_refused_where_it_is_not_lines_and_points(table):
    from mosaic_tpu.core.geometry import wkt

    poly = wkt.from_wkt(["POLYGON ((0 0, 1 0, 1 1, 0 1, 0 0))"])
    with pytest.raises(ValueError, match="LINESTRING and POINT"):
        dwithin_join(poly, radius=1.0, index_system=H3, resolution=9)
    with pytest.raises(ValueError, match="radius"):
        dwithin_join(table[1], radius=-1.0, index_system=H3, resolution=9)
    with pytest.raises(ValueError, match="lane"):
        dwithin_join(table[1], radius=1e-3, index_system=H3, resolution=9, lane="x")


# ------------------------------------------------ the kernel and its twin


def test_host_twin_is_the_device_kernel_under_x64(table):
    t, col = table
    prep = prepare_dwithin(col, radius=t["radius"], key=t["window"],
                           index_system=H3, resolution=9)
    assert prep.acc_name == "float64"
    spans = P._host_spans(prep)
    from mosaic_tpu.obs import trace

    with trace.span("proximity.call") as call:
        dev = P._device_launch(prep, None, call)[0]()
    host = P._host_codes(prep, spans, spans[3])
    assert dev.shape == host.shape and np.array_equal(dev, host)
    assert set(np.unique(host)) <= {KP.MISS, KP.HIT, KP.BAND}
    # the distances themselves, bit for bit
    import jax.numpy as jnp

    rng = np.random.default_rng(2)
    a, b = rng.normal(0, 1e-2, (2, 2, 16, 500))
    b[0, :, 250:] += 0.1  # half of the pairs well apart
    d_np, x_np = KP.piece_distance(a[0], a[1], b[0], b[1], xp=np)
    d_j, x_j = KP.piece_distance(*(jnp.asarray(v) for v in (a[0], a[1], b[0], b[1])))
    assert np.array_equal(np.asarray(d_j), d_np) and np.array_equal(np.asarray(x_j), x_np)
    assert x_np.any() and not x_np.all()
    # the whole-line twin the band uses: the reference's distances
    got = dwithin_join(col, prep=prep).pairs
    d = P.host_line_distances(prep.left, prep.right, got[:, 0], got[:, 1])
    want = REF.distances(t["xy"], t["offsets"], got[:, 0], got[:, 1])
    assert np.allclose(d, want, rtol=1e-12, atol=0) and (d[want == 0] == 0).all()


def test_crossing_test_out_misses_lines_that_only_cross():
    """An X of two long segments, their ends far apart: 0 apart where they
    cross; without the crossing test the least end-to-segment distance is
    all that is left."""
    x = np.array([[[0.0, 0.0], [1.0, 1.0]], [[0.0, 1.0], [1.0, 0.0]]])
    pad = np.repeat(x[:, 1:], 14, axis=1)
    v = np.concatenate([x, pad], axis=1)  # (2, 16, 2)
    args = (v[0, :, :1], v[0, :, 1:], v[1, :, :1], v[1, :, 1:])
    d2, crosses = KP.piece_distance(*args, xp=np)
    assert crosses[0] and np.isclose(np.sqrt(d2[0]), np.sqrt(0.5))
    crosses = np.zeros_like(crosses)  # the crossing test taken out
    code = KP.classify(d2, crosses, np.array([0.1]), np.array([1e-9]),
                       np.array([True]), xp=np)
    assert code[0] == KP.MISS
    xy, off = csr([x[0], x[1]])
    got = dwithin_join(lines(xy * 1e-2 + [-90.0, 28.0], off), radius=5e-4,
                       index_system=H3, resolution=9)
    assert got.pairs.tolist() == [[0, 1]]


def test_a_pair_on_the_threshold_goes_through_the_host_band():
    # two parallel segments exactly r + r apart (binary fractions): the
    # device's distance is within its band of the threshold
    tracks = [np.array([[-90.0, 28.0], [-89.9921875, 28.0]]),
              np.array([[-90.0, 28.00390625], [-89.9921875, 28.00390625]]),
              np.array([[-90.0, 28.0009765625], [-89.9921875, 28.0009765625]])]
    xy, off = csr(tracks)
    events = []
    telemetry.add_observer(events.append)
    try:
        got = dwithin_join(lines(xy, off), radius=0.001953125, index_system=H3,
                           resolution=9)
    finally:
        telemetry.remove_observer(events.append)
    assert got.pairs.tolist() == [[0, 1], [0, 2], [1, 2]]  # d == thr is within
    assert got.metrics["band_pairs"] == 1
    band = [e for e in events if e.get("name") == "proximity.host_band"]
    assert band and band[0]["pairs"] == 1 and band[0]["inside"] == 1
    # the band is sized from the arithmetic the device computes in
    assert P.dwithin_band("float32", "tpu") == 64 * np.finfo(np.float32).eps
    assert P.dwithin_band("float64", "tpu") == 64 * 2.0 ** -46
    assert P.dwithin_band("float64", "cpu") == 64 * np.finfo(np.float64).eps


def test_float32_tables_keep_a_nearby_pairs_frame_under_a_millimetre(table):
    """What the TPU stores: float32 piece rows. The origin's two words
    keep the frame exact to float32's step of the FRAME's extent, not of
    the longitude."""
    t, col = table
    prep = prepare_dwithin(col, radius=t["radius"], key=t["window"],
                           index_system=H3, resolution=9)
    t32 = P._to_acc(prep.left.table, np.dtype("float32"))
    pairs = dwithin_join(col, prep=prep).pairs[:400]
    pa = np.searchsorted(prep.left.owner, pairs[:, 0])
    pb = np.searchsorted(prep.left.owner, pairs[:, 1])
    ax, ay, bx, by, thr, extent = KP.pair_frame(t32[pa].T, t32[pb].T, 16, xp=np)
    ex = KP.pair_frame(prep.left.table[pa].T, prep.left.table[pb].T, 16, xp=np)
    worst = max(np.abs(ax - ex[0]).max(), np.abs(bx - ex[2]).max(),
                np.abs(by - ex[3]).max())
    assert extent.dtype == np.float32 and extent.max() < 0.1
    # a few float32 steps of the frame's extent (two millimetres at 0.1
    # degrees), where a float32 longitude alone steps 0.75 m
    assert worst < 4 * np.finfo(np.float32).eps * extent.max() < 5e-8


# ------------------------------------------------- spans, caps, the ladders


def test_rank_spans_after_self_and_sliced_emission():
    rank = np.array([0, 0, 0, 1, 2, 2, 5, 5], np.int32)  # 2 pad rows
    roff = K.run_offsets(rank[:6], 8)
    lo, cnt = K.rank_spans(rank, roff, 6, xp=np, after_self=True)
    assert cnt.tolist() == [2, 1, 0, 0, 1, 0, 0, 0] and lo.tolist()[:6] == [1, 2, 3, 4, 5, 6]
    li, ri, valid = K.emit_spans(lo, cnt, 4, 8, xp=np)
    assert list(zip(li[valid], ri[valid])) == [(0, 1), (0, 2), (1, 2), (4, 5)]
    # a slice: ranks 2.. of the same stream
    li, ri, valid = K.emit_spans(lo, cnt, 4, 4, xp=np, start=2)
    assert list(zip(li[valid], ri[valid])) == [(1, 2), (4, 5)]
    import jax.numpy as jnp

    dl, dc = K.rank_spans(jnp.asarray(rank), jnp.asarray(roff), 6, after_self=True)
    assert np.array_equal(dl, lo) and np.array_equal(dc, cnt)
    # two tables: the spans are the searched ones
    lo2, cnt2 = K.rank_spans(rank, roff, 6, xp=np)
    assert cnt2.tolist() == [3, 3, 3, 1, 2, 2, 0, 0]


def _span_set(case: str, nl: int = 64):
    """``(lo, cnt)`` int32 span sets of ``nl`` table rows, built to break
    a form that marks the rows' offsets on the slots and sums them."""
    rng = np.random.default_rng(49)
    cnt = np.zeros(nl, np.int32)
    if case == "zero_runs_at_the_head_the_tail_and_between":
        cnt[5:9] = (3, 1, 4, 2)
        cnt[20:23] = (7, 0, 2)
        cnt[40] = 5
    elif case == "one_row_spans_several_slices":
        cnt[3], cnt[4], cnt[9] = 2, 50, 3
    elif case == "every_count_zero":
        pass
    elif case == "every_row_live":
        cnt[:] = rng.integers(1, 4, nl)
    elif case == "pad_rows_behind":
        cnt[:40] = rng.integers(0, 3, 40)
    elif case == "one_live_row_last":
        cnt[-1] = 9
    elif case == "one_live_row_first":
        cnt[0] = 9
    elif case == "a_self_joins_runs":  # every run's last row counts zero
        rank = np.sort(rng.integers(0, 12, nl)).astype(np.int32)
        roff = K.run_offsets(rank, 16)
        lo, cnt = K.rank_spans(rank, roff, nl - 6, xp=np, after_self=True)
        return lo.astype(np.int32), cnt.astype(np.int32)
    else:
        raise ValueError(case)
    return rng.integers(0, 1000, nl).astype(np.int32), cnt


def _assert_same_rows(got, want, note):
    """``(li, ri, valid)`` of the device against the twin's: array for
    array, dtype for dtype."""
    for g, w in zip(got, want):
        assert np.asarray(g).dtype == w.dtype and np.array_equal(g, w), note


@pytest.mark.parametrize("bucket", [16, 64])
@pytest.mark.parametrize("case", [
    "zero_runs_at_the_head_the_tail_and_between",
    "one_row_spans_several_slices",
    "every_count_zero",
    "every_row_live",
    "pad_rows_behind",
    "one_live_row_last",
    "one_live_row_first",
    "a_self_joins_runs",
])
def test_the_device_emission_is_the_searched_twin(case, bucket):
    """The device finds a slot's left row by a scatter of the rows' span
    offsets and a running sum (`kernels.overlay._rows_by_marks`); the
    numpy twin searches. ``li``, ``ri``, ``valid`` agree array for array
    and dtype for dtype: unsliced and at every slice of the stream —
    ``start`` at a span's first slot, inside a span, at ``total`` and past
    it — capped under ``total`` or not."""
    import jax.numpy as jnp

    from mosaic_tpu.sql.overlay import _emit_program

    lo, cnt = _span_set(case)
    off = np.cumsum(cnt) - cnt
    total = int(cnt.sum())
    live = np.flatnonzero(cnt)
    starts = {0, total, total + 1, total + 3 * bucket, max(total - 1, 0)}
    starts.update(range(0, total + bucket, bucket))           # every slice
    starts.update(int(off[i]) for i in live[:6])               # a span's first slot
    starts.update(int(off[i] + cnt[i] // 2) for i in live[:6])  # inside a span
    starts.update(int(off[i] + cnt[i] - 1) for i in live[-3:])  # a span's last slot
    emit = _emit_program(bucket)
    dlo, dcnt = jnp.asarray(lo), jnp.asarray(cnt)
    checked = 0
    for limit in {total, total // 2, max(total - 1, 0), total + 5}:
        # unsliced: the overlay's call
        _assert_same_rows(emit(dlo, dcnt, limit),
                          K.emit_spans(lo, cnt, limit, bucket, xp=np), (limit,))
        for start in sorted(starts):
            got = emit(dlo, dcnt, limit, np.int32(start))
            _assert_same_rows(
                got, K.emit_spans(lo, cnt, limit, bucket, xp=np, start=start),
                (limit, start))
            checked += int(np.asarray(got[2]).sum())
    assert (checked > 0) == (total > 0)
    # the live slots of the whole stream name each row as often as it counts
    if total:
        li = np.concatenate([
            np.asarray(emit(dlo, dcnt, total, np.int32(s))[0])[
                : max(min(bucket, total - s), 0)]
            for s in range(0, total, bucket)
        ])
        assert np.array_equal(np.bincount(li, minlength=cnt.size), cnt)


def test_random_span_sets_emit_the_searched_twins_rows():
    """Three hundred seeded span sets — zero-count runs, pad rows, caps,
    slices from the middle and past the end — through one compiled
    bucket: no difference from the search."""
    import jax.numpy as jnp

    from mosaic_tpu.sql.overlay import _emit_program

    rng = np.random.default_rng(4949)
    emit = _emit_program(32)
    for _ in range(300):
        nl = 128
        n_left = int(rng.integers(0, nl + 1))
        cnt = np.zeros(nl, np.int32)
        cnt[:n_left] = rng.integers(0, 5, n_left) * (rng.random(n_left) < rng.random())
        lo = rng.integers(0, 500, nl).astype(np.int32)
        total = int(cnt.sum())
        start = int(rng.integers(0, total + 40))
        limit = int(rng.integers(0, total + 10))
        _assert_same_rows(
            emit(jnp.asarray(lo), jnp.asarray(cnt), limit, np.int32(start)),
            K.emit_spans(lo, cnt, limit, 32, xp=np, start=start),
            (start, limit, cnt.tolist()))


def test_a_cap_that_cuts_rows_yields_the_overflow_row(table):
    t, col = table
    full = dwithin_join(col, radius=t["radius"], key=t["window"],
                        index_system=H3, resolution=9)
    cut = dwithin_join(col, radius=t["radius"], key=t["window"],
                       index_system=H3, resolution=9, pair_cap=500)
    assert cut.overflow == full.metrics["raw_candidates"] - 500 > 0
    assert cut.pairs[-1].tolist() == [OVERFLOW, OVERFLOW]
    kept = set(map(tuple, cut.pairs[:-1]))
    assert kept and kept < set(map(tuple, full.pairs))
    host = dwithin_join(col, radius=t["radius"], key=t["window"],
                        index_system=H3, resolution=9, pair_cap=500, lane="host")
    assert np.array_equal(host.pairs, cut.pairs) and host.lane == "host"


def test_a_second_table_of_another_size_compiles_nothing(table, monkeypatch):
    # (small slices, so that the stream is several launches long)
    monkeypatch.setattr(P, "CHUNK_PAIRS", 1 << 12)
    t, col = table
    kw = dict(index_system=H3, resolution=9)
    warmup_dwithin(col, radius=t["radius"], key=t["window"], **kw)
    before = backend_compiles()
    for vessels, seed in ((100, 3), (160, 4), (128, 5)):
        t2, col2 = fleet_table(vessels, 2, seed)
        got = dwithin_join(col2, radius=t2["radius"], key=t2["window"], **kw)
        assert got.lane == "device" and got.metrics["launches"] > 1
        assert np.array_equal(got.pairs, reference(t2, key=t2["window"]))
    assert backend_compiles() == before


def test_a_device_fault_past_the_budget_degrades_to_the_host_twin(table, monkeypatch):
    t, col = table
    want = dwithin_join(col, radius=t["radius"], key=t["window"],
                        index_system=H3, resolution=9, lane="host")

    def boom(*a, **k):
        raise RuntimeError("injected device fault")

    monkeypatch.setattr(P, "_segpair_program", lambda *a: boom)
    events = []
    telemetry.add_observer(events.append)
    try:
        got = dwithin_join(col, radius=t["radius"], key=t["window"],
                           index_system=H3, resolution=9)
    finally:
        telemetry.remove_observer(events.append)
    assert got.lane == "host" and got.degraded
    assert "injected device fault" in got.reason
    assert np.array_equal(got.pairs, want.pairs)
    assert any(e.get("event") == "degraded" for e in events)
