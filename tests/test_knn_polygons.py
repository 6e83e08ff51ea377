"""Polygon landmarks on the ring engine's block lane (PR 42): building
footprints against an all-point `KNNIndex` — the cover made without
clipping (`knn.index.polygon_cover`), queries of several seeds
(`engine.block_chunks_multi`), every (landmark, block) chunk's
point-to-polygon distances on the device (`engine.poly_block_topk_prog`).
Held against `knn/oracle.py`'s host distance, the benchmark's plain
reference and the pairs lane it replaces, on shapes chosen to hurt: L and U
footprints, a courtyard, candidates inside, on an edge, on a vertex and in
the courtyard, more than k candidates tied at 0.0, a cover of six cells,
three edge rungs in one table."""

import os
import sys

import numpy as np
import pytest

from mosaic_tpu import functions as F
from mosaic_tpu.core.index import CustomIndexSystem, GridConf
from mosaic_tpu.core.index.h3 import H3IndexSystem
from mosaic_tpu.core.tessellate import tessellate
from mosaic_tpu.dispatch import BucketLadder
from mosaic_tpu.knn import KNNFrontend, build_knn_index, engine
from mosaic_tpu.knn import frontend as knn_frontend
from mosaic_tpu.knn import index as knn_index
from mosaic_tpu.knn.oracle import host_distance
from mosaic_tpu.models import SpatialKNN
from mosaic_tpu.models import knn as knn_model
from mosaic_tpu.runtime import faults, telemetry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from benchmark.deployments.knn_footprints import pack  # noqa: E402
from benchmark.generators import buildings  # noqa: E402
from benchmark.references import knn_bruteforce, knn_polygon_bruteforce  # noqa: E402

#: 3.90625e-3 degree cells
GRID, RES, CELL = CustomIndexSystem(GridConf(-75, -73, 40, 42, 2, 1.0, 1.0)), 8, 2.0 ** -8
K = 5
X0, Y0 = -74.0, 40.5  # a cell corner of the grid


def _ngon(cx, cy, r, n):
    t = np.linspace(0, 2 * np.pi, n, endpoint=False)
    return np.column_stack([cx + r * np.cos(t), cy + 0.8 * r * np.sin(t)])


def _rect(x0, y0, x1, y1):
    return np.array([[x0, y0], [x1, y0], [x1, y1], [x0, y1]])


@pytest.fixture(scope="module")
def shaped():
    """Seven footprints and 2,400 candidates, the special ones first."""
    c = CELL
    ell = [np.array([[0, 0], [4, 0], [4, 2], [2, 2], [2, 4], [0, 4]]) * c / 8
           + [X0 + 0.3 * c, Y0 + 0.3 * c]]
    you = [np.array([[0, 0], [6, 0], [6, 5], [4, 5], [4, 2], [2, 2], [2, 5],
                     [0, 5]]) * c / 8 + [X0 + 3.2 * c, Y0 + 1.1 * c]]
    yard = [_rect(X0 + 5.5 * c, Y0 + 0.2 * c, X0 + 6.5 * c, Y0 + 1.2 * c),
            _rect(X0 + 5.75 * c, Y0 + 0.45 * c, X0 + 6.25 * c,
                  Y0 + 0.95 * c)[::-1]]
    six = [_rect(X0 + 8.1 * c, Y0 + 2.1 * c, X0 + 9.9 * c, Y0 + 4.9 * c)]
    tiny = [_rect(X0 + 1.4 * c, Y0 + 6.4 * c, X0 + 1.5 * c, Y0 + 6.45 * c)]
    gon20 = [_ngon(X0 + 4.5 * c, Y0 + 7.5 * c, 0.7 * c, 20)]
    gon40 = [_ngon(X0 + 8.5 * c, Y0 + 8.0 * c, 0.9 * c, 40),
             _ngon(X0 + 8.5 * c, Y0 + 8.0 * c, 0.3 * c, 8)[::-1]]
    fps = [ell, you, yard, six, tiny, gon20, gon40]
    rng = np.random.default_rng(42)
    wall = np.column_stack([  # 8 in the courtyard footprint's wall: ties at 0
        rng.uniform(X0 + 5.52 * c, X0 + 5.73 * c, 8),
        rng.uniform(Y0 + 0.25 * c, Y0 + 1.15 * c, 8)])
    special = np.array([
        [X0 + 6.0 * c, Y0 + 0.7 * c],     # in the courtyard
        [X0 + 6.0 * c, Y0 + 0.5 * c],     # in the courtyard, near its ring
        [X0 + 6.5 * c, Y0 + 0.7 * c],     # on the yard's outer edge
        [X0 + 5.5 * c, Y0 + 0.2 * c],     # on its vertex
        [X0 + 0.3 * c + 3 * c / 8, Y0 + 0.3 * c + 3 * c / 8],  # the L's notch
        [X0 + 0.3 * c + c / 8, Y0 + 0.3 * c + c / 8],          # inside the L
        [X0 + 3.2 * c + 3 * c / 8, Y0 + 1.1 * c + 4 * c / 8],  # the U's notch
    ])
    cand = np.concatenate([
        special, wall,
        np.column_stack([rng.uniform(X0 - 2 * c, X0 + 12 * c, 2385),
                         rng.uniform(Y0 - 2 * c, Y0 + 11 * c, 2385)]),
    ])
    return fps, pack(fps), cand


def _model(**kw):
    args = dict(index=GRID, resolution=RES, k_neighbours=K,
                approximate=False, max_iterations=64)
    args.update(kw)
    return SpatialKNN(**args)


def _table(res, n, k=K):
    ids = np.full((n, k), -1, np.int64)
    dist = np.full((n, k), np.inf)
    ids[res.landmark_id, res.rank - 1] = res.candidate_id
    dist[res.landmark_id, res.rank - 1] = res.distance
    return ids, dist


def _oracle(land, kx, cand, k=K):
    """Ranked by `knn/oracle.py`'s host distance, every pair."""
    twin = knn_index._host_twin(land, kx.shift)
    cs = cand - kx.shift
    d = np.array([[host_distance(cs[c], twin, g) for c in range(len(cand))]
                  for g in range(len(land))])
    order = np.lexsort((np.broadcast_to(np.arange(len(cand)), d.shape), d),
                       axis=1)[:, :k]
    return order, np.take_along_axis(d, order, 1)


def _spans(events, name):
    return [e for e in events
            if e.get("event") == "span" and e["name"] == name]


# --------------------------------------- against the oracle and the reference

def test_block_lane_equals_the_oracle_and_the_plain_reference(shaped):
    fps, land, cand = shaped
    kx = build_knn_index(cand, GRID, RES)
    with telemetry.capture() as events:
        res = _model().transform(land, kx)
    ids, dist = _table(res, len(land))
    want_ids, want_d = _oracle(land, kx, cand)
    assert np.array_equal(ids, want_ids)
    np.testing.assert_allclose(dist, want_d, rtol=0, atol=1e-15)
    ref_ids, ref_d = knn_polygon_bruteforce.answers(fps, cand, K)
    assert np.array_equal(ids, ref_ids)
    np.testing.assert_allclose(dist, ref_d, rtol=0, atol=1e-14)
    m = res.metrics
    assert m["unrested_landmarks"] == 0 and m["degraded"] is False
    assert m["host_landmarks"] == 0 and m["launches"] > 0
    # the courtyard footprint: 8 candidates in its wall and 2 on its
    # boundary tie at 0.0 and rank by id; the two in the courtyard do not
    assert dist[2].tolist() == [0.0] * K
    assert ids[2].tolist() == [2, 3, 7, 8, 9]
    assert 0 not in ids[2] and 1 not in ids[2]
    assert ref_d[0, 0] == 0.0 and ids[0, 0] == 5  # inside the L, not its notch
    assert 4 not in ids[0][dist[0] == 0.0] and 6 not in ids[1][dist[1] == 0.0]
    # six seed cells for the 2 x 3 footprint, one for the tiny one
    cover = _spans(events, "knn.cover")[0]
    assert cover["landmarks"] == 7 and cover["seeds"] == m["seeds"]
    seeds = knn_index.polygon_cover(kx, land, 65)
    assert np.diff(seeds.ptr)[[3, 4]].tolist() == [6, 1]
    # three edge rungs in one table, one launch each at least
    rungs = {e["vpad"] for e in _spans(events, "knn.blocks")}
    assert rungs == {8, 32, 128}
    put = _spans(events, "knn.landmarks")[0]
    assert put["vpad"] == [8, 32, 128] and put["rows"] == 7
    # a table's rows are its pad's alone, whatever the column holds
    assert put["tables"] == 3 and put["nbytes"] == 3 * 8 * 5 * knn_index.TABLE_SLOTS
    root = _spans(events, "knn.transform")[0]
    assert {cover["parent_id"], put["parent_id"]} == {root["span_id"]}
    assert root["edges"] == land.xy.shape[0] == m["edges"]
    assert 0 < root["edge_pairs"] < root["edge_pairs_padded"]
    assert root["host_landmarks"] == 0 and "inside_pairs" not in root


def test_block_lane_and_pairs_lane_agree(shaped, tmp_path):
    """A checkpoint keeps the old lane: tessellated cover, host-made pairs,
    two padded geometry columns. Same neighbours, same distinct pairs."""
    _, land, cand = shaped
    kx = build_knn_index(cand, GRID, RES)
    new = _model().transform(land, kx)
    old = _model(checkpoint_dir=str(tmp_path / "ckpt")).transform(land, kx)
    assert "seeds" not in old.metrics and old.metrics["launches"] == 0
    assert np.array_equal(new.candidate_id, old.candidate_id)
    assert np.array_equal(new.landmark_id, old.landmark_id)
    np.testing.assert_allclose(new.distance, old.distance, rtol=0, atol=1e-14)
    assert new.metrics["pairs"] == old.metrics["pairs"]
    assert new.metrics["iterations"] == old.metrics["iterations"]


def test_a_footprint_past_the_top_rung_is_answered_by_the_host(shaped):
    fps, _, cand = shaped
    big = [_ngon(X0 + 5.0 * CELL, Y0 + 4.0 * CELL, 1.3 * CELL, 200)]
    fps = fps[:3] + [big]
    land = pack(fps)
    kx = build_knn_index(cand, GRID, RES)
    res = _model().transform(land, kx)
    assert res.metrics["host_landmarks"] == 1
    ids, dist = _table(res, 4)
    ref_ids, ref_d = knn_polygon_bruteforce.answers(fps, cand, K)
    assert np.array_equal(ids, ref_ids)
    np.testing.assert_allclose(dist, ref_d, rtol=0, atol=1e-14)


def test_degraded_launches_are_answered_by_the_host_in_f64(shaped):
    fps, land, cand = shaped
    kx = build_knn_index(cand, GRID, RES)
    with faults.transient_errors(999, sites=("knn.distance",)):
        res = _model().transform(land, kx)
    assert res.metrics["degraded"] is True and res.metrics["launches"] == 0
    ids, dist = _table(res, len(land))
    ref_ids, ref_d = knn_polygon_bruteforce.answers(fps, cand, K)
    assert np.array_equal(ids, ref_ids)
    np.testing.assert_allclose(dist, ref_d, rtol=0, atol=1e-14)


def test_threshold_and_an_approximate_search(shaped):
    fps, land, cand = shaped
    kx = build_knn_index(cand, GRID, RES)
    thr = 0.2 * CELL
    res = _model(distance_threshold=thr).transform(land, kx)
    ids, dist = _table(res, len(land))
    ref_ids, ref_d = knn_polygon_bruteforce.answers(fps, cand, K)
    keep = ref_d <= thr
    assert np.array_equal(ids[keep], ref_ids[keep]) and (ids[~keep] == -1).all()
    loose = _model(approximate=True, max_iterations=4).transform(land, kx)
    assert loose.metrics["complete_landmarks"] == len(land)


def test_host_polygon_distances_are_the_references(shaped):
    fps, land, cand = shaped
    kx = build_knn_index(cand, GRID, RES)
    rings = knn_index.pack_landmark_rings(kx, land, knn_frontend.VERTEX_LADDER)
    qi = np.repeat(np.arange(len(fps)), 300)
    ci = np.tile(np.arange(300), len(fps))
    got = knn_index.host_polygon_distances(rings, qi, cand[ci] - kx.shift)
    want = np.concatenate([
        knn_polygon_bruteforce.polygon_distance(f, cand[:300, 0], cand[:300, 1])
        for f in fps])
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-14)
    assert np.array_equal(got == 0.0, want == 0.0)


# ------------------------------------------------------------ the point lane

def test_point_landmarks_take_none_of_it(monkeypatch):
    """The single-seed path: `block_chunks` on a point's ring, the point
    program, no cover, no ring table, none of the new counters."""
    rng = np.random.default_rng(3)
    cand = np.column_stack([rng.uniform(-74.1, -73.9, 3000),
                            rng.uniform(40.5, 40.7, 3000)])
    land = cand[:60] + 1e-4
    for mod, name in ((engine, "block_chunks_multi"),
                      (engine, "poly_block_topk_prog"),
                      (knn_index, "polygon_cover"),
                      (knn_index, "pack_landmark_rings")):
        monkeypatch.setattr(mod, name, lambda *a, **k: pytest.fail(name))
    kx = build_knn_index(cand, GRID, RES)
    with telemetry.capture() as events:
        res = _model().transform(land, kx)
    assert np.array_equal(_table(res, 60)[0],
                          knn_bruteforce.answers(land, cand, K)[0])
    root = _spans(events, "knn.transform")[0]
    for name in ("seeds", "edges", "edge_pairs", "edge_pairs_padded",
                 "host_landmarks"):
        assert name not in root and name not in res.metrics
    assert not _spans(events, "knn.cover") and not _spans(events, "knn.landmarks")
    assert all("vpad" not in e for e in _spans(events, "knn.blocks"))


def test_chunks_of_several_seeds_count_a_cell_once():
    rng = np.random.default_rng(1)
    cand = np.column_stack([rng.uniform(-74.1, -73.9, 4000),
                            rng.uniform(40.5, 40.7, 4000)])
    kx = build_knn_index(cand, GRID, RES)
    pb = kx.points
    cells = pb.ucells[[10, 11, 40]]
    # query 5 searches from two neighbouring cells, query 9 from one
    active, owner = np.array([5, 9]), np.array([0, 0, 1])
    many = np.zeros(12, bool)
    many[5] = True
    met = np.zeros(0, np.int64)
    seen = [set(), set()]
    for it in (1, 2, 3):
        ring = GRID.ring_cells(cells, it)
        cq, blk, fresh, new = engine.block_chunks_multi(
            pb, active, owner, ring, met, many)
        assert not np.isin(new, met).any() and (np.diff(new) > 0).all()
        met = np.union1d(met, new)
        assert (np.diff(cq) >= 0).all()
        for q in (0, 1):
            mine = {c for s in np.flatnonzero(owner == q)
                    for c in ring[s] if c >= 0 and c in set(pb.ucells)}
            new = mine - seen[q]
            seen[q] |= mine
            pos = np.searchsorted(pb.ucells, sorted(new))
            assert fresh[q] == pb.count[pos].sum()
            want = np.concatenate(
                [np.arange(pb.blk_start[p], pb.blk_start[p + 1]) for p in pos]
                + [np.zeros(0, np.int64)])
            assert np.array_equal(np.sort(blk[cq == q]), np.sort(want))
    # only the query of several seeds is remembered
    assert set((met // pb.ucells.size).tolist()) == {5}


# ----------------------------------------------------------- warm-up, stages

def test_warmed_polygon_rungs_leave_no_cold_compile(shaped, monkeypatch):
    fps, land, cand = shaped
    monkeypatch.setattr(knn_frontend, "BLOCK_LADDER", BucketLadder(16, 256, growth=4))
    kx = build_knn_index(cand, GRID, RES)
    m = _model()
    report = m.warmup(kx, pack(fps[:1]))
    assert report["block_buckets"] == 3
    fe = m._frontend[1]
    warmed = fe.signature_count()
    with telemetry.capture() as events:
        res = m.transform(land, kx)
    met = {(e["bucket"], e["vpad"]) for e in _spans(events, "knn.blocks")}
    assert len({v for _b, v in met}) == 3
    assert fe.cold_compiles == 0 and fe.signature_count() == warmed
    assert not [e for e in events if e.get("event") == "knn_compile"]
    assert res.metrics["unrested_landmarks"] == 0
    # a table of points then meets a program this warm-up left out
    m.transform(cand[:20] + 1e-4, kx)
    assert fe.cold_compiles > 0


def test_a_rung_that_fills_several_tables(shaped, monkeypatch):
    """A rung's landmarks past one table's rows take the next, in their
    order, a launch holding one table: same answers as one table each."""
    fps, _, cand = shaped
    c = CELL
    rects = [[_rect(X0 + (i % 10) * c + 0.1 * c, Y0 + (i // 10) * c + 0.2 * c,
                    X0 + (i % 10) * c + 0.5 * c, Y0 + (i // 10) * c + 0.45 * c)]
             for i in range(70)]
    table = rects[:35] + [fps[6]] + rects[35:] + [fps[5], fps[6], fps[5]]
    land = pack(table)
    kx = build_knn_index(cand, GRID, RES)
    whole = _model().transform(land, kx)
    # 256 slots: 31 landmarks a table at 8 edges, 7 at 32, 1 at 128
    monkeypatch.setattr(knn_index, "TABLE_SLOTS", 256)
    rings = knn_index.pack_landmark_rings(kx, land, knn_frontend.VERTEX_LADDER)
    assert rings.pads.tolist() == [8, 8, 8, 32, 128, 128]
    assert rings.table[[0, 30, 31, 36, 71, 73, 35, 72]].tolist() == [
        0, 0, 1, 1, 3, 3, 4, 5]
    assert rings.row[[30, 31, 71, 73, 35, 72]].tolist() == [30, 0, 0, 1, 0, 0]
    with telemetry.capture() as events:
        split = _model().transform(land, kx)
    assert _spans(events, "knn.landmarks")[0]["tables"] == 6
    for name in ("landmark_id", "candidate_id", "rank", "distance"):
        assert np.array_equal(getattr(split, name), getattr(whole, name))
    assert split.metrics["launches"] > whole.metrics["launches"]
    for name in ("pairs", "edge_pairs", "edge_pairs_padded", "host_landmarks"):
        assert split.metrics[name] == whole.metrics[name]
    ref_ids, _ = knn_polygon_bruteforce.answers(table, cand, K)
    assert np.array_equal(_table(split, len(table))[0], ref_ids)


def test_polygon_program_registers_its_stage_table(shaped):
    from mosaic_tpu.obs import stages

    _, land, cand = shaped
    stages.clear()
    n0 = stages.lowerings()
    _model().transform(land, build_knn_index(cand, GRID, RES))
    rungs = dict(stages.registered())
    assert rungs.get("jit_knn_poly_blocks") in knn_frontend.BLOCK_LADDER.buckets
    assert "jit_knn_blocks" not in rungs and stages.lowerings() == n0
    table = stages.tables({"jit_knn_poly_blocks"}, {rungs["jit_knn_poly_blocks"]})
    assert {"knn.gather", "knn.edges", "knn.topk"} <= set(
        table["jit_knn_poly_blocks"].values())
    assert "knn.distance" not in table["jit_knn_poly_blocks"].values()


# ------------------------------------------------------------------- on H3

@pytest.fixture(scope="module")
def fabric():
    fps, kinds = buildings.fabric(
        {"count": 2000, "centre": [-74.0195, 40.4825], "seed": 11})
    box = buildings.footprints_bbox(fps)
    rng = np.random.default_rng(7)
    cand = np.column_stack([rng.uniform(box[0] - 0.01, box[2] + 0.01, 20000),
                            rng.uniform(box[1] - 0.01, box[3] + 0.01, 20000)])
    h3 = H3IndexSystem()
    return fps, kinds, cand, h3, build_knn_index(cand, h3, 10)


def test_h3_cover_is_a_superset_of_the_clippers_on_2000_footprints(fabric):
    fps, _kinds, _cand, h3, kx = fabric
    land = pack(fps)
    assert kx.lattice
    seeds = knn_index.polygon_cover(kx, land, 33)
    assert seeds.tessellated == 0 and (seeds.cells == -1).all()
    nseed = np.diff(seeds.ptr)
    assert nseed.min() >= 1 and nseed.max() <= 7 and nseed.mean() < 1.35
    own = np.repeat(np.arange(len(land)), nseed)
    have = set(zip(own.tolist(), seeds.keys.tolist()))
    table = tessellate(land, h3, 10, keep_core_geoms=False)
    keys = kx.probe_keys(np.asarray(table.cell_id, np.int64))[0]
    clipped = set(zip(table.geom_id.astype(np.int64).tolist(), keys.tolist()))
    assert clipped <= have
    assert len(have) < 1.05 * len(clipped)  # and hardly more than the cover
    # every point of a footprint lies in a seed cell: 8 points an edge
    pts, who = [], []
    for g, rings in enumerate(fps[:500]):
        for r in rings:
            a, b = r, np.roll(r, -1, axis=0)
            t = np.linspace(0, 1, 8, endpoint=False)[None, :, None]
            pts.append((a[:, None] + (b - a)[:, None] * t).reshape(-1, 2))
            who.append(np.full(pts[-1].shape[0], g))
    pts, who = np.concatenate(pts), np.concatenate(who)
    pk = kx.probe_keys(knn_index.assign_cells(h3, 10, pts))[0]
    assert set(zip(who.tolist(), pk.tolist())) <= have


def test_h3_transform_clips_nothing_and_equals_the_reference(fabric, monkeypatch):
    fps, kinds, cand, h3, kx = fabric
    pick = np.sort(np.concatenate([np.flatnonzero(kinds == 2)[:12],
                                   np.flatnonzero(kinds != 2)[:228]]))
    sample = [fps[i] for i in pick]
    for mod in (knn_index, knn_model):
        monkeypatch.setattr(mod, "tessellate",
                            lambda *a, **k: pytest.fail("tessellate called"))
    monkeypatch.setattr(engine, "ring_pairs",
                        lambda *a, **k: pytest.fail("a pair made on the host"))
    m = SpatialKNN(index=h3, resolution=10, k_neighbours=K, approximate=False,
                   max_iterations=32)
    res = m.transform(pack(sample), kx)
    assert res.metrics["host_landmarks"] == 0
    assert res.metrics["unrested_landmarks"] == 0
    assert res.metrics["seeds"] >= len(sample)
    ids, dist = _table(res, len(sample))
    ref_ids, ref_d = knn_polygon_bruteforce.answers(sample, cand, K)
    assert np.array_equal(ids, ref_ids)
    np.testing.assert_allclose(dist, ref_d, rtol=0, atol=1e-14)
    assert (ref_d == 0.0).sum() > 0


def test_h3_another_table_after_warmup_compiles_nothing(fabric, monkeypatch):
    """The compiled shapes hold nothing of the landmark column: after a
    warm-up on a sample, tables of other sizes and other counts an edge
    rung launch what it compiled, by the backend's own count."""
    from mosaic_tpu.dispatch import backend_compiles

    fps, kinds, _cand, h3, kx = fabric
    monkeypatch.setattr(knn_frontend, "BLOCK_LADDER", BucketLadder(64, 1024, growth=4))
    large, other = np.flatnonzero(kinds == 2), np.flatnonzero(kinds != 2)
    m = SpatialKNN(index=h3, resolution=10, k_neighbours=K, approximate=False,
                   max_iterations=32)
    m.warmup(kx, pack(fps[:3]))
    fe = m._frontend[1]
    warmed, c0 = fe.signature_count(), backend_compiles()
    assert c0 is not None
    counts = []
    for pick in (np.r_[other[:150], large[:9]], np.r_[other[200:260], large[9:30]],
                 other[300:700]):
        land = pack([fps[i] for i in np.sort(pick)])
        rings = knn_index.pack_landmark_rings(kx, land, knn_frontend.VERTEX_LADDER)
        assert {t.shape for t in rings.tables} <= {
            (knn_index.table_rows(v), 5, v) for v in (8, 32, 128)}
        counts.append(tuple(np.bincount(rings.table, minlength=3)))
        assert m.transform(land, kx).metrics["unrested_landmarks"] == 0
    assert len(set(counts)) == 3
    assert backend_compiles() == c0
    assert fe.cold_compiles == 0 and fe.signature_count() == warmed


@pytest.mark.parametrize("against", ["one-slab", "host-oracle"])
def test_h3_slabbed_schedule_is_the_one_slab_schedule_and_the_oracles(
    against, fabric, monkeypatch
):
    """PR 45 on polygon landmarks: `engine.SLAB_KEYS` down, so an iteration
    of 700 footprints runs several slabs — queries of several seeds among
    them, whose met cells grow slab by slab and iteration by iteration, and
    three edge rungs in every early iteration, each table carrying its own
    remainder from slab to slab, and one footprint the host answers. The answers are the one-slab schedule's to
    the bit with its launches cut for cut, and the f64 host oracle's (every
    launch refused) within its rounding."""
    fps, kinds, cand, h3, _kx = fabric
    kx = build_knn_index(cand[:400], h3, 10)  # sparse: landmarks walk rings
    pick = np.sort(np.r_[np.flatnonzero(kinds == 2)[:40],
                         np.flatnonzero(kinds != 2)[:660]])
    box = buildings.footprints_bbox(fps)
    big = [_ngon((box[0] + box[2]) / 2, (box[1] + box[3]) / 2, 4e-4, 200)]
    land = pack([fps[i] for i in pick[:350]] + [big] + [fps[i] for i in pick[350:]])
    monkeypatch.setattr(knn_frontend, "BLOCK_LADDER", BucketLadder(16, 64, growth=4))

    def run(slab_keys):
        monkeypatch.setattr(engine, "SLAB_KEYS", slab_keys)
        m = SpatialKNN(index=h3, resolution=10, k_neighbours=K,
                       approximate=False, max_iterations=32)
        with telemetry.capture() as events:
            return m.transform(land, kx), events

    cut, events = run(1500)
    (root,) = _spans(events, "knn.transform")
    assert root["iterations"] >= 3 and root["hidden_s"] > 0
    assert root["slabs"] >= root["iterations"] + 6
    # (one footprint is past the vertex ladder: the host answers its chunks
    # behind the launches of its slab)
    assert root["seeds"] > len(pick) and root["host_landmarks"] == 1
    first = [e for e in _spans(events, "knn.blocks")][:40]
    assert {e["vpad"] for e in first} == {8, 32, 128}
    if against == "one-slab":
        one, ev1 = run(1 << 40)
        (root1,) = _spans(ev1, "knn.transform")
        assert root1["slabs"] == root1["iterations"] and root1["hidden_s"] == 0
        for f in ("landmark_id", "candidate_id", "distance", "rank"):
            assert np.array_equal(getattr(one, f), getattr(cut, f)), f
        for f in ("iterations", "pairs", "pairs_padded", "launches",
                  "edge_pairs", "edge_pairs_padded", "edge_rows", "seeds"):
            assert one.metrics[f] == cut.metrics[f], f
        cuts = [sorted((e["vpad"], e["bucket"], e["chunks"], e["heads"])
                       for e in _spans(ev, "knn.blocks"))
                for ev in (ev1, events)]
        assert cuts[0] == cuts[1]
        return
    with faults.transient_errors(10 ** 6, sites=("knn.distance",)):
        host, _ = run(1500)
    assert host.metrics["degraded"] is True and host.metrics["launches"] == 0
    for f in ("landmark_id", "candidate_id", "rank"):
        assert np.array_equal(getattr(host, f), getattr(cut, f)), f
    np.testing.assert_allclose(cut.distance, host.distance, rtol=0, atol=1e-14)
    assert (host.distance == 0.0).sum() == (cut.distance == 0.0).sum() > 0


def test_h3_footprint_too_wide_for_the_lattice_cover_is_clipped(fabric):
    fps, _kinds, cand, h3, kx = fabric
    box = buildings.footprints_bbox(fps)
    wide = [_rect(box[0] + 0.002, box[1] + 0.002, box[0] + 0.062, box[1] + 0.004)]
    sample = fps[:5] + [wide]
    land = pack(sample)
    seeds = knn_index.polygon_cover(kx, land, 33)
    assert seeds.tessellated == 1 and np.diff(seeds.ptr)[5] > 30
    assert (seeds.cells[seeds.ptr[5]:] >= 0).all()
    m = SpatialKNN(index=h3, resolution=10, k_neighbours=K, approximate=False,
                   max_iterations=32)
    ids, dist = _table(m.transform(land, kx), 6)
    ref_ids, ref_d = knn_polygon_bruteforce.answers(sample, cand, K)
    assert np.array_equal(ids, ref_ids)
    np.testing.assert_allclose(dist, ref_d, rtol=0, atol=1e-14)


# ------------------------------------------------------- the voronoi lane

def test_voronoi_lane_degraded_twice_keeps_the_first_flag():
    """The one-shot cover degrades, then the ring lane of the queries the
    walk could not bound degrades too: ``degraded or fdeg`` asked an
    array for its truth value (PERF.md section 7, PR 41)."""
    grid = CustomIndexSystem(GridConf(-180, 180, -90, 90, 2, 10.0, 10.0))
    rng = np.random.default_rng(5)
    polys = []
    for _ in range(40):
        x, y, w = rng.uniform(-25, 33), rng.uniform(-25, 18), rng.uniform(0.5, 1.5)
        polys.append(f"POLYGON(({x} {y}, {x + w} {y}, {x + w} {y + w},"
                     f" {x} {y + w}, {x} {y}))")
    for _ in range(12):
        x, y = rng.uniform(-25, 32), rng.uniform(-25, 17)
        polys.append(f"POLYGON(({x} {y}, {x + 2} {y}, {x + 2} {y + 0.6},"
                     f" {x + 0.6} {y + 0.6}, {x + 0.6} {y + 2},"
                     f" {x} {y + 2}, {x} {y}))")
    kx = build_knn_index(F.st_geomfromwkt(np.array(polys)), grid, 3)
    ladders = dict(row_ladder=BucketLadder(8, 512),
                   pair_ladder=BucketLadder(64, 4096))
    # a walk's bound past 8 rings sends its query to the ring lane: 13 of 24
    fv = KNNFrontend(kx, lane="voronoi", max_iterations=8, **ladders)
    fr = KNNFrontend(kx, lane="voronoi", max_iterations=8, **ladders)
    q = np.column_stack([rng.uniform(-20, 30, 24), rng.uniform(-20, 15, 24)])
    sound, _ = fr.dispatch(q, 3)
    assert 0 < fr.stats["voronoi_fallback"] < 24
    with faults.transient_errors(999, sites=("knn.distance",)):
        out, _ = fv.dispatch(q, 3)
    from mosaic_tpu.runtime.errors import DegradedResult

    assert isinstance(out, DegradedResult)
    assert fv.stats["voronoi_fallback"] == fr.stats["voronoi_fallback"]
    np.testing.assert_allclose(np.asarray(out), np.asarray(sound),
                               rtol=0, atol=1e-12)
