"""The array ring engine (`mosaic_tpu/knn/engine.py`, PR 33): one search
under `SpatialKNN.transform` and `KNNFrontend.dispatch`, held against the
benchmark's plain brute force on clustered points (a dense head, a sparse
tail, landmarks with fewer than k candidates in reach), against a
per-query loop written the way the deleted one was, and piece by piece
(merge, chunk folding across launches, ring cells, the rest criterion).
Since PR 41 the block lane pulls one row a landmark a launch (the heads):
held bit for bit against the per-chunk pull it replaced, kept here."""

import os
import sys

import numpy as np
import pytest

from mosaic_tpu import functions as F
from mosaic_tpu.core.index import CustomIndexSystem, GridConf
from mosaic_tpu.core.index.h3 import H3IndexSystem
from mosaic_tpu.dispatch import BucketLadder
from mosaic_tpu.knn import (
    KNNFrontend, brute_force_knn, build_knn_index, decode_knn, engine,
)
from mosaic_tpu.knn import frontend as knn_frontend
from mosaic_tpu.knn.index import expand_ranges, point_coords, points_column
from mosaic_tpu.models import SpatialKNN
from mosaic_tpu.runtime import telemetry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from benchmark.references import knn_bruteforce  # noqa: E402

BOX = (-74.3, 40.4, -73.6, 41.0)
#: 3.9e-3 degree cells: the cluster fills a few, the background one in ten
GRID, RES = CustomIndexSystem(GridConf(-75, -73, 40, 42, 2, 1.0, 1.0)), 8
K = 5


@pytest.fixture(scope="module")
def clustered():
    """Candidates: 3,000 in one 300 m cluster, 900 uniform over the box.
    Landmarks: 40 in the cluster (ring 1 holds hundreds), 60 uniform (they
    walk rings), 2 in an empty corner region with candidates removed
    around them."""
    rng = np.random.default_rng(33)
    centre = np.array([-73.98, 40.75])
    cand = np.concatenate([
        centre + rng.normal(0, 0.003, (3000, 2)),
        np.column_stack([rng.uniform(BOX[0], BOX[2], 900),
                         rng.uniform(BOX[1], BOX[3], 900)]),
    ])
    land = np.concatenate([
        centre + rng.normal(0, 0.003, (40, 2)),
        np.column_stack([rng.uniform(BOX[0], BOX[2], 60),
                         rng.uniform(BOX[1], BOX[3], 60)]),
    ])
    return land, cand


def _table(res, n, k):
    ids = np.full((n, k), -1, np.int64)
    dist = np.full((n, k), np.inf)
    ids[res.landmark_id, res.rank - 1] = res.candidate_id
    dist[res.landmark_id, res.rank - 1] = res.distance
    return ids, dist


def _spans(events, name):
    return [e for e in events
            if e.get("event") == "span" and e["name"] == name]


def _model(**kw):
    args = dict(index=GRID, resolution=RES, k_neighbours=K,
                approximate=False, max_iterations=64)
    args.update(kw)
    return SpatialKNN(**args)


# ------------------------------------------------ against the brute force


def test_transform_equals_bruteforce_head_and_tail(clustered):
    land, cand = clustered
    kx = build_knn_index(cand, GRID, RES)
    assert kx.points is not None and kx.points.count.max() > 200
    res = _model().transform(land, kx)
    ids, dist = _table(res, len(land), K)
    want_ids, want_d = knn_bruteforce.answers(land, cand, K)
    assert np.array_equal(ids, want_ids)
    np.testing.assert_allclose(dist, want_d, rtol=0, atol=1e-12)
    m = res.metrics
    assert m["unrested_landmarks"] == 0 and m["complete_landmarks"] == len(land)
    assert m["iterations"] >= 4 and m["launches"] >= m["iterations"]
    # the head: a cluster landmark met hundreds of candidates in ring 1
    assert m["pairs"] > 40 * 200 and 0 < m["pairs"] <= m["pairs_padded"]


def test_frontend_dispatch_equals_bruteforce_and_transform(clustered):
    land, cand = clustered
    kx = build_knn_index(cand, GRID, RES)
    fe = KNNFrontend(kx, row_ladder=BucketLadder(8, 64))
    out, occupancy = fe.dispatch(land, K)
    ids, dist = decode_knn(np.asarray(out), K)
    want_ids, want_d = knn_bruteforce.answers(land, cand, K)
    assert np.array_equal(ids, want_ids)
    np.testing.assert_allclose(dist, want_d, rtol=0, atol=1e-12)
    assert 0 < occupancy <= 1
    tids, tdist = _table(_model().transform(land, kx), len(land), K)
    assert np.array_equal(tids, ids) and np.array_equal(tdist, dist)


def test_geometry_inputs_take_the_same_engine(clustered):
    """POINT columns on either side, and a candidate column in place of
    an index, answer as the arrays do."""
    land, cand = clustered
    lcol = F.st_point(land[:30, 0], land[:30, 1])
    ccol = points_column(cand)
    assert point_coords(lcol) is not None
    a = _table(_model().transform(lcol, ccol), 30, K)
    b = _table(_model().transform(land[:30], build_knn_index(cand, GRID, RES)),
               30, K)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])


def test_fewer_than_k_in_reach_is_reported_not_hidden():
    """Three candidates, k 5: every landmark ends with 3 matches, rested
    because no candidate is left; with far candidates and few iterations
    the cut-off landmarks are counted."""
    cand = np.array([[-74.0, 40.7], [-74.001, 40.7], [-73.999, 40.701]])
    land = np.array([[-74.0005, 40.7002], [-73.93, 40.74]])
    res = _model(max_iterations=40).transform(land, cand)
    ids, dist = _table(res, 2, K)
    want_ids, want_d = knn_bruteforce.answers(land, cand, K)
    assert np.array_equal(ids, want_ids) and (ids[:, 3:] == -1).all()
    np.testing.assert_allclose(dist[:, :3], want_d[:, :3], atol=1e-12)
    assert res.metrics["unrested_landmarks"] == 0  # candidates exhausted
    cut = _model(max_iterations=3).transform(land, cand)
    assert cut.metrics["unrested_landmarks"] == 1  # the far landmark


@pytest.mark.parametrize("lane", ["transform", "dispatch"])
def test_polygon_candidates_equal_the_host_oracle(lane):
    rng = np.random.default_rng(5)
    cx = rng.uniform(-74.1, -73.9, 30)
    cy = rng.uniform(40.6, 40.8, 30)
    s = rng.uniform(0.002, 0.02, 30)
    cand = F.st_geomfromwkt(np.array([
        f"POLYGON(({x} {y}, {x + w} {y}, {x + w} {y + w}, {x} {y + w}, {x} {y}))"
        for x, y, w in zip(cx, cy, s)
    ]))
    kx = build_knn_index(cand, GRID, RES)
    assert kx.points is None
    q = np.column_stack([rng.uniform(cx.min(), cx.max(), 12),
                         rng.uniform(cy.min(), cy.max(), 12)])
    want_ids, want_d = brute_force_knn(q, kx, 3)
    if lane == "transform":
        res = _model(k_neighbours=3).transform(q, kx)
        ids, dist = _table(res, 12, 3)
        assert res.metrics["unrested_landmarks"] == 0
    else:
        out, _ = KNNFrontend(kx, row_ladder=BucketLadder(8, 64),
                             pair_ladder=BucketLadder(64, 1024)).dispatch(q, 3)
        ids, dist = decode_knn(np.asarray(out), 3)
    assert np.array_equal(ids, want_ids) and np.array_equal(dist, want_d)


def test_polygon_landmarks_search_from_their_cover():
    rng = np.random.default_rng(8)
    cand = np.column_stack([rng.uniform(-74.2, -73.7, 400),
                            rng.uniform(40.5, 40.9, 400)])
    land = F.st_geomfromwkt(np.array([
        "POLYGON((-74.0 40.7, -73.98 40.7, -73.98 40.72, -74.0 40.72, -74.0 40.7))",
        "POLYGON((-73.8 40.6, -73.79 40.6, -73.79 40.61, -73.8 40.61, -73.8 40.6))",
    ]))
    res = _model(k_neighbours=3).transform(land, cand)
    ids, dist = _table(res, 2, 3)
    d = np.asarray(F.st_distance(
        land.take(np.repeat(np.arange(2), 400)),
        points_column(cand).take(np.tile(np.arange(400), 2)),
    )).reshape(2, 400)
    want = np.lexsort((np.broadcast_to(np.arange(400), d.shape), d), axis=1)[:, :3]
    assert np.array_equal(ids, want)
    np.testing.assert_allclose(dist, np.take_along_axis(d, want, 1), atol=1e-9)


# ----------------------------------------- against the per-query loop


def _per_query_loop(kx, pts, k, max_iterations=64):
    """The ring lane as it was before PR 33: Python once a query, sets of
    seen rows, one sort a query (kept here as the array engine's
    reference; f64 host distances)."""
    from mosaic_tpu.knn import host_pair_distances

    n = pts.shape[0]
    qs = pts - kx.shift
    dist = np.full((n, k), np.inf)
    cid = np.full((n, k), -1, np.int64)
    seen = [set() for _ in range(n)]
    seeds = np.asarray(kx.index_system.point_to_cell(pts, kx.resolution))
    for it in range(1, max_iterations + 1):
        for i in range(n):
            if len(seen[i]) >= kx.n:
                continue
            if (cid[i] >= 0).sum() >= k and (it - 1) * kx.cell_width >= dist[i, k - 1]:
                continue
            fn = kx.index_system.k_ring if it == 1 else kx.index_system.k_loop
            cells = np.asarray(fn(seeds[i : i + 1], it))
            rows = kx.candidate_rows(np.unique(cells[cells >= 0]))
            fresh = np.array(sorted(set(rows.tolist()) - seen[i]), np.int64)
            seen[i].update(fresh.tolist())
            if not fresh.size:
                continue
            d = host_pair_distances(qs, kx, np.full(fresh.size, i), fresh)
            cd = np.concatenate([dist[i], d])
            cc = np.concatenate([cid[i], fresh])
            take = np.lexsort((cc, cd))[:k]
            dist[i], cid[i] = cd[take], cc[take]
    return cid, dist


@pytest.mark.parametrize("candidates", ["points", "polygons"])
def test_array_engine_equals_the_per_query_loop(candidates, clustered):
    land, cand = clustered
    if candidates == "polygons":
        rng = np.random.default_rng(2)
        cx, cy = rng.uniform(-74.1, -73.9, 30), rng.uniform(40.6, 40.8, 30)
        cand = F.st_geomfromwkt(np.array([
            f"POLYGON(({x} {y}, {x + .01} {y}, {x + .01} {y + .01}, {x} {y + .01}, {x} {y}))"
            for x, y in zip(cx, cy)
        ]))
        land = np.column_stack([rng.uniform(-74.08, -73.92, 12),
                                rng.uniform(40.62, 40.78, 12)])
    kx = build_knn_index(cand, GRID, RES)
    want_ids, want_d = _per_query_loop(kx, land[:24], 3)
    out, _ = KNNFrontend(kx, row_ladder=BucketLadder(8, 64),
                         pair_ladder=BucketLadder(64, 1024)).dispatch(land[:24], 3)
    ids, dist = decode_knn(np.asarray(out), 3)
    assert np.array_equal(ids, want_ids) and np.array_equal(dist, want_d)


# ------------------------------------------------------- the rest criterion


def test_exact_run_is_not_ended_by_the_early_stop():
    """Every landmark holds k after ring 1 or 2, and the match counts stay
    stable from then on, while the slow landmark still widens rings for
    the exactness criterion: its true 5th neighbour lies 6 rings out, past
    nearer-looking ones found early. ``early_stop_iterations=1`` would
    have ended the old loop there."""
    w = 3.90625e-3  # the cell at RES 8
    seed = np.array([-74.0 + 0.5 * w, 40.7 + 0.5 * w])
    # five candidates 7-8 cells out (found late, all nearer than the
    # decoys' ring suggests) and five decoys in a far corner of ring 6
    near = seed + np.array([[5.6 * w, 0], [-5.6 * w, 0], [0, 5.6 * w],
                            [0, -5.6 * w], [5.5 * w, 0.3 * w]])
    decoys = seed + np.array([[5.9 * w, 5.9 * w], [-5.9 * w, 5.9 * w],
                              [5.9 * w, -5.9 * w], [-5.9 * w, -5.9 * w],
                              [5.8 * w, 5.8 * w]])
    filler = seed + np.array([[40 * w, 40 * w]]) + np.random.default_rng(1).normal(
        0, w, (50, 2))
    cand = np.concatenate([decoys, near, filler])
    land = np.concatenate([seed[None], filler[:6] + 1e-4])
    res = _model(early_stop_iterations=1).transform(land, cand)
    ids, dist = _table(res, len(land), K)
    want_ids, want_d = knn_bruteforce.answers(land, cand, K)
    assert np.array_equal(ids, want_ids)
    assert set(ids[0]) == {5, 6, 7, 8, 9}
    assert res.metrics["unrested_landmarks"] == 0
    assert res.metrics["iterations"] >= 7
    # the approximate search does stop early, and says who it left
    approx = _model(approximate=True, early_stop_iterations=1).transform(land, cand)
    assert approx.metrics["iterations"] < res.metrics["iterations"]


def test_threshold_rests_an_exact_search_and_drops_far_pairs(clustered):
    land, cand = clustered
    thr = 0.004
    res = _model(distance_threshold=thr).transform(land, cand)
    ids, dist = _table(res, len(land), K)
    want_ids, want_d = knn_bruteforce.answers(land, cand, K)
    keep = want_d <= thr
    assert np.array_equal(ids[keep], want_ids[keep])
    assert (ids[~keep] == -1).all() and (res.distance <= thr).all()
    assert res.metrics["unrested_landmarks"] == 0
    assert res.metrics["iterations"] <= 3  # (it - 1) * w reaches thr


def test_resident_index_answers_call_after_call(clustered, monkeypatch):
    land, cand = clustered
    kx = build_knn_index(cand, GRID, RES)
    m = _model()
    a = m.transform(land, kx)
    from mosaic_tpu import knn as knn_pkg

    monkeypatch.setattr(knn_pkg, "build_knn_index",
                        lambda *a, **k: pytest.fail("index rebuilt"))
    fe = m._frontend[1]
    b = m.transform(land, kx)
    assert m._frontend[1] is fe
    for f in ("landmark_id", "candidate_id", "distance", "rank"):
        assert np.array_equal(getattr(a, f), getattr(b, f))


# ------------------------------------------------------------------ pieces


def test_merge_topk_ranks_by_distance_then_id():
    dist = np.array([[0.5, np.inf], [np.inf, np.inf], [0.1, 0.2]])
    cid = np.array([[9, -1], [-1, -1], [4, 5]])
    qi = np.array([0, 0, 0, 1])
    ci = np.array([3, 7, 2, 8])
    d = np.array([0.5, 0.25, 0.9, 1.5])
    nd, nc = engine.merge_topk(dist, cid, qi, ci, d, 2)
    assert nc.tolist() == [[7, 3], [8, -1], [4, 5]]  # 3 before 9 at 0.5
    assert nd[0].tolist() == [0.25, 0.5] and nd[1, 0] == 1.5
    assert np.isinf(nd[1, 1]) and dist[0, 1] == np.inf  # input untouched


@pytest.mark.parametrize("ladder", [
    BucketLadder(2, 4), BucketLadder(4, 64, growth=4),
], ids=["rungs-2-4", "rungs-4-16-64"])
def test_chunks_of_one_query_fold_across_launches(clustered, monkeypatch, ladder):
    """A top rung of 4 (or 64) chunks: the cluster landmarks' chunks
    straddle launches, launches begin mid-landmark — and the answer is
    the default ladder's and the brute force's."""
    land, cand = clustered
    kx = build_knn_index(cand, GRID, RES)
    want = _table(_model().transform(land, kx), len(land), K)
    monkeypatch.setattr(knn_frontend, "BLOCK_LADDER", ladder)
    res = _model().transform(land, kx)
    got = _table(res, len(land), K)
    assert np.array_equal(got[0], want[0]) and np.array_equal(got[1], want[1])
    assert np.array_equal(got[0], knn_bruteforce.answers(land, cand, K)[0])
    assert res.metrics["launches"] > 30


def test_an_iteration_with_no_chunk_launches_nothing():
    """Six candidates seven cells from the landmark: rings 1 to 6 meet no
    block, so those iterations have an expand span and no distance span,
    and the answer is the brute force's."""
    w = 3.90625e-3
    land = np.array([[-74.0 + 0.5 * w, 40.7 + 0.5 * w]])
    cand = land + np.array([[7 * w, 0.1 * w * j] for j in range(6)])
    with telemetry.capture() as events:
        res = _model().transform(land, cand)
    ids, dist = _table(res, 1, K)
    want_ids, want_d = knn_bruteforce.answers(land, cand, K)
    assert np.array_equal(ids, want_ids)
    np.testing.assert_allclose(dist, want_d, rtol=0, atol=1e-12)
    assert len(_spans(events, "knn.expand")) >= 7
    assert 7 > len(_spans(events, "knn.distance")) >= 1
    assert res.metrics["unrested_landmarks"] == 0


# ------------------------------------------- heads against the per-chunk pull


def _fold_chunk_rows(cq, out_d, out_i, cap, a, k):
    """`engine.fold_heads` as it was before PR 41: every chunk's row is
    in hand, the heads are found here by a scan of ``cq``."""
    fd = np.full((a, k), np.inf)
    fi = np.full((a, k), -1, dtype=np.int64)
    at = np.arange(cq.shape[0])
    head = np.flatnonzero((at % cap == 0) | np.r_[True, cq[1:] != cq[:-1]])
    hq = cq[head]
    hd, hi = out_d[head].astype(np.float64), out_i[head].astype(np.int64)
    hi[hi == engine._NO_ID] = -1
    first = np.r_[True, hq[1:] != hq[:-1]]
    fd[hq[first]], fi[hq[first]] = hd[first], hi[first]
    rest = np.flatnonzero(~first)
    if rest.size:
        live = hi[rest] >= 0
        fd, fi = engine.merge_topk(
            fd, fi, np.repeat(hq[rest], k)[live.ravel()],
            hi[rest][live], hd[rest][live], k,
        )
    return fd, fi


def _per_chunk_path(kx, qsd, active, cq, blk, steps, thr, k, ladder):
    """The block lane before PR 41: the block program alone, a launch a
    ``cap`` chunks, every chunk's (b, k) row pulled."""
    pb, cap = kx.points, ladder.max_bucket
    prog = engine.block_topk_prog()
    qx, qy = qsd[active[cq], 0], qsd[active[cq], 1]
    ds, ids = [], []
    for c0 in range(0, cq.shape[0], cap):
        m = min(cap, cq.shape[0] - c0)
        b, sl = ladder.bucket_for(m), slice(c0, c0 + m)
        d, i = prog(
            pb.x, pb.y, pb.rid, np.pad(qx[sl], (0, b - m)),
            np.pad(qy[sl], (0, b - m)),
            np.pad(blk[sl].astype(np.int32), (0, b - m),
                   constant_values=pb.n_blocks),
            np.pad(cq[sl].astype(np.int32), (0, b - m), constant_values=-1),
            np.asarray(thr, qsd.dtype), np.int32(steps), k=k,
        )
        ds.append(np.asarray(d)[:m])
        ids.append(np.asarray(i)[:m])
    return _fold_chunk_rows(
        cq, np.concatenate(ds), np.concatenate(ids), cap, active.size, k)


#: chunks a landmark, on a ladder of rungs 4 / 16 / 64 (launches of 64)
HEAD_CASES = {
    # q1 straddles the first boundary: launch 2 begins mid-landmark
    "straddle": ([30, 50, 10], np.inf, [(64, 4), (64, 4)]),
    # one landmark fills two launches and begins a third: one head each
    "one-head": ([131], np.inf, [(64, 4), (64, 4), (4, 4)]),
    # 16 heads sit exactly on a rung, 17 take the next
    "heads-on-a-rung": ([1] * 16, np.inf, [(16, 16)]),
    "heads-one-over": ([1] * 17, np.inf, [(64, 64)]),
    # every slot of a full launch is a head; the next holds the rest
    "all-heads": ([1] * 70, np.inf, [(64, 64), (16, 16)]),
    "threshold": ([30, 50, 10, 1, 1], 0.01, [(64, 4), (64, 4)]),
}


@pytest.mark.parametrize("case", sorted(HEAD_CASES))
def test_head_rows_equal_the_per_chunk_pull_bit_for_bit(
    case, clustered, monkeypatch
):
    """Launch layouts built by hand on the fixture's blocks: what the
    heads program hands back, folded, is what the per-chunk pull gave —
    the same bits — and the plain sort's answer over the same blocks."""
    nchunk, thr, rungs = HEAD_CASES[case]
    land, cand = clustered
    ladder = BucketLadder(4, 64, growth=4)
    monkeypatch.setattr(knn_frontend, "BLOCK_LADDER", ladder)
    kx = build_knn_index(cand, GRID, RES)
    pb, a = kx.points, len(nchunk)
    rng = np.random.default_rng(len(case))
    active = np.sort(rng.choice(len(land), a, replace=False))
    cq = np.repeat(np.arange(a), nchunk)
    # a landmark meets a block once, as ring cells are disjoint
    blk = np.concatenate([rng.choice(pb.n_blocks, n, replace=False)
                          for n in nchunk])
    steps = int(max(max(nchunk) - 1, 0)).bit_length()
    fe = KNNFrontend(kx, row_ladder=BucketLadder(8, 64))
    qs64 = land - kx.shift
    with telemetry.capture() as events:
        sent = fe._block_topk(qs64, qs64, K, thr, None)(active, cq, blk)
        assert not _spans(events, "knn.pull") and sent.carry is None
        hq, hd, hi = sent.pull()
    met = [(e["bucket"], e["head_bucket"])
           for e in _spans(events, "knn.blocks")]
    assert met == rungs and sent.launches == len(rungs)
    assert sent.rows == sum(h for _b, h in rungs) and hd.shape == (hq.size, K)
    assert sent.padded == sum(b for b, _h in rungs) * pb.width
    got = engine.fold_heads(hq, hd, hi, a, K)
    old = _per_chunk_path(kx, qs64, active, cq, blk, steps, thr, K, ladder)
    assert np.array_equal(got[0], old[0]) and np.array_equal(got[1], old[1])
    rid = np.asarray(pb.rid)
    for q in range(a):
        mine = np.sort(rid[blk[cq == q]].ravel())
        mine = mine[mine >= 0]
        ids, dist = knn_bruteforce.answers(land[active[q]][None], cand[mine], K)
        keep = (dist[0] <= thr) & (ids[0] >= 0)
        assert np.array_equal(got[1][q][keep], mine[ids[0][keep]])
        assert (got[1][q][~keep] == -1).all()
        np.testing.assert_allclose(got[0][q][keep], dist[0][keep], atol=1e-12)


def test_rows_pulled_are_the_head_rungs_not_the_chunks(clustered, monkeypatch):
    land, cand = clustered
    monkeypatch.setattr(knn_frontend, "BLOCK_LADDER", BucketLadder(4, 64, growth=4))
    kx = build_knn_index(cand, GRID, RES)
    m = _model()
    with telemetry.capture() as events:
        m.transform(land, kx)
    blocks, pulls = _spans(events, "knn.blocks"), _spans(events, "knn.pull")
    (call,) = _spans(events, "knn.transform")
    rows = sum(e["head_bucket"] for e in blocks)
    assert call["rows_pulled"] == rows == sum(e["rows"] for e in pulls)
    assert m._frontend[1].metrics()["knn_rows_pulled"] == rows
    chunks = sum(e["chunks"] for e in blocks)
    assert chunks == sum(e["chunks"] for e in pulls)
    assert sum(e["heads"] for e in blocks) <= rows < chunks
    assert all(e["heads"] <= e["head_bucket"] <= e["bucket"] for e in blocks)


def test_warmed_head_rungs_leave_no_cold_compile(clustered, monkeypatch):
    """`warmup(k)` touches every (chunk rung, head rung) pair a launch
    can take; a transform that meets several of them adds no signature."""
    land, cand = clustered
    ladder = BucketLadder(4, 64, growth=4)
    monkeypatch.setattr(knn_frontend, "BLOCK_LADDER", ladder)
    kx = build_knn_index(cand, GRID, RES)
    m = _model()
    m.warmup(kx)
    fe = m._frontend[1]
    warmed = fe.signature_count()
    with telemetry.capture() as events:
        res = m.transform(land, kx)
    met = {(e["bucket"], e["head_bucket"])
           for e in _spans(events, "knn.blocks")}
    assert len(met) >= 4 and len({b for b, _h in met}) == 3
    assert fe.cold_compiles == 0 and fe.signature_count() == warmed
    assert not [e for e in events if e.get("event") == "knn_compile"]
    assert np.array_equal(
        _table(res, len(land), K)[0], knn_bruteforce.answers(land, cand, K)[0])


def test_degraded_block_launches_are_answered_by_the_host_oracle(clustered):
    """Past the retry budget the iteration's pairs come from the CSR and
    the f64 host oracle's (query, candidate, distance) triples are merged
    as before: the same neighbours, flagged."""
    from mosaic_tpu.runtime import faults

    land, cand = clustered
    kx = build_knn_index(cand, GRID, RES)
    with faults.transient_errors(999, sites=("knn.distance",)):
        res = _model().transform(land[:50], kx)
    assert res.metrics["degraded"] is True and res.metrics["launches"] == 0
    ids, dist = _table(res, 50, K)
    want_ids, want_d = knn_bruteforce.answers(land[:50], cand, K)
    assert np.array_equal(ids, want_ids)
    np.testing.assert_allclose(dist, want_d, rtol=0, atol=1e-12)


# ------------------------------------------------ the slab pipeline (PR 45)


@pytest.fixture(scope="module")
def hexed():
    """H3 res 10 (a lattice: only there is an iteration cut into slabs):
    4,000 candidates, a cluster whose cells hold up to 150 and a background
    under one a cell; 2,000 landmarks, a fifth of them in the cluster and
    two a dozen rings outside everything, in no spatial order."""
    rng = np.random.default_rng(45)
    centre = np.array([-73.98, 40.75])
    cand = np.concatenate([centre + rng.normal(0, 0.002, (2500, 2)),
                           centre + rng.uniform(-0.03, 0.03, (1500, 2))])
    land = np.concatenate([centre + rng.normal(0, 0.002, (400, 2)),
                           centre + rng.uniform(-0.03, 0.03, (1598, 2)),
                           centre + [[0.045, 0.0], [0.0, -0.045]]])
    h3 = H3IndexSystem()
    return land[rng.permutation(2000)], cand, h3, build_knn_index(cand, h3, 10)


SLAB_LADDER = BucketLadder(16, 64, growth=4)


def _slab_run(monkeypatch, hexed, slab_keys, **kw):
    land, _cand, h3, kx = hexed
    monkeypatch.setattr(knn_frontend, "BLOCK_LADDER", SLAB_LADDER)
    monkeypatch.setattr(engine, "SLAB_KEYS", slab_keys)
    args = dict(index=h3, resolution=10, k_neighbours=K, approximate=False,
                max_iterations=32)
    args.update(kw)
    with telemetry.capture() as events:
        res = SpatialKNN(**args).transform(land, kx)
    return res, events


def _by_iteration(events, name, field):
    out = {}
    for e in _spans(events, name):
        out[e["iteration"]] = out.get(e["iteration"], 0) + e[field]
    return out


SLAB_CASES = {
    "straddle": {},
    "k-over-a-cell": {"k_neighbours": 12},
    "threshold": {"distance_threshold": 0.0015},
    "approximate": {"approximate": True, "early_stop_iterations": 1},
}


@pytest.mark.parametrize("case", sorted(SLAB_CASES))
def test_slabbed_schedule_equals_the_one_slab_schedule_bit_for_bit(
    case, hexed, monkeypatch
):
    """`engine.SLAB_KEYS` down, so the early iterations run 4 to 12 slabs,
    and up, so every iteration is one: the same answers to the bit, the
    same launches cut for cut (what a slab leaves past a whole cut rides
    the next slab's first launch), and counts that follow from the chunks."""
    kw = SLAB_CASES[case]
    one, ev1 = _slab_run(monkeypatch, hexed, 1 << 40, **kw)
    cut, ev2 = _slab_run(monkeypatch, hexed, 3000, **kw)
    for f in ("landmark_id", "candidate_id", "distance", "rank"):
        assert np.array_equal(getattr(one, f), getattr(cut, f)), f
    for f in ("iterations", "pairs", "pairs_padded", "launches",
              "unrested_landmarks", "match_count"):
        assert one.metrics[f] == cut.metrics[f], f
    cuts = [[(e["bucket"], e["chunks"], e["heads"], e["head_bucket"])
             for e in _spans(ev, "knn.blocks")] for ev in (ev1, ev2)]
    assert cuts[0] == cuts[1]
    # launches and padded slots from the iterations' chunks alone
    cap, width = SLAB_LADDER.max_bucket, hexed[3].points.width
    chunks = _by_iteration(ev2, "knn.distance", "chunks")
    assert chunks == _by_iteration(ev1, "knn.distance", "chunks")
    assert cut.metrics["launches"] == sum(-(-c // cap) for c in chunks.values())
    assert cut.metrics["pairs_padded"] == width * sum(
        c - c % cap + (SLAB_LADDER.bucket_for(c % cap) if c % cap else 0)
        for c in chunks.values())
    # slabs from the ring keys: queries x the ring's cells, an iteration
    queries = _by_iteration(ev2, "knn.expand", "queries")
    want = [max(q * (7 if it == 1 else 6 * it) // 3000, 1)
            for it, q in queries.items()]
    (root1,), (root2,) = (_spans(ev, "knn.transform") for ev in (ev1, ev2))
    assert root2["slabs"] == sum(want) and 3 <= max(want) <= 12
    assert root1["slabs"] == one.metrics["iterations"] and root1["hidden_s"] == 0
    assert root2["slabs"] > cut.metrics["iterations"] and root2["hidden_s"] > 0
    pulls = _spans(ev2, "knn.pull")
    assert sum(e["hidden_s"] for e in pulls) == pytest.approx(root2["hidden_s"])
    assert {e["slabs"] for e in pulls} == set(want)
    # the expand is no part of the distance span: the call's split adds up
    ids = {e["span_id"]: e["name"] for e in _spans(ev2, "knn.distance")}
    assert all(e["parent_id"] not in ids for e in _spans(ev2, "knn.expand"))
    assert all(e["parent_id"] in ids for e in pulls)
    if case == "approximate":  # rests at k matches: the far two end it sooner
        assert 10 < cut.metrics["iterations"] < 20
    if case == "threshold":
        assert cut.distance.max() <= 0.0015 < one.metrics["max_kth_distance"] + 1
    if case == "k-over-a-cell":
        assert hexed[3].points.count.min() < 12 == cut.rank.max()


def test_a_landmark_straddles_a_launch_inside_a_slab(hexed):
    """What the case above runs into: in iteration 1 a launch boundary
    falls between two chunks of one landmark, away from any slab's edge."""
    land, _cand, h3, kx = hexed
    cells = knn_frontend.KNNFrontend(kx)._assign_cells(land)
    keys, margin = kx.probe_keys(cells)
    cq, _blk, _fresh = engine.block_chunks(
        kx.points, kx.ring_keys(cells, keys, margin, 1))
    cap = SLAB_LADDER.max_bucket
    edge = np.arange(cap, cq.size, cap)
    mid = edge[cq[edge] == cq[edge - 1]]
    real = engine.SLAB_KEYS
    try:
        engine.SLAB_KEYS = 3000
        bounds = engine.slab_bounds(np.full(len(land), 7))
    finally:
        engine.SLAB_KEYS = real
    assert bounds.tolist() == [0, 500, 1000, 1500, 2000]
    inside = ~np.isin(cq[mid], bounds) & ~np.isin(cq[mid], bounds - 1)
    assert inside.sum() >= 10


def test_slab_bounds_follow_the_keys():
    assert engine.slab_bounds(np.full(10, 7)).tolist() == [0, 10]
    keys = np.full(100_000, 7)
    b = engine.slab_bounds(keys)  # 700,000 keys: five slabs of 20,000
    assert b.size == 6 and (np.diff(b) == 20_000).all()
    # under two slabs' worth is one slab
    n = 2 * engine.SLAB_KEYS // 7
    assert engine.slab_bounds(np.full(n, 7)).tolist() == [0, n]
    assert engine.slab_bounds(np.full(n + 1, 7)).size == 3
    # seeds a query: slabs hold about the same keys, not the same queries
    keys = np.r_[np.full(1000, 7 * 40), np.full(40_000, 7)]
    b = engine.slab_bounds(keys)
    got = np.add.reduceat(keys, b[:-1])
    assert b[0] == 0 and b[-1] == keys.size and (np.diff(b) > 0).all()
    assert got.max() < 1.1 * got.min()
    # off a lattice the keys are not counted: one slab
    assert engine.slab_bounds(np.zeros(10**6, np.int64)).tolist() == [0, 10**6]


def test_chunk_pairs_are_the_blocks_candidates(clustered):
    _, cand = clustered
    kx = build_knn_index(cand, GRID, RES)
    pb = kx.points
    rid = np.asarray(pb.rid)
    rng = np.random.default_rng(2)
    blk = rng.choice(pb.n_blocks, 200)
    own = np.sort(rng.integers(0, 50, 200))
    qi, ci = engine.chunk_pairs(kx, own, blk)
    want = rid[blk]
    assert np.array_equal(ci, want[want >= 0])
    assert np.array_equal(qi, np.repeat(own, (want >= 0).sum(axis=1)))


#: ``knn.distance`` is met, in an iteration of slabs: enqueue 1, enqueue 2,
#: pull 1, enqueue 3, pull 2, ...: (site, hits let through, hits failed)
SLAB_FAULTS = {
    "expand-2-retried": ("knn.expand", 1, 1, False),
    "enqueue-2-retried": ("knn.distance", 1, 1, False),
    "pull-1-retried": ("knn.distance", 2, 1, False),
    "enqueue-2-exhausted": ("knn.distance", 1, 3, True),
    "pull-1-exhausted": ("knn.distance", 2, 3, True),
}


@pytest.mark.parametrize("case", sorted(SLAB_FAULTS))
def test_a_fault_in_one_slab_stays_in_that_slab(case, hexed, monkeypatch):
    """A transient fault in the second slab's expand or enqueue, or in the
    first slab's pull, is retried to the same bits (a retried pull launches
    again); past the budget that half's chunks — the slab's and what it
    carried, or the launches the pull gave up — are answered by the f64
    host oracle, the other slabs' device answers stand, and the call is
    flagged once."""
    from mosaic_tpu.runtime import faults

    site, skip, n, exhausted = SLAB_FAULTS[case]
    monkeypatch.setenv("MOSAIC_RETRY_BASE_S", "0.001")
    sound, _ = _slab_run(monkeypatch, hexed, 3000)
    with faults.transient_errors(n, sites=(site,), skip_first=skip):
        got, events = _slab_run(monkeypatch, hexed, 3000)
    retries = [e for e in events if e.get("event") == "transient_retry"]
    assert len(retries) == n and {e["label"] for e in retries} == {site}
    assert len([e for e in events if e.get("event") == "degraded"]) == exhausted
    assert got.metrics["degraded"] is exhausted
    assert got.metrics["pairs"] == sound.metrics["pairs"]
    for f in ("landmark_id", "candidate_id", "rank"):
        assert np.array_equal(getattr(got, f), getattr(sound, f)), f
    if not exhausted:
        assert np.array_equal(got.distance, sound.distance)
        assert got.metrics["launches"] == sound.metrics["launches"]
        return
    np.testing.assert_allclose(got.distance, sound.distance, rtol=0, atol=1e-12)
    # one slab's launches are the host's now; what it carried was theirs too
    lost = sound.metrics["launches"] - got.metrics["launches"]
    assert 0 <= lost < sound.metrics["launches"] / 2
    if case == "enqueue-2-exhausted":
        assert lost >= 1


def test_expand_ranges_and_block_layout(clustered):
    assert expand_ranges(np.array([5, 0, 9]), np.array([2, 0, 3])).tolist() == [
        5, 6, 9, 10, 11]
    _, cand = clustered
    kx = build_knn_index(cand, GRID, RES)
    pb = kx.points
    rid = np.asarray(pb.rid)
    assert rid.shape == (pb.n_blocks + 1, pb.width) and (rid[-1] == -1).all()
    assert sorted(rid[rid >= 0].tolist()) == list(range(len(cand)))
    # a cell's candidates sit in that cell's blocks, in row order
    u = int(np.argmax(pb.count))
    mine = rid[pb.blk_start[u] : pb.blk_start[u + 1]].ravel()
    assert np.array_equal(mine[mine >= 0], kx.rows[kx.cells == pb.ucells[u]])
    xs = np.asarray(pb.x)[rid >= 0]
    assert np.allclose(xs, (cand - kx.shift)[rid[rid >= 0], 0])


@pytest.mark.parametrize("res,k", [(8, 1), (8, 3), (10, 2), (10, 5)])
def test_h3_lattice_rings_equal_the_walked_rings(res, k):
    """A seed's key plus the ring's offsets are the keys of the cells
    `k_ring` / `k_loop` walk to, one each."""
    h3 = H3IndexSystem()
    rng = np.random.default_rng(res * 10 + k)
    pts = np.column_stack([rng.uniform(BOX[0], BOX[2], 12),
                           rng.uniform(BOX[1], BOX[3], 12)])
    cells = np.asarray(h3.point_to_cell(pts, res))
    keys, margin = h3.lattice_keys(cells)
    assert (keys >= 0).all() and margin.min() > 100
    got = keys[:, None] + h3.lattice_ring(k)[None, :]
    walked = np.asarray((h3.k_ring if k == 1 else h3.k_loop)(cells, k))
    want = h3.lattice_keys(walked.ravel())[0].reshape(walked.shape)
    assert got.shape == want.shape == (12, 7 if k == 1 else 6 * k)
    for a, b in zip(got, want):
        assert set(a) == set(b) and len(set(a)) == a.size


def test_h3_seeds_near_a_face_edge_or_a_pentagon_have_their_rings_walked():
    """Over the globe at res 4: a pentagon base cell's children are off
    the lattice (key -1), a seed near its face's edge has a small margin;
    `KNNIndex.ring_keys` walks those seeds' rings with the grid and looks
    the cells up, and steps the rest — the same keys either way."""
    h3 = H3IndexSystem()
    rng = np.random.default_rng(0)
    g = np.column_stack([rng.uniform(-180, 180, 600), rng.uniform(-85, 85, 600)])
    cells = np.asarray(h3.point_to_cell(g, 4))
    keys, margin = h3.lattice_keys(cells)
    aligned = np.flatnonzero(keys >= 0)
    assert 0 < (keys < 0).sum() < 60 and (margin[aligned] < 3).any()
    kx = build_knn_index(g[aligned], h3, 4)
    assert kx.lattice
    ring = kx.ring_keys(cells, keys, margin, 2)
    walked = np.asarray(h3.k_loop(cells, 2))
    want = np.where(
        walked >= 0, h3.lattice_keys(walked.ravel())[0].reshape(walked.shape), -1)
    for a, b in zip(ring, want):
        # a walked cell off the lattice (-1) holds no candidate of this table
        assert set(b[b >= 0]) <= set(a[a >= 0])
        assert set(a[a >= 0]) - set(b[b >= 0]) == set() or (b < 0).any()
    # a table holding an off-lattice cell keeps cell ids
    assert not build_knn_index(g, h3, 4).lattice


def test_h3_transform_exact_at_the_cells_resolution():
    """The benchmark's grid at a small size: H3 res 10 through the
    lattice ring program."""
    rng = np.random.default_rng(10)
    centre = np.array([-73.98, 40.75])
    cand = np.concatenate([
        centre + rng.normal(0, 0.002, (600, 2)),
        centre + rng.uniform(-0.02, 0.02, (300, 2)),
    ])
    land = np.concatenate([centre + rng.normal(0, 0.002, (10, 2)),
                           centre + rng.uniform(-0.015, 0.015, (20, 2))])
    res = SpatialKNN(index=H3IndexSystem(), resolution=10, k_neighbours=K,
                     approximate=False, max_iterations=32).transform(land, cand)
    ids, dist = _table(res, len(land), K)
    want_ids, want_d = knn_bruteforce.answers(land, cand, K)
    assert np.array_equal(ids, want_ids)
    np.testing.assert_allclose(dist, want_d, atol=1e-12)
    assert res.metrics["unrested_landmarks"] == 0


def test_h3_landmark_at_a_cell_vertex_is_exact():
    """A landmark at a vertex of its hexagon has unvisited ground one
    edge away after ring 1 — less than the ``sqrt(area) / 1.5`` the rest
    criterion credited a ring before PR 33, which let it rest on five
    neighbours 7.2e-4 out while a nearer candidate lay in a ring-2 cell
    7.0e-4 out. `H3IndexSystem.ring_width` measures the reach."""
    h3 = H3IndexSystem()
    c = np.asarray(h3.point_to_cell(np.array([[-73.98, 40.75]]), 10))
    centre = np.asarray(h3.cell_center(c))[0]
    ring1 = np.asarray(h3.k_ring(c, 1)).ravel()
    best = None
    for v in np.asarray(h3.cell_boundary(c))[0][:6]:
        out = (v - centre) / np.linalg.norm(v - centre)
        t = np.linspace(1e-5, 1.2e-3, 240)
        walk = np.asarray(h3.point_to_cell(v + t[:, None] * out, 10))
        first = t[np.flatnonzero(~np.isin(walk, ring1))[0]]
        if best is None or first < best[0]:
            best = (first, v, out)
    reach, v, out = best
    old = np.sqrt(h3.cell_area_approx(10)) / 1.5
    assert h3.ring_width(10, c) < reach < old
    land = (v - 1e-6 * out)[None]
    near = v + (reach + 3e-6) * out  # in a ring-2 cell, the true nearest
    r = (np.linalg.norm(near - land[0]) + old) / 2
    turn = lambda a: np.array([[np.cos(a), -np.sin(a)],  # noqa: E731
                               [np.sin(a), np.cos(a)]])
    decoys = np.array([land[0] + turn(a) @ (-out) * r
                       for a in np.linspace(-0.5, 0.5, 5)])
    assert np.isin(np.asarray(h3.point_to_cell(decoys, 10)), ring1).all()
    cand = np.concatenate([decoys, near[None]])
    res = SpatialKNN(index=h3, resolution=10, k_neighbours=K,
                     approximate=False, max_iterations=32).transform(land, cand)
    ids, _ = _table(res, 1, K)
    want_ids, _ = knn_bruteforce.answers(land, cand, K)
    assert ids[0, 0] == 5 and np.array_equal(ids, want_ids)
    assert res.metrics["iterations"] >= 2 and res.metrics["unrested_landmarks"] == 0


def test_ring_width_of_a_square_grid_is_what_the_model_always_credited():
    assert GRID.ring_width(RES) == pytest.approx(3.90625e-3 / 1.5)
    h3 = H3IndexSystem()
    # no cells to measure: the mean hexagon's circumradius, with room
    assert h3.ring_width(10) < 0.62 * np.sqrt(h3.cell_area_approx(10))


def test_device_programs_register_their_stage_tables(clustered):
    """A device trace names the block program's ops by scope
    (`obs.stages`): every rung launched is registered, nothing lowered
    until a reader asks."""
    from mosaic_tpu.obs import stages

    land, cand = clustered
    stages.clear()
    n0 = stages.lowerings()
    _model().transform(land, build_knn_index(cand, GRID, RES))
    rungs = dict(stages.registered())
    assert rungs.get("jit_knn_blocks") in knn_frontend.BLOCK_LADDER.buckets
    assert rungs.get("jit_knn_heads") in knn_frontend.BLOCK_LADDER.buckets
    assert stages.lowerings() == n0
    table = stages.tables({"jit_knn_blocks"}, {rungs["jit_knn_blocks"]})
    assert {"knn.gather", "knn.distance", "knn.topk"} <= set(
        table["jit_knn_blocks"].values())
    # the heads program is a module of its own: its table holds its scope
    # alone, and the block program's holds none of it
    assert "knn.heads" not in table["jit_knn_blocks"].values()
    heads = stages.tables({"jit_knn_heads"}, {rungs["jit_knn_heads"]})
    assert set(heads["jit_knn_heads"].values()) == {"knn.heads"}
