"""KNN-as-a-service contract (PR 19): served KNN answers are
bit-identical to the engine-less frontend, the batch `SpatialKNN`
model, and the brute-force f64 host oracle; KNN requests co-batch with
PIP traffic under one admission/deadline/shed budget; the Voronoi
convex fast path is exact; a hot swap mid-flight serves the old index
to completion — `mosaic_tpu/knn/` + the serve integration."""

import time

import numpy as np
import pytest

from mosaic_tpu import dispatch as _dispatch, functions as F
from mosaic_tpu.core.geometry import wkt
from mosaic_tpu.core.index import CustomIndexSystem, GridConf
from mosaic_tpu.core.tessellate import tessellate
from mosaic_tpu.knn import (
    KNNFrontend,
    brute_force_knn,
    build_knn_index,
    decode_knn,
)
from mosaic_tpu.runtime import faults
from mosaic_tpu.runtime.errors import Overloaded
from mosaic_tpu.serve import BucketLadder, ServeEngine
from mosaic_tpu.sql.join import build_chip_index

BBOX = (-25.0, -25.0, 35.0, 20.0)
RES = 3
#: small ladders so bucket boundaries are cheap to straddle in tests
ROWS = BucketLadder(8, 512)
PAIRS = BucketLadder(64, 4096)

PIP_ZONES = [
    "POLYGON ((1 1, 13 2, 12 11, 6 14, 2 9, 1 1))",
    "POLYGON ((-20 -20, -5 -20, -5 -5, -20 -5, -20 -20))",
    "POLYGON ((20 -10, 30 -10, 30 5, 20 5, 20 -10))",
]


def square_wkts(rng, n, side=(0.5, 1.5)):
    cx = rng.uniform(BBOX[0], BBOX[2], n)
    cy = rng.uniform(BBOX[1], BBOX[3], n)
    s = rng.uniform(*side, n)
    return [
        f"POLYGON(({x} {y}, {x + w} {y}, {x + w} {y + w},"
        f" {x} {y + w}, {x} {y}))"
        for x, y, w in zip(cx, cy, s)
    ], cx, cy


@pytest.fixture(scope="module")
def grid():
    return CustomIndexSystem(GridConf(-180, 180, -90, 90, 2, 10.0, 10.0))


@pytest.fixture(scope="module")
def pip_index(grid):
    col = wkt.from_wkt(PIP_ZONES)
    return build_chip_index(tessellate(col, grid, RES, keep_core_geoms=False))


@pytest.fixture(scope="module")
def knn_problem(grid):
    """Dense convex candidates + a query sampler staying strictly inside
    the candidate bbox (the shift contract the bit-identity argument
    rests on)."""
    rng = np.random.default_rng(11)
    polys, cx, cy = square_wkts(rng, 100)
    cand = F.st_geomfromwkt(np.array(polys))
    kx = build_knn_index(cand, index_system=grid, resolution=RES)
    lo = np.array([cx.min(), cy.min()])
    hi = np.array([cx.max(), cy.max()])

    def qpts(n, seed):
        r = np.random.default_rng(seed)
        return lo + r.uniform(0.1, 0.9, (n, 2)) * (hi - lo)

    return cand, kx, qpts


@pytest.fixture(scope="module")
def frontend(knn_problem):
    _, kx, _ = knn_problem
    fe = KNNFrontend(kx, lane="ring", row_ladder=ROWS, pair_ladder=PAIRS)
    rep = fe.warmup()
    assert rep["signatures"] == len(ROWS.buckets) + len(PAIRS.buckets)
    return fe


@pytest.fixture(scope="module")
def engine(pip_index, grid, frontend):
    """One warmed mixed-traffic engine shared by the whole module (the
    pre-warmed frontend is adopted as-is, so engine warmup only adds the
    PIP rungs)."""
    eng = ServeEngine(
        pip_index, grid, RES, ladder=BucketLadder(64, 1024), bounds=BBOX,
        max_wait_s=0.05, knn=frontend, default_deadline_s=120.0,
    )
    eng.warmup()
    yield eng
    eng.close()


def oracle(kx, q, k):
    return brute_force_knn(q, kx, k)


class TestServedBitIdentity:
    def test_cobatched_equals_solo_equals_batch_equals_oracle(
        self, engine, frontend, knn_problem, grid
    ):
        """Concurrent KNN requests whose sizes straddle the row-bucket
        boundary, co-batched into ONE mixed batch, answer exactly the
        bits of (a) the engine-less frontend, (b) the batch `SpatialKNN`
        model run exact, and (c) the brute-force f64 host oracle —
        neighbour ranks AND distance bits."""
        from mosaic_tpu.models import SpatialKNN

        cand, kx, qpts = knn_problem
        k = 3
        sizes = (7, 8, 9)  # straddles the 8-row rung
        qs = [qpts(n, seed=40 + n) for n in sizes]
        futs = [engine.submit_knn(q, k) for q in qs]
        answers = [f.result(timeout=120) for f in futs]
        assert engine.metrics()["cold_compiles"] == 0

        allq = np.concatenate(qs)
        # (b) batch model, exact mode, early stopping disabled
        m = SpatialKNN(
            index=grid, resolution=RES, k_neighbours=k, max_iterations=60,
            early_stop_iterations=100, approximate=False,
        )
        res = m.transform(F.st_point(allq[:, 0], allq[:, 1]), cand)
        bids = np.full((allq.shape[0], k), -1, np.int64)
        bdist = np.full((allq.shape[0], k), np.inf)
        for li, ci, d, r in zip(
            res.landmark_id, res.candidate_id, res.distance, res.rank
        ):
            bids[li, r - 1] = ci
            bdist[li, r - 1] = d
        # (c) oracle
        oids, odist = oracle(kx, allq, k)
        np.testing.assert_array_equal(bids, oids)
        assert np.array_equal(bdist, odist)

        off = 0
        for q, a in zip(qs, answers):
            n = q.shape[0]
            # (a) engine-less frontend, solo dispatch
            out, _ = frontend.dispatch(q, k)
            sids, sdist = decode_knn(np.asarray(out), k)
            np.testing.assert_array_equal(a.ids, sids)
            assert np.array_equal(a.distance, sdist)
            np.testing.assert_array_equal(a.ids, oids[off : off + n])
            assert np.array_equal(a.distance, odist[off : off + n])
            off += n
        assert engine.metrics()["cold_compiles"] == 0

    def test_mixed_batch_leaves_pip_answers_bit_identical(
        self, engine, knn_problem
    ):
        """A KNN batchmate cannot perturb PIP answers: PIP rows co-batched
        with KNN traffic return exactly the solo bits."""
        _, kx, qpts = knn_problem
        rng = np.random.default_rng(4)
        ppts = rng.uniform(BBOX[:2], BBOX[2:], (90, 2))
        fp = engine.submit(ppts)
        fk = engine.submit_knn(qpts(5, seed=77), 2)
        pip_rows = np.asarray(fp.result(timeout=120))
        a = fk.result(timeout=120)
        solo = np.asarray(engine.join(ppts, timeout=120))
        np.testing.assert_array_equal(pip_rows, solo)
        oids, odist = oracle(kx, qpts(5, seed=77), 2)
        np.testing.assert_array_equal(a.ids, oids)
        assert np.array_equal(a.distance, odist)


class TestVoronoiLane:
    def test_voronoi_equals_ring_on_convex_fixture(
        self, frontend, knn_problem
    ):
        """The Voronoi one-shot cover is EXACT: same pair programs, same
        merge — identical ids and distance bits to ring expansion on the
        all-convex fixture, with the one-dispatch lane actually taken."""
        _, kx, qpts = knn_problem
        fv = KNNFrontend(
            kx, lane="voronoi", row_ladder=ROWS, pair_ladder=PAIRS
        )
        fv.warmup()
        q = qpts(11, seed=9)
        out_r, _ = frontend.dispatch(q, 4)
        out_v, _ = fv.dispatch(q, 4)
        np.testing.assert_array_equal(np.asarray(out_v), np.asarray(out_r))
        assert fv.stats["lane_voronoi"] == 11

    def test_voronoi_equals_ring_on_mixed_fixture(self, grid):
        """Concave candidates break the convex-walk guarantee for some
        queries — those fall back to ring expansion per query, and the
        answers stay bit-identical to the pure ring lane."""
        rng = np.random.default_rng(5)
        polys, _, _ = square_wkts(rng, 40)
        # L-shaped (concave) candidates interleaved with the squares
        for i in range(12):
            x = float(rng.uniform(BBOX[0], BBOX[2] - 3))
            y = float(rng.uniform(BBOX[1], BBOX[3] - 3))
            polys.append(
                f"POLYGON(({x} {y}, {x + 2} {y}, {x + 2} {y + 0.6},"
                f" {x + 0.6} {y + 0.6}, {x + 0.6} {y + 2},"
                f" {x} {y + 2}, {x} {y}))"
            )
        cand = F.st_geomfromwkt(np.array(polys))
        kxm = build_knn_index(cand, index_system=grid, resolution=RES)
        fr = KNNFrontend(kxm, lane="ring", row_ladder=ROWS,
                         pair_ladder=PAIRS)
        fv = KNNFrontend(kxm, lane="voronoi", row_ladder=ROWS,
                         pair_ladder=PAIRS)
        fr.warmup()
        fv.warmup()
        q = np.stack([
            np.random.default_rng(8).uniform(BBOX[0] + 5, BBOX[2] - 5, 9),
            np.random.default_rng(9).uniform(BBOX[1] + 5, BBOX[3] - 5, 9),
        ], axis=1)
        out_r, _ = fr.dispatch(q, 3)
        out_v, _ = fv.dispatch(q, 3)
        np.testing.assert_array_equal(np.asarray(out_v), np.asarray(out_r))


class TestDeadlinesAndQuarantine:
    def test_stalled_knn_sheds_only_the_late_request(
        self, engine, knn_problem
    ):
        """A stall inside the KNN dispatch makes the tight-deadline KNN
        request late; it is shed (typed Overloaded) while its slack PIP
        batchmate keeps its exact result."""
        _, kx, qpts = knn_problem
        rng = np.random.default_rng(6)
        ppts = rng.uniform(BBOX[:2], BBOX[2:], (40, 2))
        shed_before = engine.metrics()["shed_deadline"]
        with faults.stalls(0.8, n=1, sites=("knn.distance",)):
            f_knn = engine.submit_knn(qpts(4, seed=3), 2, deadline_s=0.4)
            f_pip = engine.submit(ppts, deadline_s=60.0)
            with pytest.raises(Overloaded) as exc:
                f_knn.result(timeout=120)
            assert exc.value.reason == "deadline"
            pip_rows = np.asarray(f_pip.result(timeout=120))
        solo = np.asarray(engine.join(ppts, timeout=120))
        np.testing.assert_array_equal(pip_rows, solo)
        assert engine.metrics()["shed_deadline"] == shed_before + 1

    def test_poisoned_rows_quarantined_batchmates_exact(
        self, engine, knn_problem
    ):
        """Non-finite / out-of-domain query rows answer the sentinel
        (ids=-1, distance=inf); the request's clean rows and its
        batchmates answer exactly."""
        _, kx, qpts = knn_problem
        qb = qpts(6, seed=12)
        qb[1] = (np.nan, 3.0)
        qb[4] = (1e9, -1e9)
        clean = qpts(5, seed=13)
        fb = engine.submit_knn(qb, 3)
        fc = engine.submit_knn(clean, 3)
        ab, ac = fb.result(timeout=120), fc.result(timeout=120)
        assert np.all(ab.ids[[1, 4]] == -1)
        assert np.all(np.isinf(ab.distance[[1, 4]]))
        good = [0, 2, 3, 5]
        oids, odist = oracle(kx, qb[good], 3)
        np.testing.assert_array_equal(ab.ids[good], oids)
        assert np.array_equal(ab.distance[good], odist)
        oids, odist = oracle(kx, clean, 3)
        np.testing.assert_array_equal(ac.ids, oids)
        assert np.array_equal(ac.distance, odist)


class TestSwapAndKnobs:
    def test_hot_swap_mid_flight_serves_old_index_to_completion(
        self, pip_index, grid, knn_problem
    ):
        """A KNN request in flight when `hot_swap(knn=...)` lands answers
        from the OLD index (the dispatch snapshot); the next request
        answers from the new one."""
        _, kx, qpts = knn_problem
        rng = np.random.default_rng(21)
        polys, cx, cy = square_wkts(rng, 50)
        kx2 = build_knn_index(
            F.st_geomfromwkt(np.array(polys)), index_system=grid,
            resolution=RES,
        )
        fe2 = KNNFrontend(kx2, lane="ring", row_ladder=ROWS,
                          pair_ladder=PAIRS)
        fe2.warmup()
        fe1 = KNNFrontend(kx, lane="ring", row_ladder=ROWS,
                          pair_ladder=PAIRS)
        fe1.warmup()
        q = qpts(5, seed=33)
        with ServeEngine(
            pip_index, grid, RES, ladder=BucketLadder(64, 256),
            bounds=BBOX, max_wait_s=0.01, knn=fe1,
            default_deadline_s=120.0,
        ) as eng:
            eng.warmup()
            with faults.stalls(1.0, n=1, sites=("knn.expand",)):
                fut = eng.submit_knn(q, 2)
                time.sleep(0.15)  # let the batch enter dispatch
                eng.hot_swap(knn=fe2)
                old = fut.result(timeout=120)
            oids, odist = oracle(kx, q, 2)
            np.testing.assert_array_equal(old.ids, oids)
            assert np.array_equal(old.distance, odist)
            new = eng.join_knn(q, 2, timeout=120)
            oids2, odist2 = oracle(kx2, q, 2)
            np.testing.assert_array_equal(new.ids, oids2)
            assert np.array_equal(new.distance, odist2)
            # the two indexes genuinely disagree — the swap was observable
            assert not np.array_equal(old.distance, new.distance)

    def test_knn_lane_knob_precedence(
        self, pip_index, grid, knn_problem, monkeypatch
    ):
        """`knn_lane` resolves explicit > env > profile > default, like
        every other serve knob."""
        from mosaic_tpu.tune.recommend import TuningProfile

        _, kx, _ = knn_problem
        prof = TuningProfile(knn_lane="voronoi")

        def mk(**kw):
            eng = ServeEngine(
                pip_index, grid, RES, ladder=BucketLadder(64, 256),
                bounds=BBOX, knn=kx, **kw,
            )
            lane = eng.knn.lane
            eng.close()
            return lane

        assert mk() == "ring"  # default
        assert mk(profile=prof) == "voronoi"
        monkeypatch.setenv("MOSAIC_TUNE_KNN_LANE", "ring")
        assert mk(profile=prof) == "ring"  # env beats profile
        assert mk(profile=prof, knn_lane="voronoi") == "voronoi"  # explicit

    def test_engine_without_knn_rejects_knn_requests(
        self, pip_index, grid
    ):
        with ServeEngine(
            pip_index, grid, RES, ladder=BucketLadder(64, 256),
            bounds=BBOX,
        ) as eng:
            with pytest.raises(RuntimeError, match="no KNN frontend"):
                eng.submit_knn(np.zeros((2, 2)), 2)


class TestBatchModelCache:
    def test_pair_distance_program_is_registry_governed(self):
        """The batch model's pairwise-distance program lives in the
        dispatch cache registry (satellite of PR 19): visible in
        `cache_stats()`, cleared by `clear_caches()` — no private
        per-instance dict."""
        from mosaic_tpu.models.knn import _pair_distance_prog

        _pair_distance_prog()
        stats = _dispatch.cache_stats()
        assert stats["knn_pair_distance"]["currsize"] == 1
        _dispatch.clear_caches(names=["knn_pair_distance"])
        assert (
            _dispatch.cache_stats()["knn_pair_distance"]["currsize"] == 0
        )


class TestStoreRelaunch:
    def test_second_frontend_warms_from_the_store_and_compiles_nothing(
        self, knn_problem, tmp_path
    ):
        """A frontend relaunched against the program store the first one
        exported loads every rung, exports none, and serves with zero
        backend compiles — the oracle's bits."""
        _, kx, qpts = knn_problem
        store = str(tmp_path)
        kw = dict(lane="ring", row_ladder=ROWS, pair_ladder=PAIRS,
                  program_store=store)
        fe1 = KNNFrontend(kx, **kw)
        w1 = fe1.warmup()
        assert w1["aot"]["exported"] > 0 and w1["aot"]["loaded"] == 0
        # the same query through the first frontend: the engine's eager
        # host-side ops compile here at this query's shapes, so what is
        # counted below is the relaunched frontend's own programs
        q = qpts(9, seed=77)
        fe1.dispatch(q, 3)
        fe = KNNFrontend(kx, **kw)
        w2 = fe.warmup()
        assert w2["aot"] == {
            "loaded": w1["aot"]["exported"], "exported": 0, "fallback": 0,
        }
        n0 = _dispatch.backend_compiles()
        out, _ = fe.dispatch(q, 3)
        assert _dispatch.backend_compiles() == n0
        assert fe.cold_compiles == 0
        ids, dist = decode_knn(np.asarray(out), 3)
        oids, odist = oracle(kx, q, 3)
        np.testing.assert_array_equal(ids, oids)
        assert np.array_equal(dist, odist)


class TestTuneRouting:
    def test_convex_share_routes_voronoi_with_machine_rationale(self):
        from mosaic_tpu.tune.profiler import WorkloadProfile
        from mosaic_tpu.tune.recommend import recommend

        prof = WorkloadProfile(
            kind="points", n_sampled=100, n_total=1000,
            class_shares={"light": 0.2, "heavy": 0.1, "convex": 0.7},
        )
        rec = recommend(prof)
        assert rec.knn_lane == "voronoi"
        (entry,) = [r for r in rec.rationale if r["knob"] == "knn_lane"]
        assert set(entry) == {"knob", "value", "rule", "evidence"}
        assert entry["rule"] == "convex-share-voronoi-lane"
        assert entry["evidence"]["threshold"] == pytest.approx(0.5)
