"""Traffic generation: the open-loop schedule and its clock, and the
hotspot point generator."""

import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

from bh_fixtures import REPO, TINY_POINTS

from benchmark.generators import points
from benchmark.harness.context import Ctx, SpanLog, TraceSession
from benchmark.harness.spec import Spec

MIX = {
    "kind": "open_loop_requests", "rate_per_s": 200.0,
    "size_rows": {"median": 16, "sigma": 1.5, "min": 1, "max": 8192},
    "pool_rows": 20000, "points": TINY_POINTS, "schedule_seed": 11,
}


def _kind():
    return Spec(REPO).module("traffic_kinds", "open_loop_requests")


def test_schedule_same_work_every_seed_in_another_order():
    kind = _kind()
    d1, s1, st1 = kind.schedule(MIX, 1, 5.0)
    d2, s2, st2 = kind.schedule(MIX, 4_000_000_123, 5.0)
    assert len(d1) == len(d2) == 1000
    assert sorted(s1) == sorted(s2) and not np.array_equal(s1, s2)
    # the same inter-arrival gaps, permuted (each schedule starts at 0, so
    # it shows all of its gaps but the first)
    g1, g2 = np.round(np.diff(d1), 9), np.round(np.diff(d2), 9)
    assert np.isin(g1, g2).sum() >= len(g1) - 1
    # the schedule starts at 0 and spans the window
    assert d1[0] == 0.0 and d1[-1] < 5.0 and d1[-1] > 4.0
    assert s1.min() >= 1 and s1.max() <= 8192
    assert 12 <= np.median(s1) <= 20
    # same seed, same schedule
    d1b, s1b, st1b = kind.schedule(MIX, 1, 5.0)
    assert np.array_equal(d1, d1b) and np.array_equal(s1, s1b)
    assert np.array_equal(st1, st1b)


class _StalledEngine:
    """A fake server: answers in order on one thread, and sleeps once."""

    def __init__(self, stall_at: int, stall_s: float):
        self.q: list = []
        self.n = 0
        self.stall_at, self.stall_s = stall_at, stall_s
        self.cv = threading.Condition()
        self.stop = False
        self.t = threading.Thread(target=self._loop, daemon=True)  # lint: thread-context-adoption-ok (a fake server: no telemetry, spans or fault plans cross into it)
        self.t.start()

    def submit(self, pts):
        f = Future()
        with self.cv:
            self.q.append((f, len(pts)))
            self.cv.notify()
        return f

    def _loop(self):
        while True:
            with self.cv:
                while not self.q and not self.stop:
                    self.cv.wait()
                if self.stop and not self.q:
                    return
                f, n = self.q.pop(0)
            if self.n == self.stall_at:
                time.sleep(self.stall_s)
            self.n += 1
            f.set_result(np.zeros(n, np.int32))

    def metrics(self):
        return {"batches": self.n, "occupancy_sum": 0.5 * self.n,
                "batched_requests": self.n}

    def close(self):
        with self.cv:
            self.stop = True
            self.cv.notify()
        self.t.join(5)


def test_latency_runs_from_the_due_instant_so_a_stall_shows():
    """The generator keeps its schedule through a 0.3 s server stall; the
    requests queued behind the stall are timed from when they were DUE,
    so their latencies carry the wait."""
    kind = _kind()
    mix = dict(MIX, rate_per_s=100.0,
               size_rows={"median": 4, "sigma": 0.1, "min": 1, "max": 8})
    spans = SpanLog()
    ctx = Ctx(spec=Spec(REPO), cell={"chips": 1}, config={}, traffic=mix,
              seed=3, seconds=1.0, trace=False, spans=spans,
              tracer=TraceSession(False, "", spans))
    due, sizes, starts = kind.schedule(mix, 3, 1.0)
    pool = np.zeros((20000, 2))
    engine = _StalledEngine(stall_at=20, stall_s=0.3)
    st = {"engine": engine, "pool": pool, "sent": pool, "due": due,
          "sizes": sizes, "starts": starts}
    try:
        out = kind.window(ctx, st)
    finally:
        engine.close()
    assert out["attempted"] == 100 and out["failed"] == 0
    lat = ctx.series["latency_ms"]
    # requests before the stall are quick; the ones due during it waited
    assert max(lat[:15]) < 50
    assert max(lat) > 250
    waited = sum(x > 100 for x in lat)
    assert 10 <= waited <= 60          # ~30 requests were due in 0.3 s
    assert out["metrics"]["latency_p95_ms"] > 100
    assert out["metrics"]["latency_p50_ms"] < 100
    # the generator itself was not late: it never waited on a completion
    assert np.percentile(ctx.series["gen_lag_s"], 95) < 0.05


PARAMS = {"hotspot_share": 0.9, "hotspots": 64, "zipf_s": 1.1,
          "sigma_m": [300, 3000], "lat0_deg": 40.7, "layout_seed": 20260927}
BBOX = (-74.3, 40.4, -73.6, 41.0)


def test_hotspot_generator_seed_determinism():
    gen = points.make_generator(PARAMS, BBOX, 4096)
    a = np.asarray(gen(points.seed_key(4_000_000_123)))
    b = np.asarray(gen(points.seed_key(4_000_000_123)))
    c = np.asarray(gen(points.seed_key(5)))
    assert a.shape == (4096, 2) and a.dtype == np.float64
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    ring = points.make_generator(PARAMS, BBOX, 512, slots=3)
    r = np.asarray(ring(points.seed_key(9)))
    assert r.shape == (3, 512, 2)
    assert not np.array_equal(r[0], r[1])


def test_hotspot_generator_parameter_shares():
    lay = points.layout(PARAMS, BBOX)
    assert lay["centres"].shape == (64, 2) and lay["share"] == 0.9
    # sigma between 300 m and 3 km, in degrees of latitude
    sig_m = lay["sigma"][:, 1] * 111_320.0
    assert sig_m.min() >= 300 and sig_m.max() <= 3000
    assert lay["sigma"][0, 0] > lay["sigma"][0, 1]  # longitude is wider
    # the layout does not move with the run's seed
    assert np.array_equal(lay["centres"], points.layout(PARAMS, BBOX)["centres"])
    n = 200_000
    # four narrow hotspots: a point within 5 sigma of one is a hotspot
    # point, so the share shows directly (uniform points hardly ever are)
    narrow = dict(PARAMS, hotspots=4, sigma_m=[50, 60])
    nl = points.layout(narrow, BBOX)
    p = np.asarray(points.make_generator(narrow, BBOX, n)(points.seed_key(1)))
    d = (p[:, None, :] - nl["centres"][None, :, :]) / nl["sigma"][None]
    r = np.sqrt((d ** 2).sum(-1))
    assert r.min(axis=1).__lt__(5.0).mean() == pytest.approx(0.9, abs=0.005)
    # Zipf: hotspot 1 draws w1 / sum(w) of the hotspot points
    w = np.arange(1, 5) ** -1.1
    assert (r[:, 0] < 5.0).mean() == pytest.approx(
        0.9 * w[0] / w.sum(), rel=0.03)
    assert (r[:, 3] < 5.0).mean() == pytest.approx(
        0.9 * w[3] / w.sum(), rel=0.06)
    # the uniform mix is the same generator with share 0
    u = np.asarray(points.make_generator(
        dict(PARAMS, hotspot_share=0.0), BBOX, n)(points.seed_key(1)))
    assert (u[:, 0] >= BBOX[0]).all() and (u[:, 0] <= BBOX[2]).all()
    assert abs(np.median(u[:, 0]) - (BBOX[0] + BBOX[2]) / 2) < 0.01
