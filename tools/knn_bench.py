"""KNN-serving load generator: the KNN twin of `tools/serve_bench.py`.

Drives :meth:`mosaic_tpu.serve.ServeEngine.submit_knn` over a resident
:class:`mosaic_tpu.knn.KNNIndex` (dense convex candidates on the custom
grid index — the CPU-friendly fixture the knn test suite uses) and
reports the four things PR 19 promises:

- **agreement** — every served answer is bit-compared (neighbour ids
  AND f64 distance bits) against the engine-less frontend, the batch
  ``SpatialKNN`` model run exact, and the brute-force f64 host oracle;
  the headline artifact records the fraction that agree (must be 1.0);
- **closed-loop saturation** (``--requests`` / ``--concurrency``):
  workers resubmit the moment their previous answer lands — queries/s
  at saturation is the headline ``value``;
- **open-loop overload**: Poisson arrivals at ``--overload-mult`` x the
  measured closed-loop capacity; every rejected request must be a typed
  ``Overloaded`` (queue-full at submit or deadline at delivery) — the
  typed-shed fraction and a count of untyped failures (must be 0) land
  in ``detail``;
- **lane A/B** — the Voronoi convex fast path vs ring expansion on the
  same warmed batches: ``detail.voronoi_speedup_vs_ring`` is the number
  `tune/recommend.py` reads as its measured prior, and ``detail.
  voronoi_adopted`` records whether it clears the 1.3x adoption bar;
- **compile story** — signatures warmed per rung, cold compiles after
  warmup (must be 0), and a store-backed relaunch: a second frontend
  warms purely from the exported AOT program store and serves with zero
  backend compiles.

Last stdout line is ALWAYS one machine-parseable JSON object; everything
else goes to stderr.

CPU CI smoke:
  JAX_PLATFORMS=cpu python tools/knn_bench.py \
      --requests 40 --overload-requests 60 --out /tmp/KNN.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

BBOX = (-25.0, -25.0, 35.0, 20.0)
RES = 3

PIP_ZONES = [
    "POLYGON ((1 1, 13 2, 12 11, 6 14, 2 9, 1 1))",
    "POLYGON ((-20 -20, -5 -20, -5 -5, -20 -5, -20 -20))",
    "POLYGON ((20 -10, 30 -10, 30 5, 20 5, 20 -10))",
]


def _fixture(args):
    """Candidates + index + a query sampler that stays strictly inside
    the candidate bbox."""
    from mosaic_tpu import functions as F
    from mosaic_tpu.core.geometry import wkt
    from mosaic_tpu.core.index import CustomIndexSystem, GridConf
    from mosaic_tpu.core.tessellate import tessellate
    from mosaic_tpu.knn import build_knn_index
    from mosaic_tpu.sql.join import build_chip_index

    grid = CustomIndexSystem(GridConf(-180, 180, -90, 90, 2, 10.0, 10.0))
    rng = np.random.default_rng(args.seed)
    cx = rng.uniform(BBOX[0], BBOX[2], args.candidates)
    cy = rng.uniform(BBOX[1], BBOX[3], args.candidates)
    s = rng.uniform(0.5, 1.5, args.candidates)
    polys = [
        f"POLYGON(({x} {y}, {x + w} {y}, {x + w} {y + w},"
        f" {x} {y + w}, {x} {y}))"
        for x, y, w in zip(cx, cy, s)
    ]
    cand = F.st_geomfromwkt(np.array(polys))
    kx = build_knn_index(cand, index_system=grid, resolution=RES)
    pip = build_chip_index(
        tessellate(wkt.from_wkt(PIP_ZONES), grid, RES, keep_core_geoms=False)
    )
    lo = np.array([cx.min(), cy.min()])
    hi = np.array([cx.max(), cy.max()])

    def qpts(n, seed):
        r = np.random.default_rng(seed)
        return lo + r.uniform(0.1, 0.9, (n, 2)) * (hi - lo)

    return grid, cand, kx, pip, qpts


def _agreement(engine, frontend, cand, kx, qpts, args, detail) -> float:
    """Bit-compare served answers against the engine-less frontend, the
    exact batch model, and the f64 host oracle. Returns the fraction of
    queries where ALL four sources agree on ids and distance bits."""
    from mosaic_tpu.knn import brute_force_knn, decode_knn
    from mosaic_tpu.models import SpatialKNN

    k = args.k
    sizes = (args.rows - 1, args.rows, args.rows + 1)  # straddle a rung
    qs = [qpts(max(n, 1), 900 + i) for i, n in enumerate(sizes)]
    answers = [f.result() for f in
               [engine.submit_knn(q, k) for q in qs]]
    allq = np.concatenate(qs)
    sids = np.concatenate([a.ids for a in answers])
    sdist = np.concatenate([a.distance for a in answers])

    out, _ = frontend.dispatch(allq, k)
    fids, fdist = decode_knn(np.asarray(out), k)

    oids, odist = brute_force_knn(allq, kx, k)

    from mosaic_tpu import functions as F

    m = SpatialKNN(
        index=engine.index_system, resolution=RES, k_neighbours=k,
        max_iterations=64, early_stop_iterations=100, approximate=False,
    )
    res = m.transform(F.st_point(allq[:, 0], allq[:, 1]), cand)
    bids = np.full((allq.shape[0], k), -1, np.int64)
    bdist = np.full((allq.shape[0], k), np.inf)
    for li, ci, d, r in zip(
        res.landmark_id, res.candidate_id, res.distance, res.rank
    ):
        bids[li, r - 1] = ci
        bdist[li, r - 1] = d

    ok = (
        np.all(sids == fids, axis=1)
        & np.all(sids == oids, axis=1)
        & np.all(sids == bids, axis=1)
        & np.all(sdist == fdist, axis=1)
        & np.all(sdist == odist, axis=1)
        & np.all(sdist == bdist, axis=1)
    )
    detail["agreement"] = {
        "queries": int(allq.shape[0]),
        "k": k,
        "vs": ["frontend", "batch_spatial_knn", "oracle_f64"],
        "fraction": round(float(ok.mean()), 6),
    }
    return float(ok.mean())


def _closed_loop(engine, qpts, args, detail) -> float:
    """Saturation: each worker resubmits on completion. Returns measured
    queries/sec."""
    from mosaic_tpu.runtime.errors import Overloaded

    reqs = [qpts(args.rows, 100 + i) for i in range(args.requests)]
    cursor = {"i": 0}
    lock = threading.Lock()
    completed = {"q": 0, "shed": 0}

    def worker():
        while True:
            with lock:
                i = cursor["i"]
                if i >= len(reqs):
                    return
                cursor["i"] = i + 1
            try:
                engine.submit_knn(reqs[i], args.k).result()
                with lock:
                    completed["q"] += reqs[i].shape[0]
            except Overloaded:
                with lock:
                    completed["shed"] += 1

    threads = [
        threading.Thread(target=worker, daemon=True)  # lint: thread-context-adoption-ok (load generator: client-side throughput only, telemetry is emitted by the engine's own threads)
        for _ in range(max(args.concurrency, 1))
    ]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    qps = completed["q"] / max(wall, 1e-9)
    detail["closed_loop"] = {
        "requests": args.requests,
        "rows_per_request": args.rows,
        "concurrency": args.concurrency,
        "wall_s": round(wall, 3),
        "queries_per_sec": round(qps, 2),
        "requests_per_sec": round(
            (args.requests - completed["shed"]) / max(wall, 1e-9), 2
        ),
        "shed": completed["shed"],
    }
    return qps


def _open_loop(engine, qpts, args, capacity_rps, detail) -> None:
    """Overload: Poisson arrivals at ``--overload-mult`` x the measured
    request capacity. Every rejection must be a typed ``Overloaded``."""
    from mosaic_tpu.runtime.errors import Overloaded

    rate = max(capacity_rps, 0.5) * args.overload_mult
    rng = np.random.default_rng(args.seed + 1)
    n = args.overload_requests
    shed_submit = shed_deadline = untyped = completed = 0
    futures = []
    next_t = time.perf_counter()
    t0 = next_t
    for i in range(n):
        next_t += float(rng.exponential(1.0 / rate))
        lag = next_t - time.perf_counter()
        if lag > 0:
            time.sleep(lag)
        try:
            futures.append(engine.submit_knn(
                qpts(args.rows, 500 + i), args.k,
                deadline_s=args.overload_deadline_s,
            ))
        except Overloaded:
            shed_submit += 1
        except Exception:  # lint: broad-except-ok (anything untyped at submit is exactly what this lane counts)
            untyped += 1
    for f in futures:
        try:
            f.result()
            completed += 1
        except Overloaded as e:
            if e.reason == "deadline":
                shed_deadline += 1
            else:
                shed_submit += 1
        except Exception:  # lint: broad-except-ok (anything untyped at delivery is exactly what this lane counts)
            untyped += 1
    detail["open_loop"] = {
        "requests": n,
        "rate_per_sec": round(rate, 2),
        "overload_mult": args.overload_mult,
        "deadline_s": args.overload_deadline_s,
        "completed": completed,
        "shed_submit": shed_submit,
        "shed_deadline": shed_deadline,
        "typed_shed_fraction": round(
            (shed_submit + shed_deadline) / max(n, 1), 4
        ),
        "untyped_failures": untyped,
        "wall_s": round(time.perf_counter() - t0, 3),
    }


def _lane_ab(kx, qpts, args, detail) -> None:
    """Voronoi convex fast path vs ring expansion, both warmed, same
    batches; ``voronoi_speedup_vs_ring`` is the tune prior."""
    from mosaic_tpu.knn import KNNFrontend

    batches = [qpts(args.rows, 700 + i) for i in range(args.ab_batches)]

    def run(lane):
        fe = KNNFrontend(kx, lane=lane)
        fe.warmup()
        outs = []
        t0 = time.perf_counter()
        for q in batches:
            out, _ = fe.dispatch(q, args.k)
            outs.append(np.asarray(out))
        return time.perf_counter() - t0, outs, fe

    t_ring, out_r, _ = run("ring")
    t_vor, out_v, fv = run("voronoi")
    identical = all(
        np.array_equal(a, b) for a, b in zip(out_r, out_v)
    )
    speedup = t_ring / max(t_vor, 1e-9)
    detail["lane_ab"] = {
        "batches": args.ab_batches,
        "rows_per_batch": args.rows,
        "ring_wall_s": round(t_ring, 3),
        "voronoi_wall_s": round(t_vor, 3),
        "bit_identical": bool(identical),
        "voronoi_fallback_rows": fv.stats["voronoi_fallback"],
    }
    detail["voronoi_speedup_vs_ring"] = round(speedup, 3)
    detail["voronoi_adopted"] = bool(speedup >= 1.3 and identical)


def _relaunch(kx, qpts, args, detail) -> None:
    """Store-backed relaunch: warm a fresh frontend purely from the AOT
    program store exported by the first, then serve with zero backend
    compiles."""
    from mosaic_tpu.knn import KNNFrontend
    from mosaic_tpu.serve import backend_compiles

    store = tempfile.mkdtemp(prefix="knn_bench_store_")
    fe1 = KNNFrontend(kx, lane=args.lane, program_store=store)
    w1 = fe1.warmup()
    fe2 = KNNFrontend(kx, lane=args.lane, program_store=store)
    w2 = fe2.warmup()
    c0 = backend_compiles()
    for i in range(3):
        fe2.dispatch(qpts(args.rows, 800 + i), args.k)
    c1 = backend_compiles()
    detail["relaunch"] = {
        "store_exported": w1["aot"]["exported"],
        "store_loaded": w2["aot"]["loaded"],
        "relaunch_backend_compiles_serving": (
            c1 - c0 if c0 is not None and c1 is not None else None
        ),
        "relaunch_cold_compiles": fe2.cold_compiles,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--candidates", type=int, default=120)
    ap.add_argument("--requests", type=int, default=40,
                    help="closed-loop request count")
    ap.add_argument("--concurrency", type=int, default=4)
    ap.add_argument("--rows", type=int, default=8,
                    help="queries per request")
    ap.add_argument("--k", type=int, default=3)
    ap.add_argument("--lane", choices=("ring", "voronoi"), default="ring",
                    help="lane the served engine dispatches")
    ap.add_argument("--overload-mult", type=float, default=10.0)
    ap.add_argument("--overload-requests", type=int, default=60)
    ap.add_argument("--overload-deadline-s", type=float, default=2.0)
    ap.add_argument("--ab-batches", type=int, default=4)
    ap.add_argument("--queue-cap", type=int, default=8)
    ap.add_argument("--window-ms", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=19)
    ap.add_argument("--trail", default=None,
                    help="export the captured telemetry trail "
                    "(knn_stage timings included) as JSONL")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    # the LAST stdout line must be the JSON artifact
    emit_to = sys.stdout
    sys.stdout = sys.stderr

    t_all = time.perf_counter()
    detail: dict = {}
    line = {
        "metric": "knn_throughput",
        "value": 0.0,
        "unit": "queries/sec",
        "detail": detail,
    }
    from mosaic_tpu.runtime.platform import (
        configure_compile_cache,
        require_device,
    )

    # raises off-TPU unless JAX_PLATFORMS=cpu asked for the CPU
    detail["device_info"] = require_device()
    detail["compile_cache_dir"] = configure_compile_cache()
    try:
        import jax

        from mosaic_tpu.knn import KNNFrontend
        from mosaic_tpu.runtime import telemetry
        from mosaic_tpu.serve import BucketLadder, ServeEngine

        detail["device"] = str(jax.devices()[0])
        detail["lane"] = args.lane
        grid, cand, kx, pip, qpts = _fixture(args)
        detail["fixture"] = {
            "candidates": args.candidates,
            "index": "custom-grid",
            "resolution": RES,
            "voronoi_sites": int(kx.n),
        }

        fe = KNNFrontend(kx, lane=args.lane)
        engine = ServeEngine(
            pip, grid, RES, ladder=BucketLadder(64, 1024), bounds=BBOX,
            knn=fe, max_wait_s=args.window_ms / 1e3,
            queue_capacity=args.queue_cap, default_deadline_s=60.0,
        )
        t0 = time.perf_counter()
        warm = engine.warmup()
        detail["warmup"] = dict(
            warm, wall_s=round(time.perf_counter() - t0, 3)
        )

        with telemetry.capture() as events:
            main_sinks = telemetry.current_sinks()
            del main_sinks  # workers emit nothing; engine threads adopt downstream

            agreement = _agreement(
                engine, fe, cand, kx, qpts, args, detail
            )
            qps = _closed_loop(engine, qpts, args, detail)
            line["value"] = round(qps, 2)
            _open_loop(
                engine, qpts, args,
                detail["closed_loop"]["requests_per_sec"], detail,
            )

        m = engine.metrics()
        detail["engine"] = {
            "batches": m["batches"],
            "cold_compiles": m["cold_compiles"],
            "knn_queries": m["knn_queries"],
            "knn_degraded": m["knn_degraded"],
            "knn_pair_occupancy": m["knn_pair_occupancy"],
            "occupancy_mean": m["occupancy_mean"],
        }
        detail["stage_summary"] = telemetry.summarize(
            events, event="knn_stage"
        )
        engine.close()
        if args.trail:
            from mosaic_tpu import obs

            obs.write_jsonl(events, args.trail)

        _lane_ab(kx, qpts, args, detail)
        _relaunch(kx, qpts, args, detail)
        detail["agreement_ok"] = bool(agreement == 1.0)
    except Exception as e:  # lint: broad-except-ok (the artifact line must still parse — errors are reported inside it)
        detail["error"] = repr(e)[:400]
        try:
            import jax as _j

            detail.setdefault("device", str(_j.devices()[0]))
        except Exception:  # lint: broad-except-ok (best-effort device stamp on an already-failing run)
            detail.setdefault("device", "unknown")

    detail["total_wall_s"] = round(time.perf_counter() - t_all, 1)
    out = json.dumps(line)
    emit_to.write(out + "\n")
    emit_to.flush()
    if args.out:
        with open(args.out, "w") as f:
            f.write(out + "\n")
    if detail.get("error") and not line["value"]:
        sys.exit(1)


if __name__ == "__main__":
    main()
