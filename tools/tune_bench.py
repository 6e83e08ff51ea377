"""Self-tuning optimizer A/B bench: recommended knobs vs built-in defaults.

The CI twin of `mosaic_tpu/tune/`: two adversarial synthetic workloads on
the CUSTOM grid, each profiled (`tune.profiler`), each given a
recommendation (`tune.recommend` + committed bench history as priors), and
each run BOTH ways — the hand default configuration against the
recommended `TuningProfile` flowing through the normal ``profile=`` entry
points. The workloads are adversarial in opposite directions:

- **dense-urban (resident)** — many small polygons in a ~1 deg bbox, a
  large resident point stream. The hand default resolution under-
  tessellates (fat per-cell chip lists), so steady-state join time is
  dominated by probe work; the analyzer's finer resolution pays. Metric:
  warm join seconds against a resident index (build amortized, reported).
- **sparse-continental (one-shot)** — a handful of huge polygons across
  a 60x30 deg bbox, a sparse one-shot point batch. The same hand default
  resolution now OVER-tessellates (hundreds of thousands of cells for 4
  polygons); the analyzer's coarser pick collapses the build. Metric:
  end-to-end tessellate + index build + join seconds.

Asserted on the way (the CI tune-smoke lane re-asserts from the JSON):

- results are **bit-identical** across profiles on both workloads —
  ``pip_join(recheck=True)`` answers are f64-exact, hence
  resolution-independent (`detail.<workload>.bit_identical`);
- recommended is >= default on both workloads and strictly better on at
  least one (``value`` is the MIN speedup across workloads);
- the serve leg round-trips the recommendation through a versioned
  `ProfileStore` (fingerprinted against the recommended index), hot-swaps
  the live engine, and the swap introduces ZERO cold compiles
  (``detail.serve.cold_compiles_after_swap == 0``) while post-swap
  answers equal the device-path reference join;
- every recommendation carries its machine-checkable rationale
  ``{knob, value, rule, evidence}`` (re-asserted here);
- every stage lands a timed ``tune_stage.<stage>`` telemetry event
  (profile / recommend / ab_default / ab_recommended / hot_swap) — the
  keys `tools/perf_gate.py` gates.

The final stdout line is ALWAYS one machine-parseable JSON object;
everything else goes to stderr.

Usage (CI tune-smoke lane):
  python tools/tune_bench.py --points-a 200000 --trail /tmp/tune.jsonl
  python tools/perf_gate.py --golden tests/goldens/perf_gate.json \
      --trail /tmp/tune.jsonl --stages-prefix tune_stage.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

#: the hand default the recommendation is judged against — a plausible
#: global pick for the CUSTOM(10 deg root, 2 splits) grid: cells of
#: 10/2^6 ~ 0.16 deg, reasonable for country-scale data, adversarially
#: wrong in opposite directions for the two bench workloads
DEFAULT_RES = 6

#: dense-urban bbox: ~1x1 deg (small polygons, dense points)
CITY = (-74.5, 40.0, -73.5, 41.0)
#: sparse-continental bbox: 60x30 deg (4 huge polygons, sparse points)
CONT = (-60.0, -30.0, 0.0, 0.0)


def build_index(polys, grid, res):
    """(chip_index, seconds) — tessellate + chip-index build, timed."""
    from mosaic_tpu.core.tessellate import tessellate
    from mosaic_tpu.sql.join import build_chip_index

    t0 = time.perf_counter()
    index = build_chip_index(
        tessellate(polys, grid, res, keep_core_geoms=False)
    )
    return index, time.perf_counter() - t0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--points-a", type=int, default=500_000,
                    help="dense-urban resident point count")
    ap.add_argument("--points-b", type=int, default=50_000,
                    help="sparse-continental one-shot point count")
    ap.add_argument("--zones-a", type=int, default=10,
                    help="dense-urban zone grid side (n x n polygons)")
    ap.add_argument("--runs", type=int, default=2,
                    help="timed repetitions per resident arm (best-of)")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--trail", default=None,
                    help="export the captured telemetry trail as JSONL")
    args = ap.parse_args()

    emit_to = sys.stdout
    sys.stdout = sys.stderr

    detail: dict = {}
    line = {"metric": "tune_recommended_over_default", "value": 0.0,
            "unit": "x", "detail": detail}
    stages: list = []
    root_span = None
    rc = 1
    try:
        import numpy as np

        from mosaic_tpu import datasets, obs
        from mosaic_tpu.core.index import CustomIndexSystem, GridConf
        from mosaic_tpu.runtime import telemetry
        from mosaic_tpu.serve import ServeEngine
        from mosaic_tpu.sql.join import pip_join
        from mosaic_tpu.tune import (
            ProfileStore,
            TuningProfile,
            index_fingerprint,
            load_priors,
            profile_points,
            profile_polygons,
            recommend,
        )

        cap = telemetry.capture()
        stages = cap.__enter__()
        root_span = obs.start_span(
            "tune_bench", points_a=args.points_a, points_b=args.points_b
        )
        from mosaic_tpu.runtime.platform import (
            configure_compile_cache,
            require_device,
        )

        # raises off-TPU unless JAX_PLATFORMS=cpu asked for the CPU
        detail["platform"] = require_device()["platform"]
        detail["compile_cache_dir"] = configure_compile_cache()
        detail["default_resolution"] = DEFAULT_RES

        grid = CustomIndexSystem(GridConf(-180, 180, -90, 90, 2, 10.0, 10.0))
        priors = load_priors()
        detail["priors"] = sorted(
            name for name in priors.get("artifacts", {})
        )

        na = args.zones_a
        workloads = {
            "dense_urban": {
                "mode": "resident",
                "polys": datasets.synthetic_zones(na, na, bbox=CITY,
                                                  seed=args.seed),
                "points": datasets.random_points(args.points_a, bbox=CITY,
                                                 seed=args.seed + 1),
            },
            "sparse_continental": {
                "mode": "one_shot",
                "polys": datasets.synthetic_zones(2, 2, bbox=CONT,
                                                  seed=args.seed, verts=48),
                "points": datasets.random_points(args.points_b, bbox=CONT,
                                                 seed=args.seed + 2),
            },
        }

        speedups = {}
        serve_ctx = None  # (rec_index, rec_profile, default_index, points)
        for name, w in workloads.items():
            polys, pts = w["polys"], w["points"]
            wd: dict = {"mode": w["mode"], "n_points": int(pts.shape[0]),
                        "n_polygons": len(polys)}
            detail[name] = wd

            # ---- profile both sides, recommend, merge. The default
            # index doubles as the point profiler's resident target.
            default_index, build_default_s = build_index(
                polys, grid, DEFAULT_RES
            )
            prof_poly = profile_polygons(polys, grid)
            prof_pts = profile_points(
                pts, default_index, grid, DEFAULT_RES, seed=args.seed
            )
            rec = TuningProfile.merged(
                recommend(prof_poly, priors), recommend(prof_pts, priors)
            )
            bad = [r for r in rec.rationale
                   if {"knob", "value", "rule", "evidence"} - set(r)]
            if bad or not rec.rationale:
                raise AssertionError(
                    f"{name}: recommendation rationale is not "
                    f"machine-checkable: {bad or 'empty'}"
                )
            rec_res = int(rec.resolution)
            rec_index, build_rec_s = build_index(polys, grid, rec_res)
            wd["recommended"] = {
                k: v for k, v in rec.as_dict().items()
                if k not in ("rationale", "source") and v is not None
            }
            wd["rationale_rules"] = sorted(
                {r["rule"] for r in rec.rationale}
            )
            wd["build_seconds"] = {
                "default": round(build_default_s, 4),
                "recommended": round(build_rec_s, 4),
            }

            # ---- the two arms. recheck=True answers are f64-exact and
            # therefore resolution-independent: bit-identity across the
            # two profiles is a correctness assertion, not luck.
            def arm(tag, index, res, profile, kw_pts=pts, kw_name=name,
                    mode=w["mode"], kw_polys=polys):
                best, out = float("inf"), None
                runs = args.runs if mode == "resident" else 1
                for _ in range(runs):
                    with telemetry.timed(
                        "tune_stage", stage=tag, workload=kw_name
                    ):
                        t0 = time.perf_counter()
                        if mode == "one_shot":
                            # one-shot pays tessellation + build too
                            index2, _ = build_index(kw_polys, grid, res)
                        else:
                            index2 = index
                        out = pip_join(
                            kw_pts, None, grid,
                            None if profile is not None else res,
                            chip_index=index2, recheck=True,
                            profile=profile,
                        )
                        best = min(best, time.perf_counter() - t0)
                return best, np.asarray(out)

            # resident arms warm the jit caches once, untimed
            if w["mode"] == "resident":
                pip_join(pts, None, grid, DEFAULT_RES,
                         chip_index=default_index, recheck=True)
                pip_join(pts, None, grid, None, chip_index=rec_index,
                         recheck=True, profile=rec)
            default_s, out_default = arm(
                "ab_default", default_index, DEFAULT_RES, None
            )
            rec_s, out_rec = arm(
                "ab_recommended", rec_index, rec_res, rec
            )

            identical = bool(np.array_equal(out_default, out_rec))
            wd["bit_identical"] = identical
            wd["seconds"] = {"default": round(default_s, 4),
                             "recommended": round(rec_s, 4)}
            speedups[name] = default_s / max(rec_s, 1e-9)
            wd["speedup"] = round(speedups[name], 3)
            if not identical:
                raise AssertionError(
                    f"{name}: recommended profile changed the answers — "
                    "recheck=True joins must be bit-identical across "
                    "resolutions"
                )
            if name == "dense_urban":
                serve_ctx = (rec_index, rec, default_index, pts)

        # ---- serve leg: store round-trip + hot swap on the live engine
        rec_index, rec, default_index, pts = serve_ctx
        serve: dict = {}
        detail["serve"] = serve
        queries = [pts[i * 512:(i + 1) * 512] for i in range(8)]
        with tempfile.TemporaryDirectory() as tmpdir:
            store = ProfileStore(os.path.join(tmpdir, "profiles"))
            fp = index_fingerprint(rec_index)
            store.save(rec, fingerprint=fp)
            loaded, payload = store.load_latest(expect_fingerprint=fp)
            serve["store_version"] = payload["profile_version"]

            with ServeEngine(
                default_index, grid, DEFAULT_RES, max_wait_s=0.0005
            ) as engine:
                engine.warmup()
                for q in queries:  # pre-swap traffic on the old core
                    engine.join(q, timeout=30.0)
                with telemetry.timed("tune_stage", stage="hot_swap"):
                    stats = engine.hot_swap(rec_index, profile=loaded)
                serve["swap_warmup"] = stats
                post = [
                    np.asarray(engine.join(q, timeout=30.0))
                    for q in queries
                ]
                cold = int(engine.metrics()["cold_compiles"])
                serve["cold_compiles_after_swap"] = cold
                serve["post_probe"] = engine.probe
                reference = pip_join(
                    np.concatenate(queries), None, grid,
                    int(rec.resolution), chip_index=rec_index,
                    recheck=False, probe=engine.probe,
                    writeback=engine.writeback,
                )
                agree = bool(np.array_equal(
                    np.concatenate(post).astype(np.int64),
                    np.asarray(reference).astype(np.int64),
                ))
                serve["post_matches_reference"] = agree
        if cold:
            raise AssertionError(
                f"hot swap leaked {cold} cold compiles — warmup must "
                "precompile every recommended ladder rung before rebind"
            )
        if not agree:
            raise AssertionError(
                "post-swap serve answers diverge from the device-path "
                "reference join on the recommended index"
            )

        worst = min(speedups.values())
        best = max(speedups.values())
        detail["speedups"] = {k: round(v, 3) for k, v in speedups.items()}
        line["value"] = round(worst, 3)
        if worst < 1.0 or best < 1.1:
            raise AssertionError(
                f"recommendation did not pay: speedups {speedups} — must "
                "be >= 1.0 on both workloads and > 1.1 on at least one"
            )
        rc = 0
    except Exception as e:  # lint: broad-except-ok (bench must always emit its JSON line; rc carries failure)
        detail["error"] = repr(e)[:400]

    if root_span is not None:
        try:
            root_span.end()
        except Exception:  # lint: broad-except-ok (span cleanup must not mask the bench result)
            pass
    if args.trail and stages:
        try:
            from mosaic_tpu import obs as _obs

            _obs.write_jsonl(stages, args.trail)
        except Exception as e:  # lint: broad-except-ok (a sick trail disk degrades the trail, not the bench)
            detail["trail_error"] = repr(e)[:200]

    emit_to.write(json.dumps(line) + "\n")
    emit_to.flush()
    return rc


if __name__ == "__main__":
    sys.exit(main())
