"""Epochal-index bench: delta-patch speedup, kill-storm replay, publish latency.

The CI twin of `mosaic_tpu/index/epoch.py` — three lanes, one committed
`EPOCH_r*.json` artifact:

1. **churn** — a 1%-churn live-edit workload at vertex-heavy scale
   (dented 96-gon "blobs": tessellation, not index build, dominates a
   rebuild, which is exactly the regime mutable indexes exist for).
   Each round perturbs ``--churn-pct`` of the geometries, ``apply``\\ s
   the delta and ``publish``\\ es the epoch; the baseline is a warm
   from-scratch ``tessellate + build_chip_index`` of the same column.
   Headline = rebuild seconds / patch seconds (median over rounds),
   asserted ``>= --min-speedup``; every round's published index is
   asserted bit-identical to the from-scratch rebuild.
2. **kill-storm** — a synthetic kill at EVERY fault-site boundary of
   the epoch lifecycle (apply pre-tessellate / pre-append /
   post-append, publish pre-build / torn swap-vs-counter, compact
   pre-snapshot / pre-truncate / post-truncate), each followed by
   ``EpochalIndex.replay``; every survivor must be bit-identical to a
   from-scratch rebuild of the surviving epoch. ``identical`` MUST
   equal ``boundaries``.
3. **serve** — publishes driven through a live ``ServeEngine`` while a
   client thread keeps submitting joins: records publish p50/p99 and
   the worst request latency observed DURING a publish window, asserts
   traffic kept flowing (requests completed inside every publish
   window) and no request errored — the publish-never-blocks claim.

Every stage lands a timed ``epoch_stage.<stage>`` telemetry event
(tessellate / append / materialize / build / compact / replay) — the
keys `tools/perf_gate.py` gates, with the 10x ``--inject-slowdown``
negative lane in CI.

The final stdout line is ALWAYS one machine-parseable JSON object;
everything else goes to stderr.

Usage (CI epoch-smoke lane):
  python tools/epoch_bench.py --n-side 20 --reps 2 --min-speedup 1.5 \
      --trail /tmp/epoch.jsonl
  python tools/perf_gate.py --golden tests/goldens/perf_gate.json \
      --trail /tmp/epoch.jsonl --stages-prefix epoch_stage.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

#: the kill matrix the bench storms through: (site, boundaries let
#: through before the kill, epoch the log must replay to) — mirrors
#: tests/test_epoch.py::KILL_MATRIX
KILL_MATRIX = [
    ("epoch.apply", 0, 0),
    ("epoch.apply", 1, 0),
    ("epoch.apply", 2, 1),
    ("epoch.publish", 0, 1),
    ("epoch.publish", 1, 1),
    ("epoch.compact", 0, 1),
    ("epoch.compact", 1, 1),
    ("epoch.compact", 2, 1),
]


def blob_wkt(i: int, j: int, phase: float, cw: float, verts: int):
    """One dented ``verts``-gon around lattice site (i, j) — vertex-
    heavy enough that tessellation dominates, small enough (~0.8 cell
    across) that the chip table stays lean."""
    import numpy as np

    th = np.linspace(0, 2 * np.pi, verts, endpoint=False)
    cx, cy = -80.0 + i * 2.2 * cw, -84.0 + j * 2.2 * cw
    rr = 0.42 * cw * (1.0 + 0.22 * np.sin(7 * th + phase + 0.1 * (i + j)))
    xs, ys = cx + rr * np.cos(th), cy + rr * np.sin(th)
    pts = ", ".join(f"{x:.6f} {y:.6f}" for x, y in zip(xs, ys))
    return f"POLYGON (({pts}, {xs[0]:.6f} {ys[0]:.6f}))"


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-side", type=int, default=60,
                    help="blobs per lattice side (geoms = n_side^2)")
    ap.add_argument("--verts", type=int, default=96)
    ap.add_argument("--res", type=int, default=4)
    ap.add_argument("--churn-pct", type=float, default=1.0)
    ap.add_argument("--reps", type=int, default=3,
                    help="churn rounds (speedup = median over rounds)")
    ap.add_argument("--min-speedup", type=float, default=1.5,
                    help="fail below this patch-vs-rebuild speedup; "
                    "CI smoke lanes keep a conservative floor, the "
                    "committed round is the measured claim")
    ap.add_argument("--serve-publishes", type=int, default=3)
    ap.add_argument("--log-dir", default=None,
                    help="delta-log directory for the churn lane "
                    "(default: a temp dir)")
    ap.add_argument("--trail", default=None,
                    help="export the captured telemetry trail as JSONL")
    args = ap.parse_args()

    emit_to = sys.stdout
    sys.stdout = sys.stderr

    detail: dict = {}
    line = {"metric": "epoch_patch_speedup_vs_rebuild", "value": 0.0,
            "unit": "x", "detail": detail}
    stages: list = []
    root_span = None
    rc = 1
    try:
        import tempfile

        import numpy as np

        from mosaic_tpu import obs
        from mosaic_tpu.core.geometry import wkt
        from mosaic_tpu.core.index import CustomIndexSystem, GridConf
        from mosaic_tpu.core.tessellate import tessellate
        from mosaic_tpu.index import EpochalIndex, chip_index_equal
        from mosaic_tpu.runtime import faults, telemetry
        from mosaic_tpu.serve import BucketLadder, ServeEngine
        from mosaic_tpu.sql.join import build_chip_index

        cap = telemetry.capture()
        stages = cap.__enter__()
        root_span = obs.start_span("epoch_bench", n_side=args.n_side,
                                   res=args.res)
        from mosaic_tpu.runtime.platform import (
            configure_compile_cache,
            require_device,
        )

        # raises off-TPU unless JAX_PLATFORMS=cpu asked for the CPU
        detail["platform"] = require_device()["platform"]
        detail["compile_cache_dir"] = configure_compile_cache()
        grid = CustomIndexSystem(GridConf(-180, 180, -90, 90, 2,
                                          10.0, 10.0))
        cw, _ = grid.cell_size(args.res)
        n_geoms = args.n_side * args.n_side
        n_churn = max(1, int(round(n_geoms * args.churn_pct / 100.0)))
        detail["geoms"] = n_geoms
        detail["churn_geoms"] = n_churn

        def column(phase, only=None):
            gids = range(n_geoms) if only is None else only
            return wkt.from_wkt([
                blob_wkt(g % args.n_side, g // args.n_side, phase, cw,
                         args.verts)
                for g in gids
            ])

        # ------------------------------------------------ churn lane
        col = column(0.0)
        # warm the tessellation + build path so the rebuild baseline
        # measures work, not compiles
        warm = build_chip_index(
            tessellate(col, grid, args.res, keep_core_geoms=False)
        )
        detail["chips"] = int(np.asarray(warm.cells).shape[0])

        log_dir = args.log_dir or tempfile.mkdtemp(prefix="epoch-bench-")
        ep = EpochalIndex(col, grid, args.res, keep_core_geoms=False,
                          log_dir=log_dir)
        ep.publish()

        rng = np.random.default_rng(18)
        rounds = []
        for rep in range(args.reps):
            ids = np.sort(rng.choice(n_geoms, n_churn, replace=False))
            up = column(2.0 + rep, only=[int(g) for g in ids])
            t0 = time.perf_counter()
            ep.apply(upsert=up, ids=ids)
            apply_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            ep.publish()
            publish_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            scratch = build_chip_index(
                tessellate(ep.column(), grid, args.res,
                           keep_core_geoms=False)
            )
            rebuild_s = time.perf_counter() - t0
            if not chip_index_equal(ep.index, scratch):
                raise AssertionError(
                    f"round {rep}: patched epoch {ep.epoch} is NOT "
                    "bit-identical to the from-scratch rebuild"
                )
            rounds.append({
                "apply_s": round(apply_s, 6),
                "publish_s": round(publish_s, 6),
                "rebuild_s": round(rebuild_s, 6),
                "speedup": round(
                    rebuild_s / max(apply_s + publish_s, 1e-9), 3
                ),
            })
        detail["rounds"] = rounds
        speedup = float(np.median([r["speedup"] for r in rounds]))
        detail["speedup"] = round(speedup, 3)
        line["value"] = round(speedup, 3)

        # replay the whole churn log back: the durable story at scale
        t0 = time.perf_counter()
        replayed = EpochalIndex.replay(log_dir, grid)
        detail["replay_s"] = round(time.perf_counter() - t0, 6)
        if not chip_index_equal(replayed.index, ep.index):
            raise AssertionError(
                "replay of the churn log diverged from the live index"
            )
        detail["replay_epoch"] = replayed.epoch

        # ------------------------------------------- kill-storm lane
        small = wkt.from_wkt([
            blob_wkt(i, j, 0.0, cw, 24) for i in range(3) for j in range(3)
        ])
        edit = wkt.from_wkt([blob_wkt(1, 1, 9.0, cw, 24)])
        storm = {"boundaries": len(KILL_MATRIX), "identical": 0}
        for site, skip, survivor in KILL_MATRIX:
            d = tempfile.mkdtemp(prefix="epoch-storm-")
            sep = EpochalIndex(small, grid, args.res,
                               keep_core_geoms=False, log_dir=d)
            try:
                with faults.transient_errors(
                    1, sites=(site,), skip_first=skip,
                    exc_factory=lambda s: RuntimeError(f"kill @ {s}"),
                ):
                    sep.apply(upsert=edit, ids=[4])
                    if site == "epoch.publish":
                        sep.publish()
                    elif site == "epoch.compact":
                        sep.compact()
                raise AssertionError(
                    f"injected kill at {site}+{skip} did not fire"
                )
            except RuntimeError:
                pass
            r = EpochalIndex.replay(d, grid)
            want = build_chip_index(
                tessellate(r.column(), grid, args.res,
                           keep_core_geoms=False)
            )
            if r.epoch == survivor and chip_index_equal(r.index, want):
                storm["identical"] += 1
        detail["kill_storm"] = storm
        if storm["identical"] != storm["boundaries"]:
            raise AssertionError(
                f"kill storm: only {storm['identical']} of "
                f"{storm['boundaries']} boundaries replayed "
                "bit-identically"
            )

        # ------------------------------------------------ serve lane
        sep = EpochalIndex(small, grid, args.res, keep_core_geoms=False)
        sep.publish()
        bounds = (-81.0, -85.0, -74.0, -78.0)
        stop = threading.Event()
        lat: list = []
        errors: list = []
        with ServeEngine(
            sep.index, grid, args.res, ladder=BucketLadder(64, 256),
            bounds=bounds, max_wait_s=0.0,
        ) as eng:
            eng.warmup()
            prng = np.random.default_rng(7)
            pts = prng.uniform(bounds[:2], bounds[2:], (128, 2))

            def client():
                while not stop.is_set():
                    t0 = time.perf_counter()
                    try:
                        eng.join(pts, deadline_s=60.0)
                        lat.append(time.perf_counter() - t0)
                    except Exception as e:  # lint: broad-except-ok (the lane's assertion IS that no request errors; collect, don't mask)
                        errors.append(repr(e)[:200])
                        return

            t = threading.Thread(target=client, daemon=True)  # lint: thread-context-adoption-ok (load generator: client-side latency only, no telemetry emitted on this thread)
            t.start()
            pub_s, during = [], []
            for rep in range(args.serve_publishes):
                sep.apply(upsert=wkt.from_wkt(
                    [blob_wkt(1, 1, 20.0 + rep, cw, 24)]), ids=[4])
                n0 = len(lat)
                t0 = time.perf_counter()
                sep.publish(eng)
                pub_s.append(time.perf_counter() - t0)
                during.append(len(lat) - n0)
            stop.set()
            t.join(timeout=30)
        if errors:
            raise AssertionError(
                f"serve traffic errored during publish: {errors[0]}"
            )
        if min(during) < 1:
            raise AssertionError(
                "no request completed inside a publish window — "
                "publish blocked in-flight traffic"
            )
        detail["serve"] = {
            "publishes": len(pub_s),
            "publish_p50_s": round(float(np.percentile(pub_s, 50)), 6),
            "publish_p99_s": round(float(np.percentile(pub_s, 99)), 6),
            "requests": len(lat),
            "requests_during_publish": during,
            "request_p99_s": round(float(np.percentile(lat, 99)), 6),
            "request_max_s": round(max(lat), 6),
        }

        if speedup < args.min_speedup:
            raise AssertionError(
                f"patch speedup {speedup:.2f}x < --min-speedup "
                f"{args.min_speedup}x on {args.churn_pct}% churn"
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
