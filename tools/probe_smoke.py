"""Adaptive-probe smoke: every routing lane, bit-identity, lane timings.

The CI twin of the adaptive router in `sql/join.py` (light scatter/MXU
path, heavy Pallas lane, convex reduced-edge lane): build a fixture
that genuinely populates ALL THREE density classes, run the probe on
CPU (the Pallas kernel under ``interpret=True``), force each lane via
``MOSAIC_PROBE_FORCE_LANE``, and assert:

1. every probe mode (``adaptive`` + each forced lane) is bit-identical
   to the ``scatter`` baseline, per batch — including the adversarial
   batches (near-edge band, all-heavy, all-light, convex-only);
2. the rechecked adaptive join equals the exact f64 host oracle row for
   row (``host_join_with_cells``);
3. each forced lane emits one timed ``probe_stage.<lane>`` telemetry
   event.

It states no rate: what a lane does in a second is the chip's to say
(`benchmark/run.py`). The final stdout line is ALWAYS one
machine-parseable JSON object; everything else goes to stderr.

Usage (CI probe-smoke lane):
  python tools/probe_smoke.py --points 60000 --trail /tmp/probe.jsonl
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

#: probed lanes, in gate-stage order
LANES = ("light", "heavy", "convex")


def build_fixture():
    """A chip index populating all three density classes + its zones.

    The custom grid keeps CPU compiles cheap (same reasoning as
    tests/test_stream.py); ``edge_cap=8`` forces genuine tier-2 (heavy)
    cells out of ordinary zones, and the axis-aligned rectangles are
    closed convex rings, so the convex tables populate too.
    """
    from mosaic_tpu.core.geometry import wkt
    from mosaic_tpu.core.index import CustomIndexSystem, GridConf
    from mosaic_tpu.core.tessellate import tessellate
    from mosaic_tpu.sql.join import build_chip_index

    grid = CustomIndexSystem(GridConf(-180, 180, -90, 90, 2, 10.0, 10.0))
    res = 3
    # a 240-vertex star ring concentrates >32 edges into single cells —
    # the guaranteed-heavy zone; the rectangles are the convex ones
    th = np.linspace(0.0, 2 * np.pi, 240, endpoint=False)
    r = np.where(np.arange(240) % 2 == 0, 4.0, 2.0)
    sx, sy = 25.0 + r * np.cos(th), -14.0 + r * np.sin(th)
    star = ", ".join(f"{x:.6f} {y:.6f}" for x, y in zip(sx, sy))
    star += f", {sx[0]:.6f} {sy[0]:.6f}"
    zones = wkt.from_wkt(
        [
            "POLYGON ((1 1, 13 2, 12 11, 6 14, 2 9, 1 1), "
            "(5 5, 5 8, 8 8, 8 5, 5 5))",
            "POLYGON ((20 0, 30 0, 30 10, 25 4, 20 10, 20 0))",
            "MULTIPOLYGON (((-20 -20, -12 -20, -12 -12, -20 -12, "
            "-20 -20)), ((-8 -8, -2 -8, -2 -2, -8 -2, -8 -8)))",
            "POLYGON ((-24 5, -14 5, -14 15, -24 15, -24 5))",
            f"POLYGON (({star}))",
        ]
    )
    index = build_chip_index(
        tessellate(zones, grid, res, keep_core_geoms=False), edge_cap=8
    )
    return grid, res, zones, index


def classify_points(index, grid, res, pts):
    """(found, heavy, convex) bool masks per point, from the host-side
    density tables — drives the adversarial batch construction."""
    import jax.numpy as jnp

    cells = np.asarray(grid.point_to_cell(jnp.asarray(pts), res))
    ucells = np.asarray(index.cells)
    u = np.clip(np.searchsorted(ucells, cells), 0, len(ucells) - 1)
    found = ucells[u] == cells
    heavy = found & (np.asarray(index.cell_heavy)[u] >= 0)
    convex = found & (np.asarray(index.cell_convex)[u] >= 0)
    return found, heavy, convex


def near_edge_batch(index, rng, per_edge=2):
    """Points straddling real chip edges: midpoint ± a tiny normal
    offset (the band/parity stress batch), in RAW coordinates."""
    edges = np.asarray(index.cell_edges, dtype=np.float64)
    real = np.asarray(index.cell_ebits) != 0
    ab = edges[real]
    if not len(ab):
        return np.zeros((0, 2))
    ab = ab[rng.permutation(len(ab))[: 4000 // per_edge]]
    a, b = ab[:, 0:2], ab[:, 2:4]
    mid = 0.5 * (a + b)
    t = b - a
    nrm = np.stack([-t[:, 1], t[:, 0]], axis=1)
    nrm /= np.maximum(np.linalg.norm(nrm, axis=1, keepdims=True), 1e-30)
    shift = np.asarray(index.border.shift, dtype=np.float64)
    out = []
    for delta in (1e-6, 1e-4):
        out.append(mid + delta * nrm)
        out.append(mid - delta * nrm)
    return np.concatenate(out) + shift


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--points", type=int, default=60_000)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--trail", default=None,
                    help="export the captured telemetry trail as JSONL")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    emit_to = sys.stdout
    sys.stdout = sys.stderr

    detail: dict = {}
    line = {"metric": "probe_smoke", "value": 0, "unit": "lanes_verified",
            "detail": detail}
    stages: list = []
    root_span = None
    rc = 1
    try:
        import jax

        from mosaic_tpu import obs
        from mosaic_tpu.runtime import telemetry
        from mosaic_tpu.sql.join import host_join, pip_join

        cap = telemetry.capture()
        stages = cap.__enter__()
        root_span = obs.start_span("probe_smoke", points=args.points)

        grid, res, zones, index = build_fixture()
        detail["platform"] = str(jax.devices()[0].platform)
        detail["heavy_cells"] = index.num_heavy_cells
        detail["convex_cells"] = index.num_convex_cells
        if not index.num_heavy_cells or not index.num_convex_cells:
            raise AssertionError(
                "fixture drift: need heavy AND convex cells, got "
                f"H={index.num_heavy_cells} CV={index.num_convex_cells}"
            )

        rng = np.random.default_rng(args.seed)
        pts = rng.uniform((-25, -25), (35, 20), (args.points, 2))
        found, heavy, convex = classify_points(index, grid, res, pts)
        light = found & ~heavy & ~convex
        batches = {
            "mixed": pts,
            "all_light": pts[light],
            "all_heavy": pts[heavy],
            "convex_only": pts[convex],
            "near_edge_band": near_edge_batch(index, rng),
        }
        detail["batches"] = {k: int(len(v)) for k, v in batches.items()}
        for k in ("all_heavy", "convex_only", "near_edge_band"):
            if not len(batches[k]):
                raise AssertionError(f"fixture drift: empty {k} batch")

        def run(p, probe, recheck=False):
            env = os.environ.pop("MOSAIC_PROBE_FORCE_LANE", None)
            try:
                if probe.startswith("force:"):
                    os.environ["MOSAIC_PROBE_FORCE_LANE"] = probe[6:]
                    probe = "adaptive"
                return np.asarray(pip_join(
                    p, None, grid, res, chip_index=index, recheck=recheck,
                    probe=probe,
                ))
            finally:
                os.environ.pop("MOSAIC_PROBE_FORCE_LANE", None)
                if env is not None:
                    os.environ["MOSAIC_PROBE_FORCE_LANE"] = env

        # 1) bit-identity of every mode vs the scatter baseline, per batch
        modes = ["adaptive"] + [f"force:{ln}" for ln in LANES]
        mismatches = 0
        for bname, bp in batches.items():
            base = run(bp, "scatter")
            for mode in modes:
                got = run(bp, mode)
                if not np.array_equal(got, base):
                    mismatches += 1
                    detail.setdefault("mismatch", []).append(
                        {"batch": bname, "mode": mode,
                         "rows": int((got != base).sum())}
                    )
        detail["identity_checks"] = len(batches) * len(modes)
        if mismatches:
            raise AssertionError(f"{mismatches} identity check(s) failed")

        # 2) rechecked adaptive == exact f64 host oracle, row for row
        for bname in ("mixed", "near_edge_band"):
            bp = batches[bname]
            oracle = host_join(bp, index.host, grid, res)
            got = run(bp, "adaptive", recheck=True)
            if not np.array_equal(got, oracle):
                raise AssertionError(
                    f"adaptive+recheck != host oracle on {bname}: "
                    f"{int((got != oracle).sum())} rows"
                )
        detail["oracle_identical"] = True

        # 3) one timed probe_stage event a forced lane, warm
        n = len(pts)
        for lane in LANES:
            run(pts, f"force:{lane}")  # warm: compile outside the timing
            t0 = time.perf_counter()
            run(pts, f"force:{lane}")
            telemetry.record(
                "probe_stage", stage=lane,
                seconds=round(time.perf_counter() - t0, 6), n=n,
            )
        line["value"] = len(LANES)
        rc = 0
    except Exception as e:
        detail["error"] = repr(e)[:400]

    if root_span is not None:
        try:
            root_span.end()
        except Exception:
            pass
    if args.trail and stages:
        try:
            from mosaic_tpu import obs as _obs

            _obs.write_jsonl(stages, args.trail)
        except Exception as e:
            detail["trail_error"] = repr(e)[:200]

    out = json.dumps(line)
    emit_to.write(out + "\n")
    emit_to.flush()
    if args.out:
        with open(args.out, "w") as f:
            f.write(out + "\n")
    return rc


if __name__ == "__main__":
    sys.exit(main())
