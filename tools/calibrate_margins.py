"""Calibration sweep for the epsilon-band recheck constants.

The recheck band (`sql/join.py`) flags a point as borderline when its
cell-rounding margin is below ``CELL_MARGIN_K * eps`` or it lies within
``EDGE_BAND_K * eps * coord_scale`` of a probed chip edge. Those two
constants trade exactness risk against recheck cost: too narrow and an
f32-vs-f64 disagreement escapes the band (silent wrong answer); too wide
and the narrow re-join + host oracle see more points than they must.

This tool MEASURES the drift the constants must cover:

- **cell-margin drift** — over uniform global points at several H3
  resolutions (and a BNG lane), the largest margin (in units of
  ``eps(f32)``) at which the f32 cell assignment disagrees with the f64
  host path;
- **edge-band drift** — over a tessellated zone index, with cells pinned
  to the exact f64 assignment, the largest distance from a probed chip
  edge (in units of ``eps(f32) * coord_scale``) at which the f32
  ray-crossing parity path disagrees with the f64 host oracle.

Output: one JSON document (committed golden:
``tests/goldens/recheck_margins.json``); `tests/test_recheck.py` pins
that the shipped defaults keep >= 2x headroom over the recorded maxima.

Run: JAX_PLATFORMS=cpu python tools/calibrate_margins.py \
        [--n 200000] [--out tests/goldens/recheck_margins.json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

EPS32 = float(np.finfo(np.float32).eps)


def global_points(n: int, seed: int) -> np.ndarray:
    """Area-uniform points over the sphere (degrees)."""
    rng = np.random.default_rng(seed)
    lng = rng.uniform(-180, 180, n)
    lat = np.degrees(np.arcsin(rng.uniform(-0.999, 0.999, n)))
    return np.stack([lng, lat], -1)


def measure_cell_drift(index_system, points: np.ndarray, res: int) -> dict:
    """Max margin (units of eps32) among f32-vs-f64 cell disagreements."""
    import jax.numpy as jnp

    c64 = np.asarray(index_system.point_to_cell(points, res))  # host f64
    c32, m = index_system.point_to_cell_margin(
        jnp.asarray(points, dtype=jnp.float32), res
    )
    c32, m = np.asarray(c32), np.asarray(m)
    dis = c32 != c64
    worst = float(m[dis, 0].max() / EPS32) if dis.any() else 0.0
    return {
        "resolution": res,
        "n_points": int(points.shape[0]),
        "n_disagreements": int(dis.sum()),
        "max_observed_k": round(worst, 4),
    }


def _seg_dist(px, py, e):
    """(R,) min f64 distance from each point to its row of segments.

    px, py: (R,); e: (R, E, 4) ax/ay/bx/by rows (pad rows are zero-length
    segments at the origin — masked by the caller via the parity bits).
    """
    ax, ay, bx, by = e[..., 0], e[..., 1], e[..., 2], e[..., 3]
    ex, ey = bx - ax, by - ay
    qx, qy = px[:, None] - ax, py[:, None] - ay
    dd = ex * ex + ey * ey
    t = np.clip((qx * ex + qy * ey) / np.where(dd == 0, 1.0, dd), 0.0, 1.0)
    rx, ry = qx - t * ex, qy - t * ey
    return rx * rx + ry * ry  # squared, per segment


def near_edge_points(host, n: int, seed: int, spread_k: float = 64.0
                     ) -> np.ndarray:
    """Adversarial probe set: points within ``spread_k * eps32 *
    coord_scale`` of random real chip edges — uniform points almost never
    land inside the drift band (1 disagreement per 200k observed), so the
    measured ceiling would be noise without concentrating samples where
    f32 parity can actually flip."""
    rng = np.random.default_rng(seed)
    u_idx, e_idx = np.nonzero(host.cell_ebits != 0)
    take = rng.integers(0, u_idx.size, n)
    e = host.cell_edges[u_idx[take], e_idx[take]]  # (n, 4) f64, shifted
    ax, ay, bx, by = e[:, 0], e[:, 1], e[:, 2], e[:, 3]
    t = rng.uniform(0.0, 1.0, n)
    px, py = ax + t * (bx - ax), ay + t * (by - ay)
    ex, ey = bx - ax, by - ay
    ln = np.hypot(ex, ey)
    ln = np.where(ln == 0, 1.0, ln)
    mag = rng.uniform(0.0, spread_k, n) * EPS32 * host.coord_scale
    sign = rng.choice([-1.0, 1.0], n)
    return np.stack(
        [px - sign * mag * ey / ln, py + sign * mag * ex / ln], 1
    ) + host.shift  # back to raw (unshifted) coordinates


def measure_edge_drift(
    zones, index_system, res: int, points: np.ndarray, seed: int = 0
) -> dict:
    """Max edge distance (units of eps32 * coord_scale) among f32-vs-f64
    parity disagreements, with the cell assignment pinned to f64.
    ``points`` is augmented with an equal-sized near-edge probe set."""
    import jax.numpy as jnp

    from mosaic_tpu.core.tessellate import tessellate
    from mosaic_tpu.sql.join import (
        build_chip_index,
        host_join_with_cells,
        pip_join_points,
    )

    idx = build_chip_index(
        tessellate(zones, index_system, res, keep_core_geoms=False)
    )
    host = idx.host
    points = np.concatenate(
        [points, near_edge_points(host, points.shape[0], seed + 1)]
    )
    # exact f64 cells for BOTH paths: any disagreement below is pure
    # probe-arithmetic drift, the band EDGE_BAND_K must cover
    cells = np.asarray(index_system.point_to_cell(points, res))
    want = host_join_with_cells(points, cells, host)
    shifted = jnp.asarray(points - host.shift, dtype=jnp.float32)
    got = np.asarray(pip_join_points(shifted, jnp.asarray(cells), idx))
    dis = np.nonzero(got != want)[0]
    worst = 0.0
    scale = EPS32 * host.coord_scale
    if dis.size:
        p = points[dis] - host.shift
        u = np.clip(
            np.searchsorted(host.cells, cells[dis]), 0, host.cells.size - 1
        )
        d2 = _seg_dist(p[:, 0], p[:, 1], host.cell_edges[u])
        d2 = np.where(host.cell_ebits[u] != 0, d2, np.inf).min(axis=1)
        hrow = host.cell_heavy[u]
        hv = np.nonzero(hrow >= 0)[0]
        if hv.size and host.heavy_edges.shape[0]:
            h = hrow[hv]
            d2h = _seg_dist(p[hv, 0], p[hv, 1], host.heavy_edges[h])
            d2h = np.where(
                host.heavy_ebits[h] != 0, d2h, np.inf
            ).min(axis=1)
            d2[hv] = np.minimum(d2[hv], d2h)
        worst = float(np.sqrt(d2.max()) / scale)
    return {
        "resolution": res,
        "n_points": int(points.shape[0]),
        "n_disagreements": int(dis.size),
        "max_observed_k": round(worst, 4),
        "coord_scale": round(float(host.coord_scale), 6),
    }


def run_sweep(n: int, seeds=(3, 11)) -> dict:
    from mosaic_tpu.core.index import BNG, H3
    from mosaic_tpu.datasets import synthetic_zones
    from mosaic_tpu.sql.join import CELL_MARGIN_K, EDGE_BAND_K

    cell_sweep = []
    for res in (5, 7, 9, 11):
        for seed in seeds:
            r = measure_cell_drift(H3, global_points(n, seed), res)
            r["system"] = "h3"
            r["seed"] = seed
            cell_sweep.append(r)
            print(f"[calibrate] h3 cell res={res} seed={seed}: "
                  f"max_k={r['max_observed_k']}", file=sys.stderr)
    # BNG margins are exact binning distances — drift only at the binning
    # boundary itself; measured for completeness, not the binding max
    rng = np.random.default_rng(9)
    bng_pts = np.column_stack(
        [rng.uniform(0, 700000, n // 2), rng.uniform(0, 1300000, n // 2)]
    )
    rb = measure_cell_drift(BNG, bng_pts, 4)
    rb["system"] = "bng"
    rb["seed"] = 9
    cell_sweep.append(rb)

    edge_sweep = []
    bbox = (-74.05, 40.60, -73.85, 40.85)
    for seed in seeds:
        zones = synthetic_zones(12, 12, bbox=bbox, seed=seed)
        rng = np.random.default_rng(seed + 100)
        pts = rng.uniform(bbox[:2], bbox[2:], (n, 2))
        r = measure_edge_drift(zones, H3, 9, pts, seed=seed)
        r["seed"] = seed
        edge_sweep.append(r)
        print(f"[calibrate] edge res=9 seed={seed}: "
              f"max_k={r['max_observed_k']} "
              f"({r['n_disagreements']} disagreements)", file=sys.stderr)

    cell_max = max(r["max_observed_k"] for r in cell_sweep)
    edge_max = max(r["max_observed_k"] for r in edge_sweep)
    return {
        "defaults": {
            "CELL_MARGIN_K": CELL_MARGIN_K,
            "EDGE_BAND_K": EDGE_BAND_K,
        },
        "cell_margin": {
            "max_observed_k": cell_max,
            "headroom_vs_default": round(CELL_MARGIN_K / max(cell_max, 1e-9), 3),
            "sweep": cell_sweep,
        },
        "edge_band": {
            "max_observed_k": edge_max,
            "headroom_vs_default": round(EDGE_BAND_K / max(edge_max, 1e-9), 3),
            "sweep": edge_sweep,
        },
        "meta": {
            "n_points_per_config": n,
            "seeds": list(seeds),
            "contract": "defaults must keep >= 2x headroom over "
                        "max_observed_k (tests/test_recheck.py)",
        },
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=200_000)
    ap.add_argument(
        "--out", default=os.path.join(REPO, "tests", "goldens",
                                      "recheck_margins.json")
    )
    args = ap.parse_args()
    doc = run_sweep(args.n)
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
    print(json.dumps({
        "cell_max_k": doc["cell_margin"]["max_observed_k"],
        "edge_max_k": doc["edge_band"]["max_observed_k"],
        "out": args.out,
    }))


if __name__ == "__main__":
    main()
