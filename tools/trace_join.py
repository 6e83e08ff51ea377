"""Capture an XLA profiler trace of the 4M-point NYC join on the real
chip (VERDICT r4 item 2: 'capture a utils.device_trace of the 4M-point
join ... with a trace artifact in the repo').

Saves the xprof trace under traces/r05/ and prints one JSON line with
the timed phase breakdown measured around the same dispatches (cells
pipeline alone, full fused step, tier split), so the artifact carries
numbers even where the trace viewer isn't available.

Usage: python tools/trace_join.py [--points 4000000] [--out TRACE_r05.json]
(CPU validation: JAX_PLATFORMS=cpu ... --points 200000)
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--points", type=int, default=4_000_000)
    ap.add_argument("--out", default=None)
    ap.add_argument("--trace-dir", default=os.path.join(REPO, "traces", "r05"))
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from mosaic_tpu.runtime.platform import (
        configure_compile_cache,
        per_chip,
        require_device,
    )

    # raises off-TPU unless JAX_PLATFORMS=cpu asked for the CPU
    device = require_device()
    configure_compile_cache()

    from bench import RES, _load_or_build_index, _load_zones
    from mosaic_tpu.core.index.h3 import H3IndexSystem
    from mosaic_tpu.sql.join import pip_join_points
    from mosaic_tpu.utils import annotate, device_trace

    h3 = H3IndexSystem()
    zones, zones_src = _load_zones()
    b = zones.bounds()
    bbox = (
        float(np.nanmin(b[:, 0])), float(np.nanmin(b[:, 1])),
        float(np.nanmax(b[:, 2])), float(np.nanmax(b[:, 3])),
    )
    index, _, _ = _load_or_build_index(zones, zones_src, h3)
    dtype = index.border.verts.dtype
    n = args.points
    rng = np.random.default_rng(42)
    pts = jnp.asarray(rng.uniform(bbox[:2], bbox[2:], (n, 2)))
    pts.block_until_ready()

    cells_np = np.asarray(index.cells)

    @jax.jit
    def cells_only(p):
        c = h3.point_to_cell(p.astype(jnp.float32), RES)
        return (c ^ (c >> 32)).astype(jnp.int32).sum()

    @functools.partial(jax.jit, static_argnames=("fcap", "hcap"))
    def step(p, chip_index, fcap, hcap):
        with annotate("cells"):
            cells = h3.point_to_cell(p.astype(jnp.float32), RES)
        with annotate("probe"):
            shifted = (p - chip_index.border.shift).astype(dtype)
            out = pip_join_points(
                shifted, cells.astype(jnp.int64), chip_index,
                heavy_cap=hcap, found_cap=fcap,
                lookup="gather" if jax.devices()[0].platform == "cpu"
                else "mxu",
            compaction="scatter" if jax.devices()[0].platform == "cpu"
            else "mxu",
            )
        return (out ^ (out >> 16)).sum()

    pre = np.asarray(
        h3.point_to_cell(pts[:200_000].astype(jnp.float32), RES)
    )
    pos = np.clip(np.searchsorted(cells_np, pre), 0, cells_np.size - 1)
    ffrac = float((cells_np[pos] == pre).mean())
    fcap = min(((int(2 * ffrac * n) + 131071) // 131072 + 1) * 131072, n)
    hmask = np.asarray(index.cell_heavy) >= 0
    hfrac = float(np.isin(pre, cells_np[hmask]).mean())
    hcap = min(((int(2 * hfrac * n) + 131071) // 131072 + 1) * 131072, fcap)

    def timed(fn, *a):
        fn(*a).block_until_ready()  # compile
        best = np.inf
        for _ in range(3):
            t0 = time.perf_counter()
            float(fn(*a))
            best = min(best, time.perf_counter() - t0)
        return best

    cells_s = timed(cells_only, pts)
    step_s = timed(step, pts, index, fcap, hcap)

    os.makedirs(args.trace_dir, exist_ok=True)
    with device_trace(args.trace_dir):
        float(step(pts, index, fcap, hcap))
        float(cells_only(pts))

    line = {
        "metric": "join_trace",
        "value": round(n / step_s, 1),
        "unit": per_chip("points/sec", device),
        "detail": {
            "n_points": n,
            "cells_only_s": round(cells_s, 4),
            "full_step_s": round(step_s, 4),
            "probe_s_approx": round(step_s - cells_s, 4),
            "caps": [fcap, hcap],
            "device": str(jax.devices()[0]),
            "device_info": device,
            "zones": zones_src,
            "trace_dir": os.path.relpath(args.trace_dir, REPO),
        },
    }
    out = json.dumps(line)
    print(out)
    if args.out:
        with open(args.out, "w") as f:
            f.write(out + "\n")


if __name__ == "__main__":
    main()
