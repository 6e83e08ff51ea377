"""Overlay-join smoke/bench: device candidates + fused measures vs host twin.

The CI twin of the `sql/overlay.py` device lane: build two overlapping
square-grid polygon tables at >=100k-chip scale, tessellate once, run
`prepare_overlay` once (the amortized host pass), then measure the same
`st_overlap_fraction` tree two ways:

1. **device** — `overlay_measures(lane="device")`: candidate generation
   as a sorted segment equi-join on device, ONE fused clip+fold+tree
   program per `(tree-hash, buckets, index, mesh)` signature, epsilon
   -band host recheck spliced on top. Timed over ``--reps`` warm runs.
2. **host** — `overlay_measures(lane="host")`: the pure-f64 numpy twin
   (`expr/host_oracle.host_overlay_measures`) — the degradation target
   and the bit-identity oracle.

Asserted on the way (the CI overlay-smoke lane re-asserts from the
JSON):

- ``detail.agreement`` — device vs host-oracle, bitwise over the pair
  table, the evaluated value/mask lanes and the folded areas; every
  entry MUST be 1.0 (the acceptance contract of the overlay PR);
- after warmup the device lane adds ZERO backend compiles
  (``detail.warm_backend_compiles == 0``);
- ``detail.overflow == 0`` — the ladder swallowed the whole candidate
  stream, no OVERFLOW(-2) truncation at bench scale;
- ``detail.chips >= --min-chips`` (default 100k) — the scale claim is
  measured, not asserted;
- every device stage lands a timed ``overlay_stage.<stage>`` telemetry
  event (prepare / candidates / measures) — the keys
  `tools/perf_gate.py` gates, with the 10x ``--inject-slowdown``
  negative lane in CI.

``detail.speedup_vs_host`` is the committed-artifact headline the tune
router reads (`tune/recommend._overlay_lane_prior`); it is recorded,
not asserted — CI machines may be slower, the committed OVERLAY_r*.json
round is the measured claim.

The final stdout line is ALWAYS one machine-parseable JSON object;
everything else goes to stderr.

Usage (CI overlay-smoke lane):
  python tools/overlay_bench.py --n 24 --min-chips 10000 \
      --trail /tmp/overlay.jsonl
  python tools/perf_gate.py --golden tests/goldens/perf_gate.json \
      --trail /tmp/overlay.jsonl --stages-prefix overlay_stage.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def square_grid(n: int, x0: float, y0: float, size: float,
                pitch: float) -> list:
    """n x n CCW squares of ``size`` on a ``pitch`` lattice (WKT)."""
    out = []
    for j in range(n):
        for i in range(n):
            x, y = x0 + i * pitch, y0 + j * pitch
            out.append(
                f"POLYGON (({x} {y}, {x + size} {y}, "
                f"{x + size} {y + size}, {x} {y + size}, {x} {y}))"
            )
    return out


def bitwise(a, b) -> float:
    """1.0 when the two arrays match bit for bit (shape, dtype, bytes)."""
    import numpy as np

    a, b = np.asarray(a), np.asarray(b)
    same = (
        a.shape == b.shape
        and a.dtype == b.dtype
        and a.tobytes() == b.tobytes()
    )
    return 1.0 if same else 0.0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=110,
                    help="squares per side per table (geoms = 2*n^2)")
    ap.add_argument("--res", type=int, default=4)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--host-reps", type=int, default=1)
    ap.add_argument("--min-chips", type=int, default=100_000,
                    help="fail below this total chip count (the scale "
                    "claim); CI smoke lanes pass a smaller floor")
    ap.add_argument("--trail", default=None,
                    help="export the captured telemetry trail as JSONL")
    args = ap.parse_args()

    emit_to = sys.stdout
    sys.stdout = sys.stderr

    detail: dict = {}
    line = {"metric": "overlay_device_pairs_per_sec", "value": 0.0,
            "unit": "zone-pairs/s", "detail": detail}
    stages: list = []
    root_span = None
    rc = 1
    try:
        import numpy as np

        from mosaic_tpu import expr as E, obs
        from mosaic_tpu.core.geometry import wkt
        from mosaic_tpu.core.index import CustomIndexSystem, GridConf
        from mosaic_tpu.core.tessellate import tessellate
        from mosaic_tpu.dispatch import core as dispatch
        from mosaic_tpu.runtime import telemetry
        from mosaic_tpu.sql import overlay as OV

        cap = telemetry.capture()
        stages = cap.__enter__()
        root_span = obs.start_span("overlay_bench", n=args.n,
                                   res=args.res)
        from mosaic_tpu.runtime.platform import (
            configure_compile_cache,
            per_chip,
            require_device,
        )

        # raises off-TPU unless JAX_PLATFORMS=cpu asked for the CPU
        info = require_device()
        detail["platform"] = info["platform"]
        # a rate from XLA:CPU is never filed under a device's unit
        line["unit"] = per_chip("zone-pairs/s", info)
        detail["compile_cache_dir"] = configure_compile_cache()
        detail["n_per_side"] = args.n

        grid = CustomIndexSystem(GridConf(-180, 180, -90, 90, 2,
                                          10.0, 10.0))
        cw, _ = grid.cell_size(args.res)
        # squares ~2.2 cells wide on a 2.4-cell pitch: every square
        # spans a 3x3-ish cell patch (mostly border chips — the clip
        # kernel does real work), same-side squares never overlap, and
        # the right grid's ~0.7-cell offset gives each left square up
        # to 4 right partners. The origin keeps the default n=110
        # lattice inside the grid bounds (squares past the edge would
        # silently shrink the chip count).
        size, pitch = 2.2 * cw, 2.4 * cw
        left = wkt.from_wkt(square_grid(args.n, -80.0, -82.0,
                                        size, pitch))
        right = wkt.from_wkt(square_grid(args.n, -80.0 + 0.73 * cw,
                                         -82.0 + 0.49 * cw,
                                         size, pitch))

        lt = tessellate(left, grid, args.res)
        rt = tessellate(right, grid, args.res)
        chips = (int(np.asarray(lt.cell_id).shape[0])
                 + int(np.asarray(rt.cell_id).shape[0]))
        detail["chips"] = chips
        detail["geoms"] = 2 * args.n * args.n

        t0 = time.perf_counter()
        with telemetry.timed("overlay_stage", stage="prepare"):
            prep = OV.prepare_overlay(lt, rt, left, right, grid,
                                      args.res)
        detail["prepare_s"] = round(time.perf_counter() - t0, 6)

        value = E.overlap_fraction()
        OV.warmup_overlay(left, right, grid, args.res, value, prep=prep)

        # ---- device: warm timed reps that must compile NOTHING
        c0 = dispatch.backend_compiles()
        t0 = time.perf_counter()
        for _ in range(args.reps):
            dev = OV.overlay_measures(left, right, grid, args.res,
                                      value, prep=prep)
        device_s = (time.perf_counter() - t0) / max(args.reps, 1)
        warm_compiles = int((dispatch.backend_compiles() - c0) or 0)
        detail["warm_backend_compiles"] = warm_compiles

        # ---- host: the pure-f64 numpy twin (oracle + fallback target)
        t0 = time.perf_counter()
        for _ in range(args.host_reps):
            host = OV.overlay_measures(left, right, grid, args.res,
                                       value, prep=prep, lane="host")
        host_s = (time.perf_counter() - t0) / max(args.host_reps, 1)

        pairs = int(dev.pairs.shape[0])
        agree = {
            "pairs": bitwise(dev.pairs, host.pairs),
            "value": bitwise(dev.value, host.value),
            "valid": bitwise(dev.valid, host.valid),
            "area": bitwise(dev.area, host.area),
        }
        detail["agreement"] = agree
        detail["pairs"] = pairs
        detail["overflow"] = int(dev.overflow)
        detail["host_overridden"] = int(dev.host_overridden)
        detail["lane"] = dev.lane
        detail["seconds"] = {
            "device": round(device_s, 6), "host": round(host_s, 6),
        }
        detail["host_pairs_per_sec"] = round(
            pairs / max(host_s, 1e-9), 1
        )
        detail["speedup_vs_host"] = round(
            host_s / max(device_s, 1e-9), 3
        )
        line["value"] = round(pairs / max(device_s, 1e-9), 1)

        bad = {k: v for k, v in agree.items() if v != 1.0}
        if bad:
            raise AssertionError(
                f"agreement below 1.0: {bad} — the device lane broke "
                "the bit-identity contract against the f64 host oracle"
            )
        if dev.lane != "device" or dev.degraded:
            raise AssertionError(
                f"device lane degraded: lane={dev.lane} "
                f"reason={dev.reason!r}"
            )
        if warm_compiles:
            raise AssertionError(
                f"warm device run compiled {warm_compiles} programs — "
                "warmup must cover the overlay signature"
            )
        if dev.overflow:
            raise AssertionError(
                f"candidate stream overflowed by {dev.overflow} at "
                "bench scale — the ladder must swallow it uncapped"
            )
        if chips < args.min_chips:
            raise AssertionError(
                f"only {chips} chips < --min-chips {args.min_chips} — "
                "the scale claim is unmet; raise --n"
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
