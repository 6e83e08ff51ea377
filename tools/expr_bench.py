"""Expression-compiler smoke/bench: fused pipeline vs staged ops.

The CI twin of `mosaic_tpu/expr/`: write a 3-band MODIS-shaped GeoTIFF
(`tests/modis_fixture.py`), build the acceptance pipeline — NDVI, cloud
mask, zonal fold over vector zones — and run it two ways:

1. **fused** — ``ZonalEngine.map(expr)``: ONE device program per tile
   computes the whole tree and folds it (`expr/compile.py` pushes the
   expression into `zonal_fold_masked`). One launch per tile.
2. **staged** — the pre-existing op sequence: ``rst_mapbands`` evaluates
   the value tree into a NaN-nodata raster (one pixel program per
   tile), then ``ZonalEngine.zones`` folds that raster (a second fold
   program per tile). Two launches per tile, plus an intermediate
   (H, W) f64 raster that crosses the host boundary.

Asserted on the way (the CI expr-smoke lane re-asserts from the JSON):

- ``detail.agreement`` — fused vs staged AND fused vs the numpy-f64
  host interpreter (`expr/host_oracle.py`), fraction of stat rows that
  match bitwise; MUST be 1.0;
- ``detail.launches.fused < detail.launches.staged`` — launch counts
  from the per-path telemetry (tiles dispatched per stage), the fusion
  claim measured rather than asserted;
- after warmup the fused path adds ZERO backend compiles
  (``detail.warm_backend_compiles == 0``) — one program per bucket;
- every stage lands a timed ``expr_stage.<stage>`` telemetry event
  (map / pixels) — the keys `tools/perf_gate.py` gates.

The final stdout line is ALWAYS one machine-parseable JSON object;
everything else goes to stderr.

Usage (CI expr-smoke lane):
  python tools/expr_bench.py --width 960 --height 720 \
      --trail /tmp/expr.jsonl
  python tools/perf_gate.py --golden tests/goldens/perf_gate.json \
      --trail /tmp/expr.jsonl --stages-prefix expr_stage.
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

#: same bench world + zones as tools/raster_bench.py: the raster covers
#: x [-60, -12], y [4, 40]; the valid-data ellipse overlaps every zone;
#: zone edges cross tile boundaries, zone 0 carries a hole
WORLD = (-60.0, 48.0, 40.0, 36.0)
ZONES = [
    "POLYGON ((-56 12, -40 11, -34 22, -50 23, -56 21, -56 12), "
    "(-50 15, -46 15, -46 18, -50 18, -50 15))",
    "POLYGON ((-40 13, -33 13, -33 21, -36.5 17, -40 21, -40 13))",
    "POLYGON ((-58 13, -52 13, -52 17, -58 17, -58 13))",
]
NODATA = 32767


def bench_gt(width: int, height: int):
    x0, dx, y0, dy = WORLD
    return (x0, dx / width, 0.0, y0, 0.0, -dy / height)


def build_fixture(width: int, height: int, seed: int, tmpdir: str):
    """(path, grid, res, chip_index): a 3-band MODIS-shaped GeoTIFF
    (band 1 "red", band 2 "nir", band 3 "cloud score") + vector side."""
    from tests.modis_fixture import modis_like_field, write_tiled_geotiff

    from mosaic_tpu.core.geometry import wkt
    from mosaic_tpu.core.index import CustomIndexSystem, GridConf
    from mosaic_tpu.core.tessellate import tessellate
    from mosaic_tpu.sql.join import build_chip_index

    data = modis_like_field(width, height, bands=3, seed=seed)
    path = os.path.join(tmpdir, "expr_bench.tif")
    write_tiled_geotiff(
        path, data, gt=bench_gt(width, height), nodata=float(NODATA)
    )
    grid = CustomIndexSystem(GridConf(-180, 180, -90, 90, 2, 10.0, 10.0))
    res = 3
    index = build_chip_index(
        tessellate(wkt.from_wkt(ZONES), grid, res, keep_core_geoms=False)
    )
    return path, grid, res, index


def result_rows(r) -> dict:
    """{key: (count, sum, min, max)} with float bit patterns preserved
    (repr-level equality == bit identity for finite f64)."""
    return {
        int(k): (int(c), float(s), float(mn), float(mx))
        for k, c, s, mn, mx in zip(r.keys, r.count, r.sum, r.min, r.max)
    }


def agreement(got, want) -> float:
    """Fraction of stat rows that match bitwise (keys, count, and the
    f64 bit patterns of sum/min/max)."""
    a, b = result_rows(got), result_rows(want)
    keys = set(a) | set(b)
    if not keys:
        return 1.0
    return sum(1 for k in keys if a.get(k) == b.get(k)) / len(keys)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--width", type=int, default=960)
    ap.add_argument("--height", type=int, default=720)
    ap.add_argument("--tile", default="256x256", help="TH x TW")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--trail", default=None,
                    help="export the captured telemetry trail as JSONL")
    args = ap.parse_args()

    emit_to = sys.stdout
    sys.stdout = sys.stderr

    detail: dict = {}
    line = {"metric": "expr_fused_pixels_per_sec", "value": 0.0,
            "unit": "pixels/s", "detail": detail}
    stages: list = []
    root_span = None
    rc = 1
    try:
        from mosaic_tpu import expr as E, obs
        from mosaic_tpu.dispatch import core as dispatch
        from mosaic_tpu.functions.raster import rst_mapbands
        from mosaic_tpu.raster import read_raster
        from mosaic_tpu.raster.zonal import ZonalEngine
        from mosaic_tpu.runtime import telemetry
        from mosaic_tpu.sql import RasterStream

        tile = tuple(int(p) for p in args.tile.lower().split("x"))
        cap = telemetry.capture()
        stages = cap.__enter__()
        root_span = obs.start_span(
            "expr_bench", width=args.width, height=args.height
        )
        from mosaic_tpu.runtime.platform import (
            configure_compile_cache,
            require_device,
        )

        # raises off-TPU unless JAX_PLATFORMS=cpu asked for the CPU
        detail["platform"] = require_device()["platform"]
        detail["compile_cache_dir"] = configure_compile_cache()
        detail["shape"] = [args.height, args.width]
        detail["tile"] = list(tile)

        # the acceptance pipeline: NDVI, cloud mask, zonal fold. The
        # (red + nir) > 0 guard keeps 0/0 = NaN off VALID pixels —
        # NaN produced on a valid pixel is outside the bit-identity
        # contract (mask first, always)
        value = E.norm_diff(E.band(2), E.band(1)).mask_where(
            ((E.band(1) + E.band(2)) > 0.0) & (E.band(3) < 2600.0)
        )
        pipeline = value.zonal(by="zones")

        with tempfile.TemporaryDirectory() as tmpdir:
            path, grid, res, index = build_fixture(
                args.width, args.height, args.seed, tmpdir
            )
            raster = read_raster(path)
            pixels = raster.width * raster.height
            eng = ZonalEngine(grid, res, chip_index=index, lane="fold")

            # ---- fused: warmup compiles, then a warm timed map that
            # must add ZERO backend compiles (one program per bucket)
            eng.warmup_expr(pipeline, raster, tile=tile)
            c0 = dispatch.backend_compiles()
            t0 = time.perf_counter()
            fused = eng.map(pipeline, raster, tile=tile)
            fused_s = time.perf_counter() - t0
            warm_compiles = dispatch.backend_compiles() - c0
            detail["warm_backend_compiles"] = int(warm_compiles or 0)

            # ---- staged: the same pipeline as the pre-existing op
            # sequence (pixel program -> intermediate raster -> fold)
            t0 = time.perf_counter()
            ndvi_raster = rst_mapbands([raster], value, tile=tile)[0]
            staged = eng.zones(ndvi_raster, tile=tile)
            staged_s = time.perf_counter() - t0

            # ---- oracle: the numpy-f64 interpreter of the same tree
            oracle = E.host_expr_zonal_oracle(
                raster, pipeline, index_system=grid, resolution=res,
                chip_index=index, tile=tile,
            )

            # ---- fused durable scan rides the same program
            scan = RasterStream(index, grid, res).scan(
                raster, expr=pipeline, tile=tile,
                run_dir=os.path.join(tmpdir, "run"), snapshot_every=8,
            )

        agree = {
            "staged": agreement(fused, staged),
            "oracle": agreement(fused, oracle),
            "scan": agreement(scan.stats, fused),
        }
        detail["agreement"] = agree
        detail["zones_hit"] = int(len(fused.keys))

        # launch counts from the per-path telemetry: tiles dispatched
        # per stage. Fused = one program per tile; staged = a pixel
        # program per tile PLUS a fold program per tile.
        fused_tiles = staged_px_tiles = staged_fold_tiles = 0
        for e in stages:
            if e.get("event") == "expr_stage":
                if e.get("stage") == "map" and not fused_tiles:
                    fused_tiles = int(e.get("ntiles") or 0)
                elif e.get("stage") == "pixels":
                    staged_px_tiles += int(e.get("ntiles") or 0)
            elif (
                e.get("event") == "raster_stage"
                and e.get("stage") == "zonal"
            ):
                staged_fold_tiles += int(e.get("ntiles") or 0)
        launches = {
            "fused": fused_tiles,
            "staged": staged_px_tiles + staged_fold_tiles,
        }
        detail["launches"] = launches
        detail["seconds"] = {
            "fused": round(fused_s, 6),
            "staged": round(staged_s, 6),
        }
        detail["staged_over_fused"] = round(
            staged_s / max(fused_s, 1e-9), 3
        )
        line["value"] = round(pixels / max(fused_s, 1e-9), 1)

        bad = {k: v for k, v in agree.items() if v != 1.0}
        if bad:
            raise AssertionError(
                f"agreement below 1.0: {bad} — the fused program broke "
                "the bit-identity contract"
            )
        if not launches["fused"] or (
            launches["fused"] >= launches["staged"]
        ):
            raise AssertionError(
                f"fusion claim failed: {launches} — the fused path must "
                "launch strictly fewer programs than the staged one"
            )
        if warm_compiles:
            raise AssertionError(
                f"warm fused map compiled {warm_compiles} programs — "
                "warmup must cover every bucket signature"
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
