"""Raster analytics smoke/bench: decode → tile → assign → zonal → scan.

The CI twin of the raster engine (`raster/tiles.py`, `raster/zonal.py`,
`sql/raster_stream.py`): write a synthetic MODIS-shaped GeoTIFF (tiled +
deflate + predictor-2 int16, `tests/modis_fixture.py`), decode it with
the native engine, and push one band through every device stage,
asserting the f64 host-oracle bit-identity contract on the way:

1. grid fold == `host_zonal_grid_oracle`, zones fold ==
   `host_zonal_zones_oracle`, and the durable scan == the zones fold —
   ``detail.agreement`` is the fraction of stat rows that match bitwise
   and MUST be 1.0 (the CI raster-smoke lane asserts it);
2. the f32 Pallas lane (``lane="tiled"``) agrees exactly on the
   integer-valued fixture;
3. every stage lands one timed ``raster_stage.<stage>`` telemetry event
   (decode / tile / assign / zonal / scan) — the keys
   `tools/perf_gate.py` gates, so a stage regression fails CI.

The roofline rides in ``detail.roofline``: per-stage pixels/sec and
achieved GB/s from the bytes the stage actually moves (file bytes for
decode, staged values+mask for tile, centers+cells for assign,
values+segments for the fold), plus ``pct_hbm_peak`` on known TPU
device kinds (None on CPU — GB/s is still reported).

The final stdout line is ALWAYS one machine-parseable JSON object;
everything else goes to stderr.

Usage (CI raster-smoke lane):
  python tools/raster_bench.py --width 960 --height 720 \
      --trail /tmp/raster.jsonl
  python tools/perf_gate.py --golden tests/goldens/perf_gate.json \
      --trail /tmp/raster.jsonl ...
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

#: nominal HBM bandwidth per chip, GB/s, keyed by device_kind substring
#: (checked in order — "v5p" before "v5" matters); mirrors bench.py
_HBM_PEAK_GBPS = (
    ("v6e", 1640.0),
    ("v5p", 2765.0),
    ("v5e", 819.0),
    ("v5 lite", 819.0),
    ("v4", 1228.0),
    ("v3", 900.0),
)


def _hbm_peak_gbps():
    import jax

    try:
        kind = jax.devices()[0].device_kind.lower()
    except Exception:  # lint: broad-except-ok (no backend => no roofline pct, GB/s still reported)
        return None
    for pat, peak in _HBM_PEAK_GBPS:
        if pat in kind:
            return peak
    return None


#: bench world: the raster always covers x [-60, -12], y [4, 40]
#: regardless of resolution (pixel size scales with width/height), so
#: the valid-data ellipse of `modis_like_field` (x ~[-57.6, -32.6],
#: y ~[13.2, 22.2]) overlaps every zone at every --width/--height; the
#: zones cross tile boundaries and include a hole + slanted edges
WORLD = (-60.0, 48.0, 40.0, 36.0)  # x0, dx_total, y0, dy_total
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
    """(path, grid, res, chip_index): a MODIS-shaped GeoTIFF whose
    pixels cover the bench zones, plus the vector side."""
    from tests.modis_fixture import modis_like_field, write_tiled_geotiff

    from mosaic_tpu.core.geometry import wkt
    from mosaic_tpu.core.index import CustomIndexSystem, GridConf
    from mosaic_tpu.core.tessellate import tessellate
    from mosaic_tpu.sql.join import build_chip_index

    data = modis_like_field(width, height, bands=1, seed=seed)
    path = os.path.join(tmpdir, "raster_bench.tif")
    meta = (
        '<GDALMetadata>\n  <Item name="_FillValue">'
        f"{NODATA}</Item>\n</GDALMetadata>"
    )
    write_tiled_geotiff(
        path, data, gt=bench_gt(width, height), nodata=float(NODATA),
        meta_xml=meta,
    )
    grid = CustomIndexSystem(GridConf(-180, 180, -90, 90, 2, 10.0, 10.0))
    res = 3
    index = build_chip_index(
        tessellate(wkt.from_wkt(ZONES), grid, res, keep_core_geoms=False)
    )
    return path, grid, res, index


def small_valued_twin(raster):
    """The same raster with values folded into [0, 113): integer sums
    stay far below 2**24, so the f32 Pallas lane must agree with the
    f64 fold bit for bit (MODIS-scale sums would not be f32-exact)."""
    from mosaic_tpu.raster import Raster

    data = np.where(
        raster.data == NODATA, NODATA, raster.data % 113
    ).astype(raster.data.dtype)
    return Raster(
        data=data, gt=raster.gt, srid=raster.srid, nodata=raster.nodata
    )


def result_rows(r) -> dict:
    """{key: (count, sum, min, max)} with float bit patterns preserved
    (repr-level equality == bit identity for finite f64)."""
    return {
        int(k): (int(c), float(s), float(mn), float(mx))
        for k, c, s, mn, mx in zip(r.keys, r.count, r.sum, r.min, r.max)
    }


def agreement(got, want) -> float:
    """Fraction of oracle stat rows the device result matches bitwise
    (keys, count, and the f64 bit patterns of sum/min/max)."""
    a, b = result_rows(got), result_rows(want)
    keys = set(a) | set(b)
    if not keys:
        return 1.0
    same = sum(1 for k in keys if a.get(k) == b.get(k))
    return same / len(keys)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--width", type=int, default=960)
    ap.add_argument("--height", type=int, default=720)
    ap.add_argument("--tile", default="256x256", help="TH x TW, e.g. 256x256")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--trail", default=None,
                    help="export the captured telemetry trail as JSONL")
    args = ap.parse_args()

    emit_to = sys.stdout
    sys.stdout = sys.stderr

    detail: dict = {}
    line = {"metric": "raster_zonal_pixels_per_sec", "value": 0.0,
            "unit": "pixels/s", "detail": detail}
    stages: list = []
    root_span = None
    rc = 1
    try:
        from mosaic_tpu import obs
        from mosaic_tpu.raster import read_raster
        from mosaic_tpu.raster.zonal import (
            ZonalEngine,
            host_zonal_grid_oracle,
            host_zonal_zones_oracle,
        )
        from mosaic_tpu.runtime import telemetry
        from mosaic_tpu.sql import RasterStream

        tile = tuple(int(p) for p in args.tile.lower().split("x"))
        cap = telemetry.capture()
        stages = cap.__enter__()
        root_span = obs.start_span(
            "raster_bench", width=args.width, height=args.height
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
        peak = _hbm_peak_gbps()

        with tempfile.TemporaryDirectory() as tmpdir:
            path, grid, res, index = build_fixture(
                args.width, args.height, args.seed, tmpdir
            )

            # ---- decode (native tiled+deflate+predictor-2 engine)
            fbytes = os.path.getsize(path)
            t0 = time.perf_counter()
            raster = read_raster(path)
            dt = time.perf_counter() - t0
            telemetry.record(
                "raster_stage", stage="decode",
                seconds=round(dt, 6), bytes=fbytes,
                pixels=raster.width * raster.height,
            )
            pixels = raster.width * raster.height
            valid = int(raster.band(1).mask.sum())
            detail["file_bytes"] = fbytes
            detail["valid_fraction"] = round(valid / pixels, 4)
            stage_bytes = {"decode": fbytes}

            # ---- grid + zones folds (raster_stage.{tile,assign,zonal})
            eng = ZonalEngine(grid, res, chip_index=index, lane="fold")
            rgrid = eng.grid(raster, tile=tile)
            rzones = eng.zones(raster, tile=tile)
            agree = {
                "grid": agreement(
                    rgrid,
                    host_zonal_grid_oracle(raster, res, grid, tile=tile),
                ),
                "zones": agreement(
                    rzones,
                    host_zonal_zones_oracle(
                        raster, index, grid, res, tile=tile
                    ),
                ),
            }

            # ---- the f32 Pallas lane on a small-valued integer twin
            # (exact in f32, so fold vs tiled must be bit-identical)
            small = small_valued_twin(raster)
            tiled = ZonalEngine(
                grid, res, chip_index=index, lane="tiled"
            ).zones(small, tile=tile)
            fold_small = eng.zones(small, tile=tile)
            agree["tiled_lane"] = agreement(tiled, fold_small)

            # ---- durable scan (raster_stage.scan)
            scan = RasterStream(index, grid, res).scan(
                raster, tile=tile,
                run_dir=os.path.join(tmpdir, "run"), snapshot_every=8,
            )
            agree["scan"] = agreement(scan.stats, rzones)

        detail["agreement"] = agree
        detail["zones_hit"] = int(len(rzones.keys))
        detail["valid_pixels"] = valid

        # per-stage roofline from the bytes each stage actually moves
        padded = None
        for e in stages:
            if e.get("event") == "raster_stage" and e.get("stage") == "tile":
                padded = e.get("padded_pixels")
                break
        padded = int(padded or pixels)
        stage_bytes["tile"] = padded * (8 + 1)       # f64 vals + mask
        stage_bytes["assign"] = padded * (16 + 8)    # f64 centers + i64
        stage_bytes["zonal"] = padded * (8 + 4)      # f64 vals + i32 seg
        stage_bytes["scan"] = padded * (8 + 4)
        totals: dict[str, float] = {}
        for e in stages:
            if e.get("event") == "raster_stage" and "stage" in e:
                totals[e["stage"]] = (
                    totals.get(e["stage"], 0.0) + float(e["seconds"])
                )
        roofline = {}
        for st, secs in sorted(totals.items()):
            entry = {
                "seconds": round(secs, 6),
                "pixels_per_sec": round(padded / max(secs, 1e-9), 1),
            }
            if st in stage_bytes:
                gbps = stage_bytes[st] / max(secs, 1e-9) / 1e9
                entry["achieved_gbps"] = round(gbps, 3)
                entry["pct_hbm_peak"] = (
                    round(100.0 * gbps / peak, 2)
                    if peak is not None else None
                )
            roofline[st] = entry
        detail["roofline"] = roofline

        zonal_s = totals.get("zonal", 0.0)
        line["value"] = round(padded / max(zonal_s, 1e-9), 1)

        bad = {k: v for k, v in agree.items() if v != 1.0}
        if bad:
            raise AssertionError(
                f"oracle agreement below 1.0: {bad} — the zonal fold "
                "broke the bit-identity contract"
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
