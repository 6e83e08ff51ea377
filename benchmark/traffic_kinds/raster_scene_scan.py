"""Traffic kind ``raster_scene_scan``: one client in a closed loop that hands
`mosaic_tpu.sql.RasterStream.scan` one decoded scene after another — an
analyst or a nightly job folding a stack of satellite scenes into per-zone
statistics (count, sum, min, max), the next scene only once the last one's
answer is in hand — until the window has passed.

Parameters (the mix's data file): ``pool_scenes`` (distinct scenes, made by
`generators/scenes.py` from ``--seed``, held decoded in host memory as
`mosaic_tpu.raster.Raster` objects and cycled), the generator's own keys,
``arguments`` (keyword arguments of ``scan``; ``{}`` is the package's
defaults) and ``control`` (what the lower-precision control changes, see
`_control`). The scene's shape, dtype and nodata come from the
configuration (``scene``); its geotransform lays it north-up over the zone
layer's bounding box.

Set-up builds one `RasterStream` and scans every scene of the pool once:
the rows the host re-joins depend on each scene's nodata, so a scene the
warm-up has not seen could meet a shape the window then compiles.

End-to-end: ``batch_rows_per_s`` — the real (unpadded) pixels of the scenes
scanned in the window over the window's seconds, host clock from before the
first ``scan`` to after the last `RasterScanResult` is in hand, divided by
the cell's chips. The last scan started inside the window is finished and
counted.

Correct: the first pass's answers (one a scene) are kept; each later answer
is compared inside the window with the first pass's on its scene, and once
the window has closed every scene it answered only once is scanned once
more. The first pass is compared with the plain reference (`references/zonal_bruteforce.py`): the
pixels it puts in another zone, and the zones whose count agrees and whose
sum, min or max does not. A scan that raised, and a tile the host twin
answered, count in ``failed``.

A ``--trace 1`` run profiles a few tiles in the middle of the first timed
scan (`_TileTracer`); the readers of the program's events see the scans
after it.
"""

from __future__ import annotations

import gc
import time
import traceback

#: the tiles of the first timed scan a ``--trace 1`` run profiles: from this
#: tile (or the scan's middle, where it has fewer), so many of them
TRACE_FROM_TILE, TRACE_TILES = 40, 16
STATS = ("count", "sum", "min", "max")


def _control(ctx) -> dict:
    """What the lower-precision control changes, from the mix's ``control``
    group: ``geotransform_dtype`` (the scenes the window scans get their
    geotransform rounded to that dtype: pixel centres placed in lower
    precision) and ``lane`` (the sums comparison reads `ZonalEngine.zones`
    on that fold lane, the program's own float32 path, over the scenes as
    the configuration lays them). Never on in a benchmark run."""
    return dict(ctx.traffic["control"]) if ctx.control else {}


def _dense(stats, num_zones: int) -> dict:
    """A `ZonalResult` (live zones only) as ``num_zones`` rows a statistic,
    in the reference's form: int64 counts, sums, minima and maxima as the
    program's own float64, 0 where a zone is empty."""
    import numpy as np

    out = {"count": np.zeros(num_zones, np.int64)}
    out["count"][stats.keys] = stats.count
    for name in STATS[1:]:
        out[name] = np.zeros(num_zones, np.float64)
        out[name][stats.keys] = getattr(stats, name)
    return out


def _unlike(a: dict, b: dict) -> bool:
    import numpy as np

    return any(not np.array_equal(a[k], b[k]) for k in STATS)


class _TileTracer:
    """Starts the run's profiler once tile ``first - 1`` of the scan under
    way has been launched and stops it once ``tiles`` more have been: an
    observer of the program's own ``raster.zonal`` span events (one a
    launched tile, carrying ``step``), because a scan is one call."""

    def __init__(self, tracer, first: int, tiles: int):
        self.tracer, self.first, self.last = tracer, first, first + tiles - 1
        self.armed = False

    def __call__(self, evt: dict) -> None:
        if not self.armed or evt.get("event") != "span" \
                or evt.get("name") != "raster.zonal" or "step" not in evt:
            return
        if evt["step"] >= self.last:
            self.tracer.stop()
            self.armed = False
        elif evt["step"] >= self.first - 1:
            self.tracer.start()  # a no-op once it is on


def _scan(st, scene):
    return st["stream"].scan(scene, **st["args"])


def prepare(ctx) -> dict:
    import numpy as np

    from mosaic_tpu.raster import Raster
    from mosaic_tpu.sql import RasterStream

    dep, mix, cfg = ctx.deployment, ctx.traffic, ctx.config["scene"]
    gen = ctx.spec.module("generators", "scenes")
    control = _control(ctx)
    shape = (int(cfg["height"]), int(cfg["width"]))
    with ctx.spans.span("scene_pool_build"):
        arrays = gen.make_scenes(
            dict(mix, nodata=cfg["nodata"]), shape, ctx.seed)
        if any(a.dtype != np.dtype(cfg["dtype"]) for a in arrays):
            raise ValueError(f"the scenes are not {cfg['dtype']}")
        gt = gen.north_up(dep.bbox, *shape)  # what the configuration states
        scanned_gt = gen.north_up(
            dep.bbox, *shape,
            dtype=np.dtype(control.get("geotransform_dtype", "float64")))
        pool = [
            Raster(data=a[None], gt=scanned_gt, srid=4326,
                   nodata=cfg["nodata"]) for a in arrays
        ]
    stream = RasterStream(dep.index, dep.grid, dep.res)
    args = dict(mix.get("arguments", {}))
    if "tile" in args:
        args["tile"] = tuple(args["tile"])
    st = {"stream": stream, "args": args, "pool": pool, "gt": gt,
          "control": control,
          # first: the first pass's answers by scene; again: scenes a later
          # timed scan answered like the first pass; odd: scans unlike the
          # first pass's on their scene; raised: scans that raised
          "first": {}, "again": set(), "odd": 0, "raised": 0}
    with ctx.spans.span("scan_warmup"):
        for scene in pool:
            _scan(st, scene)
    ctx.say(
        "scenes_ready", pool=len(pool), shape=shape, dtype=cfg["dtype"],
        valid_share=round(float(np.mean(
            [(a != cfg["nodata"]).mean() for a in arrays])), 4),
        zones=stream.num_zones, arguments=st["args"], control=control,
        scene_pool_build_s=round(ctx.spans.seconds("scene_pool_build"), 3),
        scan_warmup_s=round(ctx.spans.seconds("scan_warmup"), 3),
    )
    return st


def window(ctx, st) -> dict:
    from mosaic_tpu.raster import plan_tiles
    from mosaic_tpu.runtime import telemetry

    pool, first = st["pool"], st["first"]
    g = st["stream"].num_zones
    plan = plan_tiles(pool[0], st["args"].get("tile"))
    tile_pixels = plan.shape[0] * plan.shape[1]
    tracing = _TileTracer(
        ctx.tracer, min(TRACE_FROM_TILE, plan.ntiles // 2), TRACE_TILES)
    if ctx.trace:  # an untraced window carries no observer of its own
        telemetry.add_observer(tracing)
    scans = degraded = 0
    walls = []
    pauses: list = []  # [start, seconds, generation] of each collection

    def on_gc(phase, info):
        if phase == "start":
            pauses.append([time.perf_counter(), None, info["generation"]])
        elif pauses and pauses[-1][1] is None:
            pauses[-1][1] = time.perf_counter() - pauses[-1][0]

    gc.callbacks.append(on_gc)
    mono0 = unprofiled_from = time.monotonic()
    t0 = time.perf_counter()
    t = t0
    try:
        while t - t0 < ctx.seconds:
            tracing.armed = scans == 0
            b = scans % len(pool)
            try:
                with ctx.spans.span("scene.scan"):
                    result = _scan(st, pool[b])
            except Exception:  # noqa: BLE001 — the client's boundary: a scan that raised is counted, the loop goes on
                traceback.print_exc()
                st["raised"] += 1
            else:
                degraded += int(result.metrics["degraded_tiles"])
                answer = _dense(result.stats, g)
                if b not in first:
                    first[b] = answer
                elif _unlike(answer, first[b]):
                    st["odd"] += 1
                else:
                    st["again"].add(b)
            scans += 1
            now = time.perf_counter()
            walls.append(now - t)
            t = now
            if ctx.trace and scans == 1 and t - t0 < ctx.seconds:
                # the readers of the program's events see the scans made
                # after the profiled one (where it was not the only one)
                ctx.tracer.stop()
                unprofiled_from = time.monotonic()
    finally:
        gc.callbacks.remove(on_gc)
        telemetry.remove_observer(tracing)
        ctx.tracer.stop()
    t1 = t
    ctx.window = (unprofiled_from, time.monotonic())
    rows = plan.pixels * scans
    ctx.counters.update(tile_pixels=tile_pixels, zones=g)  # the fold's shapes
    ctx.say(
        "scan_window", scans=scans, rows=rows, window_s=round(t1 - t0, 4),
        tiles_per_scan=plan.ntiles, tile=plan.shape, raised=st["raised"],
        degraded_tiles=degraded, unlike_first_pass=st["odd"],
        scan_s=[round(w, 4) for w in walls],
        # where a slow scan's time went: the collector's longer pauses, and
        # the program's slow spans of the window but the scan's own and its
        # staging (the harness keeps every timed event of 50 ms or more in
        # an untraced run too)
        gc=[f"gen{g}:{b * 1e3:.0f}ms@{a - t0:.2f}s" for a, b, g in pauses
            if b is not None and b >= 0.02],
        slow=[f"{e['name']}:{e['seconds'] * 1e3:.0f}ms@{e['ts_mono'] - mono0:.2f}s"
              for e in ctx.events
              if e.get("event") == "span" and e.get("seconds", 0.0) >= 0.05
              and e.get("ts_mono", 0.0) >= mono0
              and e["name"] not in ("raster.scan", "raster.tile")],
    )
    return {
        "attempted": rows,
        "failed": plan.pixels * st["raised"] + tile_pixels * degraded,
        "metrics": {"batch_rows_per_s": rows / (t1 - t0) / ctx.chips},
    }


def check(ctx, st) -> list:
    import dataclasses

    import numpy as np

    from benchmark.harness.check import Comparison

    pool, first, control = st["pool"], st["first"], st["control"]
    limits, ref = ctx.cell["check"], ctx.deployment.reference
    g = st["stream"].num_zones
    unlike = st["odd"] + st["raised"]
    # one more scan of every scene the window answered once only (of the
    # first scene where it answered each twice)
    once = [b for b in sorted(first) if b not in st["again"]]
    with ctx.spans.span("check.repeat_scans"):
        for b in once or sorted(first)[:1]:
            again = _dense(_scan(st, pool[b]).stats, g)
            unlike += _unlike(first[b], again)
    out = [Comparison(
        "zonal_scans_unlike_repeat", unlike, 0,
        "the scan is deterministic: every timed answer on a scene equals, "
        "statistic for statistic, another scan's of the same scene",
    )]
    t0 = time.perf_counter()
    zones = ref.pixel_zones(
        ctx.deployment.rings, st["gt"], (pool[0].height, pool[0].width))
    sums_of = first
    if control.get("lane"):
        # the program's own lower path for the sums: the fold lane the
        # control names, over the scenes as the configuration lays them
        from mosaic_tpu.raster import ZonalEngine

        engine = ZonalEngine(
            ctx.deployment.grid, ctx.deployment.res,
            chip_index=ctx.deployment.index, lane=control["lane"])
        sums_of = {
            b: _dense(engine.zones(
                dataclasses.replace(pool[b], gt=st["gt"]),
                tile=st["args"].get("tile")), g)
            for b in sorted(first)
        }
    pixels_unlike = valid = zones_unlike = 0
    for b in sorted(first):
        want = ref.stats(zones, pool[b].data[0], pool[b].nodata, g)
        valid += int(np.count_nonzero(pool[b].data[0] != pool[b].nodata))
        pixels_unlike += int(np.abs(first[b]["count"] - want["count"]).sum())
        got = sums_of[b]
        same_count = got["count"] == want["count"]
        differs = np.zeros(g, bool)
        for name in STATS[1:]:
            differs |= got[name] != want[name].astype(np.float64)
        zones_unlike += int(np.count_nonzero(same_count & differs))
    ctx.say(
        "reference", scenes=len(first), valid_pixels=valid,
        zoned_share=round(float((zones >= 0).mean()), 4),
        pixels_unlike=pixels_unlike, zones_unlike_sums=zones_unlike,
        seconds=round(time.perf_counter() - t0, 3),
    )
    out.append(Comparison(
        "zonal_pixels_unlike_reference",
        pixels_unlike / valid if valid else float("nan"),
        limits["max_pixels_unlike"],
        "sum over the first pass's scenes and zones of |count - the plain "
        "f64 reference's count| over the valid pixels (the limit and its "
        "reason: the cell's workloads file)",
    ))
    out.append(Comparison(
        "zonal_zones_unlike_reference_sums", zones_unlike,
        limits["max_zones_unlike_sums"],
        "zones of the first pass whose count equals the reference's and "
        "whose sum, min or max does not: sums of int16 pixels are integers "
        "far below 2^46, so an f64 accumulator holds them exactly",
    ))
    return out


def close(ctx, st) -> None:
    st.clear()
