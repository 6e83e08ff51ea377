"""Device time of the raster tile's two programs, per tile, from the device
trace: seconds of the ops under the given stage names (the harness's stage
table, `harness/stage_table.py`: the program's ``pip.*``/``zonal.*`` scopes,
recovered through `mosaic_tpu.obs.stages`) over the runs of ``module`` on
the trace's ``XLA Modules`` line — each tile runs each of its two programs
once.

``params``: ``stage`` (a name or a list summed), ``module`` (the jitted
program's module name, ``jit_<function>``), ``measure``:

- ``ms_per_tile`` (default): milliseconds a traced tile;
- ``hbm_share``: the share of the HBM roofline, in percent — the bytes the
  fold has to move a tile (`fold_bytes`, from shapes and the lane the
  program says it folded in) over the chip's peak bytes/s
  (`harness/peaks.py`), divided by those device seconds a tile.

Nothing to read without a trace, where no traced op carries one of the
stages (a program without these scopes), or where the module never ran."""


def fold_bytes(tile_pixels: int, zones: int, value_bytes: int = 8,
               stat_bytes: int = 8) -> int:
    """What one tile's fold has to move: each pixel's value and int32 zone
    row in, four per-zone statistics out. The defaults are the wide lane's
    (f64 values, 8-byte statistics); the int32 lane stages the values at
    their own width and folds into int32 (PR 28)."""
    return tile_pixels * (value_bytes + 4) + 4 * zones * stat_bytes


def fold_widths(events) -> tuple:
    """``(value_bytes, stat_bytes)`` of the lane the run's newest
    ``raster.zonal`` span or ``raster_scan`` event names (``fold_lane``,
    ``values_dtype``); the wide lane's where no event says (a program from
    before PR 28 folded every tile in f64)."""
    import numpy as np

    for e in reversed(events):
        if e.get("fold_lane") == "int32" and e.get("values_dtype"):
            return np.dtype(e["values_dtype"]).itemsize, 4
        if "fold_lane" in e:
            break
    return 8, 8


def read(ctx, params):
    tr = ctx.spec.module("readers", "_trace").of_run(ctx)
    if tr is None:
        return None
    wanted = params["stage"]
    wanted = [wanted] if isinstance(wanted, str) else wanted
    from benchmark.harness import stage_table

    table = stage_table.of_run(ctx)
    if table is None or not any(s in table for s in wanted):
        return None
    seconds = sum(table.get(s, 0.0) for s in wanted)
    # the table's seconds are the mean over the devices; so are the runs
    runs = sum(
        m[0].split("(", 1)[0] == params["module"]
        for dev in tr["devices"].values() for m in dev["modules"]
    ) / len(tr["devices"])
    if not runs or seconds <= 0.0:
        return None
    per_tile = seconds / runs
    if params.get("measure", "ms_per_tile") == "ms_per_tile":
        return 1000.0 * per_tile
    from benchmark.harness import peaks

    tile_pixels, zones = (ctx.counters.get(k) for k in ("tile_pixels", "zones"))
    if not tile_pixels or not zones:
        return None
    widths = fold_widths(getattr(ctx, "events", ()))
    least_s = fold_bytes(tile_pixels, zones, *widths) / peaks.peaks_for(
        ctx.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / per_tile
