"""Device time of the raster tile's two programs, per tile, from the device
trace: seconds of the ops under the given stage names (`trace_stage_busy`'s
table: the program's ``pip.*``/``zonal.*`` scopes, recovered through
`mosaic_tpu.obs.stages`) over the runs of ``module`` on the trace's ``XLA
Modules`` line — each tile runs each of its two programs once.

``params``: ``stage`` (a name or a list summed), ``module`` (the jitted
program's module name, ``jit_<function>``), ``measure``:

- ``ms_per_tile`` (default): milliseconds a traced tile;
- ``hbm_share``: the share of the HBM roofline, in percent — the bytes the
  fold has to move a tile (`fold_bytes`, from shapes) over the chip's peak
  bytes/s (`harness/peaks.py`), divided by those device seconds a tile.

Nothing to read without a trace, where no traced op carries one of the
stages (a program without these scopes), or where the module never ran."""


def fold_bytes(tile_pixels: int, zones: int) -> int:
    """What one tile's fold has to move: each pixel's f64 value and int32
    zone row in, four per-zone statistics of 8 bytes out."""
    return tile_pixels * (8 + 4) + 4 * zones * 8


def read(ctx, params):
    tr = ctx.spec.module("readers", "_trace").of_run(ctx)
    if tr is None:
        return None
    wanted = params["stage"]
    wanted = [wanted] if isinstance(wanted, str) else wanted
    # `trace_stage_busy` builds the run's stage table once, prints it and
    # keeps it on the run: read through it, then take the seconds
    stage_busy = ctx.spec.module("readers", "trace_stage_busy")
    if stage_busy.read(ctx, {"stage": wanted, "share": True}) is None:
        return None
    table = ctx.device_by_stage
    if not any(s in table for s in wanted):
        return None
    seconds = sum(table.get(s, 0.0) for s in wanted)
    # by_stage's seconds are the mean over the devices; so are the runs
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
    least_s = fold_bytes(tile_pixels, zones) / peaks.peaks_for(
        ctx.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / per_tile
