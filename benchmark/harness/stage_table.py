"""Device seconds by the program's own stage names, built once a traced run
by the harness: the result line's ``breakdown`` holds the table, and the
stage readers (`readers/trace_stage_busy.py` and those that read through
it) take their stages from it.

The device trace names an op by its HLO instruction (``%fusion.504 = ...``);
the program's `mosaic_tpu.obs.stages.tables()` lowers the registered
programs again and maps each instruction to the innermost
``pip.*``/``stream.*`` scope it was traced under. An op belongs to the
module run (``XLA Modules`` line) that contains it; an op of a module the
program registered no table for, or with no scope, is ``unscoped``. Seconds
are op durations summed by stage, mean over the chips used."""

import bisect
import re
import time

from . import xplane


def by_stage(ctx, tr):
    try:
        from mosaic_tpu.obs import stages
    except ImportError:  # the program has no stage tables
        return None

    totals: dict = {}
    # (a program without `recompiled` never compiles a table's program twice)
    recompiled = getattr(stages, "recompiled", lambda: 0)
    t0, n0, r0 = time.perf_counter(), stages.lowerings(), recompiled()
    for dev in tr["devices"].values():
        runs = dev["modules"]
        if not runs:
            return None
        starts = [m[1] for m in runs]
        module_of = []
        for name, s, _e in dev["ops"]:
            i = bisect.bisect_right(starts, s) - 1
            inside = i >= 0 and s <= runs[i][2]
            module_of.append(runs[i][0].split("(", 1)[0] if inside else None)
        rows = {  # every dimension an op's output type shows
            int(d) for (name, _s, _e) in dev["ops"]
            for dims in re.findall(r"\[([\d,]+)\]", xplane.op_label(name))
            for d in dims.split(",")
        }
        tables = stages.tables({m for m in module_of if m}, rows)
        for (name, s, e), module in zip(dev["ops"], module_of):
            stage = tables.get(module, {}).get(
                xplane.op_label(name), stages.UNSCOPED
            )
            totals[stage] = totals.get(stage, 0.0) + (e - s) / 1e9
    ctx.say(
        "stage_tables", programs_lowered=stages.lowerings() - n0,
        compiled_anew=recompiled() - r0,
        seconds=round(time.perf_counter() - t0, 3),
    )
    n = len(tr["devices"])
    return {k: v / n for k, v in totals.items()}


def of_run(ctx):
    """``{stage: device seconds}`` of this run's trace, built at the first
    call, kept on the run (``ctx.device_by_stage``) and printed once as
    ``[bench] device_by_stage:``; None where there is no trace, no module
    run in it or no stage table in the program."""
    tr = ctx.spec.module("readers", "_trace").of_run(ctx)
    if tr is None:
        return None
    table = getattr(ctx, "device_by_stage", None)
    if table is None:
        table = by_stage(ctx, tr)
        if not table:
            return None
        ctx.device_by_stage = table
        ctx.say("device_by_stage", **{
            k: round(v, 6) for k, v in sorted(table.items(), key=lambda kv: -kv[1])
        })
    return table
