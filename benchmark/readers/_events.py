"""Shared by the event readers: the program's telemetry events recorded
inside the measured window that match ``event`` and every ``where`` field."""


def in_window(ctx, params):
    lo, hi = ctx.window
    where = params.get("where", {})
    return [
        e for e in ctx.events
        if e.get("event") == params["event"]
        and lo <= e.get("ts_mono", lo) <= hi
        and all(e.get(k) == v for k, v in where.items())
    ]
