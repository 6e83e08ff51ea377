"""Nearest-rank percentile of one field over the program's events in the
window. ``params``: ``event``, ``where`` (field equalities), ``field``,
``q``, ``scale``."""


def read(ctx, params):
    from benchmark.harness.stats import percentile

    events = ctx.spec.module("readers", "_events").in_window(ctx, params)
    vals = [e[params["field"]] for e in events if params["field"] in e]
    if not vals:
        return None
    return percentile(vals, float(params["q"])) * float(params.get("scale", 1))
