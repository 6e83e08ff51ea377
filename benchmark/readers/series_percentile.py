"""Nearest-rank percentile of a series the traffic kind kept on its own
clock. ``params``: ``series``, ``q``, ``scale``."""


def read(ctx, params):
    from benchmark.harness.stats import percentile

    vals = ctx.series.get(params["series"])
    if not vals:
        return None
    return percentile(vals, float(params["q"])) * float(params.get("scale", 1))
