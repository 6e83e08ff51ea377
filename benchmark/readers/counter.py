"""A count or gauge the run kept. ``params``: ``name``, ``scale``."""


def read(ctx, params):
    value = ctx.counters.get(params["name"])
    if value is None:
        return None
    return value * float(params.get("scale", 1))
