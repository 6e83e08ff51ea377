"""Device-busy milliseconds per step inside the traced window (mean over
the chips used). ``params``: ``steps`` — the counter of traced steps."""


def read(ctx, params):
    red = ctx.trace_reduction
    steps = ctx.counters.get(params["steps"])
    if not red or not red["devices"] or not steps:
        return None
    return 1000.0 * red["busy_s"] / steps
