"""Device idle share of the traced window, in percent: 1 - the union of
the device's op intervals over the window, mean over the chips used."""


def read(ctx, params):
    red = ctx.trace_reduction
    if not red or not red["devices"] or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
