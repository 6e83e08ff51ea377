"""Sum of one field over the sum of another, over the program's events in
the window. ``params``: ``event``, ``where``, ``num``, ``den``, ``scale``."""


def read(ctx, params):
    events = ctx.spec.module("readers", "_events").in_window(ctx, params)
    num = sum(e[params["num"]] for e in events if params["num"] in e)
    den = sum(e[params["den"]] for e in events if params["den"] in e)
    if not events or not den:
        return None
    return num / den * float(params.get("scale", 1))
