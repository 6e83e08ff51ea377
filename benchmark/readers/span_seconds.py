"""Seconds of one of the benchmark's own spans (host clock around its
call into a layer). ``params``: ``span``."""


def read(ctx, params):
    return ctx.spans.seconds(params["span"])
