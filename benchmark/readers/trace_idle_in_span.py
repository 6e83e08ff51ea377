"""Whose time the first device's idle time is, by the program's own
spans: the share (percent) of the idle time between its ops that lies
inside a ``mosaic.<span>`` annotation named in ``params["spans"]``, on any
host thread. A gap between two ops is first cut at every annotation's
start and end — one gap of a serve cycle runs from the end of one
dispatch's last op through scatter-back, the linger and the next
dispatch's puts, and its midpoint alone would give all of it to one of
them — and each piece goes by its midpoint. Also prints, once a run,
every piece attributed to the innermost program span that covers it, as
one ``[bench] idle_by_program_span:`` line (``none``: no span covers it)."""

from benchmark.harness.xplane import covering, idle_pieces, span_index


def gaps_of(tr_mod, tr) -> list:
    """The first device's idle pieces: the gaps between its merged op
    intervals, cut at every program annotation's start and end."""
    merged = tr_mod.busy_intervals(tr_mod.first_device(tr)["ops"])
    cuts = sorted({x for _n, s, e, _t in tr["program"] for x in (s, e)})
    return idle_pieces(merged, cuts)


def read(ctx, params):
    tr_mod = ctx.spec.module("readers", "_trace")
    tr = tr_mod.of_run(ctx)
    if tr is None:
        return None
    gaps = gaps_of(tr_mod, tr)
    idle = sum(b - a for a, b in gaps)
    if idle <= 0:
        return None
    names = set(params["spans"])
    index = span_index(tr["program"])
    mine = 0.0
    by_span: dict = {}
    for a, b in gaps:
        mid = (a + b) / 2.0
        if covering(index, mid, names) is not None:
            mine += b - a
        who = covering(index, mid) or "none"
        by_span[who] = by_span.get(who, 0.0) + (b - a)
    if not getattr(ctx, "idle_by_program_span_said", False):
        ctx.idle_by_program_span_said = True
        ctx.say(
            "idle_by_program_span", idle_s=round(idle / 1e9, 6),
            under_program_spans=round(1.0 - by_span.get("none", 0.0) / idle, 4),
            **{k: round(v / 1e9, 6) for k, v in
               sorted(by_span.items(), key=lambda kv: -kv[1])},
        )
    return 100.0 * mine / idle
