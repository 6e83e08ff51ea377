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

import bisect


def gaps_of(tr_mod, tr) -> list:
    """The first device's idle pieces: the gaps between its merged op
    intervals, cut at every program annotation's start and end."""
    merged = tr_mod.busy_intervals(tr_mod.first_device(tr)["ops"])
    cuts = sorted({x for _n, s, e, _t in tr["program"] for x in (s, e)})
    pieces = []
    for a, b in zip(merged, merged[1:]):
        lo, hi = a[1], b[0]
        if hi <= lo:
            continue
        inner = cuts[bisect.bisect_right(cuts, lo):bisect.bisect_left(cuts, hi)]
        edges = [lo, *inner, hi]
        pieces.extend(zip(edges, edges[1:]))
    return pieces


def by_name(program) -> dict:
    """``{name: (starts, [(start, end)])}``, each in order of start."""
    out: dict = {}
    for name, s, e, _t in program:
        starts, ivs = out.setdefault(name, ([], []))
        starts.append(s)
        ivs.append((s, e))
    return out


def covering(index: dict, mid: float, names=None):
    """The innermost (shortest) annotation covering ``mid``, among
    ``names`` if given; None if there is none. Of one name, the two that
    started last before ``mid`` are looked at (one thread runs a span
    name one at a time; two threads may overlap)."""
    best = None
    for name in index if names is None else names:
        starts, ivs = index.get(name, ((), ()))
        i = bisect.bisect_right(starts, mid)
        for s, e in ivs[max(i - 2, 0):i]:
            if e >= mid and (best is None or e - s < best[1]):
                best = (name, e - s)
    return None if best is None else best[0]


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
    index = by_name(tr["program"])
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
