"""Are the pieces the call? Per root span that ended inside the window,
the summed seconds of its DIRECT child spans over its own seconds, in
percent. ``params``: ``root`` (span name), ``pick``: ``"p50"`` (nearest-
rank median of the share over the roots) or ``"slowest"`` (the share of
the root with the most seconds: a stalled call either has a piece's name
or lies between the spans). Prints once a run the pieces, in milliseconds
by child name without the root's prefix, of the slowest root and of the
root with the median seconds: ``[bench] slowest_call: seconds=... put=...
... uncovered=...`` and ``[bench] p50_call: ...``. Nothing to read where
no root in the window has a child span (the program has no such span)."""


def _pieces(root, kids) -> dict:
    """``{piece: ms}`` of one root: its direct children summed by name,
    then what no child covers."""
    prefix = root["name"].split(".", 1)[0] + "."
    out: dict = {}
    for k in kids:
        name = str(k.get("name", ""))
        name = name.removeprefix(prefix).replace(".", "_")
        out[name] = out.get(name, 0.0) + 1000.0 * float(k.get("seconds", 0.0))
    total = 1000.0 * float(root["seconds"])
    out["uncovered"] = total - sum(out.values())
    return {k: round(v, 3) for k, v in out.items()}


def read(ctx, params):
    from benchmark.harness.stats import percentile

    lo, hi = ctx.window
    spans = [e for e in ctx.events if e.get("event") == "span"]
    children: dict = {}
    for e in spans:
        children.setdefault(e.get("parent_id"), []).append(e)
    roots = []  # (seconds, share, root, its direct children)
    for r in spans:
        seconds = float(r.get("seconds", 0.0))
        if (r.get("name") != params["root"] or seconds <= 0.0
                or not lo <= r.get("ts_mono", lo) <= hi):
            continue
        kids = children.get(r.get("span_id"), ()) if r.get("span_id") else ()
        covered = sum(float(k.get("seconds", 0.0)) for k in kids)
        roots.append((seconds, 100.0 * covered / seconds, r, kids))
    if not any(kids for _s, _c, _r, kids in roots):
        return None
    slowest = max(roots, key=lambda t: t[0])
    if not getattr(ctx, "slowest_call_said", False):
        ctx.slowest_call_said = True
        p50 = percentile([t[0] for t in roots], 0.5)
        median = next(t for t in roots if t[0] == p50)
        for what, (seconds, _c, root, kids) in (
            ("slowest_call", slowest), ("p50_call", median),
        ):
            ctx.say(what, seconds=round(seconds, 6), calls=len(roots),
                    **_pieces(root, kids))
    if params["pick"] == "slowest":
        return slowest[1]
    return percentile([t[1] for t in roots], 0.5)
