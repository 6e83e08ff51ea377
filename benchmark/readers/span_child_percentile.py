"""Per root span, the summed seconds of its descendants of given names;
nearest-rank percentile over the roots that ended inside the window.
``params``: ``root`` (span name), ``child`` (a name or a list; the root's
own name counts the root itself), ``q``, ``scale``. Nothing to read where
no such descendant was recorded at all (the program has no such span)."""


def read(ctx, params):
    from benchmark.harness.stats import percentile

    lo, hi = ctx.window
    wanted = params["child"]
    wanted = {wanted} if isinstance(wanted, str) else set(wanted)
    spans = [e for e in ctx.events if e.get("event") == "span"]
    children: dict = {}
    for e in spans:
        children.setdefault(e.get("parent_id"), []).append(e)
    sums, found = [], 0
    for root in spans:
        if root.get("name") != params["root"]:
            continue
        if not lo <= root.get("ts_mono", lo) <= hi:
            continue
        total, stack = 0.0, [root]
        while stack:
            e = stack.pop()
            if e.get("name") in wanted:
                total += float(e.get("seconds", 0.0))
                found += 1
            stack.extend(children.get(e.get("span_id"), ()))
        sums.append(total)
    if not found:
        return None
    return percentile(sums, float(params["q"])) * float(params.get("scale", 1))
