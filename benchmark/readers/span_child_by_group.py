"""Per root span, the summed seconds of its descendants of given names,
where the roots are calls of more than one kind in turn (a loop over a pool
of prepared layers): the roots that ended inside the window are grouped by
one field of theirs, the nearest-rank percentile is taken inside each group,
and the groups' percentiles are averaged. One number whatever the parity of
the window's call count: a percentile over the mixed population would read
one kind of call or the other. ``params``: ``root`` (span name), ``child``
(a name or a list; the root's own name counts the root itself), ``by`` (the
root's field; roots without it form one group), ``q``, ``scale``. Prints
each group's percentile once a metric as ``[bench] span_by_group:``.
Nothing to read where no such descendant was recorded at all."""


def read(ctx, params):
    from benchmark.harness.stats import percentile

    lo, hi = ctx.window
    wanted = params["child"]
    wanted = {wanted} if isinstance(wanted, str) else set(wanted)
    spans = [e for e in ctx.events if e.get("event") == "span"]
    children: dict = {}
    for e in spans:
        children.setdefault(e.get("parent_id"), []).append(e)
    groups: dict = {}
    found = 0
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
        groups.setdefault(root.get(params["by"]), []).append(total)
    if not found:
        return None
    q, scale = float(params["q"]), float(params.get("scale", 1))
    each = {k: percentile(v, q) * scale for k, v in groups.items()}
    ctx.say(
        "span_by_group", child="+".join(sorted(wanted)), by=params["by"],
        **{str(k): f"{round(v, 4)}/{len(groups[k])}" for k, v in
           sorted(each.items(), key=lambda kv: str(kv[0]))},
    )
    return sum(each.values()) / len(each)
