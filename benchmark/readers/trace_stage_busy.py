"""Device seconds of one or more of the join's own stage names, from the
stage table the harness builds once a traced run
(`harness/stage_table.py`: each device op under the innermost
``pip.*``/``stream.*`` scope it was traced under, ``unscoped`` else; mean
over the chips used).

``params``: ``stage`` (one name, or a list summed); then either ``steps``
(a counter: milliseconds per step) or ``share`` true (percent of all
stages' seconds)."""

from benchmark.harness import stage_table


def read(ctx, params):
    table = stage_table.of_run(ctx)
    if table is None:
        return None
    wanted = params["stage"]
    wanted = [wanted] if isinstance(wanted, str) else wanted
    seconds = sum(table.get(s, 0.0) for s in wanted)
    if params.get("share"):
        total = sum(table.values())
        return 100.0 * seconds / total if total > 0 else None
    steps = ctx.counters.get(params["steps"])
    return 1000.0 * seconds / steps if steps else None
