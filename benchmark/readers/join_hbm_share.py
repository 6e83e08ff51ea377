"""The join's share of the HBM roofline, in percent: the bytes the join
has to move for the traced steps (`cost.bytes_per_row` from the index's
shapes and the counted match share, x rows per step per chip x steps) over
the chip's peak bytes/s, divided by the device-busy time of those steps.
It is the bandwidth bound: the join does no dense arithmetic to speak of."""


def read(ctx, params):
    from benchmark.harness import cost, peaks

    red = ctx.trace_reduction
    steps = ctx.counters.get("traced_steps")
    rows = ctx.counters.get("rows")
    if not red or not red["devices"] or not steps or not rows:
        return None
    found = ctx.counters["matches"] / rows
    per_row = cost.bytes_per_row(
        cost.index_shapes(ctx.deployment.index), found
    )
    least_s = (
        per_row * ctx.counters["rows_per_step_per_chip"] * steps
        / peaks.peaks_for(ctx.device["kind"])["hbm_bytes_per_s"]
    )
    ctx.say("join_bytes", bytes_per_row=round(per_row, 2), found_share=round(found, 4))
    return 100.0 * least_s / red["busy_s"]
