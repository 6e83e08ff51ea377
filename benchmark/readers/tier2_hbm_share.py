"""Tier 2's share of the HBM roofline, in percent: the bytes the rows that
NEED tier 2 have to move a step — a row whose cell is heavy reads that
cell's wide row (``E2`` edges of 4 coordinates and one parity word each,
``M2`` slot ids) and its own heavy-row id and answer — over the chip's peak
bytes/s, divided by the device seconds under scope ``pip.tier2`` a step.
The rows are counted by the program (``heavy_rows`` on its ``stream.run``
spans); what a lowering moves for the other rows (a stream without a cap
compacts and scatters the whole batch) is not counted, so the share can
only understate. Nothing to read on a program without the counter, the
scope or heavy cells."""


def tier2_bytes_per_row(index) -> int:
    """From the index's shapes alone."""
    e2 = int(index.heavy_edges.shape[1])
    m2 = int(index.heavy_slot_geom.shape[1])
    return e2 * (4 * int(index.heavy_edges.dtype.itemsize) + 4) + m2 * 4 + 8


def read(ctx, params):
    from benchmark.harness import peaks

    index = getattr(getattr(ctx, "deployment", None), "index", None)
    if index is None or not int(index.heavy_edges.shape[0]):
        return None
    runs = ctx.spec.module("readers", "_events").in_window(
        ctx, {"event": "span", "where": {"name": "stream.run"}}
    )
    steps = sum(e.get("n_batches", 0) for e in runs if "heavy_rows" in e)
    if not steps:
        return None
    ms = ctx.spec.module("readers", "trace_stage_busy").read(
        ctx, {"stage": "pip.tier2", "steps": "traced_steps"}
    )
    if not ms:
        return None
    rows = sum(e["heavy_rows"] for e in runs if "heavy_rows" in e) / steps
    chips = max(int(getattr(ctx, "chips", 1)), 1)
    per_row = tier2_bytes_per_row(index)
    least_s = (
        rows / chips * per_row
        / peaks.peaks_for(ctx.device["kind"])["hbm_bytes_per_s"]
    )
    ctx.say("tier2_bytes", bytes_per_row=per_row,
            heavy_rows_per_step=round(rows, 1))
    return 100.0 * least_s / (ms / 1000.0)
