"""The KNN block program's share of the HBM roofline, in percent: the
(landmark, candidate) pairs whose distance the device evaluated in the
traced calls — the program's own counter of REAL pairs (`pairs` of a
`SpatialKNN.transform` result), not the padded slots, so a change that
prunes pairs lowers the count together with the time — times the bytes one
pair must move, over the chip's peak bytes/s, divided by the device
seconds of the program's scopes (``knn.gather``, ``knn.distance``,
``knn.topk``) in those calls. The three are summed: a fused op carries one
scope's name, so the distance scope alone could read too short and the
share too long; summed, it can only understate. Nothing to read on a
program without the scopes or the counter."""

STAGES = ["knn.gather", "knn.distance", "knn.topk"]


def pair_bytes(coord_itemsize: int) -> int:
    """Both points' two coordinates, the int32 candidate row, the distance
    out."""
    return 4 * coord_itemsize + 4 + coord_itemsize


def read(ctx, params):
    from benchmark.harness import peaks

    pairs = ctx.counters.get("traced_pairs")
    index = getattr(getattr(ctx, "deployment", None), "index", None)
    if not pairs or index is None or not ctx.counters.get("traced_steps"):
        return None
    ms = ctx.spec.module("readers", "trace_stage_busy").read(
        ctx, {"stage": STAGES, "steps": "traced_steps"}
    )
    if not ms:
        return None
    seconds = ms / 1000.0 * ctx.counters["traced_steps"]
    per_pair = pair_bytes(int(index.dtype.itemsize))
    least_s = (
        pairs * per_pair
        / peaks.peaks_for(ctx.device["kind"])["hbm_bytes_per_s"]
    )
    ctx.say("pair_bytes", bytes_per_pair=per_pair, traced_pairs=pairs,
            block_program_device_s=round(seconds, 6))
    return 100.0 * least_s / seconds
