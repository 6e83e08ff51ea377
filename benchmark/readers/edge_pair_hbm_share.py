"""The polygon block program's share of the HBM roofline, in percent: the
bytes the traced calls' work must move whatever evaluates it — every REAL
(landmark, candidate) pair's candidate side (the point's two coordinates,
its int32 row, the distance out) and, once a (landmark, block) chunk, the
landmark's REAL edges (a vertex's two coordinates an edge: an edge ends
where the next begins) — over the chip's peak bytes/s, divided by the
device seconds of the program's scopes (``knn.gather``, ``knn.edges``,
``knn.topk``) in those calls. The counts are the program's own counters of
real pairs and real edges (`pairs`, `edge_rows` of a `SpatialKNN.transform`
result), not padded slots, so a change that prunes pairs or pads less
lowers the bytes together with the time. The three scopes are summed: a
fused op carries one scope's name. The arithmetic — a point-segment
distance and a crossing test an edge a pair, in float64 the chip emulates
on its vector unit, for which `harness/peaks.py` has no peak — is most of
the time, so the share understates by construction and is far under 100.
Nothing to read on a program without the scopes or the counters."""

STAGES = ["knn.gather", "knn.edges", "knn.topk"]


def pair_bytes(coord_itemsize: int) -> int:
    """The candidate's two coordinates, its int32 row, the distance out."""
    return 2 * coord_itemsize + 4 + coord_itemsize


def edge_bytes(coord_itemsize: int) -> int:
    """One vertex's two coordinates."""
    return 2 * coord_itemsize


def read(ctx, params):
    from benchmark.harness import peaks

    pairs = ctx.counters.get("traced_pairs")
    edge_rows = ctx.counters.get("traced_edge_rows")
    index = getattr(getattr(ctx, "deployment", None), "index", None)
    if (not pairs or not edge_rows or index is None
            or not ctx.counters.get("traced_steps")):
        return None
    ms = ctx.spec.module("readers", "trace_stage_busy").read(
        ctx, {"stage": STAGES, "steps": "traced_steps"}
    )
    if not ms:
        return None
    seconds = ms / 1000.0 * ctx.counters["traced_steps"]
    size = int(index.dtype.itemsize)
    nbytes = pairs * pair_bytes(size) + edge_rows * edge_bytes(size)
    least_s = nbytes / peaks.peaks_for(ctx.device["kind"])["hbm_bytes_per_s"]
    ctx.say("edge_pair_bytes", bytes_per_pair=pair_bytes(size),
            bytes_per_edge=edge_bytes(size), traced_pairs=pairs,
            traced_edge_rows=edge_rows,
            traced_edge_pairs=ctx.counters.get("traced_edge_pairs"),
            block_program_device_s=round(seconds, 6))
    return 100.0 * least_s / seconds
