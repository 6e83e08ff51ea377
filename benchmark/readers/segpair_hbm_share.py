"""The distance join's segment-pair predicate's share of the HBM roofline,
in percent: the candidate rows of the traced calls — the program's own
counter (``raw_candidates`` of a ``proximity.call`` span: every row the
equi-join emitted NEEDS a distance, whatever computes it, so a change that
answers fewer rows than need it cannot raise the share) — times the bytes
one such row must move at THAT call's pad and dtype (the span's ``vpad`` and
``acc``), summed over the traced calls, over the chip's peak bytes/s,
divided by the device seconds of the program's scopes ``proximity.gather``
+ ``proximity.segpairs`` + ``proximity.fold`` in those calls. The three are
summed: a fused op carries one scope's name, so one scope alone could read
too short and the share too long; summed, it can only understate. The
traffic kind keeps the traced calls' counters in
``ctx.series["traced_calls"]``. Nothing to read on a program without the
scopes, the counter or the pad."""

STAGES = ["proximity.gather", "proximity.segpairs", "proximity.fold"]


def row_bytes(vpad: int, itemsize: int) -> int:
    """Both pieces' padded vertices (two coordinates each) in the
    predicate's dtype, and 16 bytes of the row itself: the two origins'
    words and radii it reads with them, and the answer out."""
    return 2 * vpad * 2 * itemsize + 16


def read(ctx, params):
    import numpy as np

    from benchmark.harness import peaks

    calls = [
        e for e in ctx.series.get("traced_calls", ())
        if e.get("raw_candidates") and "vpad" in e and "acc" in e
    ]
    steps = ctx.counters.get("traced_steps")
    if not calls or not steps:
        return None
    ms = ctx.spec.module("readers", "trace_stage_busy").read(
        ctx, {"stage": STAGES, "steps": "traced_steps"}
    )
    if not ms:
        return None
    seconds = ms / 1000.0 * steps
    moved = [
        int(e["raw_candidates"])
        * row_bytes(int(e["vpad"]), np.dtype(e["acc"]).itemsize)
        for e in calls
    ]
    least_s = sum(moved) / peaks.peaks_for(ctx.device["kind"])["hbm_bytes_per_s"]
    ctx.say("segpair_bytes", traced_calls=len(calls),
            candidate_rows=[int(e["raw_candidates"]) for e in calls],
            vpad=[int(e["vpad"]) for e in calls], bytes=moved,
            segpair_program_device_s=round(seconds, 6))
    return 100.0 * least_s / seconds
