"""The overlay clip's share of the HBM roofline, in percent: the border x
border candidate rows of the traced calls — the program's own counter
(``clip_rows`` of an ``overlay.call`` span: the rows that NEED a clip, in
place, swapped, fanned or handed to the host, whatever clips them, so a
change that clips fewer rows than need it cannot raise the share) — times
the bytes one such row must move at THAT call's pad and dtype (the span's
``vpad`` and ``acc``: a pool's layers are prepared at pads of their own),
summed over the traced calls, over the chip's peak bytes/s, divided by
the device seconds of the program's scopes ``overlay.gather`` +
``overlay.clip`` + ``overlay.fan`` in those calls. The three are summed: a
fused op carries one scope's name, so the clip scope alone could read too
short and the share too long; summed, it can only understate. The traffic
kind keeps the traced calls' root spans in ``ctx.series["traced_calls"]``.
Nothing to read on a program without the scopes, the counter or the pad."""

STAGES = ["overlay.gather", "overlay.clip", "overlay.fan"]


def row_bytes(vpad: int, itemsize: int) -> int:
    """Both rings' padded vertices (two coordinates each) in the clip's
    dtype, and 16 bytes of the row itself: its two int32 table rows, its
    int32 segment and the area out."""
    return 2 * vpad * 2 * itemsize + 16


def read(ctx, params):
    import numpy as np

    from benchmark.harness import peaks

    calls = [
        e for e in ctx.series.get("traced_calls", ())
        if e.get("clip_rows") and "vpad" in e and "acc" in e
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
        int(e["clip_rows"])
        * row_bytes(int(e["vpad"]), np.dtype(e["acc"]).itemsize)
        for e in calls
    ]
    least_s = sum(moved) / peaks.peaks_for(ctx.device["kind"])["hbm_bytes_per_s"]
    ctx.say("clip_bytes", traced_calls=len(calls),
            clip_rows=[int(e["clip_rows"]) for e in calls],
            vpad=[int(e["vpad"]) for e in calls], bytes=moved,
            clip_program_device_s=round(seconds, 6))
    return 100.0 * least_s / seconds
