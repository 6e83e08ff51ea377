"""Traffic kind ``overlay_measures_loop``: one client in a closed loop that
hands `mosaic_tpu.sql.overlay.overlay_measures` the deployment's parcel layer
and, in turn, each prepared theme layer of a pool — the measure and the
prepared pair in, the folded measures of every candidate geometry pair in
host memory out — until the window has passed. It is the planning or
property-risk analyst's job: the share of every parcel that lies in each
district, each flood band.

Parameters (the mix's data file): ``layers`` (the pool: each a name, the
generator function in ``generators/themes.py`` and its parameters; made from
``--seed``), ``control`` (what the two lower-precision controls change, see
`_control`).

Set-up (inside ``setup_s``): the theme layers, `tessellate` of each under
the benchmark's span ``tessellate``, `prepare_overlay` of each under
``index_build``, `warmup_overlay` and one more call a layer under
``call_warmup``.

End-to-end: ``batch_rows_per_s`` — geometry pairs answered (the join's
output rows) by the window's finished calls over the seconds from before the
first call to after the last one's result is in host memory. Every call's
answer after the first pass over the pool is compared, inside the window,
with the first pass's on the same layer, and let go before the next call.

Correct, after the window: a seeded sample of parcels of each layer's first
timed answer against the plain reference on the whole geometries (see
`check`).
"""

from __future__ import annotations

import time

#: the calls a ``--trace 1`` run profiles: one pass over a pool of two,
#: after the first pass
TRACE_FROM_CALL, TRACE_CALLS = 2, 2


def _control(ctx):
    """``(round, name)``: what a control run does to every layer's
    coordinates before `tessellate` — nothing in a benchmark run. The mix
    lists two controls and a control run takes the one its seed's parity
    picks (`tools/limits.py` steps its seeds by an odd number, so the two
    alternate): ``float32`` rounds the British National Grid coordinates to
    float32 as they are (a step of 1/16 m at easting 530,000);
    ``float32_global_frame`` rounds them to the float32 lattice of ONE frame
    centred on the box, which is what the overlay's device tables held
    before rings were stored relative to their own cell."""
    import numpy as np

    if not ctx.control:
        return (lambda xy: xy), None
    names = list(ctx.traffic["control"]["kinds"])
    name = names[ctx.seed % len(names)]
    if name == "float32":
        return (lambda xy: xy.astype(np.float32).astype(np.float64)), name
    if name == "float32_global_frame":
        x0, y0, x1, y1 = ctx.deployment.layout.box
        c = np.array([0.5 * (x0 + x1), 0.5 * (y0 + y1)])
        return (
            lambda xy: (xy - c).astype(np.float32).astype(np.float64) + c
        ), name
    raise ValueError(f"unknown control {name!r}")


def _same(a, b) -> int:
    """Rows on which two answers differ (a differing shape counts whole)."""
    import numpy as np

    if a.pairs.shape != b.pairs.shape:
        return max(a.pairs.shape[0], b.pairs.shape[0])
    return int(np.count_nonzero(
        (a.pairs != b.pairs).any(axis=1) | (a.value != b.value)
        | (a.area != b.area) | (a.valid != b.valid)
    ))


def prepare(ctx) -> dict:
    from mosaic_tpu.core.tessellate import tessellate
    from mosaic_tpu.sql.overlay import (
        overlay_measures,
        prepare_overlay,
        warmup_overlay,
    )

    dep, mix = ctx.deployment, ctx.traffic
    themes = ctx.spec.module("generators", "themes")
    rnd, control = _control(ctx)
    parcels, pcol, ptable = dep.parcels, dep.col, dep.table
    if control:  # the parcels chipped again from the rounded coordinates
        parcels = [[rnd(r) for r in pg] for pg in dep.parcels]
        pcol = dep.pack(parcels)
        with ctx.spans.span("tessellate"):
            ptable = tessellate(pcol, dep.grid, dep.res)
    layers = []
    for spec in mix["layers"]:
        with ctx.spans.span("pool_build"):
            polygons, stats = getattr(themes, spec["generator"])(
                dep.layout, spec.get("params", {}), ctx.seed
            )
            true_polygons = polygons
            polygons = [[rnd(r) for r in pg] for pg in polygons]
            col = dep.pack(polygons)
        with ctx.spans.span("tessellate"):
            table = tessellate(col, dep.grid, dep.res)
        with ctx.spans.span("index_build"):
            prep = prepare_overlay(
                ptable, table, pcol, col, dep.grid, dep.res
            )
        layers.append({
            "name": spec["name"], "polygons": true_polygons, "col": col,
            "prep": prep, "stats": stats, "chips": len(table),
            "core_chips": table.core_count(),
        })
    with ctx.spans.span("call_warmup"):
        for lay in layers:
            warmup_overlay(
                pcol, lay["col"], dep.grid, dep.res, dep.measure,
                prep=lay["prep"],
            )
            overlay_measures(
                pcol, lay["col"], dep.grid, dep.res, dep.measure,
                prep=lay["prep"],
            )
    for lay in layers:
        p = lay["prep"]
        ctx.say(
            "overlay_layer", name=lay["name"], layer_polygons=len(lay["polygons"]),
            chips=lay["chips"], core_chips=lay["core_chips"],
            rows=p.right.n, parcel_rows=p.left.n, vpad=p.vpad,
            acc=p.acc_name, band_m2=p.band, frame_extent_m=p.scale,
            convex_rows=int(p.right.convex.sum()),
            star_rows=int(p.right.star.sum()),
            over_pad_rows=int((p.right.ring_len > p.vpad).sum()),
            hole_rows=int((p.right.sign < 0).sum()), **lay["stats"],
        )
    ctx.say(
        "overlay_ready", layers=[lay["name"] for lay in layers],
        control=control,
        pool_build_s=round(ctx.spans.seconds("pool_build"), 3),
        tessellate_s=round(ctx.spans.seconds("tessellate"), 3),
        index_build_s=round(ctx.spans.seconds("index_build"), 3),
        call_warmup_s=round(ctx.spans.seconds("call_warmup"), 3),
    )
    # the reference reads the layers as they were made: a control's
    # rounding is the program's doing, not the data's
    return {"layers": layers, "parcels": dep.parcels, "pcol": pcol,
            "first": [], "unlike": 0, "calls": [], "control": control}


def _last_call(ctx):
    """The newest ``overlay.call`` span event the run kept (every span in a
    traced run), or None."""
    for e in reversed(ctx.events):
        if e.get("event") == "span" and e.get("name") == "overlay.call":
            return e
    return None


def window(ctx, st) -> dict:
    from mosaic_tpu.sql.overlay import overlay_measures

    dep, layers, first = ctx.deployment, st["layers"], st["first"]
    k = len(layers)
    calls = rows = degraded_rows = 0
    walls, traced = [], []  # traced: the profiled calls' root spans
    unprofiled_from = time.monotonic()
    t0 = time.perf_counter()
    t = t0
    while t - t0 < ctx.seconds:
        if calls == TRACE_FROM_CALL:
            ctx.tracer.start()
        elif calls == TRACE_FROM_CALL + TRACE_CALLS and ctx.tracer.active:
            ctx.tracer.stop()
            unprofiled_from = time.monotonic()
            walls.clear()
        b = calls % k
        t_call = time.perf_counter()
        with ctx.spans.span("overlay.call"):
            answer = overlay_measures(
                st["pcol"], layers[b]["col"], dep.grid, dep.res, dep.measure,
                prep=layers[b]["prep"],
            )
        n = int(answer.pairs.shape[0])
        rows += n
        if answer.degraded:
            degraded_rows += n
        st["calls"].append({
            "layer": b, "pairs": n, "overflow": int(answer.overflow),
            "host_overridden": int(answer.host_overridden),
            "degraded": bool(answer.degraded),
        })
        if ctx.tracer.active:
            traced.append(_last_call(ctx) or {})
        if calls < k:
            first.append(answer)
        else:  # the client reads its answer, then lets it go
            st["unlike"] += _same(answer, first[b])
        del answer
        calls += 1
        t = time.perf_counter()
        if not ctx.tracer.active:
            walls.append(t - t_call)
    t1 = t
    ctx.tracer.stop()
    ctx.window = (unprofiled_from, time.monotonic())
    ctx.series["call_s"] = walls
    ctx.series["traced_calls"] = traced
    ctx.counters.update(
        rows=rows, calls=calls, window_s=t1 - t0, traced_steps=len(traced),
    )
    c = st["calls"]
    ctx.say(
        "overlay_window", calls=calls, rows=rows, window_s=round(t1 - t0, 4),
        pairs=[x["pairs"] for x in c[:k]],
        host_overridden=[x["host_overridden"] for x in c[:k]],
        overflow=[x["overflow"] for x in c[:k]],
        degraded_calls=sum(x["degraded"] for x in c),
        unlike_first_pass=st["unlike"],
        call_s=[round(w, 4) for w in walls],
    )
    return {
        "attempted": rows,
        "failed": degraded_rows,
        "metrics": {"batch_rows_per_s": rows / (t1 - t0) / ctx.chips},
    }


def check(ctx, st) -> list:
    import numpy as np

    from benchmark.harness.check import Comparison

    dep, limits = ctx.deployment, ctx.cell["check"]
    per_layer = min(int(limits["sample_parcels"]), len(st["parcels"]))
    touch = float(limits["touch_area_m2"])
    rng = np.random.default_rng(ctx.seed)
    missing = spurious = touch_nonzero = pairs_compared = 0
    worst = 0.0
    t0 = time.perf_counter()
    for lay, ans in zip(st["layers"], st["first"]):
        sample = np.sort(rng.choice(len(st["parcels"]), per_layer, replace=False))
        ref_p, ref_q, ref_a = dep.reference.overlay(
            st["parcels"], sample, lay["polygons"]
        )
        parcel_area = np.zeros(len(st["parcels"]))
        parcel_area[sample] = [
            dep.reference.area_of(st["parcels"][int(i)]) for i in sample
        ]
        width = len(lay["polygons"]) + 1
        real = ans.pairs[:, 0] >= 0  # an OVERFLOW row names no pair
        key = ans.pairs[real, 0] * width + ans.pairs[real, 1]
        order = np.argsort(key)
        key, value, area = key[order], ans.value[real][order], ans.area[real][order]
        ref_key = ref_p * width + ref_q
        pos = np.clip(np.searchsorted(key, ref_key), 0, max(key.shape[0] - 1, 0))
        found = key[pos] == ref_key if key.shape[0] else np.zeros(0, bool)
        got_area = np.where(found, area[pos], 0.0)
        got_value = np.where(found, value[pos], 0.0)
        # a pair of positive reference area the answer lacks
        missing += int(((ref_a >= touch) & ~found).sum())
        # a pair the reference calls a touch (or apart) must read exactly 0.0
        touching = found & (ref_a < touch)
        touch_nonzero += int((touching & (got_value != 0.0)).sum())
        # a positive value where the reference calls the pair disjoint: its
        # area reads 0.0 there, or the two boxes do not even meet
        in_sample = np.isin(ans.pairs[real, 0][order], sample)
        boxes_apart = in_sample & ~np.isin(key, ref_key)
        spurious += int((boxes_apart & (value > 0.0)).sum())
        spurious += int((found & (ref_a == 0.0) & (got_value > 0.0)).sum())
        want_value = ref_a / parcel_area[ref_p]
        err = np.maximum(
            np.abs(got_area - ref_a),
            np.abs(got_value - want_value) * parcel_area[ref_p],
        ) / dep.cell_area
        pairs_compared += int(ref_key.shape[0])
        if err.size:
            j = int(np.argmax(err))
            worst = max(worst, float(err[j]))
            ctx.say(
                "worst_pair", layer=lay["name"], parcel=int(ref_p[j]),
                polygon=int(ref_q[j]), area=repr(float(got_area[j])),
                reference=repr(float(ref_a[j])), error=repr(float(err[j])),
            )
    ctx.say(
        "reference", parcels=per_layer * len(st["first"]),
        pairs=pairs_compared, seconds=round(time.perf_counter() - t0, 3),
    )
    return [
        Comparison(
            "overlay_pairs_missing", missing, 0,
            "sampled (parcel, theme polygon) pairs of positive reference "
            "area that the answer does not return",
        ),
        Comparison(
            "overlay_pairs_spurious", spurious, 0,
            "pairs of the sample the answer gives a positive value where the "
            "reference calls them disjoint",
        ),
        Comparison(
            "overlay_touch_pairs_nonzero", touch_nonzero, 0,
            "pairs of the sample whose reference area is under touch_area_m2 "
            "(they touch along an edge or at a vertex, or lie apart in a "
            "shared cell) and whose value is not exactly 0.0",
        ),
        Comparison(
            "overlay_area_error", worst, limits["max_area_error"],
            "largest |area - reference| over the sample's pairs, as a share "
            "of the cell's area (the value's error counts, scaled by the "
            "parcel's area)",
        ),
        Comparison(
            "overlay_overflow_rows",
            sum(x["overflow"] for x in st["calls"]), 0,
            "no OVERFLOW row: the loop passes no pair_cap",
        ),
        Comparison(
            "overlay_answers_unlike_first_pass", st["unlike"], 0,
            "the loop is deterministic: every later answer on a layer equals "
            "the first pass's row for row",
        ),
    ]


def close(ctx, st) -> None:
    st.clear()
