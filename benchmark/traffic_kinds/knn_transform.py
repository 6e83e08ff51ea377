"""Traffic kind ``knn_transform``: one client in a closed loop that hands
`mosaic_tpu.models.SpatialKNN.transform` one landmark table after another
against the deployment's resident candidate index — float64 ``numpy``
points in, the ranked `KNNResult` in host memory out — until the window has
passed. It is the nightly job that gives every building or venue of a city
its k nearest pickups.

Parameters (the mix's data file): ``pool_tables`` (distinct landmark
tables, made by the point generator on the device from ``--seed``, pulled
to host memory and cycled), ``points`` (the generator's parameters; the
box is the candidates'), ``control`` (what the lower-precision control
changes, see `_control`). A hotspot draw that leaves the box is replaced by
a uniform one inside it, here (`generators/points.py` lets such points
stay, and out there the candidates thin to nothing: a landmark 4 sigma out
of a 3 km hotspot would walk 60 rings). Table rows come from the
configuration (``batch_rows_per_chip``).

Set-up warms every program the model can launch on the index
(`SpatialKNN.warmup`) and transforms every table of the pool once.

End-to-end: ``batch_rows_per_s`` — landmarks answered by the window's
finished calls over the seconds from before the first call to after the
last one's result is in host memory. Every call's answer after the first
pass over the pool is compared, inside the window, with the first pass's
on the same table, and dropped.

Correct, after the window: a seeded sample of each table's first timed
answer against the plain reference over ALL candidates (see `check`).
"""

from __future__ import annotations

import time

#: the calls a ``--trace 1`` run profiles: one pass over a pool of two,
#: after the first call
TRACE_FROM_CALL, TRACE_CALLS = 1, 2


def _tables(ctx, k: int, rows: int):
    """(k, rows, 2) f64 landmark tables from ``--seed``: the mix's points,
    a draw outside the box replaced by a uniform draw inside it."""
    import jax
    import numpy as np

    dep, mix = ctx.deployment, ctx.traffic
    points = ctx.spec.module("generators", "points")
    key = points.seed_key(ctx.seed)
    gen = points.make_generator(mix["points"], dep.bbox, rows, slots=k)
    flat = points.make_generator(
        dict(mix["points"], hotspot_share=0.0), dep.bbox, rows, slots=k
    )
    pool = np.array(gen(key))  # a writable host copy
    inside = np.asarray(flat(jax.random.fold_in(key, 0x6B6E6E)))
    x0, y0, x1, y1 = dep.bbox
    out = (
        (pool[..., 0] < x0) | (pool[..., 0] > x1)
        | (pool[..., 1] < y0) | (pool[..., 1] > y1)
    )
    pool[out] = inside[out]
    return pool, int(out.sum())


def _control(ctx):
    """The index and the model of this run: the deployment's own in a
    benchmark run; under the lower-precision control (never set by a
    benchmark run) the candidates indexed again in the mix's
    ``control.candidate_dtype`` and the model's arguments overridden by
    ``control.model``."""
    dep = ctx.deployment
    if not ctx.control:
        return dep.index, dep.model, {}
    import numpy as np

    from mosaic_tpu.knn import build_knn_index
    from mosaic_tpu.models import SpatialKNN

    control = dict(ctx.traffic["control"])
    index = dep.index
    if control.get("candidate_dtype"):
        index = build_knn_index(
            dep.candidates, dep.grid, dep.res,
            dtype=np.dtype(control["candidate_dtype"]),
        )
    args = dict(dep.model_args, **control.get("model", {}))
    return index, SpatialKNN(index=dep.grid, resolution=dep.res, **args), control


def _same(a, b) -> int:
    """Rows on which two results differ (a differing shape counts whole)."""
    import numpy as np

    if a.candidate_id.shape != b.candidate_id.shape:
        return max(a.candidate_id.shape[0], b.candidate_id.shape[0])
    return int(np.count_nonzero(
        (a.landmark_id != b.landmark_id) | (a.candidate_id != b.candidate_id)
        | (a.rank != b.rank) | (a.distance != b.distance)
    ))


def prepare(ctx) -> dict:
    dep, mix = ctx.deployment, ctx.traffic
    k = int(mix["pool_tables"])
    index, model, control = _control(ctx)
    with ctx.spans.span("pool_build"):
        pool, replaced = _tables(ctx, k, dep.batch)
    with ctx.spans.span("call_warmup"):
        # (a rehearsal compiles what its two tiny tables launch and no
        # more: the ladder's top rungs cost a CPU minutes and prove nothing)
        report = None if ctx.rehearsal else model.warmup(index)
        for b in range(k):
            model.transform(pool[b], index)
    ctx.say(
        "knn_ready", pool=tuple(pool.shape), dtype=str(pool.dtype),
        replaced_outside_box=replaced, warm=report, control=control,
        index_dtype=str(index.dtype),
        pool_build_s=round(ctx.spans.seconds("pool_build"), 3),
        call_warmup_s=round(ctx.spans.seconds("call_warmup"), 3),
    )
    return {"pool": pool, "k": k, "index": index, "model": model,
            "first": [], "unlike": 0, "metrics": []}


def window(ctx, st) -> dict:
    index, model = st["index"], st["model"]
    pool, k, first = st["pool"], st["k"], st["first"]
    calls = traced = traced_pairs = 0
    walls = []
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
        with ctx.spans.span("knn.call"):
            result = model.transform(pool[b], index)
        if ctx.tracer.active:
            traced += 1
            traced_pairs += int(result.metrics["pairs"])
        st["metrics"].append(result.metrics)
        if calls < k:
            first.append(result)
        else:  # the client reads its answer, then lets it go
            st["unlike"] += _same(result, first[b])
        calls += 1
        t = time.perf_counter()
        if not ctx.tracer.active:
            walls.append(t - t_call)
    t1 = t
    ctx.tracer.stop()
    ctx.window = (unprofiled_from, time.monotonic())
    ctx.series["call_s"] = walls
    rows = calls * pool.shape[1]
    m = st["metrics"]
    degraded = sum(bool(x["degraded"]) for x in m)
    ctx.counters.update(
        rows=rows, calls=calls, window_s=t1 - t0, traced_steps=traced,
        traced_pairs=traced_pairs,
        rows_per_step_per_chip=pool.shape[1],
    )
    ctx.say(
        "knn_window", calls=calls, rows=rows, window_s=round(t1 - t0, 4),
        iterations=[x["iterations"] for x in m[:k]],
        pairs=[x["pairs"] for x in m[:k]],
        pairs_padded=[x["pairs_padded"] for x in m[:k]],
        launches=[x["launches"] for x in m[:k]],
        unrested=[x["unrested_landmarks"] for x in m[:k]],
        degraded_calls=degraded, unlike_first_pass=st["unlike"],
        call_s=[round(w, 4) for w in walls],
    )
    return {
        "attempted": rows,
        "failed": degraded * pool.shape[1],
        "metrics": {"batch_rows_per_s": rows / (t1 - t0) / ctx.chips},
    }


def check(ctx, st) -> list:
    import numpy as np

    from benchmark.harness.check import Comparison

    dep, limits = ctx.deployment, ctx.cell["check"]
    pool, first, k = st["pool"], st["first"], dep.k
    per_table = int(limits["sample_landmarks"])
    tol = float(limits["rank_distance_tolerance"])
    rng = np.random.default_rng(ctx.seed)
    wrong = slots = repeats = 0
    worst = 0.0
    t0 = time.perf_counter()
    for b, res in enumerate(first):
        idx = np.sort(rng.choice(pool.shape[1], per_table, replace=False))
        got_id = np.full((pool.shape[1], k), -1, dtype=np.int64)
        got_d = np.full((pool.shape[1], k), np.inf)
        got_id[res.landmark_id, res.rank - 1] = res.candidate_id
        got_d[res.landmark_id, res.rank - 1] = res.distance
        got_id, got_d = got_id[idx], got_d[idx]
        _want_id, want_d = dep.reference.answers(pool[b][idx], dep.candidates, k)
        true_d = dep.reference.distances(pool[b][idx], dep.candidates, got_id)
        # a slot is wrong where the returned candidate's TRUE distance is
        # not the reference's distance at that rank (an empty slot reads
        # inf against a finite one)
        with np.errstate(invalid="ignore"):
            off = np.abs(true_d - want_d)
        off[(true_d == want_d)] = 0.0  # inf against inf
        bad = off > tol
        wrong += int(bad.sum())
        slots += bad.size
        filled = got_id >= 0
        if filled.any():
            worst = max(worst, float(np.abs(got_d - true_d)[filled].max()))
        srt = np.sort(got_id, axis=1)
        repeats += int(((srt[:, 1:] == srt[:, :-1]) & (srt[:, 1:] >= 0)).sum())
        for j in np.argwhere(bad)[:6]:
            ctx.say(
                "wrong_slot", table=b, landmark=int(idx[j[0]]), rank=int(j[1]) + 1,
                got=int(got_id[j[0], j[1]]), true_d=repr(float(true_d[j[0], j[1]])),
                want_d=repr(float(want_d[j[0], j[1]])),
            )
    ctx.say(
        "reference", landmarks=per_table * len(first), tables=len(first),
        candidates=int(dep.candidates.shape[0]),
        seconds=round(time.perf_counter() - t0, 3),
    )
    m = st["metrics"]
    return [
        Comparison(
            "knn_wrong_neighbour_share", wrong / max(slots, 1),
            limits["max_wrong_share"],
            "share of the sampled (landmark, rank) slots whose returned "
            "candidate's true f64 distance is not the plain reference's "
            "distance at that rank, within rank_distance_tolerance",
        ),
        Comparison(
            "knn_distance_error", worst, limits["max_distance_error"],
            "largest |returned distance - true f64 distance of the returned "
            "candidate| over the sampled slots",
        ),
        Comparison(
            "knn_repeated_ids", repeats, 0,
            "a candidate named twice in one landmark's row",
        ),
        Comparison(
            "knn_unrested_landmarks",
            max((x["unrested_landmarks"] for x in m), default=0), 0,
            "landmarks max_iterations cut off while still owed a ring: "
            "their answer is not known to be exact",
        ),
        Comparison(
            "knn_rows_unlike_first_pass", st["unlike"], 0,
            "the loop is deterministic: every later answer on a table "
            "equals the first pass's row for row",
        ),
    ]


def close(ctx, st) -> None:
    st.clear()
