"""Traffic kind ``knn_polygon_transform``: one client in a closed loop that
hands `mosaic_tpu.models.SpatialKNN.transform` one table of building
footprints after another against the deployment's resident candidate index
— a packed float64 POLYGON column in, the ranked `KNNResult` in host memory
out, read and let go before the next call — until the window has passed. It
is the nightly job that gives every building of a borough its k nearest
pickups of the day (`traffic_kinds/knn_transform.py` is the same loop on
point landmarks and is not edited for this one).

Parameters (the mix's data file): ``pool_tables`` (disjoint tables drawn
from the deployment's footprint layer by ``--seed``, each in the layer's
order, cycled), ``control`` (what the two controls change, see `_control`).
Table rows come from the configuration (``batch_rows_per_chip``).

Set-up warms every program the model can launch on landmarks like the
pool's (`SpatialKNN.warmup(index, pool[0])`: the sample picks the lane, its
counts are no part of a compiled shape) and transforms every table of the
pool once.

End-to-end: ``batch_rows_per_s`` — landmarks answered by the window's
finished calls over the seconds from before the first call to after the
last one's result is in host memory. Every call's answer after the first
pass over the pool is compared, inside the window, with the first pass's
on the same table, and dropped.

Correct, after the window: a seeded sample of each table's first timed
answer, half of it large footprints with courtyards, against the plain
reference over ALL candidates (see `check`).
"""

from __future__ import annotations

import time

#: the calls a ``--trace 1`` run profiles: one pass over a pool of two,
#: after the first call
TRACE_FROM_CALL, TRACE_CALLS = 1, 2
#: the generator's kind of a large footprint (24-80 vertices, courtyards)
LARGE = 2


def _tables(ctx, k: int, rows: int):
    """``k`` disjoint sorted row sets of the layer from ``--seed``."""
    import numpy as np

    n = len(ctx.deployment.layer)
    if k * rows > n:
        raise ValueError(f"{k} disjoint tables of {rows} from a layer of {n}")
    pick = np.random.default_rng(ctx.seed).permutation(n)[: k * rows]
    return [np.sort(pick[b * rows : (b + 1) * rows]) for b in range(k)]


def _control(ctx):
    """The index, the model and the landmarks' form of this run: the
    deployment's own and packed polygons in a benchmark run. Under a
    control (never set by a benchmark run) the seed's parity picks one of
    ``control.kinds``, an even seed the first: ``float32`` indexes the
    candidates again in float32, so candidates, ring rows and distances
    are the nearest precision below; ``first_vertex`` hands the model each
    footprint's first vertex as a point, the sibling cell's lane standing
    in for the polygon lane."""
    dep = ctx.deployment
    if not ctx.control:
        return dep.index, dep.model, None
    import numpy as np

    from mosaic_tpu.knn import build_knn_index

    kinds = ctx.traffic["control"]["kinds"]
    kind = kinds[ctx.seed % len(kinds)]
    index = dep.index
    if kind == "float32":
        index = build_knn_index(
            dep.candidates, dep.grid, dep.res, dtype=np.dtype("float32")
        )
    return index, dep.model, kind


def _same(a, b) -> int:
    """Rows on which two results differ (a differing shape counts whole)."""
    import numpy as np

    if a.candidate_id.shape != b.candidate_id.shape:
        return max(a.candidate_id.shape[0], b.candidate_id.shape[0])
    return int(np.count_nonzero(
        (a.landmark_id != b.landmark_id) | (a.candidate_id != b.candidate_id)
        | (a.rank != b.rank) | (a.distance != b.distance)
    ))


def _compiles_met() -> int:
    """Programs the backend compiled or loaded from its cache so far: what
    the pool's first calls add after the warm-up is what the warm-up missed."""
    from mosaic_tpu.dispatch import backend_compiles, compile_cache_hits

    return (backend_compiles() or 0) + (compile_cache_hits() or 0)


def prepare(ctx) -> dict:
    dep, mix = ctx.deployment, ctx.traffic
    k = int(mix["pool_tables"])
    index, model, control = _control(ctx)
    with ctx.spans.span("pool_build"):
        rows = _tables(ctx, k, dep.batch)
        pool = [dep.take(dep.layer, r) for r in rows]
        if control == "first_vertex":
            pool = [t.xy[t.ring_offsets[t.part_offsets[:-1]]] for t in pool]
    with ctx.spans.span("call_warmup"):
        # (a rehearsal compiles what its two tiny tables launch and no
        # more: the ladder's top rungs cost a CPU minutes and prove nothing)
        report = None if ctx.rehearsal else model.warmup(index, pool[0])
        met = _compiles_met()
        for table in pool:
            model.transform(table, index)
        met = _compiles_met() - met
    ctx.say(
        "knn_ready", pool=[len(t) for t in pool],
        vertices=[int(getattr(t, "xy", t).shape[0]) for t in pool],
        warm=report, first_call_compiles=met, control=control,
        index_dtype=str(index.dtype),
        pool_build_s=round(ctx.spans.seconds("pool_build"), 3),
        call_warmup_s=round(ctx.spans.seconds("call_warmup"), 3),
    )
    return {"pool": pool, "rows": rows, "k": k, "index": index,
            "model": model, "first": [], "unlike": 0, "metrics": []}


def window(ctx, st) -> dict:
    index, model = st["index"], st["model"]
    pool, k, first = st["pool"], st["k"], st["first"]
    calls = traced = 0
    sums = dict.fromkeys(("pairs", "edge_pairs", "edge_rows"), 0)
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
            for name in sums:
                sums[name] += int(result.metrics.get(name, 0))
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
    rows = calls * len(pool[0])
    m = st["metrics"]
    degraded = sum(bool(x["degraded"]) for x in m)
    ctx.counters.update(
        rows=rows, calls=calls, window_s=t1 - t0, traced_steps=traced,
        traced_pairs=sums["pairs"], traced_edge_pairs=sums["edge_pairs"],
        traced_edge_rows=sums["edge_rows"],
        rows_per_step_per_chip=len(pool[0]),
    )

    def first_pass(name):
        return [x.get(name) for x in m[:k]]

    ctx.say(
        "knn_window", calls=calls, rows=rows, window_s=round(t1 - t0, 4),
        iterations=first_pass("iterations"), pairs=first_pass("pairs"),
        pairs_padded=first_pass("pairs_padded"),
        launches=first_pass("launches"), seeds=first_pass("seeds"),
        edges=first_pass("edges"), edge_pairs=first_pass("edge_pairs"),
        edge_pairs_padded=first_pass("edge_pairs_padded"),
        host_landmarks=first_pass("host_landmarks"),
        unrested=first_pass("unrested_landmarks"),
        degraded_calls=degraded, unlike_first_pass=st["unlike"],
        call_s=[round(w, 4) for w in walls],
    )
    return {
        "attempted": rows,
        "failed": degraded * len(pool[0]),
        "metrics": {"batch_rows_per_s": rows / (t1 - t0) / ctx.chips},
    }


def check(ctx, st) -> list:
    import numpy as np

    from benchmark.harness.check import Comparison

    dep, limits = ctx.deployment, ctx.cell["check"]
    first, k = st["first"], dep.k
    per_table = int(limits["sample_landmarks"])
    tol = float(limits["rank_distance_tolerance"])
    rng = np.random.default_rng(ctx.seed)
    wrong = slots = repeats = zeros = 0
    worst = 0.0
    t0 = time.perf_counter()
    for b, res in enumerate(first):
        layer_rows = st["rows"][b]
        n = layer_rows.shape[0]
        # half the sample from the table's large footprints (courtyards,
        # many candidates inside, ties at 0.0), half from the others
        large = np.flatnonzero(dep.kinds[layer_rows] == LARGE)
        small = np.flatnonzero(dep.kinds[layer_rows] != LARGE)
        n_large = min(per_table // 2, large.size)
        idx = np.sort(np.concatenate([
            rng.choice(large, n_large, replace=False),
            rng.choice(small, min(per_table - n_large, small.size),
                       replace=False),
        ]))
        got_id = np.full((n, k), -1, dtype=np.int64)
        got_d = np.full((n, k), np.inf)
        got_id[res.landmark_id, res.rank - 1] = res.candidate_id
        got_d[res.landmark_id, res.rank - 1] = res.distance
        got_id, got_d = got_id[idx], got_d[idx]
        sample = [dep.footprints[i] for i in layer_rows[idx]]
        _want_id, want_d = dep.reference.answers(sample, dep.candidates, k)
        true_d = dep.reference.distances(sample, dep.candidates, got_id)
        # a slot is wrong where the returned candidate's TRUE distance is
        # not the reference's distance at that rank (an empty slot reads
        # inf against a finite one)
        with np.errstate(invalid="ignore"):
            off = np.abs(true_d - want_d)
        off[(true_d == want_d)] = 0.0  # inf against inf
        bad = off > tol
        wrong += int(bad.sum())
        slots += bad.size
        zeros += int((want_d == 0.0).sum())
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
        "reference", landmarks=slots // max(k, 1), tables=len(first),
        candidates=int(dep.candidates.shape[0]), slots_at_zero=zeros,
        seconds=round(time.perf_counter() - t0, 3),
    )
    m = st["metrics"]
    return [
        Comparison(
            "knn_wrong_neighbour_share", wrong / max(slots, 1),
            limits["max_wrong_share"],
            "share of the sampled (landmark, rank) slots whose returned "
            "candidate's true f64 distance to the footprint is not the "
            "plain reference's distance at that rank, within "
            "rank_distance_tolerance",
        ),
        Comparison(
            "knn_distance_error", worst, limits["max_distance_error"],
            "largest |returned distance - true f64 distance of the returned "
            "candidate to the footprint| over the sampled slots",
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
        Comparison(
            "host_landmarks",
            max((x.get("host_landmarks", 0) for x in m), default=0), 0,
            "footprints the host answered because their edges pass the "
            "block program's top rung: every footprint of the layer is the "
            "device's",
        ),
    ]


def close(ctx, st) -> None:
    st.clear()
