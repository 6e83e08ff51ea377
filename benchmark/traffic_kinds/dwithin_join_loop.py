"""Traffic kind ``dwithin_join_loop``: one client in a closed loop that
hands `mosaic_tpu.sql.proximity.dwithin_join` one table of AIS tracks after
another — a packed float64 LINESTRING column of ``windows_per_table``
consecutive 15-minute windows of the fleet, a radius a track, the window as
the equality key, joined with itself — and reads the pair table in host
memory, then lets it go, until the window has passed. It is the nightly
screening of a day's AIS for vessels that lay side by side: one block of
windows after another.

Parameters (the mix's data file): ``pool_tables`` (different blocks of
windows drawn by ``--seed``, cycled), ``windows_per_table``, ``control``
(what the two controls change, see `_control`).

Set-up makes the pool and one more table the warm-up never sees, warms
every program the join can launch on tables like the pool's
(`warmup_dwithin`: the sample's buckets and the rungs beside them), joins
every table of the pool once and then the unseen one: what that call
compiles or loads is ``first_call_compiles``, and must be nothing.

End-to-end: ``batch_rows_per_s`` — tracks answered by the window's finished
calls over the seconds from before the first call to after the last one's
pair table is in host memory. Every call's answer after the first pass over
the pool is compared, inside the window, with the first pass's on the same
table, and dropped.

Correct, after the window: on ``sample_windows`` seeded windows of each
table's first timed answer, against the plain reference over ALL the
window's tracks; and every planted transfer over the whole table (see
`check`).
"""

from __future__ import annotations

import time

#: the calls a ``--trace 1`` run profiles: one pass over a pool of two,
#: after the first call
TRACE_FROM_CALL, TRACE_CALLS = 1, 2
#: the join's child spans, for the traced run's breakdown line
CHILDREN = ("cover", "count", "emit", "launch", "pull", "glue", "host_band")


def _control(ctx):
    """What this run changes: nothing in a benchmark run. Under a control
    (never set by a benchmark run) the seed's parity picks one of
    ``control.kinds``, an even seed the first, and both change the input
    alone: ``float32_input`` rounds every coordinate to float32 as it is
    before the join sees it (a step of 7.6e-6 degrees at longitude -90,
    three quarters of a metre, where the sound path's frame resolves
    millimetres); ``float32_frame`` rounds it to float32 in ONE frame for
    the whole table, the box's centre (steps of 2.4e-7 to 4.8e-7 degrees,
    3 to 5 cm): what a float32 device table without the join's own local
    frames would hold."""
    if not ctx.control:
        return None
    kinds = ctx.traffic["control"]["kinds"]
    return kinds[ctx.seed % len(kinds)]


def _compiles_met() -> int:
    """Programs the backend compiled or loaded from its cache so far."""
    from mosaic_tpu.dispatch import backend_compiles, compile_cache_hits

    return (backend_compiles() or 0) + (compile_cache_hits() or 0)


def _join(ctx, table):
    """One call of the entry point on one table."""
    from mosaic_tpu.sql.proximity import dwithin_join

    dep = ctx.deployment
    return dwithin_join(
        table["col"], radius=table["radius"], key=table["window"],
        index_system=dep.grid, resolution=dep.res,
    )


def prepare(ctx) -> dict:
    import numpy as np

    from mosaic_tpu.sql.proximity import warmup_dwithin

    dep, mix = ctx.deployment, ctx.traffic
    k = int(mix["pool_tables"])
    windows = int(mix["windows_per_table"])
    control = _control(ctx)
    with ctx.spans.span("pool_build"):
        pool = []
        for b in range(k + 1):  # the last one is the table set-up never warms
            t = dep.gen.table(dep.fleet, windows, [ctx.seed, b], dep.layout)
            x0, y0, x1, y1 = dep.fleet["box"]
            centre = {"float32_frame": np.array([(x0 + x1) / 2, (y0 + y1) / 2])}
            xy = t["xy"]
            if control:
                c = centre.get(control, 0.0)
                xy = c + (xy - c).astype(np.float32).astype(np.float64)
            t["col"] = dep.pack(xy, t["offsets"])
            pool.append(t)
    with ctx.spans.span("call_warmup"):
        t = pool[0]
        # (a rehearsal compiles what its tiny tables launch and no more)
        if not ctx.rehearsal:
            warmup_dwithin(
                t["col"], radius=t["radius"], key=t["window"],
                index_system=dep.grid, resolution=dep.res,
            )
        for t in pool[:k]:
            _join(ctx, t)
        met = _compiles_met()
        unseen = _join(ctx, pool[k])
        met = _compiles_met() - met
    ctx.counters["first_call_compiles"] = met
    ctx.say(
        "s2s_ready", pool=[len(t["col"]) for t in pool[:k]],
        vertices=[int(t["xy"].shape[0]) for t in pool[:k]],
        planted=[int(t["planted"].shape[0]) for t in pool[:k]],
        first_call_compiles=met, unseen=unseen.metrics, control=control,
        pool_build_s=round(ctx.spans.seconds("pool_build"), 3),
        call_warmup_s=round(ctx.spans.seconds("call_warmup"), 3),
    )
    return {"pool": pool[:k], "k": k, "first": [], "unlike": 0, "metrics": []}


def _unlike(a, b) -> int:
    """Pairs two answers do not share."""
    import numpy as np

    if a.shape == b.shape and np.array_equal(a, b):
        return 0
    both = np.concatenate([a, b])
    return int((np.unique(both, axis=0, return_counts=True)[1] == 1).sum())


def window(ctx, st) -> dict:
    pool, k, first = st["pool"], st["k"], st["first"]
    calls = 0
    traced, walls = [], []
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
        with ctx.spans.span("s2s.call"):
            result = _join(ctx, pool[b])
        if ctx.tracer.active:
            traced.append(result.metrics)
        st["metrics"].append(dict(result.metrics, degraded=result.degraded))
        if calls < k:
            first.append(result.pairs)
        else:  # the client reads its answer ...
            st["unlike"] += _unlike(result.pairs, first[b])
        # ... then lets it go before the next call: a pair table that
        # outlives the following call changes what the allocator hands
        # that call (PERF.md section 6, PR 39)
        del result
        calls += 1
        t = time.perf_counter()
        if not ctx.tracer.active:
            walls.append(t - t_call)
    t1 = t
    ctx.tracer.stop()
    ctx.window = (unprofiled_from, time.monotonic())
    ctx.series["call_s"] = walls
    ctx.series["traced_calls"] = traced
    tracks = len(pool[0]["col"])
    rows = calls * tracks
    m = st["metrics"]
    degraded = sum(bool(x["degraded"]) for x in m)
    ctx.counters.update(
        rows=rows, calls=calls, window_s=t1 - t0, traced_steps=len(traced),
        rows_per_step_per_chip=tracks,
    )

    def first_pass(name):
        return [x.get(name) for x in m[:k]]

    ctx.say(
        "s2s_window", calls=calls, rows=rows, window_s=round(t1 - t0, 4),
        **{name: first_pass(name) for name in (
            "segments", "cover_rows", "tessellated", "raw_candidates",
            "candidate_pairs", "rows_per_pair", "launches", "bucket", "pairs",
            "hits",
            "band_pairs", "acc")},
        degraded_calls=degraded, unlike_first_pass=st["unlike"],
        call_s=[round(w, 4) for w in walls],
    )
    if ctx.trace:
        from benchmark.harness.stats import percentile

        lo, hi = ctx.window
        by: dict = {}
        for e in ctx.events:
            name = e.get("name", "")
            if e.get("event") == "span" and name.startswith("proximity.") \
                    and lo <= e.get("ts_mono", lo) <= hi:
                by.setdefault((e.get("parent_id") if name != "proximity.call"
                               else e.get("span_id"), name), []).append(
                    float(e.get("seconds", 0.0)))
        per_call: dict = {}
        for (_call, name), secs in by.items():
            per_call.setdefault(name, []).append(sum(secs))
        ctx.say("s2s_breakdown_ms", **{
            name.split(".", 1)[1]: round(1000 * percentile(v, 0.5), 2)
            for name, v in sorted(per_call.items())
        })
    return {
        "attempted": rows,
        "failed": degraded * tracks,
        "metrics": {"batch_rows_per_s": rows / (t1 - t0) / ctx.chips},
    }


def check(ctx, st) -> list:
    import numpy as np

    from benchmark.harness.check import Comparison

    dep, limits = ctx.deployment, ctx.cell["check"]
    V = dep.vessels
    rng = np.random.default_rng(ctx.seed)
    missing = spurious = planted_missing = unsure = wanted = 0
    t0 = time.perf_counter()

    def words(pairs):
        return pairs[:, 0] * np.int64(1 << 32) + pairs[:, 1]

    for b, got in enumerate(st["first"]):
        t = st["pool"][b]
        got = got[got[:, 0] >= 0]  # (an OVERFLOW row is counted below)
        got_w = words(got)
        windows = len(t["col"]) // V
        for w in rng.choice(windows, min(int(limits["sample_windows"]), windows),
                            replace=False):
            rows = w * V + np.arange(V)
            # the truth is the reference on the table as it was made: a
            # control's rounding is the join's to survive, not the truth's
            want, near = dep.reference.within(
                t["xy"], t["offsets"], t["radius"], rows=rows,
                rel_tol=float(limits["rel_tol"]),
            )
            mine = got_w[(got[:, 0] >= rows[0]) & (got[:, 0] <= rows[-1])]
            want_w, near_w = words(want), words(near)
            miss = np.setdiff1d(np.setdiff1d(want_w, mine), near_w)
            extra = np.setdiff1d(np.setdiff1d(mine, want_w), near_w)
            missing += miss.size
            spurious += extra.size
            unsure += near_w.size
            wanted += want_w.size
            for word in np.concatenate([miss[:3], extra[:3]]):
                a_, b_ = int(word >> 32), int(word & ((1 << 32) - 1))
                d = dep.reference.distances(t["xy"], t["offsets"], [a_], [b_])[0]
                ctx.say("wrong_pair", table=b, window=int(w), a=a_, b=b_,
                        missing=bool(word in miss), distance=repr(float(d)),
                        threshold=repr(float(t["radius"][a_] + t["radius"][b_])))
        p = t["planted"]
        planted = np.stack([p[:, 0] * V + p[:, 1], p[:, 0] * V + p[:, 2]], axis=1)
        planted_missing += int((~np.isin(words(planted), got_w)).sum())
    ctx.say(
        "reference", tables=len(st["first"]), pairs=wanted,
        within_rel_tol=unsure, seconds=round(time.perf_counter() - t0, 3),
    )
    m = st["metrics"]
    return [
        Comparison(
            "s2s_pairs_missing", missing, 0,
            "pairs of the sampled windows the plain reference holds (f64 "
            "distance <= r_a + r_b over all the window's tracks) and the "
            "answer lacks; a pair within rel_tol of its threshold is neither",
        ),
        Comparison(
            "s2s_pairs_spurious", spurious, 0,
            "pairs of the sampled windows the answer holds and the plain "
            "reference does not",
        ),
        Comparison(
            "s2s_planted_pairs_missing", planted_missing, 0,
            "planted transfers (a window in which the pair lies under 100 m "
            "apart from first ping to last) the answer lacks, over the "
            "whole of each table",
        ),
        Comparison(
            "s2s_overflow_rows", sum(int(x.get("overflow", 0)) for x in m), 0,
            "candidate rows a cap cut: the default call has no cap",
        ),
        Comparison(
            "s2s_answers_unlike_first_pass", st["unlike"], 0,
            "the loop is deterministic: every later answer on a table "
            "equals the first pass's pair for pair",
        ),
    ]


def close(ctx, st) -> None:
    st.clear()
