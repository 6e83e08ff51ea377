"""Traffic kind ``open_loop_requests``: independent clients sending small
lookups to a `ServeEngine` on a Poisson clock at one fixed rate. One
generator thread (this one) submits on the schedule and never waits on a
completion.

Parameters (the mix's data file): ``rate_per_s``; ``size_rows`` (lognormal
``median``, ``sigma``, clipped to ``min``..``max``); ``pool_rows`` and
``points`` (the host array the requests slice, made by the point
generator); ``schedule_seed``; ``freeze_gc_after_setup`` (the start-up heap is frozen
after warm-up, as a long-running server does; the collector's pauses inside
the window are counted either way). The engine is built with the package's
defaults but for the arguments in the configuration's ``serve_engine`` group.
The multiset of request sizes and of
inter-arrival gaps is fixed by ``schedule_seed`` and only permuted by
``--seed``, so every seed offers the same work in another order; which
rows a request carries comes from ``--seed``.

End-to-end: ``latency_p50_ms``, ``latency_p95_ms`` — from the instant a
request was DUE on the schedule to its future resolving, on this side's
clock; a shed or failed request has no latency and counts in ``failed``.
How late each request left the generator is kept beside it
(``gen_lag_s``).

Correct: answers are kept and compared after the window — a seeded sample
of finished requests with the longest among them against the plain
reference, and against default `pip_join` on the same rows (equal exactly:
padding and batching change nothing).
"""

from __future__ import annotations

import gc
import math
import time


def schedule(mix: dict, seed: int, seconds: float):
    """(due_s, size_rows, start_row) per request; ``due_s`` from 0."""
    import numpy as np

    n = max(int(round(float(mix["rate_per_s"]) * seconds)), 1)
    base = np.random.default_rng(int(mix["schedule_seed"]))
    sz = mix["size_rows"]
    sizes = np.clip(
        np.rint(base.lognormal(math.log(sz["median"]), sz["sigma"], n)),
        sz["min"], sz["max"],
    ).astype(np.int64)
    gaps = base.exponential(1.0, n)
    gaps *= seconds / gaps.sum()  # the schedule spans the window exactly
    rng = np.random.default_rng(int(seed))
    sizes = sizes[rng.permutation(n)]
    gaps = gaps[rng.permutation(n)]
    due = np.cumsum(gaps) - gaps[0]
    starts = rng.integers(0, int(mix["pool_rows"]) - int(sz["max"]), n)
    return due, sizes, starts


def _lower_precision(points):
    """The control: coordinates rounded to bfloat16 before `submit`."""
    import jax.numpy as jnp
    import numpy as np

    return np.asarray(
        jnp.asarray(points, jnp.float32).astype(jnp.bfloat16), np.float64
    )


def prepare(ctx) -> dict:
    import numpy as np

    from mosaic_tpu.serve import ServeEngine

    dep, mix = ctx.deployment, ctx.traffic
    points = ctx.spec.module("generators", "points")
    with ctx.spans.span("pool_build"):
        gen = points.make_generator(
            mix["points"], dep.bbox, int(mix["pool_rows"])
        )
        pool = np.asarray(gen(points.seed_key(ctx.seed)))
    sent = _lower_precision(pool) if ctx.control else pool
    due, sizes, starts = schedule(mix, ctx.seed, ctx.seconds)
    # package defaults (ladder, max_wait_s; no ``bounds``: a point outside
    # the box is a miss, not a quarantined row) but for what the
    # configuration's ``serve_engine`` group sets: the queue and the deadline
    # of a deployment that rides out a host stall instead of shedding
    engine_args = {
        k: v for k, v in ctx.config.get("serve_engine", {}).items()
        if not k.startswith("why")
    }
    engine = ServeEngine(dep.index, dep.grid, dep.res, **engine_args)
    try:
        with ctx.spans.span("engine_warmup"):
            warm = engine.warmup()
        # a few live round trips, so the batcher's first dispatch of the
        # window is not its first ever
        for i in range(min(8, len(sizes))):
            s, n = int(starts[i]), int(sizes[i])
            engine.submit(sent[s:s + n]).result(timeout=120)
    except BaseException:
        engine.close()
        raise
    if mix.get("freeze_gc_after_setup"):
        gc.collect()
        gc.freeze()
    ctx.say(
        "serve_ready", requests=len(due), rate_per_s=mix["rate_per_s"],
        rows=int(sizes.sum()), rows_max=int(sizes.max()),
        buckets=warm["buckets"], signatures=warm["signatures"],
        engine_args=engine_args,
        engine_warmup_s=round(ctx.spans.seconds("engine_warmup"), 3),
    )
    return {"engine": engine, "pool": pool, "sent": sent, "due": due,
            "sizes": sizes, "starts": starts, "futures": [], "done": []}


def window(ctx, st) -> dict:
    from benchmark.harness.stats import percentile

    engine, sent = st["engine"], st["sent"]
    due, sizes, starts = st["due"], st["sizes"], st["starts"]
    n_req = len(due)
    futures: list = [None] * n_req
    done = [None] * n_req
    lag = [0.0] * n_req
    refused = 0
    # a traced run profiles the LAST ``trace_last_seconds`` of the window
    # and stops the profiler only after the drain: starting it stalls this
    # thread for a moment and stopping it for seconds, and nothing may be
    # due meanwhile. Its host-clock per-layer metrics come from the part
    # of the window before the profiler started.
    trace_at = ctx.seconds - float(ctx.traffic.get("trace_last_seconds", 3.0))
    untraced_until = max(trace_at - 0.25, 0.0) if ctx.trace else ctx.seconds
    clock = time.perf_counter
    m0 = engine.metrics()
    pauses: list = []  # (start_s from t0, seconds, generation)

    def on_gc(phase, info):
        if phase == "start":
            pauses.append([clock(), None, info["generation"]])
        elif pauses and pauses[-1][1] is None:
            pauses[-1][1] = clock() - pauses[-1][0]

    gc.callbacks.append(on_gc)

    def stamp(i):
        def cb(_f):
            done[i] = clock()
        return cb

    mono0 = time.monotonic()
    t0 = clock() + 0.005
    for i in range(n_req):
        at = t0 + due[i]
        if ctx.trace and not ctx.tracer.active and due[i] >= trace_at:
            ctx.tracer.start()
        with ctx.spans.span("generator.sleep"):
            while True:
                d = at - clock()
                if d <= 0:
                    break
                time.sleep(d)
        lag[i] = clock() - at
        s, n = int(starts[i]), int(sizes[i])
        with ctx.spans.span("engine.submit"):
            try:
                f = engine.submit(sent[s:s + n])
            except Exception as e:  # noqa: BLE001 — Overloaded at admission
                refused += 1
                futures[i] = e
                continue
        f.add_done_callback(stamp(i))
        futures[i] = f
    # drain: every request resolves (answer, shed or error) within its
    # deadline; the generator never waited on one before this line
    with ctx.spans.span("drain"):
        for f in futures:
            if hasattr(f, "exception"):
                try:
                    f.exception(timeout=30)
                except Exception:  # noqa: BLE001 — counted below
                    pass
    t1 = clock()
    gc.callbacks.remove(on_gc)
    ctx.tracer.stop()
    pauses = [(a - t0, b, g) for a, b, g in pauses if b is not None]
    ctx.counters["gc_pause_s"] = sum(b for _a, b, _g in pauses)
    ctx.say("gc", collections=len(pauses),
            pause_s=round(ctx.counters["gc_pause_s"], 4),
            longest=[f"{b * 1e3:.0f}ms@{a:.2f}s/gen{g}" for a, b, g in
                     sorted(pauses, key=lambda p: -p[1])[:4]])
    ctx.window = (
        mono0, mono0 + untraced_until if ctx.trace else time.monotonic()
    )
    lat, ok, shed, errored, degraded = [], [], 0, 0, 0
    for i, f in enumerate(futures):
        if not hasattr(f, "exception") or not f.done():
            shed += 1
            continue
        exc = f.exception()
        if exc is not None:
            if type(exc).__name__ == "Overloaded":
                shed += 1
            else:
                errored += 1
            continue
        if getattr(f.result(), "degraded", False):
            degraded += 1
            continue
        ok.append(i)
        lat.append((done[i] - (t0 + due[i])) * 1000.0)
    if not lat:
        raise RuntimeError("no request finished inside the window")
    m1 = engine.metrics()
    batches = m1["batches"] - m0["batches"]
    ctx.counters.update(
        requests=n_req, finished=len(ok), shed=shed, errored=errored,
        degraded=degraded, refused_at_admission=refused,
        rows=int(sizes.sum()), batches=batches, window_s=t1 - t0,
        occupancy_mean=(
            (m1["occupancy_sum"] - m0["occupancy_sum"]) / batches
            if batches else None
        ),
        requests_per_batch=(
            (m1["batched_requests"] - m0["batched_requests"]) / batches
            if batches else None
        ),
    )
    ctx.series["gen_lag_s"] = [
        x for x, d in zip(lag, due) if d < untraced_until
    ]
    ctx.series["latency_ms"] = [
        x for i, x in zip(ok, lat) if due[i] < untraced_until
    ]
    half = n_req // 2
    first = [x for i, x in zip(ok, lat) if i < half]
    second = [x for i, x in zip(ok, lat) if i >= half]
    ctx.counters["p95_first_half_ms"] = (
        percentile(first, 0.95) if first else None
    )
    ctx.counters["p95_second_half_ms"] = (
        percentile(second, 0.95) if second else None
    )
    ctx.say(
        "serve_window", requests=n_req, latency_samples=len(lat), shed=shed,
        refused_at_admission=refused, errored=errored, degraded=degraded, batches=batches,
        window_s=round(t1 - t0, 4),
        p50_ms=round(percentile(lat, 0.5), 4),
        p95_ms=round(percentile(lat, 0.95), 4),
        p99_ms=round(percentile(lat, 0.99), 4),
        p95_first_half_ms=ctx.counters["p95_first_half_ms"],
        p95_second_half_ms=ctx.counters["p95_second_half_ms"],
        gen_lag_p95_ms=round(percentile(lag, 0.95) * 1000.0, 4),
    )
    slow = sorted(zip(lat, ok), reverse=True)[:6]
    ctx.say("serve_slowest", **{
        f"r{i}": f"{ms:.1f}ms@{due[i]:.2f}s/{int(sizes[i])}rows"
        for ms, i in slow
    }, over_50ms=sum(x > 50.0 for x in lat), rows_max=int(sizes.max()))
    # where a stall sat: the slowest stages of the engine's own record
    # (a run without a trace keeps only those over 50 ms)
    slow_d = sorted(
        (e for e in ctx.events if e.get("event") == "serve_stage"
         and e.get("stage") in ("dispatch", "batch")
         and e.get("ts_mono", 0) >= mono0),
        key=lambda e: -e["seconds"],
    )[:6]
    ctx.say("serve_slowest_stages", **{
        f"d{j}": f"{e['stage']}:{e['seconds'] * 1e3:.1f}ms"
                 f"@{e['ts_mono'] - mono0:.2f}s"
                 f"/bucket{e.get('bucket')}/{e.get('rows')}rows"
        for j, e in enumerate(slow_d)
    })
    st["futures"], st["ok"] = futures, ok
    return {
        "attempted": n_req,
        "failed": shed + errored + degraded,
        "metrics": {
            "latency_p50_ms": percentile(lat, 0.5),
            "latency_p95_ms": percentile(lat, 0.95),
        },
    }


def check(ctx, st) -> list:
    import numpy as np

    from benchmark.harness.check import Comparison, disagreement
    from mosaic_tpu.sql.join import pip_join

    dep = ctx.deployment
    futures, ok = st["futures"], st["ok"]
    sizes, starts = st["sizes"], st["starts"]
    limits = ctx.config["guarantees"]
    # a seeded sample of the finished requests, the longest among them
    rng = np.random.default_rng(ctx.seed)
    order = [int(ok[j]) for j in rng.permutation(len(ok))]
    longest = max(ok, key=lambda i: sizes[i])
    picked, rows = [longest], int(sizes[longest])
    budget = int(ctx.cell["check"]["sample_rows"])
    for i in order:
        if rows >= budget:
            break
        if i != longest:
            picked.append(i)
            rows += int(sizes[i])
    got = np.concatenate([np.asarray(futures[i].result()) for i in picked])
    sl = [slice(int(starts[i]), int(starts[i]) + int(sizes[i])) for i in picked]
    asked = np.concatenate([st["pool"][s] for s in sl])
    sent = np.concatenate([st["sent"][s] for s in sl])
    t0 = time.perf_counter()
    want = dep.reference.answers(dep.rings, asked)
    ctx.say(
        "reference", requests=len(picked), rows=len(asked),
        longest_rows=int(sizes[longest]),
        seconds=round(time.perf_counter() - t0, 3),
        matched_share=round(float((want >= 0).mean()), 4),
    )
    # the batch join sees a fixed number of rows (the budget), so that its
    # program is compiled once and found in the cache by every later run
    n_fixed = min(budget, len(sent))
    batch = pip_join(
        sent[:n_fixed], None, dep.grid, dep.res, chip_index=dep.index
    )
    return [
        Comparison(
            "serve_disagreement_share", disagreement(got, want),
            limits["serve_max_disagreement"],
            "share of sampled served rows that differ from the plain f64 "
            "reference; the default probe is f32 on recentred coordinates",
        ),
        Comparison(
            "serve_rows_unlike_batch_join",
            int((got[:n_fixed] != np.asarray(batch)).sum())
            + int(bool(getattr(batch, "degraded", False))),
            0,
            "a served answer equals default pip_join on the same rows: "
            "padding and batching change nothing",
        ),
    ]


def close(ctx, st) -> None:
    engine = st.get("engine")
    if engine is not None:
        engine.close()
    if ctx.traffic.get("freeze_gc_after_setup"):
        gc.unfreeze()
    st.clear()
