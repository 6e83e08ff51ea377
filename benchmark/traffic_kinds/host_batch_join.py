"""Traffic kind ``host_batch_join``: one client in a closed loop that hands
`mosaic_tpu.sql.join.pip_join` one host batch after another — float64
``numpy`` points in, ``int32`` zone rows out, the answer pulled to the host
by the call itself — until the window has passed. It is the call a notebook
user makes, or a job that joins one partition of points at a time: the host
feeds every row and waits for every answer.

Parameters (the mix's data file): ``pool_batches`` (distinct batches, made
by the point generator on the device from ``--seed``, pulled to host memory
and cycled), ``points`` (the generator's parameters), ``arguments`` (the
keyword arguments of `pip_join` beyond the index; ``{}`` is the package's
defaults), ``control`` (what the lower-precision control changes, see
`_control`). Batch rows come from the configuration
(``batch_rows_per_chip``).

Set-up calls `pip_join` once on every batch of the pool: the call sizes its
caps from each batch's own counts (rounded up to a power of two), so a
batch the warm-up has not seen could compile inside the window.

``pool_banded_batches`` (optional; with it ``pool_max_draws``, 16 where it is
not given) makes every seed's pool the same work where ``recheck=True``
does a fixed piece of work for a batch as a whole: a batch that holds even
one row in the cell band (the program's `recheck_narrow` event, ``band``)
pays a compaction, alternate cells, counts and a re-join, a batch that holds
none pays nothing, and a seed's first ``pool_batches`` draws hold none to
all of them by chance. With the parameter set-up draws one batch
after another — draw ``i`` is slot ``i`` of the one-call pool, so the first
draws ARE the pool a mix without the parameter gets — warms each with its
one call as before, reads the event that call recorded, and keeps the first
``pool_banded_batches`` banded and the first ``pool_batches -
pool_banded_batches`` unbanded batches, in the order drawn. After
``pool_max_draws`` draws it keeps whatever comes and says so
(``pool_composed=False``); a call that records no such event (no recheck:
the control) keeps the first draws.

End-to-end: ``batch_rows_per_s`` — rows answered in the window over the
window's seconds, host clock from before the first call to after the last
one's answer has arrived and been read, divided by the cell's chips. The
client reads every answer once: each call's answer after the first pass
over the pool is compared, inside the window, with the first pass's answer
on the same batch, and dropped before the next call is made (keeping every
16 MB answer made later calls slower: PERF.md section 6, PR 26; keeping
only the last one through the next call did too: PR 39).

Correct: the first pass's answers are kept. Once the window has closed each
batch of the pool is joined once more and every timed answer on that batch
must equal it row for row; the first pass's answers — a seeded sample of
them, or every row where the cell's ``sample_rows`` reaches the pool — are
compared with the plain reference. Where the cell's check gives
``edge_within_deg``, the rows unlike the reference that lie within that
distance of a zone's edge (the reference's own geometry) are counted apart:
they are what an edge test in lower precision gets wrong, and what
``recheck=True`` repairs. A `DegradedResult` or a row equal to ``OVERFLOW``
counts in ``failed``.
"""

from __future__ import annotations

import time

OVERFLOW = -2
#: the calls a ``--trace 1`` run profiles: one pass over a pool of four,
#: after the first call (constants until a mix needs another value)
TRACE_FROM_CALL, TRACE_CALLS = 1, 4


def _control(ctx) -> dict:
    """What the lower-precision control changes, from the mix's ``control``
    group: ``arguments`` (these replace the mix's: the program's own lower
    path switched on) or ``cell_dtype`` (cell assignment in that dtype,
    through an argument `pip_join` already takes). Never on in a benchmark
    run."""
    if not ctx.control:
        return {}
    return dict(ctx.traffic["control"])


def _arguments(ctx) -> dict:
    import jax.numpy as jnp

    control = _control(ctx)
    args = dict(control.get("arguments", ctx.traffic.get("arguments", {})))
    if control.get("cell_dtype"):
        args["cell_dtype"] = jnp.dtype(control["cell_dtype"])
    return args


def _join(ctx, args: dict, batch):
    from mosaic_tpu.sql import join

    dep = ctx.deployment
    return join.pip_join(
        batch, None, dep.grid, dep.res, chip_index=dep.index, **args
    )


class _BandWatch:
    """Observer of the program's `recheck_narrow` events: the cell-band
    rows of the calls made since `reset` (None where none was recorded)."""

    def __init__(self):
        self.band = None

    def reset(self) -> None:
        self.band = None

    def __call__(self, evt: dict) -> None:
        if evt.get("event") == "recheck_narrow":
            self.band = (self.band or 0) + int(evt.get("band", 0))


def compose_pool(draw, k: int, banded: int, max_draws: int):
    """Keep the first ``banded`` banded and the first ``k - banded``
    unbanded of the batches ``draw(i)`` gives, ``i`` = 0, 1, …, in the
    order drawn. ``draw(i)`` returns ``(batch, band)``: the batch, warmed,
    and the cell-band rows its call recorded (None: no event, which keeps
    the batch whatever it is). After ``max_draws`` draws every batch is
    kept. Returns ``(batches, bands, draws, composed)``."""
    room = {True: banded, False: k - banded}
    kept, bands, draws = [], [], 0
    while len(kept) < k:
        batch, band = draw(draws)
        draws += 1
        cls = None if band is None else band > 0
        if cls is not None and room[cls] > 0:
            room[cls] -= 1
        elif cls is not None and draws <= max_draws:
            continue  # its class is full: draw again
        kept.append(batch)
        bands.append(band)
    composed = all(v == 0 for v in room.values())
    return kept, bands, draws, composed


def prepare(ctx) -> dict:
    import numpy as np

    dep, mix = ctx.deployment, ctx.traffic
    points = ctx.spec.module("generators", "points")
    k = int(mix["pool_batches"])
    args, control = _arguments(ctx), _control(ctx)
    note = {}
    if "pool_banded_batches" not in mix:
        with ctx.spans.span("pool_build"):
            gen = points.make_generator(
                mix["points"], dep.bbox, dep.batch, slots=k
            )
            on_device = gen(points.seed_key(ctx.seed))
            pool = np.asarray(on_device)  # (k, batch, 2) float64, host memory
            del on_device
        with ctx.spans.span("call_warmup"):
            for b in range(k):
                _join(ctx, args, pool[b])
    else:
        import jax

        from mosaic_tpu.runtime import telemetry

        gen = points.make_generator(mix["points"], dep.bbox, dep.batch)
        key, watch = points.seed_key(ctx.seed), _BandWatch()

        def draw(i):
            with ctx.spans.span("pool_build"):
                batch = np.asarray(gen(jax.random.fold_in(key, i)))
            watch.reset()
            with ctx.spans.span("call_warmup"):
                _join(ctx, args, batch)
            return batch, watch.band

        telemetry.add_observer(watch)
        try:
            kept, bands, draws, composed = compose_pool(
                draw, k, int(mix["pool_banded_batches"]),
                int(mix.get("pool_max_draws", 16)),
            )
        finally:
            telemetry.remove_observer(watch)
        with ctx.spans.span("pool_build"):
            pool = np.stack(kept)  # (k, batch, 2) float64, host memory
            del kept
        note = {"pool_draws": draws, "pool_band_rows": bands,
                "pool_composed": composed}
    ctx.say(
        "batch_ready", pool=tuple(pool.shape), dtype=str(pool.dtype),
        arguments={k_: str(v) for k_, v in args.items()},
        control=control,
        pool_build_s=round(ctx.spans.seconds("pool_build"), 3),
        call_warmup_s=round(ctx.spans.seconds("call_warmup"), 3),
        **note,
    )
    # first: the first pass's answers, one a pool batch; odd: (batch,
    # answer) of every later call whose answer was unlike the first pass's
    return {"pool": pool, "k": k, "args": args,
            "first": [], "odd": [], "unlike": 0}


def window(ctx, st) -> dict:
    import numpy as np

    pool, k, args = st["pool"], st["k"], st["args"]
    first, odd = st["first"], st["odd"]
    calls = traced = 0
    walls = []  # seconds of each call made with the profiler off
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
        traced += ctx.tracer.active
        b = calls % k
        t_call = time.perf_counter()
        with ctx.spans.span("batch.call"):
            answer = _join(ctx, args, pool[b])
        if calls < k:
            first.append(answer)
        else:  # the client reads its answer, then lets it go
            differ = int(np.count_nonzero(answer != first[b]))
            if differ or getattr(answer, "degraded", False):
                odd.append((b, answer))
                st["unlike"] += differ
        # let go BEFORE the next call: an answer still bound here through
        # the next call put the allocator into a cycle over the pool, one
        # call in four or two in four 35 ms longer (PERF.md section 6, PR 39)
        del answer
        calls += 1
        t = time.perf_counter()
        if not ctx.tracer.active:
            walls.append(t - t_call)
    t1 = t
    ctx.tracer.stop()
    # the readers of the program's events and of ``call_s`` see the calls
    # made after the profiler stopped (it lengthens a host-fed call): all
    # of them in an untraced run, or where the window closed first
    ctx.window = (unprofiled_from, time.monotonic())
    ctx.series["call_s"] = walls
    # every answer the window saw, with how often: a later answer equal to
    # the first pass's counts as the first pass's
    seen = [
        (a, calls // k + (b < calls % k) - sum(ob == b for ob, _ in odd))
        for b, a in enumerate(first)
    ] + [(a, 1) for _, a in odd]
    rows = sum(a.shape[0] * n for a, n in seen)
    overflow = sum(int((a == OVERFLOW).sum()) * n for a, n in seen)
    degraded = sum(
        a.shape[0] * n for a, n in seen if getattr(a, "degraded", False)
    )
    ctx.counters.update(
        rows=rows, calls=calls, window_s=t1 - t0,
        matches=sum(int((a >= 0).sum()) * n for a, n in seen),
        overflow=overflow, traced_steps=traced,
        rows_per_step_per_chip=ctx.deployment.batch,
    )
    ctx.say(
        "batch_window", calls=calls, rows=rows,
        window_s=round(t1 - t0, 4), overflow=overflow, degraded_rows=degraded,
        match_share=round(ctx.counters["matches"] / max(rows, 1), 4),
        unlike_first_pass=st["unlike"],
        call_s=[round(w, 4) for w in walls],
    )
    return {
        "attempted": rows,
        "failed": overflow + degraded,
        "metrics": {"batch_rows_per_s": rows / (t1 - t0) / ctx.chips},
    }


def _edge_distance_deg(rings, point) -> float:
    """Distance in degrees (longitude scaled by cos latitude) from
    ``point`` to the nearest edge of any zone ring: plain geometry on the
    rings themselves, nothing of the program."""
    import numpy as np

    a = np.concatenate([np.asarray(r, dtype=np.float64) for r in rings])
    b = np.concatenate(
        [np.roll(np.asarray(r, dtype=np.float64), -1, axis=0) for r in rings]
    )
    scale = np.array([np.cos(np.radians(point[1])), 1.0])
    a, b = (a - point) * scale, (b - point) * scale
    d = b - a
    t = np.clip(-(a * d).sum(1) / np.maximum((d * d).sum(1), 1e-300), 0, 1)
    return float(np.hypot(*(a + t[:, None] * d).T).min())


def check(ctx, st) -> list:
    import numpy as np

    from benchmark.harness.check import Comparison, disagreement

    pool, args = st["pool"], st["args"]
    first, rings = st["first"], ctx.deployment.rings
    limits = ctx.cell["check"]
    called = range(len(first))  # the pool batches the window joined
    unlike = st["unlike"]
    with ctx.spans.span("check.repeat_calls"):
        for b in called:
            again = np.asarray(_join(ctx, args, pool[b]))
            unlike += int(np.count_nonzero(np.asarray(first[b]) != again))
    out = [Comparison(
        "batch_rows_unlike_repeat", unlike, 0,
        "the loop is deterministic: every timed answer on a batch equals, "
        "row for row, one more call's on the same batch",
    )]
    # the first pass's answers against the plain reference: a seeded
    # sample of each batch, or every row where sample_rows reaches the pool
    per_batch = min(
        max(int(limits["sample_rows"]) // len(called), 1), pool.shape[1]
    )
    rng = np.random.default_rng(ctx.seed)
    got, pts = [], []
    for b in called:
        idx = slice(None)
        if per_batch < pool.shape[1]:
            idx = np.sort(rng.choice(pool.shape[1], per_batch, replace=False))
        got.append(np.asarray(first[b])[idx])
        pts.append(pool[b][idx])
    t0 = time.perf_counter()
    want = [ctx.deployment.reference.answers(rings, p) for p in pts]
    got, pts, want = (np.concatenate(v) for v in (got, pts, want))
    ctx.say(
        "reference", rows=len(pts), batches=len(called),
        seconds=round(time.perf_counter() - t0, 3),
        matched_share=round(float((want >= 0).mean()), 4),
    )
    differ = np.nonzero(got != want)[0]
    edge_deg = [_edge_distance_deg(rings, pts[j]) for j in differ]
    for j, d in list(zip(differ, edge_deg))[:24]:
        ctx.say("disagreeing_row", x=repr(float(pts[j, 0])),
                y=repr(float(pts[j, 1])), got=int(got[j]), want=int(want[j]),
                zone_edge_deg=f"{d:.3e}")
    out.append(Comparison(
        "batch_disagreement_share", disagreement(got, want),
        limits["max_disagreement"],
        "share of the compared timed rows that differ from the plain f64 "
        "reference (the limit and its reason: the cell's workloads file)",
    ))
    if "edge_within_deg" in limits:
        out.append(Comparison(
            "batch_edge_rows_unlike_reference",
            sum(d < float(limits["edge_within_deg"]) for d in edge_deg),
            limits["max_edge_rows"],
            "compared timed rows that differ from the plain f64 reference "
            "and lie within edge_within_deg of a zone's edge: what a "
            "lower-precision edge test gets wrong",
        ))
    return out


def close(ctx, st) -> None:
    st.clear()
