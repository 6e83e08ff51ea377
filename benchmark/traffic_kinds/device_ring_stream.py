"""Traffic kind ``device_ring_stream``: a ring of point batches generated
on the device from the seed, joined by `StreamJoin.run` back to back until
the window has passed. The host feeds nothing and pulls one fold per
dispatch.

Parameters (the mix's data file): ``ring_slots``, ``steps_per_dispatch``,
``points`` (the point generator's parameters). Batch rows per chip and the
mesh come from the configuration.

End-to-end: ``rows_per_s`` — rows answered in the window over the window's
seconds (host clock around dispatches that end in a host pull of the
fold), divided by the cell's chips.

Correct: once the window has closed the same entry point runs the same
ring and step count once more with ``collect=True``; its fold must equal
every timed dispatch's fold exactly, and a seeded sample of its rows is
compared with the plain reference. OVERFLOW rows and degraded runs count
in ``failed``.
"""

from __future__ import annotations

import time

OVERFLOW = -2


def _control_kwargs(ctx) -> dict:
    """The lower-precision control: cell assignment in bfloat16, through
    an argument `StreamJoin` already takes. Never on in a benchmark run."""
    if not ctx.control:
        return {}
    import jax.numpy as jnp

    return {"cell_dtype": jnp.bfloat16}


def prepare(ctx) -> dict:
    from jax.sharding import NamedSharding, PartitionSpec as P

    from mosaic_tpu.sql.stream import StreamJoin

    dep, mix = ctx.deployment, ctx.traffic
    points = ctx.spec.module("generators", "points")
    k, nb = int(mix["ring_slots"]), int(mix["steps_per_dispatch"])
    chips = dep.mesh or 1
    batch = dep.batch * chips
    sj = StreamJoin(
        dep.index, dep.grid, dep.res, mesh=dep.mesh, **_control_kwargs(ctx)
    )
    sharding = None
    if sj.mesh is not None:
        # each chip's own partition of every slot, as it would arrive
        sharding = NamedSharding(sj.mesh, P(None, sj.mesh.axis_names, None))
    gen = points.make_generator(
        mix["points"], dep.bbox, batch, slots=k, out_sharding=sharding
    )
    with ctx.spans.span("ring_build"):
        ring = gen(points.seed_key(ctx.seed))
        ring.block_until_ready()
    if sharding is not None:
        shards = ring.addressable_shards
        if not (
            ring.sharding.is_equivalent_to(sharding, ring.ndim)
            and len({s.device for s in shards}) == chips
            and all(s.data.shape == (k, dep.batch, 2) for s in shards)
        ):
            raise RuntimeError(
                f"ring is not sharded over its batch axis on the stream's "
                f"mesh: {ring.sharding}"
            )
    with ctx.spans.span("loop_warmup"):
        sj.compile(ring, nb)
    ctx.say(
        "stream_ready", ring=tuple(ring.shape), steps_per_dispatch=nb,
        probe=sj.probe, mesh=None if sj.mesh is None else dict(sj.mesh.shape),
        ring_build_s=round(ctx.spans.seconds("ring_build"), 3),
        loop_warmup_s=round(ctx.spans.seconds("loop_warmup"), 3),
    )
    return {"sj": sj, "ring": ring, "nb": nb, "k": k, "batch": batch,
            "results": []}


def window(ctx, st) -> dict:
    sj, ring, nb = st["sj"], st["ring"], st["nb"]
    trace_from = int(ctx.traffic.get("trace_from_dispatch", 1))
    trace_dispatches = int(ctx.traffic.get("trace_dispatches", 2))
    results = st["results"]
    traced = 0
    m0 = time.monotonic()
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < ctx.seconds:
        i = len(results)
        if i == trace_from:
            ctx.tracer.start()
        elif i == trace_from + trace_dispatches:
            ctx.tracer.stop()
        traced += ctx.tracer.active
        with ctx.spans.span("stream.run"):
            results.append(sj.run(ring, nb))
    t1 = time.perf_counter()
    ctx.tracer.stop()
    ctx.window = (m0, time.monotonic())
    rows = sum(r.n_points for r in results)
    overflow = sum(r.overflow for r in results)
    degraded = sum(
        r.n_points for r in results if r.metrics.get("degraded")
    )
    ctx.counters.update(
        rows=rows, dispatches=len(results), steps=len(results) * nb,
        matches=sum(r.matches for r in results), overflow=overflow,
        window_s=t1 - t0, traced_steps=traced * nb,
        rows_per_step_per_chip=ctx.deployment.batch,
    )
    ctx.say(
        "stream_window", dispatches=len(results), rows=rows,
        window_s=round(t1 - t0, 4), overflow=overflow,
        match_share=round(ctx.counters["matches"] / max(rows, 1), 4),
        dispatch_s=[round(r.wall_s, 4) for r in results],
    )
    return {
        "attempted": rows,
        "failed": overflow + degraded,
        "metrics": {"rows_per_s": rows / (t1 - t0) / ctx.chips},
    }


def check(ctx, st) -> list:
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness.check import Comparison, disagreement

    sj, ring, nb, k = st["sj"], st["ring"], st["nb"], st["k"]
    results = st["results"]
    limits = ctx.config["guarantees"]
    with ctx.spans.span("check.collect_run"):
        again = sj.run(ring, nb, collect=True)
    fold = (again.checksum, again.matches, again.overflow)
    differing = sum(
        (r.checksum, r.matches, r.overflow) != fold for r in results
    )
    out = [
        Comparison(
            "stream_fold_mismatches", differing, 0,
            "every timed dispatch folds the same ring: its (checksum, "
            "matches, overflow) equals the collected run's exactly",
        ),
        Comparison(
            "stream_overflow_rows", again.overflow, 0,
            "an uncapped stream marks no row OVERFLOW",
        ),
    ]
    # a seeded sample of the collected rows against the plain reference
    n = min(int(ctx.cell["check"]["sample_rows"]), nb * st["batch"])
    per_step = max(n // nb, 1)
    rng = np.random.default_rng(ctx.seed)
    got, pts = [], []
    for step in range(nb):
        idx = np.sort(rng.choice(st["batch"], per_step, replace=False))
        got.append(np.asarray(again.outs[step])[idx])
        pts.append(np.asarray(ring[step % k][jnp.asarray(idx)]))
    got, pts = np.concatenate(got), np.concatenate(pts)
    t0 = time.perf_counter()
    want = ctx.deployment.reference.answers(ctx.deployment.rings, pts)
    ctx.say(
        "reference", rows=len(pts), seconds=round(time.perf_counter() - t0, 3),
        matched_share=round(float((want >= 0).mean()), 4),
    )
    out.append(Comparison(
        "stream_disagreement_share", disagreement(got, want),
        limits["stream_max_disagreement"],
        "share of sampled rows that differ from the plain f64 reference; "
        "the stream assigns cells in the dtype its rule chose for the index "
        "(stream_cell_dtype: f32 on the taxi zones, f64 on the buildings)",
    ))
    return out


def close(ctx, st) -> None:
    st.clear()
