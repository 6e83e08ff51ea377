"""Traffic kind ``device_ring_durable``: the ring of `device_ring_stream`,
joined by checkpointed jobs that are killed and resumed. Closed loop, one
client; the host feeds nothing, and hashes, writes and reads what a durable
job hashes, writes and reads.

One unit of work is a *job*: ``StreamJoin.run_durable(ring, job_batches,
run_dir=<fresh>, snapshot_every=...)`` under a fault plan that makes the
dispatch of one segment fatal (a non-transient ``RuntimeError``: "device
lost"); the kind catches it, drops that `StreamJoin`, builds a fresh one on
the same resident index (a restarted worker: the compiled program bundle is
shared process-wide, the per-instance warm set is not) and, with the fault
plan closed, calls ``resume(run_dir, ring)`` to the end; the ``run_dir`` is
then removed. All of that is inside the clock. Jobs run back to back until
the window has passed; the last one runs to its end.

Parameters (the mix's data file): ``ring_slots``, ``points`` (the point
generator's), ``kill`` (``skip_first``, ``fail_first``, ``sites``: the fault
plan), ``trace_job``. Batch rows, ``job_batches`` and ``durable.snapshot_every``
come from the configuration; every other argument of ``run_durable`` /
``resume`` is the package default (``MOSAIC_STREAM_PIPELINE=1`` in the
environment is the program's own knob for its pipelined loop: the builder's
reading of both loops goes through it, a benchmark run leaves it unset).

End-to-end: ``batch_rows_per_s`` — ``job_batches`` x batch rows x completed
jobs over the seconds from the first job's start to the last job's end. A
batch that was replayed counts once.

Correct (after the window): one unbroken ``StreamJoin.run(ring, job_batches,
collect=True)``; every job's final fold equals its fold exactly; every job
resumed from a snapshot boundary no later than the kill and within the
configuration's bounded-loss guarantee; one more job run with
``collect=True`` through the same kill and resume gives, for the batches
after ``resumed_from``, the unbroken run's rows exactly, and a seeded sample
of them is held to the plain reference. A job with a degraded segment, a
missing snapshot, an OVERFLOW row or a fold unlike the warm job's counts in
``failed``.

Two controls (``ctx.control``, never on in a benchmark run; the seed's
parity picks one): cell assignment in bfloat16 (even seeds), and a
*durability* control (odd seeds) that, between the kill and the resume,
moves the newest snapshot's ``step`` back one segment and keeps its ``acc``:
the resumed job folds a segment twice.

A ``--trace 1`` run profiles, of job ``trace_job``, from the last whole
segment before the kill through the first segment after the resume.
"""

from __future__ import annotations

import os
import shutil
import tempfile
import threading
import time

KILLED = "device lost"
#: span names a job's time is reported by (`[bench] job_breakdown`)
PIECES = (
    "stream.segment", "stream.snapshot", "stream.fingerprint",
    "stream.resume", "stream.resume.load", "dispatch.compile",
    "stream.pipeline.drain", "stream.pipeline.flush",
)


def _control(ctx) -> str | None:
    if not ctx.control:
        return None
    return "bfloat16_cells" if ctx.seed % 2 == 0 else "snapshot_moved_back"


def _stream_join(ctx, control):
    """A worker: a `StreamJoin` on the resident index, package defaults
    (the lower-precision control assigns cells in bfloat16, through an
    argument `StreamJoin` already takes)."""
    from mosaic_tpu.sql.stream import StreamJoin

    dep = ctx.deployment
    kw = {}
    if control == "bfloat16_cells":
        import jax.numpy as jnp

        kw["cell_dtype"] = jnp.bfloat16
    return StreamJoin(dep.index, dep.grid, dep.res, **kw)


def _move_back(run_dir: str, every: int) -> None:
    """The durability control: the newest snapshot's carry under the step
    one segment earlier (the ring has as many slots as a segment has
    batches, so the prefetched cells are those of that step too)."""
    from mosaic_tpu.runtime import checkpoint

    step, arrays, meta = checkpoint.load_latest(run_dir)
    for name in os.listdir(run_dir):
        if name.startswith(f"snap-{step:08d}."):
            os.remove(os.path.join(run_dir, name))
    checkpoint.save_snapshot(run_dir, step - every, arrays, meta)


def _job(ctx, st, *, collect: bool = False) -> dict:
    """One job, start to end: run, kill, a fresh worker, resume, clean up."""
    from mosaic_tpu.runtime import checkpoint, faults

    ring, nb, every, kill = st["ring"], st["nb"], st["every"], st["kill"]
    run_dir = tempfile.mkdtemp(prefix="job-", dir=st["tmp"])
    t0 = time.perf_counter()
    try:
        with faults.inject(
            fail_first=int(kill["fail_first"]),
            skip_first=int(kill["skip_first"]),
            sites=tuple(kill["sites"]),
            exc_factory=lambda site: RuntimeError(f"{KILLED} @ {site}"),
        ):
            try:
                st["sj"].run_durable(
                    ring, nb, run_dir=run_dir, snapshot_every=every,
                    collect=collect,
                )
            except RuntimeError as e:
                if KILLED not in str(e):
                    raise
            else:
                raise RuntimeError("the fault plan killed no segment")
        t_kill = time.perf_counter()
        at_kill = checkpoint.list_snapshots(run_dir)
        # the worker is lost with its device: a fresh one resumes
        st["sj"] = _stream_join(ctx, st["control"])
        if st["control"] == "snapshot_moved_back":
            _move_back(run_dir, every)
        t_worker = time.perf_counter()
        res = st["sj"].resume(run_dir, ring, collect=collect)
        t_resumed = time.perf_counter()
        at_end = checkpoint.list_snapshots(run_dir)
    finally:
        shutil.rmtree(run_dir, ignore_errors=True)
    t1 = time.perf_counter()
    boundaries = list(range(every, nb, every)) + [nb]
    return {
        "t0": t0, "t1": t1, "outs": res.outs,
        "fold": (res.checksum, res.matches, res.overflow),
        "resumed_from": res.metrics.get("resumed_from"),
        "cursor_at_kill": at_kill[-1] if at_kill else 0,
        "pipelined": "pipeline" in res.metrics,
        "snapshots_missing": len(set(boundaries) - set(at_end)),
        "degraded": bool(res.metrics.get("degraded")),
        "seconds": {
            "to_kill": t_kill - t0, "new_worker": t_worker - t_kill,
            "resume": t_resumed - t_worker, "cleanup": t1 - t_resumed,
        },
    }


def _bad(job, fold0) -> bool:
    return bool(
        job["degraded"] or job["snapshots_missing"] or job["fold"][2]
        or job["fold"] != fold0
    )


def prepare(ctx) -> dict:
    import jax

    dep, mix, cfg = ctx.deployment, ctx.traffic, ctx.config
    points = ctx.spec.module("generators", "points")
    k, nb = int(mix["ring_slots"]), int(cfg["job_batches"])
    every = int(cfg["durable"]["snapshot_every"])
    control = _control(ctx)
    gen = points.make_generator(mix["points"], dep.bbox, dep.batch, slots=k)
    with ctx.spans.span("ring_build"):
        ring = gen(points.seed_key(ctx.seed))
        ring.block_until_ready()
    st = {
        "ring": ring, "nb": nb, "k": k, "every": every, "batch": dep.batch,
        "kill": mix["kill"], "control": control, "jobs": [],
        "tmp": tempfile.mkdtemp(prefix="mosaic-durable-"),
        "sj": _stream_join(ctx, control),
    }
    # one whole job with its kill and resume: the segment program and every
    # eager op of the path compile here, on both workers
    try:
        with ctx.spans.span("job_warmup"):
            st["warm"] = _job(ctx, st)
    except BaseException:
        close(ctx, st)  # no state is returned: nobody else would
        raise
    stats = jax.devices()[0].memory_stats() or {}
    ctx.say(
        "stream_ready", ring=tuple(ring.shape), job_batches=nb,
        snapshot_every=every, kill=dict(mix["kill"]), control=control,
        pipelined=st["warm"]["pipelined"], probe=st["sj"].probe,
        ring_mb=round(ring.nbytes / 1e6, 1),
        index_mb=round(dep.index_bytes / 1e6, 1),
        carry_mb=round(dep.batch * 8 / 1e6, 1),
        device_peak_mb=round(stats.get("peak_bytes_in_use", 0) / 1e6, 1),
        device_in_use_mb=round(stats.get("bytes_in_use", 0) / 1e6, 1),
        ring_build_s=round(ctx.spans.seconds("ring_build"), 3),
        job_warmup_s=round(ctx.spans.seconds("job_warmup"), 3),
        warm_job_s={n: round(s, 3) for n, s in st["warm"]["seconds"].items()},
        resumed_from=st["warm"]["resumed_from"],
    )
    return st


def _tracing(ctx, st):
    """The observer that opens the profiler when the last-but-one whole
    segment before the kill has ended, and closes it after the first
    segment of the resume (at the end of the snapshot that follows it, so
    that the segment's own annotation is written)."""
    start_step = (int(st["kill"]["skip_first"]) - 2) * st["every"]
    main = threading.get_ident()
    phase = {"at": "armed"}

    def observer(evt: dict) -> None:
        if evt.get("event") != "span" or threading.get_ident() != main:
            return
        name, at = evt.get("name"), phase["at"]
        if name == "stream.segment":
            if at == "armed" and evt.get("step") == start_step:
                ctx.tracer.start()
                phase["at"] = "tracing"
            elif at == "tracing" and "error" in evt:
                phase["at"] = "killed"
            elif at == "killed" and "error" not in evt:
                phase["at"] = "resumed"
        elif name == "stream.snapshot" and at == "resumed":
            ctx.tracer.stop()
            phase["at"] = "done"

    return observer


def window(ctx, st) -> dict:
    from mosaic_tpu.runtime import telemetry

    jobs, fold0 = st["jobs"], st["warm"]["fold"]
    trace_job = int(ctx.traffic.get("trace_job", 0))
    rows_per_job = st["nb"] * st["batch"]
    m0 = time.monotonic()
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < ctx.seconds:
        observer = None
        if ctx.trace and len(jobs) == trace_job:
            observer = _tracing(ctx, st)
            telemetry.add_observer(observer)
        try:
            with ctx.spans.span("durable.job"):
                jobs.append(_job(ctx, st))
        finally:
            if observer is not None:
                telemetry.remove_observer(observer)
                ctx.tracer.stop()
    t1 = jobs[-1]["t1"]
    ctx.window = (m0, time.monotonic())
    seconds = t1 - jobs[0]["t0"]
    rows = rows_per_job * len(jobs)
    failed = rows_per_job * sum(_bad(j, fold0) for j in jobs)
    ctx.counters.update(
        rows=rows, jobs=len(jobs), window_s=seconds,
        matches=sum(j["fold"][1] for j in jobs),
    )
    ctx.series["job_s"] = [j["t1"] - j["t0"] for j in jobs]
    pieces: dict = {}
    for e in ctx.events:  # every span a traced run kept, else the slow ones
        if e.get("event") == "span" and e.get("name") in PIECES and (
            m0 <= e.get("ts_mono", m0)
        ):
            pieces.setdefault(e["name"], []).append(e.get("seconds", 0.0))
    ctx.say(
        "durable_window", jobs=len(jobs), rows=rows,
        window_s=round(seconds, 4), pipelined=jobs[0]["pipelined"],
        job_s=[round(s, 3) for s in ctx.series["job_s"]],
        resumed_from=[j["resumed_from"] for j in jobs],
        cursor_at_kill=[j["cursor_at_kill"] for j in jobs],
        replayed_batches=[
            j["cursor_at_kill"] - (j["resumed_from"] or 0) for j in jobs
        ],
        match_share=round(ctx.counters["matches"] / max(rows, 1), 4),
        **{n: [round(j["seconds"][n], 3) for j in jobs]
           for n in jobs[0]["seconds"]},
    )
    ctx.say("job_breakdown", per_job_s={
        n: round(sum(v) / len(jobs), 4) for n, v in sorted(pieces.items())
    }, spans_per_job={
        n: round(len(v) / len(jobs), 2) for n, v in sorted(pieces.items())
    })
    return {
        "attempted": rows,
        "failed": failed,
        "metrics": {"batch_rows_per_s": rows / seconds / ctx.chips},
    }


def _violates(job, st, limits) -> bool:
    """Not a snapshot boundary, later than the kill, or further back from
    the kill than the bounded-loss guarantee allows."""
    r, every = job["resumed_from"], st["every"]
    kill_step = int(st["kill"]["skip_first"]) * every
    in_flight = int(limits.get("pipelined_segments_in_flight", 4)) \
        if job["pipelined"] else 0
    return (
        r is None or r % every != 0 or r > kill_step
        or kill_step - r > every * (1 + in_flight)
    )


def check(ctx, st) -> list:
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness.check import Comparison, disagreement

    ring, nb, k = st["ring"], st["nb"], st["k"]
    limits = ctx.config["guarantees"]
    with ctx.spans.span("check.unbroken_run"):
        whole = st["sj"].run(ring, nb, collect=True)
    fold = (whole.checksum, whole.matches, whole.overflow)
    with ctx.spans.span("check.collected_job"):
        again = _job(ctx, st, collect=True)
    jobs = st["jobs"] + [again]
    start = int(again["resumed_from"] or 0)
    rows = again["outs"]
    out = [
        Comparison(
            "durable_fold_mismatches",
            sum(j["fold"] != fold for j in jobs),
            limits["fold_max_mismatches"],
            "exactly once: every killed and resumed job's final (checksum, "
            "matches, overflow) equals the unbroken run's, bit for bit",
        ),
        Comparison(
            "durable_boundary_violations",
            sum(_violates(j, st, limits) for j in jobs),
            limits["bounded_loss_max_violations"],
            "bounded loss: every job resumed from a snapshot boundary no "
            "later than the kill and at most snapshot_every x (1 + segments "
            "in flight) batches before it",
        ),
        Comparison(
            "durable_overflow_rows", whole.overflow, 0,
            "an uncapped stream marks no row OVERFLOW",
        ),
        Comparison(
            "durable_resumed_rows_differing",
            int((rows != whole.outs[start:]).sum())
            if rows.shape == whole.outs[start:].shape else rows.size,
            0,
            "the rows a resumed job collects after resumed_from are the "
            "unbroken run's rows for those batches",
        ),
    ]
    # a seeded sample of the resumed job's rows against the plain reference
    steps = nb - start
    n = min(int(ctx.cell["check"]["sample_rows"]), steps * st["batch"])
    per_step = max(n // steps, 1)
    rng = np.random.default_rng(ctx.seed)
    got, pts = [], []
    for i in range(steps):
        idx = np.sort(rng.choice(st["batch"], per_step, replace=False))
        got.append(np.asarray(rows[i])[idx])
        pts.append(np.asarray(ring[(start + i) % k][jnp.asarray(idx)]))
    got, pts = np.concatenate(got), np.concatenate(pts)
    t0 = time.perf_counter()
    want = ctx.deployment.reference.answers(ctx.deployment.rings, pts)
    ctx.say(
        "reference", rows=len(pts), seconds=round(time.perf_counter() - t0, 3),
        matched_share=round(float((want >= 0).mean()), 4),
        resumed_from=start, jobs_held=len(jobs),
    )
    out.append(Comparison(
        "stream_disagreement_share", disagreement(got, want),
        limits["stream_max_disagreement"],
        "share of sampled rows of a RESUMED job that differ from the plain "
        "f64 reference; the stream assigns cells in f32 on the taxi zones "
        "(stream_cell_dtype), as taxi.stream's limit states",
    ))
    return out


def close(ctx, st) -> None:
    shutil.rmtree(st.get("tmp", ""), ignore_errors=True)
    st.clear()
