#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that mosaic-tpu still starts on the chip.

One process, no child. It drives the system's main path once, through the
entry points a user calls and with the package's own defaults, at the size
the reference Quickstart runs (`BASELINE.json` ``configs[0]``, reference
``QuickstartNotebook.scala:149-216``): H3 resolution 9, a zone layer of
NYC-taxi-zone count, points in batches of 4,000,000. The NYC zone fixture
is not in the repository, so the zones are `datasets.synthetic_zones(16, 16)`
(256 zones) and the points `datasets.random_points(..., seed=--seed)` — said
in the output, not hidden.

Phases (all in this process, under one telemetry capture):

1. **batch**   `pip_join` of one batch with the defaults, then the same call
               with ``recheck=True`` on a prefix; both against the f64 host
               oracle (`host_join`), outside any timed region.
2. **stream**  `StreamJoin.run` over a device-generated ring; the fold must
               equal the fold of the per-slot `step` answers, one slot is
               checked against the oracle, ``overflow == 0``, and peak HBM
               comes from ``memory_stats``.
3. **serve**   `ServeEngine` with the default ladder: warm-up, a few hundred
               `submit()` requests from several threads (every answer equal
               to the batch path's, zero ``dispatch.compile`` spans), then a
               second engine warmed from the `ProgramStore` the first one
               exported (0 backend compiles, same answers).
4. **kernels** the two Pallas kernels through the library, compiled:
               `pip_heavy_tiled` via ``probe="adaptive"``/``adaptive-heavy``
               (plain and banded) against ``probe="scatter"``, and
               `zonal_tiled` via `zonal_zones(lane="tiled")` on a raster over
               the zones against the ``fold`` lane.
5. **mesh**    only when JAX reports more than one device: the replicated-
               index lane (`pip_join(mesh=)`), `dist_pip_join` on a
               ``(dp, cell)`` mesh and `StreamJoin(mesh=)`, each bit-identical
               to the one-device answer with one output shard per device.

It FAILS — non-zero exit, the reason on the last line, no result line — when
the platform is not ``tpu``, when any phase raises, when any answer is a
`DegradedResult`, or when the captured telemetry holds one ``degraded``,
``retry_exhausted``, ``transient_retry``, ``watchdog_stall`` or
``program_store_fallback`` event. Nothing is caught and reported beside an
exit code of 0. On success the last two lines of stdout are the per-phase
report (``report: {...}``) and then one JSON object with exactly these keys,
the device as JAX reports it::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

``--tiny`` is the CPU debugging size (the tier-1 test runs it). It is refused
unless ``JAX_PLATFORMS=cpu`` asked for the CPU by name; the plain command
never takes the CPU, whatever the environment holds.

Every time printed here is set-up information for the benchmark that
follows this script, not a metric.
"""

from __future__ import annotations

import argparse
import importlib.metadata
import json
import sys
import tempfile
import threading
import time
import traceback

#: size rows. ``full`` is the deployment; a cut forced by the chip tool's
#: time limit goes to ``stream_batches`` or the top rung of ``ladder``,
#: never to the batch width, the resolution or the zone count.
SIZES = {
    "full": dict(
        index_system="H3", resolution=9, zones=(16, 16), bbox=None,
        batch=4_000_000, recheck_rows=1_000_000,
        ring_slots=8, stream_batches=25, slot_check_rows=1_000_000,
        ladder=(64, 65536), requests=300, threads=4, rows_max=1024,
        kernel_points=60_000, raster_side=2400,
        mesh_rows=1 << 20,
        # a 1M-row step, so that tier 2 runs in row chunks (419,328 at E2 80)
        buildings=dict(index_system="H3", resolution=11, count=2_000,
                       rows=1_000_000, check_rows=100_000),
    ),
    # a coarse custom grid: H3's unrolled digit pipeline costs the CPU
    # ~15 s of compiles that prove nothing about this script's control flow
    "tiny": dict(
        index_system="CUSTOM(-180,180,-90,90,2,10,10)", resolution=2,
        zones=(3, 3), bbox=(-25.0, -25.0, 35.0, 20.0),
        batch=8_192, recheck_rows=2_048,
        ring_slots=2, stream_batches=3, slot_check_rows=2_048,
        ladder=(64, 128), requests=12, threads=2, rows_max=100,
        kernel_points=1_000, raster_side=48,
        mesh_rows=2_048,
        buildings=dict(index_system="CUSTOM(-75,-73,40,42,2,1,1)",
                       resolution=11, count=300, rows=4_096,
                       check_rows=4_096),
    ),
}

#: telemetry events that mean the device path was retried, abandoned or
#: bypassed — any one of them fails the smoke
FORBIDDEN_EVENTS = (
    "degraded", "retry_exhausted", "transient_retry", "watchdog_stall",
    "program_store_fallback",
)

_T0 = time.perf_counter()


class SmokeFailure(AssertionError):
    """A check of the smoke did not hold."""


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(phase: str, **kv) -> None:
    body = " ".join(f"{k}={v}" for k, v in kv.items())
    print(f"[{time.perf_counter() - _T0:7.1f}s] {phase}: {body}", flush=True)


def not_degraded(x, what: str):
    check(
        not getattr(x, "degraded", False),
        f"{what} was answered by the host oracle (DegradedResult: "
        f"{getattr(x, 'reason', '')})",
    )
    return x


# ------------------------------------------------------------------ set-up

def build_deployment(size: dict, seed: int) -> dict:
    """Zones -> tessellate -> build_chip_index, points, and the oracle."""
    import jax
    import numpy as np

    import mosaic_tpu
    from mosaic_tpu.core.tessellate import tessellate
    from mosaic_tpu.datasets import random_points, synthetic_zones
    from mosaic_tpu.sql.join import build_chip_index

    ctx = mosaic_tpu.enable_mosaic(size["index_system"])
    h3 = ctx.index_system
    res = size["resolution"]
    zones = synthetic_zones(
        *size["zones"], **({"bbox": size["bbox"]} if size["bbox"] else {})
    )
    t0 = time.perf_counter()
    table = tessellate(zones, h3, res, keep_core_geoms=False)
    t1 = time.perf_counter()
    index = build_chip_index(table)
    t2 = time.perf_counter()
    b = zones.bounds()
    bbox = (
        float(np.nanmin(b[:, 0])), float(np.nanmin(b[:, 1])),
        float(np.nanmax(b[:, 2])), float(np.nanmax(b[:, 3])),
    )
    index_bytes = sum(
        int(getattr(a, "nbytes", 0)) for a in jax.tree_util.tree_leaves(index)
    )
    say(
        "setup",
        zones=f"synthetic_zones{size['zones']} ({len(zones)} zones; the NYC "
        "taxi-zone fixture is not in the repository)",
        index_system=size["index_system"], resolution=res, chips=len(table), cells=int(index.cells.shape[0]),
        heavy_cells=index.num_heavy_cells, convex_cells=index.num_convex_cells,
        index_mb=round(index_bytes / 1e6, 1),
        tessellate_s=round(t1 - t0, 1), build_index_s=round(t2 - t1, 1),
    )
    return dict(
        h3=h3, res=res, zones=zones, index=index, bbox=bbox,
        pts=random_points(size["batch"], bbox=bbox, seed=seed),
    )


# ------------------------------------------------------------------- batch

def phase_batch(dep: dict, size: dict) -> dict:
    import numpy as np

    from mosaic_tpu.dispatch import backend_compiles
    from mosaic_tpu.sql.join import host_join, pip_join

    h3, res, index, pts = dep["h3"], dep["res"], dep["index"], dep["pts"]
    c0 = backend_compiles()
    t0 = time.perf_counter()
    out = not_degraded(
        pip_join(pts, None, h3, res, chip_index=index), "pip_join"
    )
    cold_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    again = not_degraded(
        pip_join(pts, None, h3, res, chip_index=index), "pip_join (repeat)"
    )
    warm_s = time.perf_counter() - t0
    nr = size["recheck_rows"]
    t0 = time.perf_counter()
    exact = not_degraded(
        pip_join(pts[:nr], None, h3, res, chip_index=index, recheck=True),
        "pip_join(recheck=True)",
    )
    recheck_s = time.perf_counter() - t0
    compiles = backend_compiles() - c0

    # ---- correctness, outside every timed region: the f64 host oracle
    t0 = time.perf_counter()
    truth = host_join(pts, index.host, h3, res)
    oracle_s = time.perf_counter() - t0
    out, exact = np.asarray(out), np.asarray(exact)
    check(out.shape == (len(pts),) and out.dtype == np.int32,
          f"pip_join returned {out.dtype}{out.shape}")
    check(np.array_equal(out, np.asarray(again)),
          "pip_join is not deterministic across two identical calls")
    # tolerance 0: recheck=True sends every point whose cell margin or
    # chip-edge distance is inside the epsilon band to the f64 oracle, so
    # the contract is row-for-row identity (round 5 on the chip:
    # join_agreement_after 1.0)
    n_bad = int((exact != truth[:nr]).sum())
    check(n_bad == 0,
          f"recheck=True differs from the f64 host oracle on {n_bad} of "
          f"{nr} rows (contract: bit-identical)")
    # tolerance 1e-4 of rows: the default answer probes f32 chip edges
    # (the index is recentred, then narrowed), so a point within a few f32
    # ulps of a zone boundary may land on the other side; round 5 measured
    # 0.99996 on the chip
    agree = float((out == truth).mean())
    check(agree >= 0.9999,
          f"default pip_join agrees with the f64 host oracle on {agree:.6f} "
          "of rows (< 0.9999)")
    dep["batch_out"], dep["truth"] = out, truth
    rep = dict(
        rows=len(pts), agreement_default=round(agree, 6),
        recheck_rows=nr, agreement_recheck=1.0,
        match_rate=round(float((truth >= 0).mean()), 4),
        backend_compiles=compiles, cold_s=round(cold_s, 1),
        warm_call_s=round(warm_s, 2), recheck_call_s=round(recheck_s, 1),
        host_oracle_s=round(oracle_s, 1),
    )
    say("batch", **rep)
    return rep


# ------------------------------------------------------------------ stream

def phase_stream(dep: dict, size: dict, seed: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mosaic_tpu.dispatch import backend_compiles
    from mosaic_tpu.sql.join import host_join
    from mosaic_tpu.sql.stream import (
        StreamJoin,
        fold_stats,
        hbm_peak,
        ring_from_generator,
    )

    h3, res, index, bbox = dep["h3"], dep["res"], dep["index"], dep["bbox"]
    batch, k, nb = size["batch"], size["ring_slots"], size["stream_batches"]
    lo = jnp.asarray(bbox[:2], dtype=jnp.float64)
    span = jnp.asarray(
        [bbox[2] - bbox[0], bbox[3] - bbox[1]], dtype=jnp.float64
    )

    @jax.jit
    def gen_batch(key):
        u = jax.random.uniform(key, (batch, 2), dtype=jnp.float32)
        return (lo + u * span).astype(jnp.float64)

    c0 = backend_compiles()
    t0 = time.perf_counter()
    ring = ring_from_generator(gen_batch, jax.random.PRNGKey(seed), k)
    sj = StreamJoin(index, h3, res)
    # the per-slot answers through the single-batch entry point, folded on
    # the device — the reference the one-dispatch loop must reproduce
    fold = jax.jit(fold_stats)
    slot_rows0 = None
    slot_stats = []
    for s in range(k):
        rows = sj.step(ring[s])
        if s == 0:
            slot_rows0 = rows
        slot_stats.append(np.asarray(fold(rows), dtype=np.int64))
    sj.compile(ring, nb)
    setup_s = time.perf_counter() - t0
    result = sj.run(ring, nb)
    compiles = backend_compiles() - c0

    want = sum(slot_stats[i % k] for i in range(nb))
    checksum = int(want[0]) & 0xFFFFFFFF  # the device fold is int32
    if checksum >= 1 << 31:
        checksum -= 1 << 32
    check(
        (result.checksum, result.matches, result.overflow)
        == (checksum, int(want[1]), int(want[2])),
        f"stream fold {result.checksum, result.matches, result.overflow} != "
        f"fold of the per-slot step answers "
        f"{checksum, int(want[1]), int(want[2])}",
    )
    check(result.overflow == 0,
          f"stream reported {result.overflow} OVERFLOW rows")
    check(result.n_points == nb * batch, "stream point count is off")

    # one slot against the f64 oracle. Tolerance 1e-3 of rows: StreamJoin
    # assigns cells in f32 (its default ``cell_dtype``), which moves ~0.1%
    # of points into a neighbouring cell; the answer changes only when the
    # point also sits at a zone boundary (a CPU count on this index gave
    # 0.9998)
    ns = min(size["slot_check_rows"], batch)
    slot_pts = np.asarray(ring[0][:ns])
    slot_truth = host_join(slot_pts, index.host, h3, res)
    slot_agree = float((np.asarray(slot_rows0[:ns]) == slot_truth).mean())
    check(slot_agree >= 0.999,
          f"stream slot agrees with the f64 host oracle on {slot_agree:.6f} "
          "of rows (< 0.999)")

    peak, source = hbm_peak()
    if jax.devices()[0].platform == "tpu":
        check(source == "memory_stats.peak_bytes_in_use" and peak > 0,
              f"peak HBM came from {source!r}, not memory_stats")
    rep = dict(
        rows=result.n_points, batches=nb, batch=batch, ring_slots=k,
        matches=result.matches, overflow=result.overflow,
        slot_check_rows=ns, slot_agreement=round(slot_agree, 6),
        backend_compiles=compiles, cold_setup_s=round(setup_s, 1),
        run_wall_s=round(result.wall_s, 2), peak_hbm_bytes=peak,
        hbm_source=source,
    )
    say("stream", **rep)
    return rep


# --------------------------------------------------------------- buildings

def phase_buildings(size: dict, seed: int) -> dict:
    """A fabric of building footprints on a grid fine enough to hold it
    (the benchmark's `osm-buildings-h3r11` at a 30th of its size): an index
    with heavy cells, so tier 2 runs — in row chunks at the full size — and
    cells so small that the stream's rule assigns them in f64. One stream
    step with package defaults against the f64 host oracle."""
    import jax
    import numpy as np

    import mosaic_tpu
    from benchmark.generators import buildings
    from mosaic_tpu.core.tessellate import tessellate
    from mosaic_tpu.core.types import GeometryBuilder, GeometryType
    from mosaic_tpu.datasets import random_points
    from mosaic_tpu.sql.join import build_chip_index, host_join
    from mosaic_tpu.sql.stream import StreamJoin

    b = size["buildings"]
    grid = mosaic_tpu.enable_mosaic(b["index_system"]).index_system
    res = b["resolution"]
    footprints, _ = buildings.fabric(
        {"count": b["count"], "centre": [-73.95, 40.70], "seed": 11})
    col = GeometryBuilder()
    for rings in footprints:
        col.add_geometry(GeometryType.POLYGON, [rings], srid=4326)
    t0 = time.perf_counter()
    index = build_chip_index(
        tessellate(col.build(), grid, res, keep_core_geoms=False))
    build_s = time.perf_counter() - t0
    check(index.num_heavy_cells > 0,
          "the fabric index has no heavy cell: tier 2 would not run")
    sj = StreamJoin(index, grid, res)
    check(sj.cell_dtype == "float64",
          f"the stream's rule chose {sj.cell_dtype} cells at resolution "
          f"{res}, where an f32 ulp is a fortieth of a cell")
    pts = random_points(
        b["rows"], bbox=buildings.footprints_bbox(footprints), seed=seed)
    ring = jax.numpy.asarray(pts)[None]
    result = sj.run(ring, 1, collect=True)
    check(result.overflow == 0,
          f"stream reported {result.overflow} OVERFLOW rows")
    n = b["check_rows"]
    truth = host_join(pts[:n], index.host, grid, res)
    agree = float((result.outs[0][:n] == truth).mean())
    check(agree >= 0.9995,
          f"the fabric stream agrees with the f64 host oracle on "
          f"{agree:.6f} of rows (< 0.9995)")
    check(result.metrics["heavy_rows"] > 0, "no row's cell was heavy")
    rep = dict(
        footprints=len(footprints), cells=index.num_cells,
        heavy_cells=index.num_heavy_cells,
        E2=int(index.heavy_edges.shape[1]),
        M2=int(index.heavy_slot_geom.shape[1]), rows=b["rows"],
        heavy_rows=result.metrics["heavy_rows"],
        cell_dtype=result.metrics["cell_dtype"], check_rows=n,
        agreement=round(agree, 6), matches=result.matches,
        index_build_s=round(build_s, 1), run_wall_s=round(result.wall_s, 2),
    )
    say("buildings", **rep)
    return rep


# ------------------------------------------------------------------- serve

def _serve_load(engine, dep: dict, size: dict, seed: int):
    """A few hundred closed-loop requests from several threads; returns
    the telemetry captured while they ran."""
    import numpy as np

    from mosaic_tpu.runtime import telemetry

    pts, want = dep["pts"], dep["batch_out"]
    rng = np.random.default_rng(seed)
    n_req = size["requests"]
    sizes = rng.integers(1, size["rows_max"] + 1, n_req)
    starts = rng.integers(0, len(pts) - size["rows_max"], n_req)
    errors: list = []
    lock = threading.Lock()
    cursor = [0]
    with telemetry.capture() as events:
        sinks = telemetry.current_sinks()

        def worker():
            telemetry.adopt_sinks(sinks)
            while True:
                with lock:
                    i = cursor[0]
                    cursor[0] += 1
                if i >= n_req:
                    return
                s, n = int(starts[i]), int(sizes[i])
                try:
                    got = engine.submit(pts[s : s + n]).result(timeout=120)
                    not_degraded(got, f"serve request {i}")
                    if not np.array_equal(np.asarray(got), want[s : s + n]):
                        raise SmokeFailure(
                            f"serve request {i} ({n} rows) differs from "
                            "the batch path's answer"
                        )
                except BaseException as e:  # noqa: BLE001 — re-raised below
                    with lock:
                        errors.append(e)
                    return

        threads = [
            threading.Thread(target=worker) for _ in range(size["threads"])
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        check(not any(t.is_alive() for t in threads),
              "serve load threads did not finish")
    if errors:
        raise errors[0]
    return events, int(sizes.sum())


def phase_serve(dep: dict, size: dict, seed: int) -> dict:
    import numpy as np

    from mosaic_tpu.dispatch import (
        BucketLadder,
        ProgramStore,
        backend_compiles,
    )
    from mosaic_tpu.serve import ServeEngine

    h3, res, index, bbox = dep["h3"], dep["res"], dep["index"], dep["bbox"]
    ladder = (
        None if size["ladder"] == (64, 65536)  # the package default
        else BucketLadder(*size["ladder"])
    )
    with tempfile.TemporaryDirectory(prefix="chip_smoke_store_") as store:
        c0 = backend_compiles()
        t0 = time.perf_counter()
        with ServeEngine(
            index, h3, res, bounds=bbox, ladder=ladder, program_store=store
        ) as engine:
            warm = engine.warmup()
            warm_s = time.perf_counter() - t0
            cold_compiles = backend_compiles() - c0
            t0 = time.perf_counter()
            events, rows = _serve_load(engine, dep, size, seed)
            load_s = time.perf_counter() - t0
            m = engine.metrics()
        recompiles = [
            e for e in events
            if e.get("event") == "span" and e.get("name") == "dispatch.compile"
        ]
        check(not recompiles,
              f"{len(recompiles)} dispatch.compile spans after warm-up")
        check(m["cold_compiles"] == 0 and m["degraded"] == 0 and m["shed"] == 0,
              f"serve metrics after load: cold_compiles={m['cold_compiles']} "
              f"degraded={m['degraded']} shed={m['shed']}")
        rungs = warm["buckets"]
        check(warm["aot"]["exported"] == 2 * rungs,
              f"first engine exported {warm['aot']} for {rungs} rungs")

        # the relaunch: a second engine warms from what the first exported
        c1 = backend_compiles()
        t0 = time.perf_counter()
        with ServeEngine(
            index, h3, res, bounds=bbox, ladder=ladder,
            program_store=ProgramStore(store),
        ) as twin:
            warm2 = twin.warmup()
            warm2_s = time.perf_counter() - t0
            store_compiles = backend_compiles() - c1
            probe_rows = dep["pts"][: size["rows_max"]]
            got = not_degraded(
                twin.submit(probe_rows).result(timeout=120),
                "store-warmed engine",
            )
            check(np.array_equal(np.asarray(got),
                                 dep["batch_out"][: size["rows_max"]]),
                  "store-warmed engine differs from the batch path")
        check(warm2["aot"] == {"loaded": 2 * rungs, "exported": 0,
                               "fallback": 0},
              f"store-warmed engine aot stats {warm2['aot']}")
        check(store_compiles == 0,
              f"store-warmed engine compiled {store_compiles} programs")
    rep = dict(
        rungs=rungs, requests=size["requests"], rows=rows,
        threads=size["threads"], batches=m["batches"],
        occupancy=m["occupancy_mean"], degraded=m["degraded"],
        real_row_share=round(m["batched_rows"] / m["padded_rows"], 4),
        linger_closed_by={
            k: m["linger_closed_by_" + k]
            for k in ("window", "rows", "put_back")
        },
        h2d_bytes=m["h2d_bytes"], d2h_bytes=m["d2h_bytes"],
        compile_spans_after_warmup=0, backend_compiles=cold_compiles,
        cold_warmup_s=round(warm_s, 1), load_wall_s=round(load_s, 2),
        store_warmed_backend_compiles=store_compiles,
        store_warmup_s=round(warm2_s, 1),
    )
    say("serve", **rep)
    return rep


# ----------------------------------------------------------------- kernels

def phase_kernels(dep: dict, size: dict, seed: int) -> dict:
    import jax
    import numpy as np

    from mosaic_tpu.dispatch import backend_compiles
    from mosaic_tpu.raster import Raster
    from mosaic_tpu.raster.zonal import zonal_zones
    from mosaic_tpu.runtime import telemetry
    from mosaic_tpu.runtime.platform import interpret_kernels
    from mosaic_tpu.sql.join import host_join, pip_join
    from tools.probe_smoke import build_fixture

    # the one interpret rule must have chosen the Mosaic compiler here
    if jax.devices()[0].platform == "tpu":
        check(not interpret_kernels(),
              "interpret_kernels() chose interpret mode on a TPU")
    c0 = backend_compiles()
    t0 = time.perf_counter()

    # ---- pip_heavy_tiled: the fixture that populates light, heavy AND
    # convex cells (the 256 synthetic zones have no heavy cell)
    grid, gres, _zones, gindex = build_fixture()
    check(gindex.num_heavy_cells > 0 and gindex.num_convex_cells > 0,
          "probe fixture lost its heavy or convex cells")
    kp = np.random.default_rng(seed).uniform(
        (-25, -25), (35, 20), (size["kernel_points"], 2)
    )

    def join(probe, recheck):
        return np.asarray(not_degraded(
            pip_join(kp, None, grid, gres, chip_index=gindex, probe=probe,
                     recheck=recheck),
            f"pip_join(probe={probe!r}, recheck={recheck})",
        ))

    heavy_rows = 0
    for recheck in (False, True):  # plain kernel, then the banded kernel
        base = join("scatter", recheck)
        for probe in ("adaptive", "adaptive-heavy"):
            with telemetry.capture() as ev:
                got = join(probe, recheck)
            heavy_rows = max(
                [heavy_rows]
                + [e["heavy"] for e in ev if e.get("event") == "probe_route"]
            )
            # tolerance 0: the kernel reproduces `_ray_parity`'s evaluation
            # order on the same f32 tables, so the lanes are bit-identical
            n_bad = int((got != base).sum())
            check(n_bad == 0,
                  f"probe={probe!r} recheck={recheck} differs from "
                  f"probe='scatter' on {n_bad} rows (contract: identical)")
    check(heavy_rows > 0, "no point was routed through the heavy lane")
    oracle = host_join(kp, gindex.host, grid, gres)
    n_bad = int((join("adaptive", True) != oracle).sum())
    check(n_bad == 0,
          f"adaptive + recheck differs from the f64 oracle on {n_bad} rows")
    pip_s = time.perf_counter() - t0

    # ---- zonal_tiled through zonal_zones, against the fold lane.
    # Tolerance 0 on count, sum, min and max: pixel values are integers in
    # [0, 113), so every partial and total sum is an integer below 2**24 and
    # the kernel's f32 accumulators hold it exactly, whatever the order
    t0 = time.perf_counter()
    side = size["raster_side"]
    bbox = dep["bbox"]
    data = np.random.default_rng(seed + 1).integers(
        0, 113, (1, side, side)
    ).astype(np.float32)
    gt = (bbox[0], (bbox[2] - bbox[0]) / side, 0.0,
          bbox[3], 0.0, -(bbox[3] - bbox[1]) / side)
    raster = Raster(data=data, gt=gt, srid=4326)
    tiled = zonal_zones(raster, dep["index"], dep["h3"], dep["res"],
                        lane="tiled")
    fold = zonal_zones(raster, dep["index"], dep["h3"], dep["res"],
                       lane="fold")
    check(len(fold.keys) > 0 and fold.pixels > 0, "zonal fold saw no pixel")
    for name in ("keys", "count", "sum", "min", "max"):
        check(np.array_equal(getattr(tiled, name), getattr(fold, name)),
              f"zonal tiled lane differs from the fold lane in {name!r}")
    zonal_s = time.perf_counter() - t0
    rep = dict(
        interpret=interpret_kernels(), pip_rows=len(kp),
        heavy_lane_rows=heavy_rows, pip_identical=True,
        raster=f"{side}x{side}", zones_hit=len(fold.keys),
        zonal_pixels=fold.pixels, zonal_identical=True,
        backend_compiles=backend_compiles() - c0,
        pip_s=round(pip_s, 1), zonal_s=round(zonal_s, 1),
    )
    say("kernels", **rep)
    return rep


# -------------------------------------------------------------------- mesh

def _one_shard_per_device(arr, n: int, what: str) -> None:
    devs = {s.device for s in arr.addressable_shards}
    check(len(arr.addressable_shards) == n and len(devs) == n,
          f"{what}: {len(arr.addressable_shards)} shards on {len(devs)} "
          f"devices, wanted one on each of {n} ({arr.sharding})")


def _mesh_replicated(dep: dict, pts, want, n: int):
    """DispatchCore's replicated-index lane, the way `pip_join(mesh=)`
    reaches it (chunks of the ladder's top rung)."""
    import jax.numpy as jnp
    import numpy as np

    from mosaic_tpu import dispatch
    from mosaic_tpu.sql.join import pip_join

    h3, res, index = dep["h3"], dep["res"], dep["index"]
    top = dispatch.DEFAULT_MAX_BUCKET
    got = not_degraded(
        pip_join(pts, None, h3, res, chip_index=index, mesh=n,
                 batch_size=top),
        "pip_join(mesh=)",
    )
    check(np.array_equal(np.asarray(got), want),
          "pip_join(mesh=) differs from the one-device answer")
    # the same cached program, called once more for its DEVICE output
    # (pip_join hands back numpy): where do the shards live?
    core = dispatch.core_for(index, h3, res, mesh=n)
    padded, rows = core.ladder.pad(pts[:top])
    fcap, hcap, ccap = core.caps(padded.shape[0])
    prog = dispatch.sharded_join_prog(
        core.mesh, writeback=core.writeback,
        probe=core.probe, found_cap=fcap, heavy_cap=hcap, convex_cap=ccap,
    )
    cells = dispatch.cells_prog(h3, res, "cells")(jnp.asarray(padded))
    shifted = jnp.asarray(
        np.asarray(padded - index.host.shift, dtype=index.border.verts.dtype)
    )
    out = prog(shifted, cells, index)
    _one_shard_per_device(out, n, "replicated-index lane")
    check(np.array_equal(np.asarray(out)[:rows], want[:rows]),
          "sharded_join_prog differs from the one-device answer")


def _mesh_dist_join(dep: dict, pts, want, n: int):
    """The (dp, cell) mesh: index sharded over ``cell`` and all-gathered
    inside the step, zone histogram psum-reduced."""
    import jax.numpy as jnp
    import numpy as np

    from mosaic_tpu import dispatch
    from mosaic_tpu.parallel import (
        dist_pip_join,
        distributed_join_step,
        make_mesh,
        pad_index_for_shards,
    )

    h3, res, index = dep["h3"], dep["res"], dep["index"]
    mesh = make_mesh(n)
    nz = len(dep["zones"])
    table = int(index.table_cell.shape[0])
    cells = np.asarray(
        dispatch.cells_prog(h3, res, "cells")(jnp.asarray(pts))
    )
    match, counts = dist_pip_join(pts, cells, index, mesh, nz,
                                  table_size=table)
    not_degraded(match, "dist_pip_join")
    check(np.array_equal(np.asarray(match), want),
          "dist_pip_join differs from the one-device answer")
    check(np.array_equal(
        counts, np.bincount(want[want >= 0], minlength=nz)[:nz]),
        "dist_pip_join zone counts differ from the one-device histogram")
    # the unmanaged step, for its device output's placement
    step = distributed_join_step(mesh, nz, table_size=table)
    shifted = jnp.asarray(
        np.asarray(pts - index.host.shift, dtype=index.border.verts.dtype)
    )
    m2, _counts = step(
        shifted, jnp.asarray(cells),
        pad_index_for_shards(index, int(mesh.shape["cell"])),
    )
    _one_shard_per_device(m2, n, "dist_join (dp, cell) lane")
    check(np.array_equal(np.asarray(m2), want),
          "distributed_join_step differs from the one-device answer")


def _mesh_stream(dep: dict, pts, n: int):
    """The stream's scan with the probe sharded inside the loop, against
    the one-device stream (same f32 cell assignment on both sides)."""
    import jax.numpy as jnp
    import numpy as np

    from mosaic_tpu.sql.stream import StreamJoin

    h3, res, index = dep["h3"], dep["res"], dep["index"]
    one = StreamJoin(index, h3, res)
    many = StreamJoin(index, h3, res, mesh=n)
    dev_pts = jnp.asarray(pts)
    a, b = one.step(dev_pts), many.step(dev_pts)
    _one_shard_per_device(b, n, "StreamJoin(mesh=) step")
    check(np.array_equal(np.asarray(a), np.asarray(b)),
          "StreamJoin(mesh=) differs from the one-device stream step")
    ring = jnp.stack([dev_pts, dev_pts[::-1]])
    ra, rb = one.run(ring, 4), many.run(ring, 4)
    check((ra.checksum, ra.matches, ra.overflow)
          == (rb.checksum, rb.matches, rb.overflow),
          "StreamJoin(mesh=).run fold differs from the one-device fold")
    check(rb.overflow == 0, f"meshed stream reported {rb.overflow} OVERFLOW")


def phase_mesh(dep: dict, size: dict) -> dict:
    """Every mesh lane, bit-identical to the one-device answer and with
    its output spread one shard per device (not everything on device 0)."""
    import jax

    n = len(jax.devices())
    rows = min(size["mesh_rows"], len(dep["pts"]))
    rows -= rows % (n * 64)
    pts, want = dep["pts"][:rows], dep["batch_out"][:rows]
    t0 = time.perf_counter()
    _mesh_replicated(dep, pts, want, n)
    _mesh_dist_join(dep, pts, want, n)
    _mesh_stream(dep, pts, n)
    rep = dict(devices=n, rows=rows, identical=True, shards_per_output=n,
               wall_s=round(time.perf_counter() - t0, 1))
    say("mesh", **rep)
    return rep


# -------------------------------------------------------------------- main

def run(args) -> tuple[dict, dict]:
    """The device as JAX reports it, and the per-phase report."""
    size_name = "tiny" if args.tiny else "full"
    size = SIZES[size_name]

    import jax
    import jaxlib

    from mosaic_tpu.dispatch import backend_compiles, compile_cache_hits
    from mosaic_tpu.runtime import telemetry
    from mosaic_tpu.runtime.platform import (
        configure_compile_cache,
        require_device,
    )

    # the plain command needs a TPU; --tiny needs the CPU asked for by name
    device = require_device(allow_cpu=args.tiny)
    if args.tiny:
        check(device["platform"] == "cpu",
              "--tiny is the CPU debugging size; run the full size on a chip")
    cache_dir = configure_compile_cache()
    if device["platform"] == "cpu":
        cache_dir += " (switched off on the cpu platform)"
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    say("start", size=size_name, platform=device["platform"],
        device_kind=device["kind"], device_count=device["count"],
        jax=jax.__version__, jaxlib=jaxlib.__version__, libtpu=libtpu,
        compile_cache=cache_dir, seed=args.seed)

    report: dict = {"size": size_name, "compile_cache": cache_dir}
    with telemetry.capture() as events:
        dep = build_deployment(size, args.seed)
        report["batch"] = phase_batch(dep, size)
        report["stream"] = phase_stream(dep, size, args.seed)
        report["serve"] = phase_serve(dep, size, args.seed)
        report["kernels"] = phase_kernels(dep, size, args.seed)
        report["buildings"] = phase_buildings(size, args.seed)
        if device["count"] > 1:
            report["mesh"] = phase_mesh(dep, size)
    bad = [e for e in events if e.get("event") in FORBIDDEN_EVENTS]
    check(not bad,
          f"{len(bad)} forbidden telemetry events, first: "
          f"{ {k: bad[0][k] for k in list(bad[0])[:6]} if bad else None}")
    report["backend_compiles"] = backend_compiles()
    report["compile_cache_hits"] = compile_cache_hits()
    report["wall_s"] = round(time.perf_counter() - _T0, 1)
    say("done", backend_compiles=report["backend_compiles"],
        compile_cache_hits=report["compile_cache_hits"],
        wall_s=report["wall_s"])
    return device, report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=11,
                    help="seed of the points, the ring and the request mix")
    ap.add_argument("--tiny", action="store_true",
                    help="CPU debugging size; needs JAX_PLATFORMS=cpu")
    args = ap.parse_args(argv)
    try:
        device, report = run(args)
    except BaseException as e:  # noqa: BLE001 — reported, then exit != 0
        traceback.print_exc()
        sys.stderr.flush()
        print(f"FAIL: {type(e).__name__}: {str(e)[:600]}", flush=True)
        return 1
    print("report: " + json.dumps(report), flush=True)
    # the contract's last line: these two keys and nothing else
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
