"""Streamed ≥100M-point PIP join: the 1B-point north-star architecture.

Round-5 diagnosis (`STREAM_1B_r05.json`): the device-gen stream sustained
47.2M pts/s against a 132.2M single-batch rate (0.357x) because point
GENERATION ran inside every loop iteration and nothing overlapped cell
assignment with the probe — and `peak_hbm_bytes` came back 0 because that
run's backend exposed no memory stats. This bench measures through the
`mosaic_tpu.sql.stream` pipeline layer, which separates the stages:

- **generator rate** — `gen_batch` alone in an identical fori_loop;
- **pure-join sustained rate** (the headline `value` in ring mode) — the
  loop cycles a pre-generated ring of K batches resident in HBM, with
  double-buffered prefetch of batch i+1's cell assignment overlapping
  batch i's PIP passes (`--no-ab` skips the prefetch-off comparison);
- **single-batch rate** — the same fused step on one pre-staged batch;
  `sustained_frac_of_single` is pure-join sustained over this;
- **peak_hbm_bytes** — runtime memory stats at the loop's high-water
  mark, falling back to a live-buffer census when the backend reports
  none (never 0 again); per-stage wall timings ride along in
  ``detail.stages`` (captured `stream_stage` telemetry events).

The final stdout line is ALWAYS one machine-parseable JSON object (all
other output goes to stderr). ``--verify`` (CPU CI) additionally asserts
the streamed loop is bit-identical to the per-batch path.

Usage:
  python tools/stream_bench.py --points 1000000000 --device-gen [--out F]
  python tools/stream_bench.py --points 100000000            # host-stream
  (CPU validation: JAX_PLATFORMS=cpu ... --points 200000
   --batch 50000 --ring 2 --device-gen --verify)
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _bucket(n: int) -> int:
    """bench.py's cap bucketing: pow2 below 128k, 128k multiples above —
    cap size directly scales tier gather/matmul cost."""
    if n <= 131072:
        return max(16, 1 << int(np.ceil(np.log2(n + 1))))
    return (n + 131071) // 131072 * 131072


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--points", type=int, default=100_000_000)
    ap.add_argument("--batch", type=int, default=4_000_000)
    ap.add_argument("--ring", type=int, default=8,
                    help="HBM-resident ring slots (device-gen mode)")
    ap.add_argument("--device-gen", action="store_true",
                    help="pure-join ring mode (device-generated batches)")
    ap.add_argument("--donate", action="store_true",
                    help="A/B the donate_ring lane: rerun the join loop "
                    "over a sacrificial ring copy with the ring buffer "
                    "donated to XLA, and record the rate delta plus the "
                    "bytes the copy-free loop keeps out of HBM")
    ap.add_argument("--no-ab", action="store_true",
                    help="skip the prefetch-off comparison compile")
    ap.add_argument("--fused", action="store_true",
                    help="also run the r05-style gen-in-loop stream")
    ap.add_argument("--verify", action="store_true",
                    help="assert stream == per-batch bit-identity (CPU)")
    ap.add_argument("--durable", action="store_true",
                    help="run the ring loop through run_durable "
                    "(checkpoint/resume, watchdog, retry+degradation)")
    ap.add_argument("--pipeline", action="store_true",
                    help="A/B the pipelined durable executor "
                    "(dispatch/pipeline.py) against the synchronous "
                    "segment loop: emits detail.pipeline with the "
                    "sustained-rate delta, window depth, and the "
                    "snapshot/device overlap fraction (implies "
                    "--durable)")
    ap.add_argument("--resume", action="store_true",
                    help="resume an interrupted --durable run from "
                    "--run-dir instead of starting fresh")
    ap.add_argument("--run-dir", default=None,
                    help="snapshot directory for --durable/--resume "
                    "(default: ./stream_run)")
    ap.add_argument("--snapshot-every", type=int, default=16,
                    help="ring cycles between durable snapshots")
    ap.add_argument("--poison", type=int, default=0,
                    help="inject N NaN rows into the staged batches "
                    "before admission (quarantine demo lane)")
    ap.add_argument("--slo", action="store_true",
                    help="evaluate the run's trail against the default "
                    "SLO specs (MOSAIC_SLO_* thresholds; set "
                    "MOSAIC_SLO_STREAM_RATE_MIN for the sustained-rate "
                    "floor) — verdicts land in detail.slo and breaches "
                    "emit real slo_violation events into the trail")
    ap.add_argument("--trail", default=None,
                    help="export the captured telemetry trail "
                    "(spans included) as JSONL")
    ap.add_argument("--chrome-trace", default=None,
                    help="export the trail as Chrome trace-event JSON "
                    "(Perfetto-loadable)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    # the LAST stdout line must be the JSON artifact: stray library prints
    # and progress chatter all divert to stderr
    emit_to = sys.stdout
    sys.stdout = sys.stderr

    from mosaic_tpu.runtime.platform import (
        configure_compile_cache,
        per_chip,
        require_device,
    )

    t_all = time.perf_counter()
    # raises off-TPU unless JAX_PLATFORMS=cpu asked for the CPU
    device_info = require_device()
    detail: dict = {
        "device_info": device_info,
        "compile_cache_dir": configure_compile_cache(),
    }
    line = {
        "metric": "stream_join_sustained",
        "value": 0.0,
        "unit": per_chip("points/sec", device_info),
        "detail": detail,
    }
    stages: list[dict] = []
    root_span = None
    try:
        import functools

        import jax
        import jax.numpy as jnp

        from bench import RES, _load_or_build_index, _load_zones
        from mosaic_tpu.core.index.h3 import H3IndexSystem
        from mosaic_tpu.runtime import telemetry
        from mosaic_tpu.sql.stream import (
            StreamJoin,
            fold_stats,
            generator_rate,
            hbm_peak,
            ring_from_generator,
        )

        from mosaic_tpu import obs

        cap_events = telemetry.capture()
        stages = cap_events.__enter__()
        # one root span: ring build, compiles, the measured loops, and
        # the durable lane are ONE trace in the exported trail
        root_span = obs.start_span(
            "stream_bench", mode="device-gen" if args.device_gen else "host",
        )

        h3 = H3IndexSystem()
        zones, zones_src = _load_zones()
        b = zones.bounds()
        bbox = (
            float(np.nanmin(b[:, 0])), float(np.nanmin(b[:, 1])),
            float(np.nanmax(b[:, 2])), float(np.nanmax(b[:, 3])),
        )
        index, _, _ = _load_or_build_index(zones, zones_src, h3)
        dev = jax.devices()[0]
        detail.update(device=str(dev), zones=zones_src)

        batch = min(args.batch, args.points)
        n_batches = (args.points + batch - 1) // batch

        # caps from a host presample with a margin; an overflow
        # in any batch is counted on device, reported in detail.overflow
        rng = np.random.default_rng(77)
        n_pre = min(200_000, max(20_000, batch))
        pre = rng.uniform(bbox[:2], bbox[2:], (n_pre, 2))
        pre_cells = np.asarray(
            h3.point_to_cell(jnp.asarray(pre, jnp.float32), RES)
        )
        cells_np = np.asarray(index.cells)
        pos = np.clip(
            np.searchsorted(cells_np, pre_cells), 0, cells_np.size - 1
        )
        ffrac = float((cells_np[pos] == pre_cells).mean())
        fcap = min(_bucket(int(1.5 * ffrac * batch)), batch)
        hmask = np.asarray(index.cell_heavy) >= 0
        hfrac = float(np.isin(pre_cells, cells_np[hmask]).mean())
        hcap = min(_bucket(int(1.5 * hfrac * batch)), fcap)

        lo = jnp.asarray(bbox[:2], dtype=jnp.float64)
        span = jnp.asarray(
            [bbox[2] - bbox[0], bbox[3] - bbox[1]], dtype=jnp.float64
        )

        @jax.jit
        def gen_batch(key):
            u = jax.random.uniform(key, (batch, 2), dtype=jnp.float32)
            return (lo + u * span).astype(jnp.float64)

        key = jax.random.PRNGKey(5)
        sj = StreamJoin(
            index, h3, RES, found_cap=fcap, heavy_cap=hcap, prefetch=True
        )
        detail.update(
            n_points=n_batches * batch, n_batches=n_batches, batch=batch,
            caps=[fcap, hcap],
        )

        # sync round-trip: every blocking scalar pull pays this — it must
        # stay OUT of the streamed loop
        rtt_t = time.perf_counter()
        float(jnp.float32(1.0) + 1.0)
        rtt = time.perf_counter() - rtt_t
        detail["sync_rtt_s"] = round(rtt, 4)

        # compile + single-batch compute rate (pre-staged input)
        warm = gen_batch(jax.random.fold_in(key, 0))
        warm.block_until_ready()
        np.asarray(sj.step_stats(warm))
        reps = []
        for _ in range(3):
            t0 = time.perf_counter()
            np.asarray(sj.step_stats(warm))
            reps.append(time.perf_counter() - t0)
        # rtt can exceed a fully-pipelined wall sample: floor the device
        # estimate at 20% of wall, never negative
        single_s = max(min(reps) - rtt, min(reps) * 0.2, 1e-9)
        single_rate = batch / single_s
        detail["single_batch_rate"] = round(single_rate, 1)
        # in the trail too, so stall_report can decompose sustained-vs-
        # single loss from the trail alone (artifacts embed stages)
        telemetry.record(
            "stream_stage", stage="single_batch",
            seconds=round(single_s, 6), batch=batch,
            points_per_sec=round(single_rate, 1),
        )

        if args.device_gen:
            detail["mode"] = "device-gen-ring"

            # (1) the generator alone, in an identical fori_loop — the
            # cost the r05 stream folded invisibly into its number
            gen_rate, gen_wall = generator_rate(
                gen_batch, key, n_batches, batch
            )
            detail["generator_points_per_sec"] = round(gen_rate, 1)
            detail["gen_wall_s"] = round(gen_wall, 3)

            # (2) the ring: K device-generated batches resident in HBM
            k = max(2, min(args.ring, n_batches))
            ring = ring_from_generator(gen_batch, key, k)
            detail["ring_k"] = k
            detail["ring_bytes"] = int(ring.nbytes)

            # (2b) durable lane: quarantine admission (+ optional poison
            # demo) and the checkpointed segment loop — slower than the
            # one-dispatch loop (one snapshot D2H per segment), priced
            # separately in detail.durable, never the headline
            if args.pipeline:
                args.durable = True
            if args.poison or args.durable or args.resume:
                host_batches = [np.array(b) for b in np.asarray(ring)]
                if args.poison:
                    host_batches[0][: args.poison] = np.nan
                ring, q_report = sj.admit(host_batches, bounds=bbox)
                detail["quarantine"] = q_report.metrics()
            if args.durable or args.resume:
                run_dir = args.run_dir or "stream_run"
                if args.resume:
                    res_d = sj.resume(run_dir, ring)
                else:
                    res_d = sj.run_durable(
                        ring, n_batches, run_dir=run_dir,
                        snapshot_every=args.snapshot_every,
                        extra_arrays={"gen_key": np.asarray(key)},
                    )
                detail["durable"] = dict(
                    res_d.metrics,
                    wall_s=round(res_d.wall_s, 3),
                    points_per_sec=round(res_d.points_per_sec, 1),
                    checksum=res_d.checksum,
                    matches=res_d.matches,
                    overflow=res_d.overflow,
                    sustained_frac_of_single=round(
                        res_d.points_per_sec / single_rate, 4
                    ),
                )
                # (2c) pipelined A/B: the same durable workload through
                # the asynchronous executor — the trail slice gives the
                # snapshot/device overlap fraction ("snapshots off the
                # critical path" as a measured number, not prose)
                if args.pipeline and not args.resume:
                    from mosaic_tpu.obs import timeline as _tl

                    i0 = len(stages)
                    res_p = sj.run_durable(
                        ring, n_batches, run_dir=run_dir + "_pipe",
                        snapshot_every=args.snapshot_every,
                        extra_arrays={"gen_key": np.asarray(key)},
                        pipeline=True,
                    )
                    tracks = _tl.build_tracks(stages[i0:])

                    def _iv(key_):
                        return tracks.get(key_, {}).get("intervals", [])

                    sync_rate = res_d.points_per_sec
                    pipe_rate = res_p.points_per_sec
                    detail["pipeline"] = dict(
                        res_p.metrics.get("pipeline", {}),
                        points_per_sec=round(pipe_rate, 1),
                        wall_s=round(res_p.wall_s, 3),
                        sustained_frac_of_single=round(
                            pipe_rate / single_rate, 4
                        ),
                        sustained_frac_delta_vs_sync=round(
                            (pipe_rate - sync_rate) / single_rate, 4
                        ),
                        speedup_vs_sync=round(
                            pipe_rate / max(sync_rate, 1e-9), 3
                        ),
                        snapshot_overlap_fraction=_tl.overlap_fraction(
                            _iv("span.stream.snapshot"),
                            _iv("span.stream.pipeline.drain")
                            + _iv("span.stream.segment"),
                        ),
                        consistent_with_sync=bool(
                            res_p.checksum == res_d.checksum
                            and res_p.matches == res_d.matches
                            and res_p.overflow == res_d.overflow
                        ),
                    )

            # (3) the join loop over the ring, prefetch on — ONE
            # dispatch, one (3,) result pull (per-batch python dispatch
            # measured 146 ms/batch for a ~63 ms device step in r05: the
            # host loop was dispatch-bound)
            sj.compile(ring, n_batches)
            res = sj.run(ring, n_batches)
            join_wall = max(res.wall_s - rtt, 1e-9)
            join_rate = res.n_points / join_wall
            line["value"] = round(join_rate, 1)
            detail.update(
                join_points_per_sec=round(join_rate, 1),
                join_wall_s=round(join_wall, 3),
                prefetch=True,
                sustained_frac_of_single=round(join_rate / single_rate, 4),
                match_rate=round(res.matches / res.n_points, 4),
                overflow=res.overflow,
                checksum=res.checksum,
            )
            if "durable" in detail:
                # the checkpointed segment loop must fold to the same
                # stats as the one-dispatch loop (free cross-check)
                detail["durable"]["consistent_with_loop"] = bool(
                    detail["durable"]["checksum"] == res.checksum
                    and detail["durable"]["matches"] == res.matches
                    and detail["durable"]["overflow"] == res.overflow
                )

            # (4) prefetch A/B: same ring without the double buffer
            # (costs one extra loop compile — --no-ab skips it)
            if not args.no_ab:
                sj0 = StreamJoin(
                    index, h3, RES, found_cap=fcap, heavy_cap=hcap,
                    prefetch=False,
                )
                sj0.compile(ring, n_batches)
                r0 = sj0.run(ring, n_batches)
                detail["no_prefetch_points_per_sec"] = round(
                    r0.n_points / max(r0.wall_s - rtt, 1e-9), 1
                )
                if (r0.checksum, r0.matches, r0.overflow) != (
                    res.checksum, res.matches, res.overflow
                ):
                    detail["prefetch_mismatch"] = True  # never expected

            # (4c) donation A/B: same loop with the ring buffer donated
            # to XLA — the loop reuses the ring's HBM in place of a
            # working copy, so the delta is the copy the non-donating
            # loop pays (ring_bytes of extra peak HBM + the copy time)
            if args.donate:
                sj_d = StreamJoin(
                    index, h3, RES, found_cap=fcap, heavy_cap=hcap,
                    prefetch=True, donate_ring=True,
                )
                ring_d = jnp.array(ring, copy=True)  # sacrificial
                sj_d.compile(ring_d, n_batches)
                rd = sj_d.run(ring_d, n_batches)
                d_rate = rd.n_points / max(rd.wall_s - rtt, 1e-9)
                detail["donation"] = dict(
                    {k: rd.metrics[k] for k in (
                        "donate_ring", "ring_donated", "ring_bytes",
                    ) if k in rd.metrics},
                    points_per_sec=round(d_rate, 1),
                    delta_vs_copy=round(d_rate - join_rate, 1),
                    consistent_with_loop=bool(
                        rd.checksum == res.checksum
                        and rd.matches == res.matches
                        and rd.overflow == res.overflow
                    ),
                )

            # (5) optional r05-comparable fused lane: gen inside the loop
            if args.fused:
                @functools.partial(jax.jit, static_argnames=("nb",))
                def stream_fused(kk, nb):
                    def body(i, acc):
                        pts = gen_batch(jax.random.fold_in(kk, i))
                        cells = sj.assign(pts)
                        return acc + fold_stats(
                            sj.join(pts, cells, index)
                        )

                    return jax.lax.fori_loop(
                        0, nb, body, jnp.zeros(3, jnp.int32)
                    )

                np.asarray(stream_fused(key, n_batches))  # compile
                t0 = time.perf_counter()
                np.asarray(stream_fused(key, n_batches))
                fw = max(time.perf_counter() - t0 - rtt, 1e-9)
                detail["fused_points_per_sec"] = round(
                    n_batches * batch / fw, 1
                )

            # (6) high-water memory AFTER the loop (cumulative peak) —
            # every lane must report a REAL number: the census fallback
            # always sees at least the ring, so 0 is a measurement bug
            # (STREAM_r05's peak_hbm_bytes: 0), never a valid artifact
            peak, src = hbm_peak(dev)
            detail["peak_hbm_bytes"] = peak
            detail["hbm_source"] = src
            assert peak > 0, (
                f"peak_hbm_bytes must be > 0 (source={src!r}) — the "
                "live-buffer census fallback should at least see the ring"
            )

            # (7) bit-identity against the per-batch path (CPU CI)
            if args.verify:
                nb_v = min(n_batches, 2 * k + 1)
                rs = sj.run(ring, nb_v, collect=True)
                rb = sj.run_batched(ring, nb_v)
                same = bool(np.array_equal(rs.outs, rb.outs)) and (
                    rs.checksum, rs.matches, rs.overflow
                ) == (rb.checksum, rb.matches, rb.overflow)
                detail["verified"] = same
                if not same:
                    raise AssertionError("stream path != per-batch path")
        else:
            # host-stream: double-buffered H2D; stats accumulate ON
            # DEVICE and cross to the host once per SYNC_EVERY batches
            # (a per-batch float() pays one sync round trip each). This
            # mode is bounded by the host-to-device link, not the join
            # (reported as host_transfer_limited, not hidden).
            detail["mode"] = "host-stream"
            fold = jax.jit(fold_stats)

            def host_batch(i):
                r = np.random.default_rng(1000 + i)
                return r.uniform(bbox[:2], bbox[2:], (batch, 2))

            def stage_put(i):
                return jax.device_put(jnp.asarray(host_batch(i)))

            SYNC_EVERY = 16
            h2d_s = 0.0
            t0 = time.perf_counter()
            acc = None
            nxt = stage_put(0)
            for i in range(n_batches):
                cur = nxt
                if i + 1 < n_batches:
                    th = time.perf_counter()
                    nxt = stage_put(i + 1)  # async put overlaps batch i
                    h2d_s += time.perf_counter() - th
                s = fold(sj.step(cur))
                acc = s if acc is None else acc + s
                if (i + 1) % SYNC_EVERY == 0:
                    np.asarray(acc)
            acc_np = np.asarray(acc)
            wall = time.perf_counter() - t0
            n_total = n_batches * batch
            sustained = n_total / wall
            line["value"] = round(sustained, 1)
            detail.update(
                wall_s=round(wall, 2),
                host_stage_s=round(h2d_s, 2),
                join_points_per_sec=round(sustained, 1),
                sustained_frac_of_single=round(
                    sustained / single_rate, 4
                ),
                host_transfer_limited=bool(sustained < 0.5 * single_rate),
                match_rate=round(int(acc_np[1]) / n_total, 4),
                overflow=int(acc_np[2]),
                checksum=int(acc_np[0]),
            )
            peak, src = hbm_peak(dev)
            detail["peak_hbm_bytes"] = peak
            detail["hbm_source"] = src
            assert peak > 0, (
                f"peak_hbm_bytes must be > 0 (source={src!r}) — the "
                "census fallback should at least see the staged batch"
            )
        root_span.end()
        if args.slo:
            # still inside the capture scope: breach transitions emit
            # REAL slo_violation events that land in the exported trail
            from mosaic_tpu.obs import slo as _slo

            detail["slo"] = _slo.evaluate_trail(stages)
        cap_events.__exit__(None, None, None)
    except Exception as e:  # the artifact line must still parse
        detail["error"] = repr(e)[:400]
        try:
            import jax as _j

            detail.setdefault("device", str(_j.devices()[0]))
        except Exception:
            detail.setdefault("device", "unknown")

    if args.trail or args.chrome_trace:
        try:
            from mosaic_tpu import obs as _obs

            if root_span is not None:
                root_span.end()  # idempotent; closes on the error path
            if args.trail:
                _obs.write_jsonl(stages, args.trail)
            if args.chrome_trace:
                _obs.write_chrome_trace(stages, args.chrome_trace)
            traces = _obs.trace_summary(stages)
            detail["traces"] = {
                "count": len(traces),
                "connected": sum(
                    1 for t in traces.values()
                    if t["roots"] == 1 and not t["orphans"]
                ),
            }
        except Exception as e:
            detail["trail_error"] = repr(e)[:200]
    detail["stages"] = [
        s for s in stages if s.get("event") == "stream_stage"
    ]
    # percentile rollup via the shared helper (the serve bench uses the
    # same one for request latencies — one p99 definition everywhere)
    try:
        from mosaic_tpu.runtime import telemetry as _tele

        detail["stage_summary"] = _tele.summarize(
            detail["stages"], event="stream_stage"
        )
    except Exception:
        pass
    detail["total_wall_s"] = round(time.perf_counter() - t_all, 1)
    out = json.dumps(line)
    emit_to.write(out + "\n")
    emit_to.flush()
    if args.out:
        with open(args.out, "w") as f:
            f.write(out + "\n")
    if detail.get("error"):
        sys.exit(1)


if __name__ == "__main__":
    main()
