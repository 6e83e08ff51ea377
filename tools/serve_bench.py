"""Online-serving load generator: the request-facing twin of
`tools/stream_bench.py`.

Drives a :class:`mosaic_tpu.serve.ServeEngine` (resident zone index,
warmed bucket ladder) with either load model:

- **closed loop** (``--mode closed``): ``--concurrency`` workers each
  submit their next request the moment the previous one resolves — the
  saturation throughput measurement;
- **open loop** (``--mode open``): requests arrive on a Poisson clock at
  ``--rate`` req/s regardless of completions — the overload measurement.
  When the arrival rate exceeds capacity the engine must SHED (typed
  ``Overloaded`` at admission or deadline expiry), never queue without
  bound: the shed rate and the p99 of *admitted* requests are the
  headline here.

Reported (last stdout line is ALWAYS one machine-parseable JSON object;
everything else goes to stderr): request + row throughput, latency
percentiles of admitted requests (`telemetry.summarize` over the
engine's ``serve_request`` events — the same helper stream_bench uses),
batch occupancy, shed/quarantine counters, and the compile story
(ladder size, warmup signatures, cold compiles after warmup, backend
compile count when jax's monitoring hook is available).

CPU CI smoke:
  JAX_PLATFORMS=cpu python tools/serve_bench.py \
      --mode closed --requests 200 --concurrency 8 --rows-max 512
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _tenant_ab(index, h3, bbox, args, detail) -> float:
    """The --tenants lane: tenant 0 floods at ``--aggressor-mult`` x the
    base rate while tenants 1..N-1 run at the base rate, once against a
    :class:`ServeRouter` (hard isolation: per-tenant queues/deadlines)
    and once against a single shared-queue engine. Per-tenant admission,
    shed-by-reason counts, and client-side latency percentiles land in
    ``detail``; returns the isolated lane's worst VICTIM shed rate (the
    headline — structurally ~0, because the aggressor cannot occupy a
    victim's quota)."""
    import concurrent.futures as cf
    import tempfile

    from bench import RES
    from mosaic_tpu.runtime import telemetry
    from mosaic_tpu.runtime.errors import Overloaded
    from mosaic_tpu.serve import BucketLadder, ServeEngine, ServeRouter

    n = args.tenants
    mult = args.aggressor_mult
    reqs = {}
    for t in range(n):
        r = np.random.default_rng(args.seed + t)
        count = int(args.requests * (mult if t == 0 else 1))
        sizes = r.integers(args.rows_min, args.rows_max + 1, count)
        reqs[t] = [
            r.uniform(bbox[:2], bbox[2:], (int(k), 2)) for k in sizes
        ]
    rates = {t: args.rate * (mult if t == 0 else 1.0) for t in range(n)}

    def load(submit):
        """Open-loop Poisson per tenant; latency stamped by the future's
        done-callback (completion time, not drain time)."""
        stats = {
            t: {"admitted": 0, "shed_submit": 0, "shed_deadline": 0,
                "shed_other": 0, "lat": []}
            for t in range(n)
        }
        lock = threading.Lock()
        futures: list = []
        sinks = telemetry.current_sinks()

        def worker(t):
            # router_stage.admit is recorded on the submitting thread;
            # adopting the caller's sinks puts it in the bench trail
            telemetry.adopt_sinks(sinks)
            r = np.random.default_rng(1000 + t)
            next_t = time.perf_counter()
            for pts in reqs[t]:
                next_t += float(r.exponential(1.0 / rates[t]))
                lag = next_t - time.perf_counter()
                if lag > 0:
                    time.sleep(lag)
                t0 = time.perf_counter()
                try:
                    f = submit(t, pts)
                except Overloaded:
                    with lock:
                        stats[t]["shed_submit"] += 1
                    continue
                with lock:
                    stats[t]["admitted"] += 1
                    futures.append(f)

                def done(f, t=t, t0=t0):
                    dt = time.perf_counter() - t0
                    exc = f.exception()
                    with lock:
                        if exc is None:
                            stats[t]["lat"].append(dt)
                        elif (
                            isinstance(exc, Overloaded)
                            and exc.reason == "deadline"
                        ):
                            stats[t]["shed_deadline"] += 1
                        else:
                            stats[t]["shed_other"] += 1

                f.add_done_callback(done)

        threads = [
            threading.Thread(target=worker, args=(t,), daemon=True)  # lint: thread-context-adoption-ok (load generator: client-side latency only, no telemetry emitted on these threads)
            for t in range(n)
        ]
        t0 = time.perf_counter()
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        cf.wait(futures)
        wall = time.perf_counter() - t0
        per = {}
        for t in range(n):
            s = stats[t]
            lat = np.asarray(s["lat"])
            total = len(reqs[t])
            per[f"tenant_{t}"] = {
                "requests": total,
                "admitted": s["admitted"],
                "completed": int(lat.size),
                "shed_submit": s["shed_submit"],
                "shed_deadline": s["shed_deadline"],
                "shed_other": s["shed_other"],
                "shed_rate": round(
                    (s["shed_submit"] + s["shed_deadline"]) / max(total, 1),
                    4,
                ),
                "p50": round(float(np.percentile(lat, 50)), 6)
                if lat.size else None,
                "p99": round(float(np.percentile(lat, 99)), 6)
                if lat.size else None,
            }
        return per, wall

    ekw = dict(
        ladder=BucketLadder(args.min_bucket, args.max_bucket),
        max_batch_rows=min(args.max_batch, args.max_bucket),
        max_wait_s=args.window_ms / 1e3,
        queue_capacity=args.queue_cap,
        default_deadline_s=args.deadline_ms / 1e3,
        bounds=bbox,
    )

    # isolated: per-tenant engines behind the router; the shared AOT
    # store means tenant 0 exports the ladder once and every other
    # tenant warms by loading it
    store = tempfile.mkdtemp(prefix="serve_tenants_")
    router = ServeRouter(
        h3, max_resident=n, program_store=store, engine_defaults=ekw,
    )
    t0 = time.perf_counter()
    warm = {}
    for t in range(n):
        warm[f"tenant_{t}"] = router.add_tenant(
            f"tenant_{t}", index, RES
        ).get("aot")
    warm_wall = time.perf_counter() - t0
    iso_per, iso_wall = load(
        lambda t, pts: router.submit(f"tenant_{t}", pts)
    )
    rm = router.metrics()
    router_shed = {
        name: {
            "submitted": m["submitted_router"],
            "shed_admit": m["shed_admit_router"],
        }
        for name, m in rm["tenants"].items()
    }
    router.close()

    # shared: one engine, one queue — every tenant behind the aggressor
    eng = ServeEngine(index, h3, RES, **ekw)
    eng.warmup()
    sh_per, sh_wall = load(lambda t, pts: eng.submit(pts))
    eng.close()

    victims = [f"tenant_{t}" for t in range(1, n)]
    iso_victim = max(iso_per[v]["shed_rate"] for v in victims)
    sh_victim = max(sh_per[v]["shed_rate"] for v in victims)
    detail.update(
        tenants=n,
        aggressor="tenant_0",
        aggressor_mult=mult,
        rate_per_tenant=args.rate,
        isolated={
            "per_tenant": iso_per,
            "router_shed": router_shed,
            "warmup": {"aot": warm, "wall_s": round(warm_wall, 3)},
            "resident": rm["resident"],
            "wall_s": round(iso_wall, 3),
        },
        shared={"per_tenant": sh_per, "wall_s": round(sh_wall, 3)},
        victim_shed_rate={"isolated": iso_victim, "shared": sh_victim},
    )
    return iso_victim


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=("closed", "open"), default="closed")
    ap.add_argument("--requests", type=int, default=500)
    ap.add_argument("--concurrency", type=int, default=8,
                    help="closed-loop worker count")
    ap.add_argument("--rate", type=float, default=200.0,
                    help="open-loop arrival rate, requests/sec")
    ap.add_argument("--rows-min", type=int, default=1)
    ap.add_argument("--rows-max", type=int, default=1024)
    ap.add_argument("--deadline-ms", type=float, default=2000.0)
    ap.add_argument("--window-ms", type=float, default=2.0,
                    help="micro-batch max-wait window")
    ap.add_argument("--max-batch", type=int, default=16384)
    ap.add_argument("--queue-cap", type=int, default=64)
    ap.add_argument("--min-bucket", type=int, default=64)
    ap.add_argument("--max-bucket", type=int, default=16384)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--tenants", type=int, default=0,
                    help=">= 2 runs the multi-tenant isolation A/B lane "
                    "instead of the single-engine bench: tenant 0 floods "
                    "at --aggressor-mult x the base rate, once against a "
                    "ServeRouter (per-tenant queues) and once against one "
                    "shared-queue engine; per-tenant shed counts and "
                    "latency land in the final JSON")
    ap.add_argument("--aggressor-mult", type=float, default=10.0,
                    help="tenant 0's rate/request multiplier in the "
                    "--tenants lane")
    ap.add_argument("--poison", type=int, default=0,
                    help="inject N NaN rows into one request "
                    "(quarantine demo lane)")
    ap.add_argument("--slo", action="store_true",
                    help="evaluate the run's trail against the default "
                    "SLO specs (MOSAIC_SLO_* thresholds) over the whole "
                    "run; verdicts land in detail.slo and breaches emit "
                    "real slo_violation events into the trail")
    ap.add_argument("--trail", default=None,
                    help="export the captured telemetry trail "
                    "(spans included) as JSONL")
    ap.add_argument("--chrome-trace", default=None,
                    help="export the trail as Chrome trace-event JSON "
                    "(Perfetto-loadable)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    # the LAST stdout line must be the JSON artifact
    emit_to = sys.stdout
    sys.stdout = sys.stderr

    t_all = time.perf_counter()
    detail: dict = {}
    line = {
        "metric": "serve_throughput",
        "value": 0.0,
        "unit": "requests/sec",
        "detail": detail,
    }
    from mosaic_tpu.runtime.platform import (
        configure_compile_cache,
        require_device,
    )

    # raises off-TPU unless JAX_PLATFORMS=cpu asked for the CPU
    detail["device_info"] = require_device()
    detail["compile_cache_dir"] = configure_compile_cache()
    try:
        import jax

        from bench import RES, _load_or_build_index, _load_zones
        from mosaic_tpu.core.index.h3 import H3IndexSystem
        from mosaic_tpu.runtime import telemetry
        from mosaic_tpu.runtime.errors import Overloaded
        from mosaic_tpu.serve import BucketLadder, ServeEngine
        from mosaic_tpu.sql.join import join_cache_stats

        h3 = H3IndexSystem()
        zones, zones_src = _load_zones()
        b = zones.bounds()
        bbox = (
            float(np.nanmin(b[:, 0])), float(np.nanmin(b[:, 1])),
            float(np.nanmax(b[:, 2])), float(np.nanmax(b[:, 3])),
        )
        index, _, _ = _load_or_build_index(zones, zones_src, h3)
        detail.update(
            device=str(jax.devices()[0]), zones=zones_src, mode=args.mode,
        )

        if args.tenants >= 2:
            # multi-tenant isolation A/B: the headline is the WORST
            # victim shed rate under per-tenant queues (should be ~0
            # while the shared-queue lane's victims shed at the
            # aggressor's mercy)
            line["metric"], line["unit"] = "victim_shed_rate", "fraction"
            with telemetry.capture() as events:
                line["value"] = _tenant_ab(index, h3, bbox, args, detail)
                if args.slo:
                    # inside capture: breach transitions emit REAL
                    # slo_violation events that land in the trail
                    from mosaic_tpu.obs import slo as _slo

                    detail["slo"] = _slo.evaluate_trail(events)
            if args.trail or args.chrome_trace:
                from mosaic_tpu import obs

                if args.trail:
                    obs.write_jsonl(events, args.trail)
                if args.chrome_trace:
                    obs.write_chrome_trace(events, args.chrome_trace)
            detail["total_wall_s"] = round(time.perf_counter() - t_all, 1)
            out = json.dumps(line)
            emit_to.write(out + "\n")
            emit_to.flush()
            if args.out:
                with open(args.out, "w") as f:
                    f.write(out + "\n")
            return

        engine = ServeEngine(
            index, h3, RES,
            ladder=BucketLadder(args.min_bucket, args.max_bucket),
            max_batch_rows=args.max_batch,
            max_wait_s=args.window_ms / 1e3,
            queue_capacity=args.queue_cap,
            default_deadline_s=args.deadline_ms / 1e3,
            bounds=bbox,
        )
        t0 = time.perf_counter()
        warm = engine.warmup()
        detail["warmup"] = dict(warm, wall_s=round(
            time.perf_counter() - t0, 3))

        rng = np.random.default_rng(args.seed)
        sizes = rng.integers(
            args.rows_min, args.rows_max + 1, args.requests
        )
        reqs = [
            rng.uniform(bbox[:2], bbox[2:], (int(n), 2)) for n in sizes
        ]
        if args.poison and reqs:
            reqs[0][: args.poison] = np.nan

        shed_submit = 0
        shed_lock = threading.Lock()
        futures: list = []

        with telemetry.capture() as events:
            # capture sinks are thread-local: closed-loop workers adopt
            # the main thread's so their serve_request events land here
            main_sinks = telemetry.current_sinks()
            t_load = time.perf_counter()
            if args.mode == "closed":
                cursor = {"i": 0}
                cursor_lock = threading.Lock()

                def worker():
                    nonlocal shed_submit
                    telemetry.adopt_sinks(main_sinks)
                    while True:
                        with cursor_lock:
                            i = cursor["i"]
                            if i >= len(reqs):
                                return
                            cursor["i"] = i + 1
                        try:
                            f = engine.submit(reqs[i])
                            with shed_lock:
                                futures.append(f)
                            try:
                                f.result()
                            except Overloaded:
                                pass
                        except Overloaded:
                            with shed_lock:
                                shed_submit += 1

                threads = [
                    threading.Thread(target=worker, daemon=True)  # lint: thread-context-adoption-ok (load generator: each submit captures its own request context; engine threads adopt downstream)
                    for _ in range(max(args.concurrency, 1))
                ]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
            else:
                # open loop: Poisson arrivals at --rate, submits never
                # wait on completions; at overload the engine sheds
                next_t = time.perf_counter()
                for pts in reqs:
                    next_t += float(rng.exponential(1.0 / args.rate))
                    lag = next_t - time.perf_counter()
                    if lag > 0:
                        time.sleep(lag)
                    try:
                        futures.append(engine.submit(pts))
                    except Overloaded:
                        shed_submit += 1
                for f in futures:
                    try:
                        f.result()
                    except Overloaded:
                        pass
            load_wall = time.perf_counter() - t_load
            if args.slo:
                # inside capture: breach transitions emit REAL
                # slo_violation events that land in the exported trail
                from mosaic_tpu.obs import slo as _slo

                detail["slo"] = _slo.evaluate_trail(events)

        m = engine.metrics()
        lat = telemetry.summarize(events, event="serve_request")
        stages = telemetry.summarize(events, event="serve_stage")
        completed_rows = int(
            sum(
                e.get("rows", 0)
                for e in events
                if e.get("event") == "serve_request"
            )
        )
        admitted = len(futures)
        line["value"] = round(m["completed"] / max(load_wall, 1e-9), 1)
        detail.update(
            requests=args.requests,
            admitted=admitted,
            completed=m["completed"],
            shed_submit=shed_submit,
            shed_deadline=m["shed_deadline"],
            shed_total=shed_submit + m["shed_deadline"],
            shed_rate=round(
                (shed_submit + m["shed_deadline"]) / max(args.requests, 1),
                4,
            ),
            quarantined=m["quarantined"],
            degraded=m["degraded"],
            load_wall_s=round(load_wall, 3),
            requests_per_sec=line["value"],
            rows_per_sec=round(completed_rows / max(load_wall, 1e-9), 1),
            latency=lat,
            deadline_s=args.deadline_ms / 1e3,
            p99_under_deadline=bool(lat["p99"] <= args.deadline_ms / 1e3),
            batches=m["batches"],
            occupancy_mean=m["occupancy_mean"],
            requests_per_batch=round(
                m["batched_requests"] / max(m["batches"], 1), 2
            ),
            stage_summary=stages,
            compiles={
                "buckets": len(engine.ladder.buckets),
                "warmup_signatures": warm["signatures"],
                "cold_compiles": m["cold_compiles"],
                "backend_compiles_warmup": warm.get("backend_compiles"),
            },
            join_cache=join_cache_stats(emit=False),
        )
        engine.close()
        if args.trail or args.chrome_trace:
            from mosaic_tpu import obs

            if args.trail:
                obs.write_jsonl(events, args.trail)
            if args.chrome_trace:
                obs.write_chrome_trace(events, args.chrome_trace)
            traces = obs.trace_summary(events)
            detail["traces"] = {
                "count": len(traces),
                "connected": sum(
                    1 for t in traces.values()
                    if t["roots"] == 1 and not t["orphans"]
                ),
            }
    except Exception as e:  # the artifact line must still parse
        detail["error"] = repr(e)[:400]
        try:
            import jax as _j

            detail.setdefault("device", str(_j.devices()[0]))
        except Exception:
            detail.setdefault("device", "unknown")

    detail["total_wall_s"] = round(time.perf_counter() - t_all, 1)
    out = json.dumps(line)
    emit_to.write(out + "\n")
    emit_to.flush()
    if args.out:
        with open(args.out, "w") as f:
            f.write(out + "\n")
    if detail.get("error") and not line["value"]:
        sys.exit(1)


if __name__ == "__main__":
    main()
