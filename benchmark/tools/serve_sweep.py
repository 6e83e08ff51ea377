#!/usr/bin/env python3
"""Find the knee of an open-loop serve cell once, on the chip: one engine,
one window per swept rate, in one process. The knee is the highest swept
rate at which no request is shed and the p95 of the window's second half
is within 1.25x of its first half, and at which the generator offered the
schedule (its own lag's p95 within ``--max-lag-ms``: a generator that runs
late is a backlog too, one that both halves share); the cell's traffic
file then fixes the
rate at 0.8 of it as a number. PERF.md holds the table this prints.

    python3 benchmark/tools/serve_sweep.py --workload taxi.serve \
        --rates 100,200,400,800,1600 --seconds 10

Not part of a benchmark run: the driver never calls it.
"""

from __future__ import annotations

import argparse
import json
import sys

try:
    from . import _cell
except ImportError:  # run as a script
    import _cell


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=2_300_100_003)
    ap.add_argument("--max-lag-ms", type=float, default=5.0)
    ap.add_argument("--set", action="append", default=[], metavar="KEY=JSON",
                    help="override a parameter of the mix, to explore")
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--root", default=_cell.ROOT)
    args = ap.parse_args(argv)

    from benchmark.harness.stats import percentile

    opened = _cell.open_cell(args.root, args.workload, args.rehearsal)
    traffic, device, kind = opened[3], opened[4], opened[5]
    for item in args.set:
        key, _, value = item.partition("=")
        traffic[key] = json.loads(value)
    ctx = _cell.context(opened, args.seed, args.seconds,
                        rehearsal=args.rehearsal)
    state = kind.prepare(ctx)
    table = []
    try:
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            mix = dict(traffic, rate_per_s=rate)
            ctx.traffic = mix
            due, sizes, starts = kind.schedule(mix, args.seed + i, args.seconds)
            state.update(due=due, sizes=sizes, starts=starts)
            result = kind.window(ctx, state)
            lat = ctx.series["latency_ms"]
            first = ctx.counters["p95_first_half_ms"]
            second = ctx.counters["p95_second_half_ms"]
            row = {
                "rate_per_s": rate, "requests": result["attempted"],
                "failed": result["failed"], "shed": ctx.counters["shed"],
                "p50_ms": percentile(lat, 0.5), "p95_ms": percentile(lat, 0.95),
                "p99_ms": percentile(lat, 0.99),
                "p95_first_half_ms": first, "p95_second_half_ms": second,
                "gen_lag_p95_ms": percentile(ctx.series["gen_lag_s"], 0.95) * 1e3,
                "occupancy": ctx.counters["occupancy_mean"],
                "requests_per_batch": ctx.counters["requests_per_batch"],
                "rows_per_s": ctx.counters["rows"] / ctx.counters["window_s"],
            }
            row["steady"] = bool(
                row["shed"] == 0 and first and second
                and second <= 1.25 * first
            )
            # was the schedule in fact offered? A generator that runs late
            # has a backlog of its own, which halves of one window share
            row["offered"] = bool(row["gen_lag_p95_ms"] <= args.max_lag_ms)
            table.append(row)
            print("sweep-row " + json.dumps(row), flush=True)
    finally:
        kind.close(ctx, state)
    steady = [
        r["rate_per_s"] for r in table if r["steady"] and r["offered"]
    ]
    print("sweep-summary " + json.dumps({
        "workload": args.workload, "device": device,
        "knee_per_s": max(steady) if steady else None,
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
