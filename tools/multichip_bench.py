"""Multi-chip sharded dispatch: measured scaling with asserted identity.

The dispatch core (`mosaic_tpu/dispatch`) runs every frontend's device
program data-parallel over a 1-D mesh with the ChipIndex replicated.
This bench is the lane's measurement twin: for each device count it
pads one batch to the bucket ladder, dispatches it through
`DispatchCore` on a ``dp``-sized mesh, and reports points/sec plus a
``scaling_efficiency`` number (rate at the largest mesh over
device-count x the single-device rate).

Identity is the non-negotiable part: at EVERY device count the sharded
result must equal the single-device result bit for bit, and the
single-device result must equal the exact f64 host oracle
(`host_join`). A rate without those asserts would be a number about a
different join.

On CPU the bench forces virtual host devices
(``--xla_force_host_platform_device_count``) so CI proves the identity
contract at mesh 1/2/4/8 — but virtual devices share the same host
cores, so CPU ``scaling_efficiency`` is correctness evidence, not a
perf claim. The >=0.8-of-linear-at-8-chips target is recorded as a
pending TPU-window criterion (``detail.scaling_gate``).

The final stdout line is ALWAYS one machine-parseable JSON object (all
other output goes to stderr). Stage timings ride the trail as
``multichip_stage.*`` events for `tools/perf_gate.py` (its own odds
pool — see the multichip-smoke CI job).

Usage:
  python tools/multichip_bench.py --points 262144 --out MULTICHIP_r07.json
  (CPU: env JAX_PLATFORMS=cpu; the bench then forces 8 virtual host
   devices itself unless XLA_FLAGS already pins a count)
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

#: the synthetic fixture: pure-arithmetic grid (the H3 digit pipeline
#: costs minutes to compile on CPU; the scaling contract is
#: index-system-agnostic) over zones with holes, multipolygons, and a
#: heavy-ish candidate mix
ZONES = [
    "POLYGON ((1 1, 13 2, 12 11, 6 14, 2 9, 1 1), "
    "(5 5, 5 8, 8 8, 8 5, 5 5))",
    "POLYGON ((20 0, 30 0, 30 10, 25 4, 20 10, 20 0))",
    "MULTIPOLYGON (((-20 -20, -12 -20, -12 -12, -20 -12, -20 -20)), "
    "((-8 -8, -2 -8, -2 -2, -8 -2, -8 -8)))",
]
BBOX = (-25.0, -25.0, 35.0, 20.0)
RES = 3


def _force_host_devices(n: int) -> None:
    """Before jax imports: expose ``n`` virtual CPU devices unless the
    caller already pinned a count (CI sets the flag explicitly)."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}".strip()
        )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--points", type=int, default=262_144)
    ap.add_argument("--passes", type=int, default=3,
                    help="timed dispatches per device count")
    ap.add_argument("--devices", default="1,2,4,8",
                    help="comma-separated mesh sizes to measure")
    ap.add_argument("--trail", default=None,
                    help="export the telemetry trail (spans included) "
                    "as JSONL")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    counts = sorted({int(c) for c in args.devices.split(",") if c.strip()})
    if counts[0] != 1:
        counts = [1] + counts  # the scaling baseline is not optional

    # the LAST stdout line must be the JSON artifact
    emit_to = sys.stdout
    sys.stdout = sys.stderr

    from mosaic_tpu.runtime.platform import (
        configure_compile_cache,
        cpu_requested,
        require_device,
    )

    if cpu_requested():  # before the first backend touch
        _force_host_devices(max(counts))

    t_all = time.perf_counter()
    # raises off-TPU unless JAX_PLATFORMS=cpu asked for the CPU
    detail: dict = {
        "device_info": require_device(),
        "compile_cache_dir": configure_compile_cache(),
    }
    line = {
        "metric": "multichip_join_points_per_sec",
        "value": 0.0,
        "unit": "points/sec",
        "detail": detail,
    }
    stages: list[dict] = []
    root_span = None
    try:
        import jax

        from mosaic_tpu import obs
        from mosaic_tpu.core.geometry import wkt
        from mosaic_tpu.core.index import CustomIndexSystem, GridConf
        from mosaic_tpu.core.tessellate import tessellate
        from mosaic_tpu.dispatch import core as dispatch
        from mosaic_tpu.runtime import telemetry
        from mosaic_tpu.sql.join import build_chip_index, host_join

        cap_events = telemetry.capture()
        stages = cap_events.__enter__()
        root_span = obs.start_span(
            "multichip_bench", devices=len(jax.devices()),
        )

        avail = len(jax.devices())
        skipped = [c for c in counts if c > avail]
        counts = [c for c in counts if c <= avail]
        if skipped:
            detail["skipped_device_counts"] = skipped

        grid = CustomIndexSystem(GridConf(-180, 180, -90, 90, 2, 10.0, 10.0))
        index = build_chip_index(
            tessellate(wkt.from_wkt(ZONES), grid, RES, keep_core_geoms=False)
        )
        rng = np.random.default_rng(42)
        pts = rng.uniform(BBOX[:2], BBOX[2:], (args.points, 2))
        detail.update(
            device=str(jax.devices()[0]),
            n_devices_available=avail,
            points=args.points,
            passes=args.passes,
        )

        # the ground truth every rate hangs off: exact f64 host join
        with telemetry.timed("multichip_stage", stage="oracle"):
            oracle = host_join(pts, index.host, grid, RES)
        detail["match_rate"] = round(float((oracle >= 0).mean()), 4)

        from mosaic_tpu.dispatch.bucket import BucketLadder

        # one bucket big enough for the whole batch: the bench measures
        # steady-state dispatch, not ladder selection (min_bucket must
        # divide over the largest mesh)
        top_bucket = 1 << max(10, (args.points - 1).bit_length())
        ladder = BucketLadder(min(1024, top_bucket), top_bucket)

        per_dev: dict = {}
        single = None
        for dp in counts:
            core = dispatch.DispatchCore(
                index, grid, RES, ladder=ladder,
                mesh=None if dp == 1 else dp,
            )
            padded, nn = core.ladder.pad(pts)
            detail.setdefault("bucket", int(padded.shape[0]))
            # first dispatch pays the (bucket, index, mesh) compile —
            # priced apart so the steady-state rate stays honest
            with telemetry.timed(
                "multichip_stage", stage=f"compile_dp{dp}"
            ):
                out = core.execute_padded(padded)[:nn]
            if dp == 1:
                single = out
                identical = bool(np.array_equal(out, oracle))
            else:
                identical = bool(np.array_equal(out, single)) and bool(
                    np.array_equal(out, oracle)
                )
            t0 = time.perf_counter()
            for _ in range(args.passes):
                with telemetry.timed("multichip_stage", stage=f"dp{dp}"):
                    core.execute_padded(padded)
            wall = time.perf_counter() - t0
            rate = args.passes * nn / max(wall, 1e-9)
            per_dev[str(dp)] = {
                "points_per_sec": round(rate, 1),
                "wall_s": round(wall, 4),
                "bit_identical": identical,
                "signatures": len(core.signatures),
            }
            sys.stderr.write(
                f"dp={dp}: {rate / 1e6:.2f}M pts/s, identical={identical}\n"
            )
            if not identical:
                raise AssertionError(
                    f"sharded dispatch at dp={dp} is not bit-identical"
                )

        detail["per_device_count"] = per_dev
        top = counts[-1]
        r1 = per_dev["1"]["points_per_sec"]
        rt = per_dev[str(top)]["points_per_sec"]
        line["value"] = rt
        detail["bit_identical_all"] = True
        detail["scaling_efficiency"] = round(rt / (top * r1), 4) if top > 1 else 1.0
        detail["scaling_gate"] = {
            "target": ">=0.8 of linear at 8 chips",
            "measured_at": top,
            "status": (
                "pending-tpu-window"
                if jax.devices()[0].platform == "cpu"
                else ("pass" if rt / (top * r1) >= 0.8 else "FAIL")
            ),
            "note": (
                "CPU virtual devices share the same host cores — the "
                "identity asserts are the CPU payload; efficiency gates "
                "on real chips"
            ),
        }
        root_span.end()
        cap_events.__exit__(None, None, None)
    except Exception as e:  # lint: broad-except-ok (bench must always emit its JSON line; rc carries failure)
        detail["error"] = repr(e)[:400]

    if args.trail:
        try:
            from mosaic_tpu import obs as _obs

            if root_span is not None:
                root_span.end()  # idempotent; closes on the error path
            _obs.write_jsonl(stages, args.trail)
        except Exception as e:  # lint: broad-except-ok (a sick trail disk degrades the trail, not the bench)
            detail["trail_error"] = repr(e)[:200]
    detail["stages"] = [
        s for s in stages if s.get("event") == "multichip_stage"
    ]
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
