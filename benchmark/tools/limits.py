#!/usr/bin/env python3
"""Read, on the chip and at the cell's own size, the numbers `correct`
compares: the program's over many seeds and the lower-precision control's
over a few — in ONE process, so the index is built and the programs are
compiled once. The limits in the configuration files were set from this
tool's output (PERF.md section 2 gives the readings).

    python3 benchmark/tools/limits.py --workload taxi.stream \
        --seeds 12 --control-seeds 3 --seconds 4

Not part of a benchmark run: the driver never calls it.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

try:
    from . import _cell
except ImportError:  # run as a script
    import _cell


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--first-seed", type=int, default=2_300_000_001)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--root", default=_cell.ROOT)
    args = ap.parse_args(argv)

    opened = _cell.open_cell(args.root, args.workload, args.rehearsal)
    device, kind = opened[4], opened[5]
    deployment = None
    rows = []
    plan = [(False, args.first_seed + 7919 * i) for i in range(args.seeds)]
    plan += [(True, args.first_seed + 7919 * i) for i in range(args.control_seeds)]
    for control, seed in plan:
        ctx = _cell.context(
            opened, seed, args.seconds, rehearsal=args.rehearsal,
            control=control, deployment=deployment,
        )
        deployment = ctx.deployment
        t0 = time.perf_counter()
        state = kind.prepare(ctx)
        try:
            result = kind.window(ctx, state)
            comps = kind.check(ctx, state)
        finally:
            kind.close(ctx, state)
        row = {
            "control": control, "seed": seed, "failed": result["failed"],
            "seconds": round(time.perf_counter() - t0, 2),
            **{c.name: c.value for c in comps},
            **{"ok." + c.name: c.ok for c in comps},
        }
        rows.append(row)
        print("limits-row " + json.dumps(row), flush=True)
    names = [k for k in rows[0] if not k.startswith("ok.") and k not in
             ("control", "seed", "failed", "seconds")]
    summary = {}
    for n in names:
        sound = [r[n] for r in rows if not r["control"]]
        ctrl = [r[n] for r in rows if r["control"]]
        summary[n] = {
            "sound_max": max(sound) if sound else None,
            "sound_min": min(sound) if sound else None,
            "control_min": min(ctrl) if ctrl else None,
            "control_max": max(ctrl) if ctrl else None,
        }
    print("limits-summary " + json.dumps(
        {"workload": args.workload, "device": device, "summary": summary}
    ), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
