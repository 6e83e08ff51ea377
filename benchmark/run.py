#!/usr/bin/env python3
"""Run one cell of the benchmark once, in this process, and print its
result as the last line of standard output.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

It needs the TPU (no CPU fallback), builds the cell's deployment and
traffic from ``--seed``, warms the cell's shapes (set-up), measures for
``--seconds``, checks the answers against the plain reference after the
window, and prints one JSON object with exactly the keys ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` with
``--trace 1``) and, last, ``checks``: each number compared beside its
limit, which are the last lines of standard error too. On any failure it
exits non-zero and prints no result.

``--rehearsal`` is the CPU debugging mode of the harness's own tests: it is
refused unless ``JAX_PLATFORMS=cpu`` asked for the CPU by name and the
configuration is a test fixture (``"rehearsal": true``), which no entry of
``BENCHMARK.json`` is.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()  # process start, as near as Python can see it

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


def main(argv=None, *, root: str | None = None, t_start: float | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU debugging of test fixtures; needs JAX_PLATFORMS=cpu")
    args = ap.parse_args(argv)
    from benchmark.harness.run_cell import run_cell

    try:
        line = run_cell(
            root or _ROOT, args.workload, args.seed, args.seconds,
            bool(args.trace), t_start=_T0 if t_start is None else t_start,
            rehearsal=args.rehearsal,
        )
    except Exception as e:  # noqa: BLE001 — the boundary: report, exit != 0
        traceback.print_exc()
        sys.stderr.flush()
        print(f"FAIL: {type(e).__name__}: {str(e)[:600]}", flush=True)
        return 1
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
