"""Performance regression gate: enforce the committed stage-time shape.

The `BENCH_*.json` trajectory records how fast each round was; nothing
so far FAILED a build when a stage silently got slower. This gate turns
the bench trails (`--trail`, exported by serve_bench/stream_bench)
into an enforced contract against a committed golden
(`tests/goldens/perf_gate.json`), MLPerf-style but CPU-safe:

**What is compared.** For every stage key (see
`tools/trace_report.py`: ``stream_stage.join_loop``,
``serve_stage.dispatch``, ...) the gate computes the stage's *odds* —
its total seconds over the total of every OTHER stage in the same
trail. Each ``--trail`` is its own odds pool: one bench's wall time
cannot dilute another bench's odds (pooling across benches would sink
small stages below the noise floor, where a 10x slowdown can no longer
escape ``odds_floor``); a stage that appears in several trails gates
on its worst pool. Odds are invariant under uniform machine speed (a
CI runner 3x slower than the golden machine scales every stage alike),
but a regression in ONE stage moves its odds by the regression factor
— so the tolerance can be modest (default 3x) while a genuine 10x
stage slowdown still fails loudly on any machine (the negative lane in
CI injects exactly that via ``--inject-slowdown``).

**Gate rule** per golden stage with recorded odds g: fresh odds must
satisfy ``odds <= g * tolerance + odds_floor`` (the floor forgives
sub-noise stages); a golden stage marked ``"require": true`` that is
absent from the fresh trails fails (a vanished stage is a coverage
regression, not a speedup). Optional per-stage ``"max_seconds"`` adds
an absolute ceiling for lanes where wall time itself is the contract.

``--update`` rewrites the golden from the fresh trails (commit the
result). The last stdout line is one JSON object; exit 0 = green.

Usage (CI obs-smoke lane):
  python tools/stream_bench.py ... --trail /tmp/stream.jsonl
  python tools/serve_bench.py ...  --trail /tmp/serve.jsonl
  python tools/perf_gate.py --golden tests/goldens/perf_gate.json \
      --trail /tmp/stream.jsonl --trail /tmp/serve.jsonl
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))

DEFAULT_GOLDEN = os.path.join(REPO, "tests", "goldens", "perf_gate.json")
DEFAULT_TOLERANCE = 3.0
DEFAULT_ODDS_FLOOR = 0.02
#: stage keys the gate ignores — spans double-count their timed events,
#: and one-off sub-ms bookkeeping events are pure noise
SKIP_PREFIXES = ("span.",)


def stage_odds(events) -> dict:
    """``{stage_key: {"seconds", "count", "odds"}}`` over ONE trail's
    events (one odds pool); odds = seconds / (total - seconds)."""
    from trace_report import stage_breakdown

    stages = {
        k: v
        for k, v in stage_breakdown(events).items()
        if not k.startswith(SKIP_PREFIXES)
    }
    total = sum(v["total_s"] for v in stages.values())
    out = {}
    for key, v in stages.items():
        rest = max(total - v["total_s"], 1e-9 * max(total, 1e-9))
        out[key] = {
            "seconds": v["total_s"],
            "count": v["count"],
            "odds": round(v["total_s"] / rest, 6),
        }
    return out


def apply_slowdown(pool: dict, stage: str, factor: float) -> dict:
    """Scale one stage's seconds within its pool and recompute every
    odds in that pool (what a real single-stage regression does)."""
    scaled = {
        k: dict(v, seconds=v["seconds"] * (factor if k == stage else 1.0))
        for k, v in pool.items()
    }
    total = sum(v["seconds"] for v in scaled.values())
    for v in scaled.values():
        rest = max(total - v["seconds"], 1e-9 * max(total, 1e-9))
        v["odds"] = round(v["seconds"] / rest, 6)
    return scaled


def merge_pools(pools) -> dict:
    """Union of per-trail pools: seconds/count sum across trails, odds
    gate on the worst (largest) pool — a stage must be healthy in every
    bench it appears in."""
    out: dict = {}
    for pool in pools:
        for k, v in pool.items():
            cur = out.get(k)
            if cur is None:
                out[k] = dict(v)
            else:
                cur["seconds"] = round(cur["seconds"] + v["seconds"], 6)
                cur["count"] += v["count"]
                cur["odds"] = max(cur["odds"], v["odds"])
    return out


def evaluate(
    fresh: dict, golden: dict
) -> tuple[bool, dict]:
    """Apply the gate rule; returns (green, per-stage verdicts)."""
    tol = float(golden.get("tolerance", DEFAULT_TOLERANCE))
    floor = float(golden.get("odds_floor", DEFAULT_ODDS_FLOOR))
    verdicts = {}
    green = True
    for key, g in sorted(golden.get("stages", {}).items()):
        f = fresh.get(key)
        if f is None:
            ok = not g.get("require", False)
            verdicts[key] = {
                "status": "missing" if ok else "MISSING_REQUIRED",
                "ok": ok,
            }
            green &= ok
            continue
        limit = float(g["odds"]) * tol + floor
        ok = f["odds"] <= limit
        v = {
            "status": "ok" if ok else "SLOW",
            "ok": ok,
            "odds": f["odds"],
            "golden_odds": g["odds"],
            "limit": round(limit, 6),
            "seconds": f["seconds"],
        }
        max_s = g.get("max_seconds")
        if max_s is not None and f["seconds"] > float(max_s):
            v.update(status="OVER_ABSOLUTE", ok=False)
            ok = False
        verdicts[key] = v
        green &= ok
    return green, verdicts


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--trail", action="append", required=True,
                    help="trail file (repeatable; each trail is its "
                    "own odds pool)")
    ap.add_argument("--golden", default=DEFAULT_GOLDEN)
    ap.add_argument("--update", action="store_true",
                    help="rewrite the golden from these trails")
    ap.add_argument("--tolerance", type=float, default=None,
                    help="override the golden's odds tolerance")
    ap.add_argument("--inject-slowdown", default=None,
                    metavar="STAGE:FACTOR",
                    help="test knob: scale one fresh stage's seconds "
                    "(the CI negative lane proves the gate turns red)")
    ap.add_argument("--stages-prefix", action="append", default=None,
                    metavar="PREFIX",
                    help="gate only golden stages under these key "
                    "prefixes (repeatable) — a job that produces one "
                    "lane's trail (multichip-smoke) gates its own pool "
                    "without every other bench's trail on hand")
    args = ap.parse_args()

    from mosaic_tpu.obs import export

    pools = [stage_odds(export.read_trail(p)) for p in args.trail]

    if args.inject_slowdown:
        stage, factor = args.inject_slowdown.rsplit(":", 1)
        if not any(stage in pool for pool in pools):
            sys.stderr.write(f"inject-slowdown: no stage {stage!r}\n")
            return 2
        pools = [
            apply_slowdown(pool, stage, float(factor))
            if stage in pool else pool
            for pool in pools
        ]
    fresh = merge_pools(pools)

    if args.update:
        golden = {
            "tolerance": args.tolerance or DEFAULT_TOLERANCE,
            "odds_floor": DEFAULT_ODDS_FLOOR,
            "note": (
                "stage odds (seconds vs all other stages) from the CPU "
                "smoke lanes; regenerate: python tools/perf_gate.py "
                "--update --trail ... (commit the result)"
            ),
            "stages": {
                k: {
                    "odds": v["odds"],
                    "seconds": round(v["seconds"], 4),
                    "require": True,
                }
                for k, v in sorted(fresh.items())
            },
        }
        os.makedirs(os.path.dirname(args.golden), exist_ok=True)
        with open(args.golden, "w") as f:
            json.dump(golden, f, indent=2, sort_keys=True)
            f.write("\n")
        sys.stderr.write(
            f"wrote {args.golden} ({len(golden['stages'])} stages)\n"
        )
        sys.stdout.write(json.dumps(
            {"metric": "perf_gate", "updated": args.golden,
             "stages": len(golden["stages"])}
        ) + "\n")
        return 0

    with open(args.golden) as f:
        golden = json.load(f)
    if args.tolerance is not None:
        golden["tolerance"] = args.tolerance
    if args.stages_prefix:
        pref = tuple(args.stages_prefix)
        golden["stages"] = {
            k: v for k, v in golden["stages"].items()
            if k.startswith(pref)
        }
        if not golden["stages"]:
            sys.stderr.write(
                f"stages-prefix {pref} matches no golden stage\n"
            )
            return 2
    green, verdicts = evaluate(fresh, golden)

    for key, v in sorted(verdicts.items()):
        mark = "ok " if v["ok"] else "RED"
        extra = (
            f" odds {v['odds']:.4f} vs limit {v['limit']:.4f}"
            if "odds" in v else ""
        )
        sys.stderr.write(f"  [{mark}] {key}: {v['status']}{extra}\n")
    sys.stderr.write(
        f"perf gate: {'GREEN' if green else 'RED'} "
        f"({len(verdicts)} gated stages, "
        f"tolerance {golden.get('tolerance', DEFAULT_TOLERANCE)}x)\n"
    )
    sys.stdout.write(json.dumps({
        "metric": "perf_gate",
        "pass": green,
        "golden": args.golden,
        "stages": verdicts,
    }) + "\n")
    return 0 if green else 1


if __name__ == "__main__":
    sys.exit(main())
