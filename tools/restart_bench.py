"""Restart-storm bench: zero-cold-start serving over the AOT program store.

The claim under test (`mosaic_tpu/dispatch/programs.py`): once a serve
process has exported its compiled ladder, killing the process and
relaunching it against the same ``MOSAIC_PROGRAM_STORE`` must warm up by
LOADING serialized executables — ``cold_compiles == 0``, zero backend
compiles, admitted p99 within the deadline from the very first admitted
request — and every failure path must degrade to plain compilation with
bit-identical answers, never a wrong program, never a crash.

Lanes (parent process; each serve run is a REAL child process so jax's
in-memory executable cache cannot mask a store miss):

- **cold**: empty store, runs to completion — exports the ladder and
  records the compile-storm warmup cost the store amortizes;
- **storm**: ``--restarts`` relaunches, each SIGKILLed mid-load (after
  its early report flush) and each asserted to have warmed purely from
  the store (``aot.loaded > 0``, ``aot.exported == 0``,
  ``backend_compiles == 0``);
- **kill_mid_export**: a fresh store's child is SIGKILLed the moment the
  first payload lands — the atomic payload-before-sidecar write order
  means the relaunch sees at worst an orphaned payload (clean miss) and
  re-exports;
- **corrupt**: one payload's bytes are flipped in the populated store —
  the relaunch must record ``program_store_corrupt_skipped``, fall back
  to compilation, self-heal the entry, and still answer bit-identically.

Every lane's child answers a fixed probe set and reports its SHA-256;
the parent asserts ALL lanes hash identically. The last stdout line is
one machine-parseable JSON object (committed as ``SERVE_RESTART_r16``).

Fleet story: every child exports its telemetry trail (early flush
before the load phase, so a SIGKILLed child still leaves evidence;
final flush when it survives), each headed by the child's incarnation
id. The parent stitches ALL of them with `tools/fleet_report.py` into
one wall-clock timeline — ``detail.fleet`` carries the restart chain
(one link per incarnation, with the dark-gap seconds between a kill
and the relaunch's first event).

CPU CI smoke:
  JAX_PLATFORMS=cpu python tools/restart_bench.py \
      --restarts 2 --requests 120 --rate 120
"""

from __future__ import annotations

import argparse
import glob
import hashlib
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

BBOX = (-25.0, -25.0, 35.0, 20.0)
RES = 3
PROBE_REQUESTS = 16
PROBE_ROWS = 96


def _build_index():
    """Deterministic synthetic workload: rebuildable identically in every
    child, so the tessellation fingerprint (the program-store key) is
    restart-stable by construction."""
    from mosaic_tpu.core.geometry import wkt
    from mosaic_tpu.core.index import CustomIndexSystem, GridConf
    from mosaic_tpu.core.tessellate import tessellate
    from mosaic_tpu.sql.join import build_chip_index

    grid = CustomIndexSystem(GridConf(-180, 180, -90, 90, 2, 10.0, 10.0))
    col = wkt.from_wkt(
        [
            "POLYGON ((1 1, 13 2, 12 11, 6 14, 2 9, 1 1))",
            "POLYGON ((-20 -20, -5 -20, -5 -5, -20 -5, -20 -20))",
            "POLYGON ((20 -10, 30 -10, 30 5, 20 5, 20 -10))",
        ]
    )
    index = build_chip_index(tessellate(col, grid, RES, keep_core_geoms=False))
    return index, grid


def _probe_set():
    rng = np.random.default_rng(123)
    return [
        rng.uniform(BBOX[:2], BBOX[2:], (PROBE_ROWS, 2))
        for _ in range(PROBE_REQUESTS)
    ]


def _write_report(path: str, report: dict) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(report, f)
    os.replace(tmp, path)


def child_main(args) -> None:
    """One serve lifetime: warm from the store, answer the probe set,
    flush an early report (the parent's kill gate), then serve open-loop
    until done or killed."""
    from mosaic_tpu.runtime import telemetry
    from mosaic_tpu.runtime.errors import Overloaded
    from mosaic_tpu.runtime.platform import require_device
    from mosaic_tpu.serve import BucketLadder, ServeEngine, backend_compiles

    # raises off-TPU unless JAX_PLATFORMS=cpu asked for the CPU. No
    # persistent compile cache here, on purpose: the cold lane's compile
    # storm is the thing this bench prices against the program store.
    require_device()

    t0 = time.perf_counter()
    index, grid = _build_index()
    bc0 = backend_compiles()
    with telemetry.capture() as events:
        engine = ServeEngine(
            index, grid, RES,
            ladder=BucketLadder(64, 1024),
            max_wait_s=0.002,
            queue_capacity=args.queue_cap,
            default_deadline_s=args.deadline_ms / 1e3,
            bounds=BBOX,
            program_store=args.store,
        )
        t_warm = time.perf_counter()
        warm = engine.warmup()
        warmup_wall = time.perf_counter() - t_warm

        # fixed probe set: the cross-lane bit-identity witness
        sha = hashlib.sha256()
        t_first = time.perf_counter()
        first_latency = None
        for pts in _probe_set():
            out = np.asarray(engine.join(pts, timeout=30.0))
            if first_latency is None:
                first_latency = time.perf_counter() - t_first
            sha.update(out.astype(np.int64).tobytes())

        def store_events():
            return {
                "corrupt_skipped": sum(
                    1 for e in events
                    if e.get("event") == "program_store_corrupt_skipped"
                ),
                "mismatch": sum(
                    1 for e in events
                    if e.get("event") == "program_store_mismatch"
                ),
                "loaded": sum(
                    1 for e in events
                    if e.get("event") == "program_store_loaded"
                ),
                "saved": sum(
                    1 for e in events
                    if e.get("event") == "program_store_saved"
                ),
            }

        bc1 = backend_compiles()
        report = {
            "phase": "serving",
            "incarnation": telemetry.INCARNATION,
            "warmup": warm,
            "warmup_wall_s": round(warmup_wall, 3),
            "startup_wall_s": round(time.perf_counter() - t0, 3),
            "first_latency_s": round(first_latency, 4),
            "backend_compiles": (
                bc1 - bc0 if bc0 is not None and bc1 is not None else None
            ),
            "cold_compiles": engine.metrics()["cold_compiles"],
            "answers_sha256": sha.hexdigest(),
            "store_events": store_events(),
        }
        # early flush BEFORE the load phase: a SIGKILLed child still
        # leaves its warmup/compile story for the parent to assert on
        _write_report(args.report, report)
        if args.trail:
            # same early-flush discipline for the trail: a SIGKILL
            # mid-load must still leave this incarnation's warmup
            # events for the parent's fleet stitch
            from mosaic_tpu import obs

            obs.write_jsonl(list(events), args.trail)

        rng = np.random.default_rng(args.seed)
        reqs = [
            rng.uniform(BBOX[:2], BBOX[2:], (int(n), 2))
            for n in rng.integers(1, args.rows_max + 1, args.requests)
        ]
        shed_submit = 0
        futures = []
        next_t = time.perf_counter()
        t_load = time.perf_counter()
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

    m = engine.metrics()
    lat = telemetry.summarize(events, event="serve_request")
    bc2 = backend_compiles()
    report.update(
        phase="done",
        requests=args.requests,
        admitted=len(futures),
        shed_submit=shed_submit,
        shed_deadline=m["shed_deadline"],
        completed=m["completed"],
        load_wall_s=round(load_wall, 3),
        latency=lat,
        deadline_s=args.deadline_ms / 1e3,
        p99_under_deadline=bool(lat["p99"] <= args.deadline_ms / 1e3),
        cold_compiles=m["cold_compiles"],
        backend_compiles=(
            bc2 - bc0 if bc0 is not None and bc2 is not None else None
        ),
        store_events=store_events(),
    )
    engine.close()
    _write_report(args.report, report)
    if args.trail:
        from mosaic_tpu import obs

        obs.write_jsonl(events, args.trail)


# --------------------------------------------------------------- parent
#
# One process for each chip: a process that has initialised a JAX backend
# holds the chip, and a child that needs it then fails or hangs. So the
# parent never touches a device — it imports no backend-initialising
# module (``main`` asserts it at the end) — and its children run strictly
# one after another: every launcher below returns only after
# ``proc.wait()`` has reaped its child, SIGKILLed or not, and a dead
# process holds no device.

def _spawn(store: str, report: str, args, extra=(), trail=None):
    if os.path.exists(report):
        os.remove(report)
    cmd = [
        sys.executable, os.path.abspath(__file__), "--child",
        "--store", store, "--report", report,
        "--requests", str(args.requests), "--rate", str(args.rate),
        "--rows-max", str(args.rows_max), "--queue-cap", str(args.queue_cap),
        "--deadline-ms", str(args.deadline_ms), "--seed", str(args.seed),
        *(("--trail", trail) if trail else ()),
        *extra,
    ]
    return subprocess.Popen(cmd, stdout=sys.stderr, stderr=sys.stderr)


def _wait_report(proc, report: str, timeout: float) -> dict:
    """Block until the child's (early or final) report exists."""
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if os.path.exists(report):
            try:
                with open(report) as f:
                    return json.load(f)
            except ValueError:
                pass  # mid-replace; retry
        if proc.poll() is not None and not os.path.exists(report):
            raise RuntimeError(
                f"child exited rc={proc.returncode} without a report"
            )
        time.sleep(0.05)
    raise RuntimeError(f"no child report after {timeout}s")


def _run_to_completion(
    store: str, report: str, args, timeout=600.0, trail=None
) -> dict:
    proc = _spawn(store, report, args, trail=trail)
    rc = proc.wait(timeout=timeout)
    if rc != 0:
        raise RuntimeError(f"child failed rc={rc}")
    with open(report) as f:
        out = json.load(f)
    if out.get("phase") != "done":
        raise RuntimeError(f"child finished in phase {out.get('phase')!r}")
    return out


def _kill_mid_load(
    store: str, report: str, args, kill_after: float, trail=None
) -> dict:
    """Launch, wait for the early report (serving has begun), then
    SIGKILL mid-load and return the early report."""
    proc = _spawn(store, report, args, trail=trail)
    out = _wait_report(proc, report, timeout=600.0)
    time.sleep(kill_after)
    if proc.poll() is None:
        proc.send_signal(signal.SIGKILL)
    proc.wait(timeout=30.0)
    with open(report) as f:
        return json.load(f)


def _kill_mid_export(store: str, report: str, args, trail=None) -> int:
    """Launch against a fresh store and SIGKILL the instant the first
    payload file lands — the tightest window around the export write."""
    proc = _spawn(store, report, args, trail=trail)
    t0 = time.monotonic()
    while time.monotonic() - t0 < 600.0:
        if glob.glob(os.path.join(store, "prog-*.bin")):
            break
        if proc.poll() is not None:
            break
        time.sleep(0.001)
    if proc.poll() is None:
        proc.send_signal(signal.SIGKILL)
    proc.wait(timeout=30.0)
    return len(glob.glob(os.path.join(store, "prog-*.bin")))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", action="store_true")
    ap.add_argument("--store", default=None)
    ap.add_argument("--report", default=None)
    ap.add_argument("--restarts", type=int, default=3,
                    help="SIGKILL-mid-load relaunch count in the storm lane")
    ap.add_argument("--requests", type=int, default=200)
    ap.add_argument("--rate", type=float, default=150.0)
    ap.add_argument("--rows-max", type=int, default=256)
    ap.add_argument("--queue-cap", type=int, default=64)
    ap.add_argument("--deadline-ms", type=float, default=2000.0)
    ap.add_argument("--kill-after", type=float, default=0.4,
                    help="seconds into the load phase to SIGKILL")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--trail", default=None,
                    help="(child) export this lifetime's telemetry "
                    "trail as JSONL, incarnation-headed; the parent "
                    "sets this per child and stitches the fleet")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    if args.child:
        child_main(args)
        return

    emit_to = sys.stdout
    sys.stdout = sys.stderr

    t_all = time.perf_counter()
    detail: dict = {}
    line = {
        "metric": "restart_warmup_s",
        "value": 0.0,
        "unit": "s",
        "detail": detail,
    }
    failures: list = []

    def check(cond, what):
        if not cond:
            failures.append(what)
            print(f"FAIL: {what}", file=sys.stderr)

    try:
        work = tempfile.mkdtemp(prefix="restart_bench_")
        store = os.path.join(work, "programs")
        report = os.path.join(work, "report.json")
        fleet_dir = os.path.join(work, "fleet")
        os.makedirs(fleet_dir, exist_ok=True)
        fleet_trails: list[str] = []

        def _t(lane: str) -> str:
            path = os.path.join(fleet_dir, f"{lane}.jsonl")
            fleet_trails.append(path)
            return path

        # ---- cold: empty store, full run; exports the ladder
        cold = _run_to_completion(store, report, args, trail=_t("cold"))
        detail["cold"] = {
            k: cold[k] for k in (
                "warmup_wall_s", "startup_wall_s", "backend_compiles",
                "cold_compiles", "latency", "p99_under_deadline",
            )
        }
        detail["cold"]["aot"] = cold["warmup"].get("aot")
        check(cold["warmup"]["aot"]["exported"] > 0, "cold run exported programs")
        check(cold["cold_compiles"] == 0, "cold run cold_compiles == 0")
        ref_hash = cold["answers_sha256"]
        hashes = {"cold": ref_hash}

        # ---- storm: kill mid-load, relaunch; every relaunch must warm
        # purely from the store
        storm = []
        for i in range(max(args.restarts, 1)):
            final = i == args.restarts - 1
            if final:
                rep = _run_to_completion(
                    store, report, args, trail=_t(f"storm_{i}")
                )
            else:
                rep = _kill_mid_load(
                    store, report, args, args.kill_after,
                    trail=_t(f"storm_{i}"),
                )
            aot = rep["warmup"].get("aot") or {}
            entry = {
                "killed": not final,
                "warmup_wall_s": rep["warmup_wall_s"],
                "startup_wall_s": rep["startup_wall_s"],
                "first_latency_s": rep["first_latency_s"],
                "backend_compiles": rep["backend_compiles"],
                "cold_compiles": rep["cold_compiles"],
                "aot": aot,
            }
            if final:
                entry["latency"] = rep["latency"]
                entry["p99_under_deadline"] = rep["p99_under_deadline"]
                entry["admitted"] = rep["admitted"]
                entry["shed_submit"] = rep["shed_submit"]
                entry["shed_deadline"] = rep["shed_deadline"]
                check(
                    rep["p99_under_deadline"],
                    f"restart {i}: admitted p99 {rep['latency']['p99']} "
                    f"within deadline",
                )
            storm.append(entry)
            hashes[f"restart_{i}"] = rep["answers_sha256"]
            check(rep["cold_compiles"] == 0, f"restart {i}: cold_compiles == 0")
            check(
                rep["backend_compiles"] in (0, None),
                f"restart {i}: backend_compiles == 0 "
                f"(got {rep['backend_compiles']})",
            )
            check(aot.get("loaded", 0) > 0, f"restart {i}: warmed from store")
            check(aot.get("exported", 1) == 0, f"restart {i}: nothing re-exported")
        detail["storm"] = storm
        line["value"] = storm[-1]["warmup_wall_s"]
        detail["warmup_speedup"] = round(
            cold["warmup_wall_s"] / max(storm[-1]["warmup_wall_s"], 1e-9), 2
        )

        # ---- kill mid-export: fresh store, SIGKILL inside the export
        # window; the relaunch sees at worst an orphaned payload
        store2 = os.path.join(work, "programs_killed")
        payloads_at_kill = _kill_mid_export(
            store2, report, args, trail=_t("kill_mid_export")
        )
        sidecars_at_kill = len(glob.glob(os.path.join(store2, "prog-*.json")))
        rep = _run_to_completion(
            store2, report, args, trail=_t("relaunch")
        )
        detail["kill_mid_export"] = {
            "payloads_at_kill": payloads_at_kill,
            "sidecars_at_kill": sidecars_at_kill,
            "relaunch_aot": rep["warmup"].get("aot"),
            "relaunch_cold_compiles": rep["cold_compiles"],
            "store_events": rep["store_events"],
        }
        hashes["kill_mid_export"] = rep["answers_sha256"]
        check(rep["cold_compiles"] == 0, "kill_mid_export relaunch serves")

        # ---- corrupt: flip bytes in one payload of the GOOD store; the
        # relaunch must skip it (typed telemetry), recompile, self-heal
        victim = sorted(glob.glob(os.path.join(store, "prog-*.bin")))[0]
        blob = bytearray(open(victim, "rb").read())
        blob[len(blob) // 2] ^= 0xFF
        with open(victim, "wb") as f:
            f.write(blob)
        rep = _run_to_completion(store, report, args, trail=_t("corrupt"))
        detail["corrupt"] = {
            "aot": rep["warmup"].get("aot"),
            "cold_compiles": rep["cold_compiles"],
            "store_events": rep["store_events"],
        }
        hashes["corrupt"] = rep["answers_sha256"]
        check(
            rep["store_events"]["corrupt_skipped"] >= 1,
            "corrupt entry skipped with typed telemetry",
        )
        check(
            rep["warmup"]["aot"]["exported"] >= 1,
            "corrupt entry self-healed by re-export",
        )
        check(rep["cold_compiles"] == 0, "corrupt lane still serves")
        # self-heal proof: one more run loads everything cleanly
        rep = _run_to_completion(store, report, args, trail=_t("healed"))
        hashes["healed"] = rep["answers_sha256"]
        check(
            rep["store_events"]["corrupt_skipped"] == 0
            and rep["warmup"]["aot"]["exported"] == 0
            and rep["backend_compiles"] in (0, None),
            "store fully healed after corrupt-lane re-export",
        )

        # ---- fleet stitch: every child trail (killed children left
        # their early flush) merged onto one wall-clock timeline
        import fleet_report as _fleet

        live = [p for p in fleet_trails if os.path.exists(p)]
        _, fleet = _fleet.stitch(live)
        detail["fleet"] = {
            "trails": len(live),
            "incarnations": len(fleet["incarnations"]),
            "chain": fleet["chain"],
        }
        check(
            len(fleet["incarnations"]) == len(live),
            f"fleet stitch: one incarnation per child "
            f"({len(fleet['incarnations'])} vs {len(live)} trails)",
        )
        check(
            all("gap_s" in link for link in fleet["chain"][1:]),
            "fleet chain links every incarnation to its predecessor",
        )

        import jax._src.xla_bridge as _xb  # loaded by fleet_report's imports

        check(
            not _xb.backends_are_initialized(),
            "parent stayed off the device (no JAX backend initialised)",
        )

        detail["answers_sha256"] = hashes
        check(
            len(set(hashes.values())) == 1,
            f"bit-identical answers across every lane ({hashes})",
        )
        detail["bit_identical"] = len(set(hashes.values())) == 1
        detail["restarts"] = args.restarts
        detail["requests"] = args.requests
        detail["deadline_s"] = args.deadline_ms / 1e3
        detail["failures"] = failures
        detail["passed"] = not failures
    except Exception as e:  # lint: broad-except-ok (the bench artifact line must still parse on ANY failure; the error lands in detail.failures and the exit code)
        detail["error"] = repr(e)[:400]
        detail["failures"] = failures + [f"exception: {e!r}"[:200]]
        detail["passed"] = False

    detail["total_wall_s"] = round(time.perf_counter() - t_all, 1)
    out = json.dumps(line)
    emit_to.write(out + "\n")
    emit_to.flush()
    if args.out:
        with open(args.out, "w") as f:
            f.write(out + "\n")
    if not detail.get("passed"):
        sys.exit(1)


if __name__ == "__main__":
    main()
