"""One run of one cell, from process start to the result line.

The flow is the same for every cell and holds no cell's name: the cell
names a configuration and a traffic mix; the configuration names its
deployment builder and its plain reference; the mix names its traffic
kind; each per-layer metric names its reader. All are found by name
(`spec.Spec.module`).
"""

from __future__ import annotations

import os
import sys
import time

from . import check as _check
from . import stage_table, xplane
from .context import Ctx, SpanLog, TraceSession
from .spec import Spec

#: telemetry events that mean the device path was retried, abandoned or
#: bypassed (`chip_smoke.py`'s list): any one of them counts in ``failed``
#: and makes the run not correct
FORBIDDEN_EVENTS = (
    "degraded", "retry_exhausted", "transient_retry", "watchdog_stall",
    "program_store_fallback",
)
#: events kept in a ``--trace 0`` run (the rest only when tracing)
ALWAYS_KEPT = FORBIDDEN_EVENTS + ("serve_shed", "stream_stage")
#: a timed event this long is kept in every run: a stall's own record
SLOW_EVENT_S = 0.05


class BenchFailure(RuntimeError):
    """The run cannot give a result line."""


def _device_block(ctx: Ctx) -> dict:
    import jax

    devs = jax.devices()[: max(ctx.chips, 1)]
    peaks = []
    for d in devs:
        st = d.memory_stats() or {}
        peaks.append(int(st.get("peak_bytes_in_use", 0) or 0))
    return {
        "platform": ctx.device["platform"],
        "kind": ctx.device["kind"],
        "count": ctx.device["count"],
        "memory_peak_bytes": max(peaks) if peaks else 0,
    }


def run_cell(
    root: str, workload: str, seed: int, seconds: float, trace: bool,
    *, t_start: float, rehearsal: bool = False, control: bool = False,
) -> dict:
    """Run the cell and return the result line's object."""
    spans = SpanLog()
    spec = Spec(root)
    cell = spec.cell(workload)
    config = spec.config(cell["config"])
    traffic = spec.traffic(cell["traffic"])
    if rehearsal and not config.get("rehearsal"):
        raise BenchFailure(
            f"--rehearsal runs test fixtures only; configuration "
            f"{config['name']!r} is a real cell's and runs on the chip"
        )

    with spans.span("runtime_start"):
        import jax

        import mosaic_tpu  # noqa: F401 — enables x64
        from mosaic_tpu.runtime import telemetry
        from mosaic_tpu.runtime.platform import (
            configure_compile_cache,
            require_device,
        )

        device = require_device(allow_cpu=rehearsal)
        if device["platform"] == "cpu" and not rehearsal:
            raise BenchFailure("the benchmark runs on the TPU, not the CPU")
        if device["count"] < int(cell["chips"]):
            raise BenchFailure(
                f"cell {workload!r} needs {cell['chips']} chips, JAX found "
                f"{device['count']}"
            )
        cache_dir = configure_compile_cache()
    trace_dir = os.path.join(spec.tree, ".traces", workload)
    ctx = Ctx(
        spec=spec, cell=cell, config=config, traffic=traffic, seed=seed,
        seconds=seconds, trace=trace, rehearsal=rehearsal, control=control,
        device=device, spans=spans,
        tracer=TraceSession(trace, trace_dir, spans),
    )
    ctx.say(
        "start", workload=workload, seed=seed, seconds=seconds,
        trace=int(trace), platform=device["platform"],
        kind=device["kind"], count=device["count"], jax=jax.__version__,
        compile_cache=cache_dir,
    )

    def observer(evt: dict) -> None:
        if (trace or evt.get("event") in ALWAYS_KEPT
                or evt.get("seconds", 0.0) >= SLOW_EVENT_S):
            ctx.events.append(evt)

    telemetry.add_observer(observer)
    kind = spec.module("traffic_kinds", traffic["kind"])
    state = None
    try:
        with spans.span("deployment"):
            ctx.deployment = spec.module(
                "deployments", config["deployment"]
            ).build(ctx)
        with spans.span("warmup"):
            state = kind.prepare(ctx)
        from mosaic_tpu.dispatch import backend_compiles, compile_cache_hits

        c0 = backend_compiles()
        setup_s = time.perf_counter() - t_start
        ctx.counters["setup_s"] = setup_s
        result = kind.window(ctx, state)
        ctx.tracer.stop()
        ctx.counters["compiles_in_window"] = backend_compiles() - c0
        device_block = _device_block(ctx)
        ctx.say(
            "window", attempted=result["attempted"],
            failed=result["failed"],
            compiles_in_window=ctx.counters["compiles_in_window"],
            compile_cache_hits=compile_cache_hits(), setup_s=round(setup_s, 3),
        )
        t_check = time.perf_counter()
        comparisons = kind.check(ctx, state)
        ctx.say("check", seconds=round(time.perf_counter() - t_check, 3))
    finally:
        telemetry.remove_observer(observer)
        if state is not None:
            kind.close(ctx, state)

    bad = [e for e in ctx.events if e.get("event") in FORBIDDEN_EVENTS]
    for e in bad[:5]:
        ctx.say("forbidden_event", **{k: e[k] for k in list(e)[:6]})
    failed = int(result["failed"]) + len(bad)
    comparisons.append(_check.Comparison(
        "forbidden_events", len(bad), 0,
        "a retried, degraded or host-answered call is not the timed path",
    ))
    correct = _check.decide(comparisons)

    metrics: dict = {}
    if not trace:
        measured = dict(result["metrics"], setup_s=setup_s)
        for m in spec.end_to_end(workload):
            if m["name"] not in measured:
                raise BenchFailure(
                    f"cell {workload!r} lists end-to-end metric "
                    f"{m['name']!r}, which traffic kind "
                    f"{traffic['kind']!r} did not measure"
                )
            metrics[m["name"]] = {
                "value": float(measured[m["name"]]), "unit": m["unit"],
            }
    else:
        ctx.say("end_to_end_while_traced", **result["metrics"],
                setup_s=setup_s)
        red = xplane.reduce_file(
            xplane.newest_xplane(trace_dir), ctx.tracer.window_s
        )
        ctx.trace_reduction = red
        if red["busy_s"] <= 0.0 and not rehearsal:
            raise BenchFailure(
                "the traced window holds no device operation"
            )
        device_block["busy_s"] = red["busy_s"]
        device_block["window_s"] = red["window_s"]
        # built here, before any reader: the breakdown's `device_stages`
        # and the stage readers take the one table
        stage_seconds = stage_table.of_run(ctx)
        for m in spec.per_layer(workload):
            desc = spec.data("layer_metrics", m["name"])
            value = spec.module("readers", desc["reader"]).read(
                ctx, desc.get("params", {})
            )
            if value is None:
                ctx.say("nothing_to_read", metric=m["name"])
                continue
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    line = {
        "correct": bool(correct),
        "attempted": int(result["attempted"]),
        "failed": failed,
        "metrics": metrics,
        "device": device_block,
    }
    if trace:
        line["breakdown"] = xplane.breakdown(
            ctx.trace_reduction, stage_seconds)
    # each number compared beside its limit: the line's last key, and the
    # last lines of standard error (what a record of a failed run keeps)
    line["checks"] = {c.name: c.entry() for c in comparisons}
    for c in comparisons:
        print(c.line(), file=sys.stderr, flush=True)
    return line

