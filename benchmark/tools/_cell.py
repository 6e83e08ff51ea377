"""What the chip tools share: one cell's data, the device, and a context
per window — the same start-up `harness.run_cell` makes, without its
result line (these tools run many windows in one process)."""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def open_cell(root: str, workload: str, rehearsal: bool):
    """(spec, cell, config, traffic, device, traffic-kind module)."""
    from benchmark.harness.spec import Spec

    spec = Spec(root)
    cell = spec.cell(workload)
    config = spec.config(cell["config"])
    traffic = spec.traffic(cell["traffic"])

    import mosaic_tpu  # noqa: F401 — enables x64
    from mosaic_tpu.runtime.platform import (
        configure_compile_cache,
        require_device,
    )

    device = require_device(allow_cpu=rehearsal)
    configure_compile_cache()
    kind = spec.module("traffic_kinds", traffic["kind"])
    return spec, cell, config, traffic, device, kind


def context(opened, seed: int, seconds: float, *, rehearsal: bool,
            control: bool = False, deployment=None):
    """An untraced run context; builds the deployment unless one is given."""
    from benchmark.harness.context import Ctx, SpanLog, TraceSession

    spec, cell, config, traffic, device, _kind = opened
    spans = SpanLog()
    ctx = Ctx(
        spec=spec, cell=cell, config=config, traffic=traffic, seed=seed,
        seconds=seconds, trace=False, rehearsal=rehearsal, control=control,
        device=device, spans=spans, tracer=TraceSession(False, "", spans),
    )
    ctx.deployment = deployment or spec.module(
        "deployments", config["deployment"]
    ).build(ctx)
    return ctx
