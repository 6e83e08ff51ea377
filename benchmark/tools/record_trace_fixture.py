#!/usr/bin/env python3
"""Record the readers' test fixture on the chip: a short traced window of
the serve cell with what the trace readers need beside it.

    chiprun -- python3 benchmark/tools/record_trace_fixture.py taxi.serve <seed> <out-dir>

Runs the cell in a temporary copy of the benchmark whose mix traces only
the last ``TRACE_SECONDS`` of a ``WINDOW_SECONDS`` window (a 3 s trace is
megabytes), and writes to ``<out-dir>``: ``trace.xplane.pb.gz`` (stripped
to what the readers read: the device planes' ``XLA Ops`` and ``XLA
Modules`` lines and the host plane's ``mosaic.*`` and ``bench.*`` events;
the HLO protos of ``/host:metadata`` alone are megabytes);
``events.jsonl.gz`` (the program's ``span`` events of the run's end);
``stage_tables.json`` (`mosaic_tpu.obs.stages.tables()` for the ops in
the trace: the test cannot lower a TPU program); ``result.json`` (the
run's result line and the ``ctx`` fields the readers use)."""

from __future__ import annotations

import gzip
import json
import os
import shutil
import sys
import tempfile
import time

from _cell import ROOT

WINDOW_SECONDS = 4.0
TRACE_SECONDS = 0.25


def strip(raw: bytes) -> bytes:
    """The serialized XSpace with only what the readers read."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    space.ParseFromString(raw)
    kept = xplane_pb2.XSpace()
    for plane in space.planes:
        device = plane.name.startswith("/device:TPU:")
        if not device and plane.name != "/host:CPU":
            continue
        new = kept.planes.add()
        new.id, new.name = plane.id, plane.name
        for k, v in plane.stat_metadata.items():
            new.stat_metadata[k].CopyFrom(v)
        used = set()
        for line in plane.lines:
            if device and line.name not in ("XLA Ops", "XLA Modules"):
                continue
            events = [
                ev for ev in line.events
                if device or plane.event_metadata[ev.metadata_id].name.startswith(
                    ("mosaic.", "bench."))
            ]
            if not events:
                continue
            nl = new.lines.add()
            nl.CopyFrom(line)
            del nl.events[:]
            nl.events.extend(events)
            used.update(ev.metadata_id for ev in events)
        for k in used:
            meta = new.event_metadata[k]
            meta.id = plane.event_metadata[k].id
            meta.name = plane.event_metadata[k].name
            meta.display_name = plane.event_metadata[k].display_name
    return kept.SerializeToString()


def main(cell: str, seed: int, out: str) -> int:
    tmp = tempfile.mkdtemp(prefix="fixture_")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp)
    shutil.copytree(
        os.path.join(ROOT, "benchmark"), os.path.join(tmp, "benchmark"),
        ignore=shutil.ignore_patterns("__pycache__", ".traces"),
    )
    with open(os.path.join(tmp, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    entry = next(w for w in bench["workloads"] if w["name"] == cell)
    mix_path = os.path.join(tmp, "benchmark", "traffic", entry["traffic"] + ".json")
    with open(mix_path, encoding="utf-8") as f:
        mix = json.load(f)
    mix["trace_last_seconds"] = TRACE_SECONDS
    with open(mix_path, "w", encoding="utf-8") as f:
        json.dump(mix, f)

    from benchmark.harness import run_cell as rc
    from benchmark.harness.context import Ctx
    from mosaic_tpu.runtime import telemetry

    kept: dict = {}

    class Keeping(Ctx):
        def __init__(self, **kw):
            super().__init__(**kw)
            kept["ctx"] = self

    rc.Ctx = Keeping
    spans: list = []
    telemetry.add_observer(
        lambda e: spans.append(e) if e.get("event") == "span" else None
    )
    line = rc.run_cell(tmp, cell, seed, WINDOW_SECONDS, True,
                       t_start=time.perf_counter())
    ctx = kept["ctx"]
    os.makedirs(out, exist_ok=True)
    from benchmark.harness import xplane
    from mosaic_tpu.obs import stages

    src = xplane.newest_xplane(ctx.tracer.log_dir)
    with open(src, "rb") as f, gzip.open(
        os.path.join(out, "trace.xplane.pb.gz"), "wb", 9
    ) as g:
        g.write(strip(f.read()))
    lo, hi = ctx.window
    with gzip.open(os.path.join(out, "events.jsonl.gz"), "wt", encoding="utf-8") as f:
        for e in spans:
            if e.get("ts_mono", 0) >= hi - 0.5:  # the window's last half second on
                f.write(json.dumps(e) + "\n")
    tr = ctx.spec.module("readers", "_trace").load(src)
    modules = {
        m[0].split("(", 1)[0]
        for dev in tr["devices"].values() for m in dev["modules"]
    }
    seen = {
        xplane.op_label(op[0])
        for dev in tr["devices"].values() for op in dev["ops"]
    }
    with open(os.path.join(out, "stage_tables.json"), "w", encoding="utf-8") as f:
        json.dump({
            m: {k: v for k, v in t.items() if k in seen}
            for m, t in stages.tables(modules).items()
        }, f, indent=0, sort_keys=True)
    with open(os.path.join(out, "result.json"), "w", encoding="utf-8") as f:
        json.dump({
            "line": line, "window": [hi - 0.5, hi],
            "tracer_window_s": ctx.tracer.window_s,
            "device_by_stage": getattr(ctx, "device_by_stage", None),
        }, f)
    print(json.dumps({k: os.path.getsize(os.path.join(out, k))
                      for k in sorted(os.listdir(out))}))
    shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], int(sys.argv[2]), sys.argv[3]))
