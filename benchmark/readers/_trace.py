"""Shared by the readers that read the profiler's trace themselves: the
device planes' ops and module runs, and the program's own ``mosaic.*``
annotations on ``/host:CPU`` (`mosaic_tpu/obs/trace.py` enters one per
span, carrying ``t``: the span's start on the program's monotonic clock,
in nanoseconds). All times are nanoseconds on the trace's clock. A trace
without a device plane or without such annotations reads as empty lists:
the caller then has nothing to read."""

import bisect
import gzip
import os

from benchmark.harness import xplane

MODULES_LINE = "XLA Modules"
PROGRAM_PREFIX = xplane.PROGRAM_PREFIX
_CACHE: dict = {}


def trace_path(ctx):
    """The newest ``.xplane.pb`` of this run, or None."""
    log_dir = getattr(ctx.tracer, "log_dir", None)
    if not log_dir or not os.path.isdir(log_dir):
        return None
    try:
        return xplane.newest_xplane(log_dir)
    except FileNotFoundError:
        return None


def load(path: str) -> dict:
    """``{"devices": {plane: {"ops": [(name, start, end)], "modules":
    [(name, start, end)]}}, "program": [(name, start, end, t)]}``, each
    list in order of start; ``name`` of a program span without its
    prefix. Control-flow containers are left out of ``ops``."""
    if path in _CACHE:
        return _CACHE[path]
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    devices: dict = {}
    program: list = []
    for plane in data.planes:
        if plane.name.startswith(xplane.DEVICE_PREFIX):
            ops, modules = [], []
            for line in plane.lines:
                if line.name not in (xplane.OPS_LINE, MODULES_LINE):
                    continue
                into = ops if line.name == xplane.OPS_LINE else modules
                for ev in line.events:
                    s = float(ev.start_ns)
                    into.append((ev.name, s, s + float(ev.duration_ns)))
            ops = [
                op for op in ops
                if not xplane.CONTAINER_OPS.match(xplane.op_name(op[0]))
            ]
            if ops:
                devices[plane.name] = {
                    "ops": sorted(ops, key=lambda o: o[1]),
                    "modules": sorted(modules, key=lambda o: o[1]),
                }
        elif plane.name == xplane.HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if not ev.name.startswith(PROGRAM_PREFIX):
                        continue
                    t = dict(ev.stats).get("t")
                    s = float(ev.start_ns)
                    program.append((
                        ev.name[len(PROGRAM_PREFIX):], s,
                        s + float(ev.duration_ns),
                        None if t is None else int(t),
                    ))
    program.sort(key=lambda p: p[1])
    _CACHE.clear()  # one trace a run: keep the newest only
    _CACHE[path] = {"devices": devices, "program": program}
    return _CACHE[path]


def of_run(ctx):
    """The loaded trace of this run, or None where there is no trace, no
    device plane or no program annotation in it."""
    path = trace_path(ctx)
    if path is None:
        return None
    tr = load(path)
    if not tr["devices"] or not tr["program"]:
        return None
    return tr


def first_device(tr: dict) -> dict:
    return tr["devices"][sorted(tr["devices"])[0]]


def busy_intervals(ops) -> list:
    """The merged (start, end) intervals in which some op ran."""
    return xplane.union_seconds([(s, e) for _n, s, e in ops])[1]


def busy_inside(merged, starts, lo: float, hi: float) -> float:
    """Nanoseconds of ``merged`` (disjoint, sorted; ``starts`` their
    starts) that lie inside ``[lo, hi]``."""
    total = 0.0
    i = max(bisect.bisect_right(starts, lo) - 1, 0)
    while i < len(merged) and merged[i][0] < hi:
        total += max(min(merged[i][1], hi) - max(merged[i][0], lo), 0.0)
        i += 1
    return total

