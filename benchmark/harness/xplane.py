"""Reduction of a JAX profiler trace (``.xplane.pb``) to numbers.

Read with `jax.profiler.ProfileData` alone. A device plane is named
``/device:TPU:<n>``; its line ``XLA Ops`` holds one event per executed HLO
operation (start and duration in nanoseconds). Host threads are the lines
of ``/host:CPU``; the benchmark's own `jax.profiler.TraceAnnotation` spans
(names starting ``bench.``) appear there on the same clock.

- busy: the union of the op intervals of one device (control-flow
  containers such as ``while`` left out: they only hold other ops);
- idle share: 1 - busy / traced window, mean over the devices used;
- op durations by name (the HLO instruction's own name, ``%fusion.12``);
- idle gaps of the first device, cut at every annotation's start and end,
  each piece given to the innermost of the program's own ``mosaic.*``
  spans that covers its midpoint, else to the innermost ``bench.*`` span of
  the benchmark, else to ``host:other``.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
ANNOTATION_PREFIX = "bench."
#: the program's own spans (`mosaic_tpu/obs/trace.py` enters one annotation
#: per non-detached span while a profiler session is on)
PROGRAM_PREFIX = "mosaic."
#: control-flow ops that only contain other ops: their interval says when
#: the loop ran, not that an operation was running, so they count neither
#: as busy time nor in the ranking
CONTAINER_OPS = re.compile(r"^(while|conditional|call)(\.\d+)?$")
#: HLO instruction names of the cross-chip collectives
COLLECTIVE_WORDS = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast",
)


def newest_xplane(log_dir: str) -> str:
    files = sorted(
        glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    )
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def op_name(event_name: str) -> str:
    """``%fusion.11 = s32[...] fusion(...)`` -> ``fusion.11``."""
    head = event_name.split(" = ", 1)[0].strip()
    return head.lstrip("%") or event_name[:48]


def op_label(event_name: str) -> str:
    """The op's name with its output type, which says more to a reader
    than a fusion's number: ``fusion.504 f32[4000000,153]``."""
    short = op_name(event_name)
    rest = event_name.split(" = ", 1)
    if len(rest) < 2:
        return short
    out = rest[1].strip().split("{", 1)[0].split(" ", 1)[0]
    return f"{short} {out}"[:96] if out else short


def union_seconds(intervals) -> tuple[float, list]:
    """Total length of the union of ``(start_ns, end_ns)`` intervals, and
    the merged intervals in order."""
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged) / 1e9, merged


def read_planes(path: str) -> dict:
    """``{"devices": {plane: [(name, start_ns, end_ns)]}, "host":
    [(name, start_ns, end_ns)], "program": [(name, start_ns, end_ns)]}``
    from one ``.xplane.pb``: ``host`` the benchmark's ``bench.*``
    annotations, ``program`` the program's ``mosaic.*`` ones, names whole."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    devices: dict = {}
    host: list = []
    program: list = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    ops.append(
                        (ev.name, float(ev.start_ns),
                         float(ev.start_ns) + float(ev.duration_ns))
                    )
            devices[plane.name] = ops
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(ANNOTATION_PREFIX):
                        into = host
                    elif ev.name.startswith(PROGRAM_PREFIX):
                        into = program
                    else:
                        continue
                    into.append(
                        (ev.name, float(ev.start_ns),
                         float(ev.start_ns) + float(ev.duration_ns))
                    )
    return {"devices": devices, "host": host, "program": program}


def idle_pieces(merged, cuts) -> list:
    """The gaps between the merged busy intervals, each cut at every one of
    the sorted ``cuts`` inside it. One gap of a serve cycle runs from a
    dispatch's last op through scatter-back, the linger and the next
    dispatch's puts: its midpoint alone would give all of it to one."""
    pieces = []
    for a, b in zip(merged, merged[1:]):
        lo, hi = a[1], b[0]
        if hi <= lo:
            continue
        inner = cuts[bisect.bisect_right(cuts, lo):bisect.bisect_left(cuts, hi)]
        edges = [lo, *inner, hi]
        pieces.extend(zip(edges, edges[1:]))
    return pieces


def span_index(spans) -> dict:
    """``{name: (starts, [(start, end)])}``, each in order of start, from
    tuples that begin ``(name, start, end)``."""
    out: dict = {}
    for name, s, e, *_rest in sorted(spans, key=lambda sp: sp[1]):
        starts, ivs = out.setdefault(name, ([], []))
        starts.append(s)
        ivs.append((s, e))
    return out


def covering(index: dict, mid: float, names=None):
    """The innermost (shortest) span covering ``mid``, among ``names`` if
    given; None if there is none. Of one name, the two that started last
    before ``mid`` are looked at (one thread runs a span name one at a
    time; two threads may overlap)."""
    best = None
    for name in index if names is None else names:
        starts, ivs = index.get(name, ((), ()))
        i = bisect.bisect_right(starts, mid)
        for s, e in ivs[max(i - 2, 0):i]:
            if e >= mid and (best is None or e - s < best[1]):
                best = (name, e - s)
    return None if best is None else best[0]


def reduce_planes(planes: dict, window_s: float, top: int = 10) -> dict:
    """The numbers the per-layer readers and the result line use."""
    devices = {
        k: [op for op in v if not CONTAINER_OPS.match(op_name(op[0]))]
        for k, v in planes["devices"].items()
    }
    devices = {k: v for k, v in devices.items() if v}
    if not devices:
        return {
            "devices": 0, "busy_s": 0.0, "window_s": window_s,
            "busy_by_device": {}, "op_seconds": {}, "collective_s": 0.0,
            "first_device_busy_s": 0.0, "device_ops": [], "idle_gaps": [],
        }
    busy_by_device = {}
    merged_first = None
    first = sorted(devices)[0]
    for name in sorted(devices):
        busy, merged = union_seconds(
            [(s, e) for _n, s, e in devices[name]]
        )
        busy_by_device[name] = busy
        if name == first:
            merged_first = merged
    op_seconds: dict = {}
    collective_ns = 0.0
    for name, s, e in devices[first]:
        short = op_label(name)
        op_seconds[short] = op_seconds.get(short, 0.0) + (e - s) / 1e9
        if any(w in op_name(name) for w in COLLECTIVE_WORDS):
            collective_ns += e - s
    gaps: dict = {}
    annotations = [*planes.get("program", ()), *planes["host"]]
    program = span_index(planes.get("program", ()))
    bench = span_index(planes["host"])
    cuts = sorted({x for _n, s, e in annotations for x in (s, e)})
    for lo, hi in idle_pieces(merged_first, cuts):
        mid = (lo + hi) / 2.0
        who = covering(program, mid) or covering(bench, mid) or "host:other"
        gaps[who] = gaps.get(who, 0.0) + (hi - lo) / 1e9
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]  # noqa: E731
    return {
        "devices": len(devices),
        "busy_s": sum(busy_by_device.values()) / len(devices),
        "window_s": window_s,
        "busy_by_device": busy_by_device,
        "first_device_busy_s": busy_by_device[first],
        "op_seconds": op_seconds,
        "collective_s": collective_ns / 1e9,
        "device_ops": [[k, v] for k, v in rank(op_seconds)],
        "idle_gaps": [[k, v] for k, v in rank(gaps)],
    }


def reduce_file(path: str, window_s: float) -> dict:
    return reduce_planes(read_planes(path), window_s)


def breakdown(reduction: dict, stage_seconds=None, top: int = 10) -> dict:
    """The result line's ``breakdown``, three rankings of ``[name, seconds]``
    with at most ``top`` rows each: ``device_ops`` the longest ops by HLO
    name, ``idle_gaps`` the idle time by what the host was doing, and
    ``device_stages`` the device seconds by the program's own stage names
    (`harness/stage_table.py`; the stages' seconds contain the ops', so the
    table has a key of its own, left out where the run has no table)."""
    out = {"device_ops": reduction["device_ops"][:top],
           "idle_gaps": reduction["idle_gaps"][:top]}
    stages = sorted(
        ([k, v] for k, v in (stage_seconds or {}).items() if v > 0.0),
        key=lambda kv: -kv[1])
    if stages:
        out["device_stages"] = stages[:top]
    return out
