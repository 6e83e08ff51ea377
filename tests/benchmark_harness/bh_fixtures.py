"""Test fixtures of the benchmark's harness: a temporary copy of the
benchmark (``BENCHMARK.json`` + the benchmark's tree) to which a tiny
configuration, traffic mixes, cells and a per-layer metric are ADDED as new
files and appended entries — no file that is there is edited. The sizes
here are test fixtures, never entries of the real ``workloads``."""

from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TINY_POINTS = {
    "hotspot_share": 0.9, "hotspots": 5, "zipf_s": 1.1,
    "sigma_m": [100000, 400000], "lat0_deg": 0.0, "layout_seed": 3,
}


def _write(path: str, obj) -> None:
    assert not os.path.exists(path), f"{path} is there already"
    with open(path, "w", encoding="utf-8") as f:
        json.dump(obj, f, indent=1)


def tiny_config(mesh=None) -> dict:
    return {
        "source": "test fixture", "rehearsal": True, "row": "point",
        "deployment": "zone_join", "reference": "pip_bruteforce",
        # a coarse custom grid: H3's digit pipeline costs the CPU ~15 s of
        # compiles that prove nothing about the harness's control flow
        "index_system": "CUSTOM(-180,180,-90,90,2,10,10)", "resolution": 2,
        "zones": {"kind": "star_lattice", "nx": 3, "ny": 3,
                  "bbox": [-25.0, -25.0, 35.0, 20.0], "seed": 7,
                  "verts": 10, "jitter": 0.45},
        "batch_rows_per_chip": 2048, "chips": mesh or 1, "mesh": mesh,
        "guarantees": {"stream_max_disagreement": 0.001,
                       "serve_max_disagreement": 0.0001},
    }


def make_copy(tmp: str, mesh=None) -> str:
    """Copy the benchmark into ``tmp`` and add the tiny cells as files.
    Returns the copy's root."""
    root = os.path.join(str(tmp), "copy")
    os.makedirs(root)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    shutil.copytree(
        os.path.join(REPO, "benchmark"), os.path.join(root, "benchmark"),
        ignore=shutil.ignore_patterns("__pycache__", ".traces", ".cache"),
    )
    before = _snapshot(root)
    tree = os.path.join(root, "benchmark")
    _write(os.path.join(tree, "configs", "tiny-zones.json"), tiny_config(mesh))
    _write(os.path.join(tree, "traffic", "tiny-ring.json"), {
        "kind": "device_ring_stream", "ring_slots": 2,
        "steps_per_dispatch": 2, "points": TINY_POINTS,
        "trace_from_dispatch": 0, "trace_dispatches": 1,
    })
    _write(os.path.join(tree, "traffic", "tiny-open.json"), {
        "kind": "open_loop_requests", "rate_per_s": 40.0,
        "size_rows": {"median": 48, "sigma": 1.0, "min": 1, "max": 1000},
        "pool_rows": 16384, "points": TINY_POINTS, "schedule_seed": 5,
        "trace_last_seconds": 0.5,
    })
    _write(os.path.join(tree, "workloads", "tiny.stream.json"),
           {"check": {"sample_rows": 2048}})
    _write(os.path.join(tree, "workloads", "tiny.serve.json"),
           {"check": {"sample_rows": 20000}})
    # a NEW per-layer metric with a NEW reader of its own
    _write(os.path.join(tree, "layer_metrics", "tiny_dispatches.json"),
           {"reader": "tiny_counter_twice", "params": {"name": "dispatches"}})
    reader = os.path.join(tree, "readers", "tiny_counter_twice.py")
    assert not os.path.exists(reader)
    with open(reader, "w", encoding="utf-8") as f:
        f.write(
            "def read(ctx, params):\n"
            "    v = ctx.counters.get(params['name'])\n"
            "    return None if v is None else 2 * v\n"
        )
    path = os.path.join(root, "BENCHMARK.json")
    with open(path, encoding="utf-8") as f:
        bench = json.load(f)
    chips = mesh or 1
    bench["configs"].append({
        "name": "tiny-zones", "source": "test fixture",
        "file": "benchmark/configs/tiny-zones.json", "reduced": [],
        "why": "test fixture",
    })
    bench["workloads"] += [
        {"name": "tiny.stream", "config": "tiny-zones",
         "traffic": "tiny-ring", "chips": chips, "why": "test fixture"},
        {"name": "tiny.serve", "config": "tiny-zones",
         "traffic": "tiny-open", "chips": 1, "why": "test fixture"},
    ]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" not in m:
            continue
        if any(c.endswith(".stream") for c in m["workloads"]):
            if not m["name"].endswith(".x4") or mesh:
                m["workloads"].append("tiny.stream")
        if any(c.endswith(".serve") for c in m["workloads"]):
            m["workloads"].append("tiny.serve")
    bench["per_layer"].append({
        "name": "tiny_dispatches", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "frontends",
        "moves": "rows_per_s", "workloads": ["tiny.stream"],
    })
    with open(path, "w", encoding="utf-8") as f:
        json.dump(bench, f, indent=1)
    after = _snapshot(root)
    changed = [
        p for p, h in before.items()
        if p != "BENCHMARK.json" and after.get(p) != h
    ]
    assert not changed, f"fixtures edited existing files: {changed}"
    return root


def append_as_a_pr(root: str, add) -> None:
    """Run ``add(tree, bench)`` — new files under the copy's tree, appended
    entries in ``bench`` — and hold it to what a later PR may do: no file
    that was there is written, ``BENCHMARK.json`` alone is rewritten."""
    before = _snapshot(root)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path, encoding="utf-8") as f:
        bench = json.load(f)
    add(os.path.join(root, "benchmark"), bench)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(bench, f, indent=1)
    after = _snapshot(root)
    changed = [p for p, h in before.items()
               if p != "BENCHMARK.json" and after.get(p) != h]
    assert not changed, f"the additions edited existing files: {changed}"


def _snapshot(root: str) -> dict:
    import hashlib

    out = {}
    for d, _dirs, files in os.walk(root):
        for fn in files:
            p = os.path.join(d, fn)
            with open(p, "rb") as f:
                out[os.path.relpath(p, root)] = hashlib.sha1(f.read()).hexdigest()
    return out
