"""North-star benchmark: NYC PIP join, points/sec on one chip.

Workload follows the reference Quickstart
(`notebooks/examples/scala/QuickstartNotebook.scala:149-216`): the
reference's own NYC taxi-zone fixture (when the file is on disk) is
tessellated to H3 chips; N random pickup points get a cell id and join
against the chip index (`is_core || contains`). Without the fixture the
zones are `synthetic_zones(16, 16)`; ``detail.zones`` says which.

One process, no child, no fallback: the platform is whatever JAX found,
and `runtime.platform.require_device` raises unless that is a TPU (or
``JAX_PLATFORMS=cpu`` asked for the CPU by name, in which case the unit
says so). Prints ONE JSON line; a lane that raises is recorded in
``detail.lane_errors`` AND makes the exit code non-zero.

Timing protocol (see docs/ARCHITECTURE.md measurement doctrine):
- N passes (default 3) each over DISTINCT pre-staged input batches;
- completion of every batch is forced by a device-side full-bit XOR-fold
  to one scalar whose value is pulled with ``float(...)``;
- the fixed sync round-trip (measured as the min of three scalar pulls of
  precomputed values) is subtracted from each pass;
- the reported time is the min over the N non-identical passes; raw pass
  times are recorded in ``detail.passes_s``.

``vs_baseline`` compares against the single-thread C++ host join
(`native/src/evalgeom.cpp mg_eval_pip_join`, detail.baseline_kind =
native_cpp_single_thread) — the honest analog of the reference's JTS
codegen row path, since the reference publishes no numbers (SURVEY.md
§6); the vectorized NumPy lane is also reported
(detail.numpy_points_per_sec), and is the fallback baseline when the
native toolchain is unavailable.

Env knobs: MOSAIC_BENCH_POINTS,
MOSAIC_BENCH_PASSES (default 3), MOSAIC_BENCH_SCALE_POINTS (default 16M,
TPU only), MOSAIC_BENCH_CELL_DTYPE=f32|f64 (default f32 — the fast H3
cell-assignment path; every run quantifies its cost end to end:
``detail.cell_f32_f64_agreement`` counts points assigned a different cell
than the f64 path, ``detail.join_f32_f64_agreement`` counts join results
that actually differ, with a 0.998 floor flagged on violation).
"""

from __future__ import annotations

import datetime
import functools
import json
import os
import sys
import time

import numpy as np

RES = 9
NYC_FIXTURE = "/root/reference/src/test/resources/NYC_Taxi_Zones.geojson"
_I32_MAX = np.iinfo(np.int32).max

_T0 = time.perf_counter()


_PARTIAL_PATH = os.environ.get("MOSAIC_BENCH_PARTIAL")


class _QuickSkip(Exception):
    """Raised inside optional lanes when MOSAIC_BENCH_QUICK is set."""


def _prog(msg: str) -> None:
    """Stderr progress mark (stdout carries only the JSON line): some
    compiles are minutes-long, and without these marks a slow lane is
    indistinguishable from a hang.

    When MOSAIC_BENCH_PARTIAL names a file, the current ``detail`` dict is
    also checkpointed there at every mark, so a run killed at its time
    limit still leaves the lanes it finished."""
    print(f"[bench +{time.perf_counter() - _T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)
    detail = getattr(_prog, "detail", None)
    if _PARTIAL_PATH and detail is not None:
        try:
            tmp = _PARTIAL_PATH + ".tmp"
            with open(tmp, "w") as f:
                json.dump({"stage": msg, "detail": detail}, f,
                          indent=1, default=str)
            os.replace(tmp, _PARTIAL_PATH)
        except Exception:  # noqa: BLE001 — best-effort: a salvage helper
            pass           # must never be what kills the bench


def _np_parity(px, py, e, bits):
    # single source of truth for the host parity lives in the library
    from mosaic_tpu.sql.join import _np_parity as lib_parity

    return lib_parity(px, py, e, bits)


def _numpy_join(points, index, pcells):
    """Pure-NumPy oracle of pip_join_points over the flat-edge layout."""
    cells_sorted = np.asarray(index.cells)
    cell_edges = np.asarray(index.cell_edges, dtype=np.float64)
    cell_ebits = np.asarray(index.cell_ebits)
    slot_geom = np.asarray(index.cell_slot_geom)
    slot_core = np.asarray(index.cell_slot_core)
    cell_heavy = np.asarray(index.cell_heavy)
    heavy_edges = np.asarray(index.heavy_edges, dtype=np.float64)
    heavy_ebits = np.asarray(index.heavy_ebits)
    heavy_geom = np.asarray(index.heavy_slot_geom)

    U = cells_sorted.shape[0]
    u = np.clip(np.searchsorted(cells_sorted, pcells), 0, U - 1)
    fidx = np.nonzero(cells_sorted[u] == pcells)[0]  # only found points pay
    uf = u[fidx]
    px, py = points[fidx, 0], points[fidx, 1]
    par = _np_parity(px, py, cell_edges[uf], cell_ebits[uf])
    M = slot_geom.shape[1]
    inside = ((par[:, None] >> np.arange(M, dtype=np.uint32)) & 1).astype(bool)
    g = slot_geom[uf]
    hit = (g >= 0) & (slot_core[uf] | inside)
    bestf = np.where(hit, g, _I32_MAX).min(axis=1)
    if heavy_edges.shape[0]:
        hs = cell_heavy[uf]
        rows = np.nonzero(hs >= 0)[0]
        if rows.size:
            h = hs[rows]
            par2 = _np_parity(px[rows], py[rows], heavy_edges[h], heavy_ebits[h])
            M2 = heavy_geom.shape[1]
            in2 = ((par2[:, None] >> np.arange(M2, dtype=np.uint32)) & 1).astype(
                bool
            )
            g2 = heavy_geom[h]
            b2 = np.where((g2 >= 0) & in2, g2, _I32_MAX).min(axis=1)
            bestf[rows] = np.minimum(bestf[rows], b2)
    best = np.full(points.shape[0], _I32_MAX, dtype=np.int64)
    best[fidx] = bestf
    return np.where(best == _I32_MAX, -1, best).astype(np.int32)


#: nominal HBM bandwidth per chip, GB/s, keyed by device_kind substring
#: (checked in order — "v5p" before "v5" matters)
_HBM_PEAK_GBPS = (
    ("v6e", 1640.0),
    ("v5p", 2765.0),
    ("v5e", 819.0),
    ("v5 lite", 819.0),
    ("v4", 1228.0),
    ("v3", 900.0),
)


def _hbm_peak_gbps():
    """Peak HBM GB/s of device 0; None on the CPU platform (the roofline
    then reports achieved GB/s without a %-of-peak figure). A TPU whose
    ``device_kind`` is not in the table is an error, not a default."""
    import jax

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return None
    kind = dev.device_kind.lower()
    for pat, peak in _HBM_PEAK_GBPS:
        if pat in kind:
            return peak
    raise KeyError(
        f"no HBM peak recorded for device_kind {dev.device_kind!r} — add "
        "it to _HBM_PEAK_GBPS with its source"
    )


_CACHE_VERSION = 7  # bump when ChipIndex/HostRecheck layout changes


def _load_or_build_index(zones, zones_src: str, h3):
    """Tessellation is pure host work recomputed identically every run
    (~3s, ~20% of bench wall-clock noise): cache the built ChipIndex."""
    import jax.numpy as jnp

    from mosaic_tpu.core.geometry.device import DeviceGeometry
    from mosaic_tpu.core.tessellate import tessellate
    from mosaic_tpu.sql.join import ChipIndex, HostRecheck, build_chip_index

    import zlib

    xy = np.ascontiguousarray(np.asarray(zones.xy, dtype=np.float64))
    fp = zlib.crc32(xy.tobytes()) ^ zlib.crc32(bytes(str(len(zones)), "ascii"))
    key = f"{zones_src}-{RES}-v{_CACHE_VERSION}-{fp:08x}"
    cache = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         ".bench_cache", key + ".npz")
    import dataclasses as _dc

    border_names = [f.name for f in _dc.fields(DeviceGeometry)]
    index_names = [
        f.name for f in _dc.fields(ChipIndex) if f.name != "border"
    ]
    if os.path.exists(cache):
        try:
            z = np.load(cache)
            border = DeviceGeometry(
                **{n: jnp.asarray(z[f"b_{n}"]) for n in border_names}
            )
            ix = ChipIndex(
                border=border,
                **{n: jnp.asarray(z[n]) for n in index_names},
            )
            ix.host = HostRecheck.from_arrays(z)  # f64 recheck companion
            return ix, True, None
        except Exception:
            pass  # stale/corrupt cache: rebuild
    t0 = time.perf_counter()
    table = tessellate(zones, h3, RES, keep_core_geoms=False)
    tess_only_s = time.perf_counter() - t0
    index = build_chip_index(table)
    try:
        os.makedirs(os.path.dirname(cache), exist_ok=True)
        np.savez_compressed(
            cache,
            **{n: np.asarray(getattr(index, n)) for n in index_names},
            **{f"b_{n}": np.asarray(getattr(index.border, n))
               for n in border_names},
            **index.host.save_arrays(),
        )
    except OSError:
        pass
    return index, False, tess_only_s


def _load_zones():
    """(zones, source): the reference NYC taxi-zone fixture when the file
    is on disk, else `synthetic_zones(16, 16)` — said out loud, and a
    fixture that exists but does not parse raises."""
    if os.path.exists(NYC_FIXTURE):
        from mosaic_tpu.readers.vector import read_geojson

        return read_geojson(NYC_FIXTURE).geometry, "nyc_taxi_zones"
    from mosaic_tpu.datasets import synthetic_zones

    _prog(f"zone fixture {NYC_FIXTURE} not on disk: using "
          "synthetic_zones(16, 16)")
    return synthetic_zones(16, 16), "synthetic"


def main():
    detail: dict = {
        "ts": datetime.datetime.now(datetime.timezone.utc).isoformat(
            timespec="seconds"
        )
    }
    t_start = time.perf_counter()
    _prog.detail = detail  # type: ignore[attr-defined] — partial checkpoints

    # artifact hygiene (BENCH_r05 "parsed: null"): the metric JSON must be
    # the LAST stdout line, single-line, always. Anything any library
    # prints to stdout mid-run (retry chatter, backend warnings) diverts
    # to stderr; only _emit writes to the real stdout.
    emit_to = sys.stdout
    sys.stdout = sys.stderr

    # MOSAIC_BENCH_TRAIL=/path.jsonl captures the full telemetry trail
    # (join.pip spans, recheck/escalation/retry events, stage timings)
    # and exports it at emit — feed it to tools/trace_report.py or
    # tools/perf_gate.py
    trail_path = os.environ.get("MOSAIC_BENCH_TRAIL")
    trail_events: list = []
    if trail_path:
        from mosaic_tpu.runtime import telemetry as _telemetry

        _telemetry.current_sinks().append(trail_events)

    def _emit(obj: dict) -> None:
        if trail_path:
            try:
                from mosaic_tpu.obs import write_jsonl as _write_jsonl

                _write_jsonl(trail_events, trail_path)
                obj.setdefault("detail", {})["trail"] = trail_path
            except Exception as e:  # the artifact line must still emit
                obj.setdefault("detail", {})["trail_error"] = repr(e)[:200]
        # a lane that raised is recorded under a ``*_error`` key; each
        # one also fails the run (exit code below)
        d = obj.setdefault("detail", {})
        d["lane_errors"] = sorted(
            k for src in (d, d.get("writeback", {}))
            for k in src if k.endswith("_error")
        )
        emit_to.write(json.dumps(obj) + "\n")
        emit_to.flush()

    from mosaic_tpu.runtime.platform import (
        configure_compile_cache,
        per_chip,
        require_device,
    )

    # raises (no line, non-zero exit) unless this is a TPU, or the CPU
    # asked for by name with JAX_PLATFORMS=cpu
    device_info = require_device()
    unit = per_chip("points/sec", device_info)
    detail["device_info"] = device_info
    detail["compile_cache_dir"] = configure_compile_cache()
    try:
        import jax
        import jax.numpy as jnp

        from mosaic_tpu.core.index.h3 import H3IndexSystem
        from mosaic_tpu.datasets import random_points
        from mosaic_tpu.sql.join import pip_join_points

        detail["device"] = str(jax.devices()[0])
        _prog(f"device: {detail['device']}")
        on_tpu = device_info["platform"] == "tpu"
        detail["platform"] = device_info["platform"]
        # MOSAIC_BENCH_FORCE_TPU_LANES exercises the TPU-only lanes on CPU
        # (code-path testing; the numbers are meaningless there)
        force_lanes = bool(os.environ.get("MOSAIC_BENCH_FORCE_TPU_LANES"))
        # quick mode: headline + writeback autotune + pallas + baselines
        # only — a number inside a short time limit (scale defaults off in
        # quick mode; an explicit MOSAIC_BENCH_SCALE_POINTS still enables it)
        quick = bool(os.environ.get("MOSAIC_BENCH_QUICK"))
        if quick:
            detail["quick"] = True
        n_device = int(
            os.environ.get(
                "MOSAIC_BENCH_POINTS", 4_000_000 if on_tpu else 1_000_000
            )
        )
        n_passes = max(1, int(os.environ.get("MOSAIC_BENCH_PASSES", "3")))
        n_base = 200_000
        cell_dtype = (
            jnp.float32
            if os.environ.get("MOSAIC_BENCH_CELL_DTYPE", "f32") == "f32"
            else jnp.float64
        )

        h3 = H3IndexSystem()
        zones, zones_src = _load_zones()
        b = zones.bounds()
        bbox = (
            float(np.nanmin(b[:, 0])),
            float(np.nanmin(b[:, 1])),
            float(np.nanmax(b[:, 2])),
            float(np.nanmax(b[:, 3])),
        )
        t0 = time.perf_counter()
        index, cache_hit, tess_only_s = _load_or_build_index(
            zones, zones_src, h3
        )
        _prog(f"index ready (cache_hit={cache_hit})")
        # on a hit this is npz-load time, NOT tessellation speed — the
        # flag keeps cross-round comparisons honest
        tess_s = time.perf_counter() - t0
        detail["tessellate_s"] = round(tess_s, 2)
        detail["tessellate_cache_hit"] = cache_hit
        if tess_only_s:
            # BASELINE's secondary metric: H3 tessellate chips/sec —
            # timed around tessellate() alone (not index build or the
            # cache write), and only when actually computed
            detail["tessellate_chips_per_sec"] = round(
                int(index.chip_geom.shape[0]) / tess_only_s, 1
            )
        detail.update(
            n_zones=len(zones),
            n_chips=int(index.chip_geom.shape[0]),
            h3_res=RES,
            zones=zones_src,
            n_heavy_cells=index.num_heavy_cells,
            edge_cap=int(index.cell_edges.shape[1]),
        )

        # one contiguous host pool sliced into n_passes DISTINCT point
        # sets, so no pass can be answered from a cached result
        _prog("generating host point pool")
        all_pts = random_points(n_passes * n_device, bbox=bbox, seed=11)
        shift = np.asarray(index.border.shift, dtype=np.float64)
        dtype = index.border.verts.dtype

        index_cells = np.asarray(index.cells)

        @jax.jit
        def cells_of(points_f64):
            c = h3.point_to_cell(points_f64.astype(cell_dtype), RES)
            return c.astype(jnp.int64)

        @functools.partial(
            jax.jit,
            static_argnames=(
                "found_cap", "heavy_cap", "writeback", "lookup", "compaction"
            ),
        )
        def step(points_f64, chip_index, found_cap, heavy_cap,
                 writeback="scatter", lookup="gather",
                 compaction="scatter"):
            cells = h3.point_to_cell(points_f64.astype(cell_dtype), RES)
            shifted = (points_f64 - chip_index.border.shift).astype(dtype)
            return pip_join_points(
                shifted,
                cells.astype(jnp.int64),
                chip_index,
                heavy_cap=heavy_cap,
                found_cap=found_cap,
                writeback=writeback,
                lookup=lookup,
                compaction=compaction,
            )

        # full-bit XOR-shift fold: every result bit stays live (a masked
        # sum lets XLA dead-code the high half); int32 end to end
        _fold = jax.jit(lambda m: (m ^ (m >> 16)).sum())
        # device-side stats so the 4M-row match array never crosses to the
        # host
        _stats = jax.jit(lambda m: ((m >= 0).sum(), (m == -2).sum()))

        def bucket(n):
            """128k-multiple buckets above 128k (pow2 below): tighter than
            pure pow2 — a 530k estimate caps at 640k, not 1M, and cap size
            directly scales the tier-1 gather and scatter-back cost."""
            if n <= 131072:
                return max(16, 1 << int(np.ceil(np.log2(n + 1))))
            return (n + 131071) // 131072 * 131072

        def caps_for(cnp, margin, clamp):
            """Bucketed compaction caps from host-side counts, with a
            safety margin so one presample sizes every batch (an overflow
            (-2) in any output triggers a redo at doubled caps)."""
            pos = np.clip(
                np.searchsorted(index_cells, cnp), 0, index_cells.size - 1
            )
            fnp = index_cells[pos] == cnp
            n_found = int(fnp.sum() * margin)
            fcap = min(bucket(n_found), clamp)
            hcap = None
            if index.num_heavy_cells:
                hmask = np.asarray(index.cell_heavy) >= 0
                n_heavy = int(np.isin(cnp[fnp], index_cells[hmask]).sum() * margin)
                hcap = min(bucket(n_heavy), fcap)
            return fcap, hcap, float(fnp.mean())

        # size the compaction caps once from a host presample (the timed
        # loop then runs sync-free); scale counts to the batch size
        batch = min(4_000_000, n_device)
        pre = np.asarray(cells_of(jnp.asarray(all_pts[:n_base])))
        fcap, hcap, ffrac = caps_for(
            pre, margin=1.5 * batch / n_base, clamp=batch
        )

        # warm up compile on one batch; on compile failure halve the batch
        # and retry so the bench always records a real number
        attempts = []
        _prog(f"compiling main step (batch={batch})")
        while True:
            try:
                first = jnp.asarray(all_pts[:batch])
                t0 = time.perf_counter()
                float(_fold(step(first, index, fcap, hcap)))
                detail["compile_s"] = round(time.perf_counter() - t0, 2)
                break
            except Exception as e:
                attempts.append({"batch": batch, "error": repr(e)[:200]})
                if batch <= 125_000:
                    raise
                batch //= 2
                fcap = min(fcap, batch)
                hcap = min(hcap, fcap) if hcap else hcap
        if attempts:
            detail["compile_attempts"] = attempts
        _prog(f"main step compiled in {detail.get('compile_s')}s")
        detail["batch"] = batch
        detail["caps"] = [fcap, hcap]

        # pre-stage every pass's batches in HBM (a real pipeline overlaps
        # host ingest with device compute; the metric is the join itself)
        def stage(pts):
            sp = [
                jax.device_put(jnp.asarray(pts[s : s + batch]))
                for s in range(0, len(pts), batch)
            ]
            for sb in sp:
                sb.block_until_ready()
            return sp

        _prog("staging passes to device")
        staged_passes = [
            stage(all_pts[p * n_device : (p + 1) * n_device])
            for p in range(n_passes)
        ]
        _prog("staging done")

        # fixed sync round-trip: min of three scalar pulls of values that
        # are already computed — subtracted from every timed pass
        _bump = jax.jit(lambda s: s + 1)
        readies = [_bump(jnp.int32(i)) for i in range(3)]
        for r_ in readies:
            r_.block_until_ready()
        rtts = []
        for r_ in readies:
            t0 = time.perf_counter()
            float(r_)
            rtts.append(time.perf_counter() - t0)
        rtt = min(rtts)
        detail["sync_rtt_s"] = round(rtt, 4)

        def run_pass(sp, fc, hc, wb="scatter", lk="gather", cp="scatter"):
            """Time one pass: dispatch every batch, force completion via
            the device fold of each output pulled as one chained scalar."""
            t0 = time.perf_counter()
            outs = [
                step(sb, index, fc, hc, writeback=wb, lookup=lk,
                     compaction=cp)
                for sb in sp
            ]
            tot = None
            for o in outs:
                s = _fold(o)
                tot = s if tot is None else tot + s
            float(tot)
            return time.perf_counter() - t0, outs

        def measure(fc, hc):
            # overflow is checked on EVERY pass (each pass joins a distinct
            # point set, so a cap overflow may appear only in a later one
            # — the min-time pass must not be reported with invalid outputs)
            times, outs0, n_match, n_over = [], None, 0, 0
            for p, sp in enumerate(staged_passes):
                dt, outs = run_pass(sp, fc, hc)
                times.append(round(dt, 4))
                for o in outs:
                    m, v = _stats(o)
                    n_over += int(v)
                    if p == 0:
                        n_match += int(m)
                if p == 0:
                    outs0 = outs
            return times, outs0, n_match, n_over

        _prog("measuring scatter writeback")
        times, outs0, n_match, n_over = measure(fcap, hcap)
        if n_over:  # compaction cap overflow: redo at doubled caps
            fcap = min(fcap * 2, batch)
            hcap = min((hcap or 16) * 2, fcap)
            detail["caps_redo"] = [fcap, hcap]
            run_pass(staged_passes[0], fcap, hcap)  # discard: recompile
            times, outs0, n_match, n_over = measure(fcap, hcap)
        detail["passes_s"] = times
        dev_s = max(min(times) - rtt, 1e-9)
        dev_rate = n_device / dev_s
        detail["writeback"] = {"scatter": round(dev_rate, 1)}
        detail["main_points_per_sec"] = round(dev_rate, 1)

        # TPU autotune: A/B the probe plumbing variants and headline the
        # winner. (writeback, lookup) pairs — "mxu" replaces the tier-1
        # row gather with a bit-exact one-hot MXU matmul (measured
        # 2026-07-31 on v5e: scatter+mxu 63.4M vs scatter+gather 34.9M
        # pts/s). Each variant has its own try: one failure (the direct
        # lane has hit tpu_compile_helper crashes) must not lose the rest.
        win_wb, win_lk, win_cp = "scatter", "gather", "scatter"
        if on_tpu or force_lanes:
            variants = [
                ("scatter", "mxu", "scatter"),
                ("scatter", "mxu", "mxu"),
                ("scatter", "mxu2", "scatter"),
                ("gather", "gather", "scatter"),
                ("gather", "mxu", "mxu"),
                ("direct", "gather", "scatter"),
            ]
            detail["writeback"]["winner"] = "scatter"
            for wb, lk, cp in variants:
                name = wb if lk == "gather" else f"{wb}+{lk}"
                if cp != "scatter":
                    name += "+cmxu"
                try:
                    _prog(f"{name} variant lane")
                    run_pass(staged_passes[0], fcap, hcap, wb=wb, lk=lk,
                             cp=cp)
                    v_times = [
                        round(
                            run_pass(sp, fcap, hcap, wb=wb, lk=lk, cp=cp)[0],
                            4,
                        )
                        for sp in staged_passes
                    ]
                    v_s = max(min(v_times) - rtt, 1e-9)
                    detail["writeback"][name] = round(n_device / v_s, 1)
                    detail["writeback"][f"{name}_passes_s"] = v_times
                    if v_s < dev_s:
                        dev_s, dev_rate = v_s, n_device / v_s
                        detail["writeback"]["winner"] = name
                        win_wb, win_lk, win_cp = wb, lk, cp
                except Exception as e:
                    detail["writeback"][f"{name}_error"] = repr(e)[:200]
            detail["main_points_per_sec"] = round(dev_rate, 1)
        # probe traffic roofline, computed from the arrays one probe
        # actually touches (never hand-written): a miss stops at one hash
        # bucket row, a found point adds its cell's tier-1 edge row,
        # heavy-cell points additionally the tier-2 row. Emitted per
        # writeback variant so a lane-plumbing change shows up as a
        # bandwidth delta, not just a pts/s delta.
        bucket_b = int(index.table_cell.shape[1]) * (
            index.table_cell.dtype.itemsize + index.table_slot.dtype.itemsize
        )
        edge_b = (
            int(index.cell_edges.shape[-1]) * index.cell_edges.dtype.itemsize
            + index.cell_ebits.dtype.itemsize
        )
        e1 = int(index.cell_edges.shape[1])
        e2 = int(index.heavy_edges.shape[1]) if index.num_heavy_cells else 0
        e3 = (
            int(index.convex_edges.shape[2])
            if index.num_convex_cells
            else 0
        )
        hfrac = float((np.asarray(index.cell_heavy) >= 0).mean())
        bpp = bucket_b + edge_b * (e1 + e2 * hfrac) * ffrac
        peak = _hbm_peak_gbps()
        roofline = {
            "bytes_per_point": round(bpp, 1),
            "bucket_bytes": bucket_b,
            "edge_bytes": edge_b,
            "hbm_peak_gbps": peak,
            "heavy_cell_frac": round(hfrac, 4),
            # what the adaptive router's lanes each cost per routed point
            # (light = tier-1 row, heavy adds the tier-2 row, convex reads
            # the y-bucketed reduced row instead of the tier-1 row)
            "per_lane_bytes_per_point": {
                "light": bucket_b + edge_b * e1,
                "heavy": bucket_b + edge_b * (e1 + e2),
                "convex": bucket_b + edge_b * e3,
            },
            "per_writeback": {},
        }
        for vname, vrate in detail["writeback"].items():
            if not isinstance(vrate, (int, float)):
                continue  # "winner" tag, pass-time lists, error strings
            v_gbps = bpp * vrate / 1e9
            entry = {
                "points_per_sec": vrate,
                "achieved_gbps": round(v_gbps, 2),
            }
            if peak:
                entry["pct_hbm_peak"] = round(100.0 * v_gbps / peak, 2)
            roofline["per_writeback"][vname] = entry
        detail.update(
            n_points=n_device,
            device_s=round(dev_s, 3),
            match_rate=round(n_match / n_device, 4),
            found_rate=round(ffrac, 4),
            overflow=n_over,
            roofline=roofline,
        )

        # Pallas zone-level kernel lane (the BASELINE.json north-star
        # kernel): brute-force PIP against every zone polygon, compiled
        # (not interpret). Runs unconditionally on TPU; elsewhere the skip
        # is recorded loudly instead of silently dropping the lane.
        if on_tpu or force_lanes:
            try:
                _prog("pallas lane")
                from mosaic_tpu.core.geometry.device import pack_to_device
                from mosaic_tpu.kernels.pip import edge_planes, pip_zone

                zdev = pack_to_device(zones, dtype=jnp.float32, recenter=True)
                planes, n_real = edge_planes(zdev)
                zshift = np.asarray(zdev.shift, dtype=np.float64)
                n_pal = min(500_000, n_device)
                from mosaic_tpu.runtime.platform import interpret_kernels

                # the one interpret rule: compiled on every chip, so a
                # Mosaic refusal fails this lane (and the run) loudly
                pal_jit = jax.jit(
                    functools.partial(
                        pip_zone, n_real_g=n_real,
                        interpret=interpret_kernels(),
                    )
                )
                # two DISTINCT staged slices when the point pool allows
                # (one otherwise); compile on the first
                n_sl = 2 if 2 * n_pal <= len(all_pts) else 1
                pslices = [
                    jnp.asarray(
                        (all_pts[i * n_pal : (i + 1) * n_pal] - zshift).astype(
                            np.float32
                        )
                    )
                    for i in range(n_sl)
                ]
                out0 = pal_jit(pslices[0], planes)
                float(_fold(out0))  # compile + force
                pal_times = []
                for ps in pslices:
                    t0 = time.perf_counter()
                    out = pal_jit(ps, planes)
                    float(_fold(out))
                    pal_times.append(time.perf_counter() - t0)
                pal_s = max(min(pal_times) - rtt, 1e-9)
                detail["pallas_points_per_sec"] = round(n_pal / pal_s, 1)
                # pts/s alone misreads: this kernel is BRUTE FORCE
                # (every point x every zone x every edge — no index), so
                # also report the arithmetic rate it sustains. ~8 VPU
                # flops per (point, zone-slot, edge) crossing test.
                E_pal, G_pal = int(planes.shape[1]), int(planes.shape[2])
                detail["pallas_brute_force_work"] = (
                    f"{n_pal} pts x {G_pal} zone slots x {E_pal} edges"
                )
                detail["pallas_achieved_gflops"] = round(
                    8.0 * n_pal * G_pal * E_pal / pal_s / 1e9, 1
                )
                m, _ = _stats(out0)
                detail["pallas_match_rate"] = round(int(m) / n_pal, 4)
            except Exception as e:  # kernel failure must not kill the bench
                detail["pallas_error"] = repr(e)[:200]
        else:
            detail["pallas_skipped"] = (
                f"not measured: device is {detail['device']} (TPU required)"
            )

        # scale lane (TPU only): ≥16M points generated ON DEVICE (no
        # host transfer), same compiled step — quantifies achieved HBM
        # bandwidth headroom toward the 1B-point north star
        # quick mode defaults the slowest lane OFF, but an explicit env
        # override always wins (matches the comment at the quick flag)
        n_scale = int(
            os.environ.get(
                "MOSAIC_BENCH_SCALE_POINTS", "0" if quick else "16000000"
            )
        )
        if (on_tpu or force_lanes) and n_scale >= n_device:
            try:
                _prog(f"scale lane ({n_scale} pts, device-generated)")
                nb = (n_scale + batch - 1) // batch
                lo = jnp.asarray(bbox[:2], dtype=jnp.float32)
                span = jnp.asarray(
                    [bbox[2] - bbox[0], bbox[3] - bbox[1]], dtype=jnp.float32
                )

                @functools.partial(jax.jit, static_argnames=("n",))
                def gen_batch(key, n):
                    u = jax.random.uniform(key, (n, 2), dtype=jnp.float32)
                    return (lo + u * span).astype(jnp.float64)

                key = jax.random.PRNGKey(1234)
                scale_passes = []
                for p in range(2):  # two distinct generated sets
                    sp = [
                        gen_batch(jax.random.fold_in(key, p * nb + i), batch)
                        for i in range(nb)
                    ]
                    for sb in sp:
                        sb.block_until_ready()
                    scale_passes.append(sp)
                stimes = []
                souts0: list = []
                for p, sp in enumerate(scale_passes):
                    t0 = time.perf_counter()
                    outs = [
                        step(sb, index, fcap, hcap, writeback=win_wb,
                             lookup=win_lk, compaction=win_cp)
                        for sb in sp
                    ]
                    tot = None
                    for o in outs:
                        s = _fold(o)
                        tot = s if tot is None else tot + s
                    float(tot)
                    stimes.append(round(time.perf_counter() - t0, 4))
                    if p == 0:
                        souts0 = outs  # reuse for overflow stats below
                s_dev = max(min(stimes) - rtt, 1e-9)
                s_rate = nb * batch / s_dev
                n_sover = sum(int(_stats(o)[1]) for o in souts0)
                detail["scale"] = {
                    "n_points": nb * batch,
                    "passes_s": stimes,
                    "points_per_sec": round(s_rate, 1),
                    "achieved_gb_per_s": round(bpp * s_rate / 1e9, 1),
                    "overflow": n_sover,
                }
            except Exception as e:
                detail["scale_error"] = repr(e)[:200]

        # NumPy baseline on a subsample of the same workload (same flat
        # layout, same cell assignment — the single-core competitor)
        _prog("numpy baseline lane")
        sub = all_pts[:n_base]
        pcells = np.asarray(
            h3.point_to_cell(jnp.asarray(sub, dtype=cell_dtype), RES)
        ).astype(np.int64)
        t0 = time.perf_counter()
        base = _numpy_join((sub - shift).astype(np.float64), index, pcells)
        base_s = time.perf_counter() - t0
        base_rate = n_base / base_s
        detail["numpy_points_per_sec"] = round(base_rate, 1)
        # device agreement on the shared prefix — slice on device first so
        # only n_base rows cross to the host
        nb0 = min(n_base, int(outs0[0].shape[0]))  # batch may have shrunk
        dev_prefix = np.asarray(outs0[0][:nb0])
        detail["numpy_agreement"] = float((base[:nb0] == dev_prefix).mean())

        # single-thread C++ reference-path lane (VERDICT r4 #4): binary-
        # search equi-join + per-chip `is_core || contains` over clipped
        # chip rings — the honest JTS-codegen analog this environment can
        # run. ``vs_baseline`` is measured against THIS lane when the
        # native library builds (numpy otherwise).
        _prog("native C++ baseline lane")
        base_kind = "numpy"
        try:
            from mosaic_tpu.core.geometry.second import (
                chip_index_csr,
                eval_pip_join,
            )

            csr_xy, csr_ro, csr_cro = chip_index_csr(
                np.asarray(index.border.verts),
                np.asarray(index.border.ring_len),
            )
            nat_args = (
                csr_xy, csr_ro, csr_cro,
                np.asarray(index.chip_core), np.asarray(index.chip_geom),
                np.asarray(index.cells), np.asarray(index.chip_rows),
                (sub - shift).astype(np.float64), pcells,
            )
            native = eval_pip_join(*nat_args)  # warm (may build the .so)
            t0 = time.perf_counter()
            native = eval_pip_join(*nat_args)
            nat_s = time.perf_counter() - t0
            detail["native_points_per_sec"] = round(n_base / nat_s, 1)
            detail["native_agreement"] = float(
                (native[:nb0] == dev_prefix).mean()
            )
            base_rate = n_base / nat_s
            base_kind = "native_cpp_single_thread"
        except Exception as e:  # missing toolchain: keep the numpy lane
            detail["native_error"] = repr(e)[:200]
        detail["baseline_kind"] = base_kind

        # f32 cell assignment knowingly trades near-edge points for
        # throughput — quantify the END-TO-END effect every run: same
        # NumPy join fed f64-assigned cells, floor 0.998 on join results
        # (cell-level disagreement overstates it: a moved cell only flips
        # the answer when the point also sits near a zone boundary)
        if cell_dtype == jnp.float32:
            from mosaic_tpu.runtime.retry import RetryPolicy, call_with_retry

            try:
                # transient device failures retry via the shared runtime
                # policy before the lane is abandoned
                c64 = np.asarray(
                    call_with_retry(
                        lambda: jax.jit(
                            lambda p: h3.point_to_cell(p, RES).astype(
                                jnp.int64
                            )
                        )(jnp.asarray(sub, dtype=jnp.float64)),
                        policy=RetryPolicy(
                            max_attempts=3, base_delay_s=2.0,
                            max_delay_s=30.0, timeout_s=120.0,
                        ),
                        label="bench.agreement_lane",
                    )
                )
                detail["cell_f32_f64_agreement"] = round(
                    float((pcells == c64).mean()), 6
                )
                base64 = _numpy_join(
                    (sub - shift).astype(np.float64), index, c64
                )
                jagree = float((base == base64).mean())
                detail["join_f32_f64_agreement"] = round(jagree, 6)
                if jagree < 0.998:
                    detail["join_f32_f64_floor_violated"] = True
            except Exception as e:  # non-transient: the headline already
                # measured; record and keep the bench line
                detail["agreement_error"] = repr(e)[:200]

        # epsilon-band borderline recheck lane (SURVEY §7, VERDICT r4 #3):
        # band sizes, corrected agreement vs the exact f64 host oracle
        # (the bar is EXACTLY 1.0), and the throughput cost of the band-
        # instrumented step. On TPU the full fused step is timed over the
        # same staged passes; on CPU a 60k eager-path subsample checks
        # correctness only (the fused compile costs minutes there).
        _prog("recheck lane" + (" (skipped: quick)" if quick else ""))
        try:
            if quick:
                raise _QuickSkip()
            from mosaic_tpu.sql.join import (
                CELL_MARGIN_K,
                EDGE_BAND_K,
                _compact,
                host_join,
                pip_join,
            )

            rc: dict = {}
            detail["recheck"] = rc
            host = index.host
            cell_np = np.float32 if cell_dtype == jnp.float32 else np.float64
            km_val = CELL_MARGIN_K * float(np.finfo(cell_np).eps)
            eps2_val = (
                EDGE_BAND_K * float(np.finfo(np.dtype(dtype)).eps)
                * host.coord_scale
            ) ** 2
            if on_tpu or force_lanes:
                # band-compacted narrow recheck: size the flag cap from
                # the presample's measured band fraction (1.25x margin +
                # floor) instead of a flat batch//8 — the alt re-join's
                # cost is linear in this cap, and the r05 lane paid a
                # 12.5%-of-batch re-join for a ~4.7% band. The margin is
                # ~50 sigma of the binomial count at 4M; band points
                # beyond the cap escalate to the host oracle via overF
                # (exact, just slower), never a wrong answer.
                _, m_pre = jax.jit(
                    lambda p: h3.point_to_cell_margin(p, RES)
                )(jnp.asarray(all_pts[:n_base], dtype=cell_dtype))
                band_pre = float(
                    (np.asarray(m_pre)[:, 0] < km_val).mean()
                )
                flag_cap = min(
                    bucket(int(1.25 * band_pre * batch) + 2048), batch
                )
                rc["band_frac_presample"] = round(band_pre, 5)
                rc["flag_cap"] = flag_cap

                @jax.jit
                def step_rc(points_f64, chip_index):
                    cells, margins = h3.point_to_cell_margin(
                        points_f64.astype(cell_dtype), RES
                    )
                    cells = cells.astype(jnp.int64)
                    shifted = (
                        points_f64 - chip_index.border.shift
                    ).astype(dtype)
                    out, near = pip_join_points(
                        shifted, cells, chip_index,
                        heavy_cap=hcap, found_cap=fcap,
                        edge_eps2=jnp.asarray(eps2_val, dtype),
                        writeback=win_wb, lookup=win_lk,
                        compaction=win_cp,
                    )
                    flagged = margins[..., 0] < km_val
                    srcF, validF, overF, _ = _compact(flagged, flag_cap)
                    alt = h3.point_to_cell_alt(
                        points_f64[srcF].astype(cell_dtype), RES
                    ).astype(jnp.int64)
                    # the single narrow re-join over the compacted band,
                    # on the autotuned winner's probe plumbing
                    r_alt = pip_join_points(
                        shifted[srcF], alt, chip_index,
                        lookup=win_lk, compaction=win_cp,
                    )
                    tie = validF & (
                        (r_alt != out[srcF])
                        | (margins[srcF, 1] < km_val)
                        | (alt < 0)
                    )
                    esc = (near | overF).at[srcF].max(tie)
                    return out, esc, flagged

                # compile + timed passes over the same staged batches
                float(_fold(step_rc(staged_passes[0][0], index)[0]))
                rc_times = []
                outs_rc0 = None
                for p, sp in enumerate(staged_passes):
                    t0 = time.perf_counter()
                    outs = [step_rc(sb, index) for sb in sp]
                    tot = None
                    for o, e, f in outs:
                        s = _fold(o) + e.sum() + f.sum()
                        tot = s if tot is None else tot + s
                    float(tot)
                    rc_times.append(round(time.perf_counter() - t0, 4))
                    if p == 0:
                        outs_rc0 = outs
                rc_dev_s = max(min(rc_times) - rtt, 1e-9)
                rc["passes_s"] = rc_times
                rc["device_cost_frac"] = round(rc_dev_s / dev_s - 1.0, 4)
                # correctness on pass-0 batch 0 vs the exact host oracle
                o0, e0, f0 = outs_rc0[0]
                out_np = np.asarray(o0)
                esc_np = np.asarray(e0)
                flag_np = np.asarray(f0)
                pts0 = all_pts[:batch]
                rows = np.nonzero(esc_np)[0]
                t0 = time.perf_counter()
                corrected = np.array(out_np)
                if rows.size:
                    corrected[rows] = host_join(pts0[rows], host, h3, RES)
                host_s = time.perf_counter() - t0
                rc["host_recheck_s"] = round(host_s, 4)
                rc["host_cost_frac"] = round(host_s / max(rc_dev_s, 1e-9), 4)
                t0 = time.perf_counter()
                truth = host_join(pts0, host, h3, RES)
                detail["host_oracle_points_per_sec"] = round(
                    batch / (time.perf_counter() - t0), 1
                )
                rc["band_frac"] = round(float(flag_np.mean()), 5)
                rc["esc_frac"] = round(float(esc_np.mean()), 5)
                rc["join_agreement_before"] = round(
                    float((out_np == truth).mean()), 6
                )
                rc["join_agreement_after"] = float(
                    (corrected == truth).mean()
                )
                # cell-level closure: flagged rows take the f64 cell
                c32 = np.asarray(cells_of(jnp.asarray(pts0)))
                c64h = np.asarray(h3.point_to_cell(pts0, RES))
                rc["cell_agreement_after"] = float(
                    ((c32 == c64h) | flag_np).mean()
                )
            else:
                sub = all_pts[:60_000]
                got = pip_join(
                    sub, None, h3, RES, chip_index=index,
                    recheck=True, cell_dtype=jnp.float32,
                )
                truth = host_join(sub, host, h3, RES)
                rc["join_agreement_after"] = float((got == truth).mean())
                import jax.numpy as _jnp

                _, m = h3.point_to_cell_margin(
                    _jnp.asarray(sub, dtype=_jnp.float32), RES
                )
                m = np.asarray(m)
                rc["band_frac"] = round(
                    float((m[:, 0] < km_val).mean()), 5
                )
                rc["mode"] = "cpu_subsample_60k"
        except _QuickSkip:
            detail["recheck"] = {"skipped": "quick"}
        except Exception as e:  # the lane must not kill the bench
            detail["recheck_error"] = repr(e)[:300]

        # secondary micro-lanes: the row-wise ST_Intersects pair predicate
        # (the compute core of the overlay-join config; NOT the full BNG
        # indexed join) and a small SpatialKNN transform. Same timing
        # doctrine as the main lane: warm compile, then min over passes
        # with DISTINCT inputs, dispatch RTT subtracted.
        _prog("secondary lanes" + (" (skipped: quick)" if quick else ""))
        try:
            if quick:
                raise _QuickSkip()
            sec: dict = {}
            from mosaic_tpu import functions as Fn
            from mosaic_tpu.datasets import synthetic_zones
            from mosaic_tpu.functions.formats import st_point
            from mosaic_tpu.models.knn import SpatialKNN

            bbox_b = (
                bbox[0], bbox[1],
                bbox[0] + 0.7 * (bbox[2] - bbox[0]),
                bbox[1] + 0.7 * (bbox[3] - bbox[1]),
            )
            pairs = [
                (
                    synthetic_zones(16, 16, bbox=bbox, seed=s),
                    synthetic_zones(16, 16, bbox=bbox_b, seed=s + 1),
                )
                for s in (7, 21)
            ]
            hits = np.asarray(Fn.st_intersects(*pairs[0]))  # compile/warm
            ov_times = []
            for za, zb_arr in pairs:
                t0 = time.perf_counter()
                hits = np.asarray(Fn.st_intersects(za, zb_arr))
                ov_times.append(time.perf_counter() - t0)
            ov_s = max(min(ov_times) - rtt, 1e-9)
            sec["overlay_pairs_per_sec"] = round(len(hits) / ov_s, 1)
            sec["overlay_hit_frac"] = round(float(hits.mean()), 3)

            rng_k = np.random.default_rng(5)

            def knn_inputs():
                return (
                    st_point(*rng_k.uniform(bbox[:2], bbox[2:], (8, 2)).T),
                    st_point(*rng_k.uniform(bbox[:2], bbox[2:], (4096, 2)).T),
                )

            knn = SpatialKNN(
                index=h3, resolution=RES - 2, k_neighbours=4,
                max_iterations=8,
            )
            knn.transform(*knn_inputs())  # warm/compile
            kn_times = []
            for _ in range(2):
                lm, cd = knn_inputs()  # distinct draws per pass
                t0 = time.perf_counter()
                r_knn = knn.transform(lm, cd)
                kn_times.append(time.perf_counter() - t0)
            sec["knn_transform_s"] = round(max(min(kn_times) - rtt, 1e-9), 3)
            sec["knn_matches"] = int(r_knn.landmark_id.shape[0])

            # ship2ship core: buffered-track corridors -> indexed
            # intersects join. This is a HOST lane (tessellation +
            # oracle refinement are host work by design; the device
            # backend would recompile per distinct pair-list shape), so
            # no RTT subtraction applies; warm-up uses a set that is
            # never measured
            from mosaic_tpu.core.geometry import wkt as Wk
            from mosaic_tpu.sql.overlay import intersects_join

            def tracks(n, seed):
                rg = np.random.default_rng(seed)
                out = []
                for _ in range(n):
                    x, y = rg.uniform(bbox[0], bbox[2]), rg.uniform(
                        bbox[1], bbox[3]
                    )
                    hd = rg.uniform(0, 2 * np.pi)
                    pts = []
                    for _k in range(6):
                        pts.append(f"{x:.6f} {y:.6f}")
                        x += 0.02 * np.cos(hd) + rg.normal(0, 0.003)
                        y += 0.02 * np.sin(hd) + rg.normal(0, 0.003)
                    out.append("LINESTRING (" + ", ".join(pts) + ")")
                return Wk.from_wkt(out)

            s2s_sets = [
                (
                    Fn.st_buffer(tracks(24, s), 0.004),
                    Fn.st_buffer(tracks(24, s + 1), 0.004),
                )
                for s in (3, 31, 57)
            ]
            intersects_join(*s2s_sets[0], h3, RES - 2)  # warm caches
            s2s_times = []
            for ba, bb in s2s_sets[1:]:
                t0 = time.perf_counter()
                prs = intersects_join(ba, bb, h3, RES - 2)
                s2s_times.append(time.perf_counter() - t0)
            sec["ship2ship_join_host_s"] = round(min(s2s_times), 3)
            sec["ship2ship_pairs"] = int(np.asarray(prs).shape[0])
            detail["secondary"] = sec  # only a complete record is exposed
        except _QuickSkip:
            detail["secondary"] = {"skipped": "quick"}
        except Exception as e:
            detail["secondary_error"] = repr(e)[:200]

        _prog("all lanes done")
        _emit({
            "metric": "nyc_pip_join_throughput",
            "value": round(dev_rate, 1),
            "unit": unit,
            "vs_baseline": round(dev_rate / base_rate, 2),
            "detail": detail,
        })
    except Exception as e:  # emit what was measured, then fail
        detail["error"] = repr(e)[:500]
        detail["elapsed_s"] = round(time.perf_counter() - t_start, 1)
        _emit({
            "metric": "nyc_pip_join_throughput",
            "value": float(detail.get("main_points_per_sec") or 0.0),
            "unit": unit,
            "detail": detail,
        })
        sys.exit(1)
    if detail["lane_errors"]:
        _prog(f"FAILED lanes: {detail['lane_errors']}")
        sys.exit(1)


if __name__ == "__main__":
    main()
