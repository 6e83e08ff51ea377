"""Epsilon-band borderline recheck (SURVEY §7 precision strategy).

Reference contract: JTS evaluates `contains` in exact f64 arithmetic
(`core/geometry/MosaicGeometryJTS.scala:61-101`); the TPU fast path runs
f32. These tests pin the three layers that close the gap:

1. the cell-rounding margin (`IndexSystem.point_to_cell_margin`) flags
   EVERY point whose f32 cell differs from the f64 cell, with 2x headroom
   on the calibrated constant `sql.join.CELL_MARGIN_K`;
2. the runner-up cell (`point_to_cell_alt`) + the vertex/invalid flags
   cover the true cell for every flagged point (so only genuine result
   ties escalate to the host oracle);
3. end to end, `pip_join(recheck=True)` with f32 cell assignment equals
   the exact f64 host join everywhere.
"""

import json
import os

import jax.numpy as jnp
import numpy as np

from mosaic_tpu.core.geometry import wkt
from mosaic_tpu.core.index import BNG, H3
from mosaic_tpu.runtime import telemetry
from mosaic_tpu.sql.join import (
    CELL_MARGIN_K,
    EDGE_BAND_K,
    build_chip_index,
    host_join,
    pip_join,
)
from mosaic_tpu.core.tessellate import tessellate

EPS32 = float(np.finfo(np.float32).eps)
GOLDEN = os.path.join(
    os.path.dirname(__file__), "goldens", "recheck_margins.json"
)


def _global_points(n, seed=3):
    rng = np.random.default_rng(seed)
    lng = rng.uniform(-180, 180, n)
    lat = np.degrees(np.arcsin(rng.uniform(-0.999, 0.999, n)))
    return np.stack([lng, lat], -1)


def test_margin_covers_all_f32_disagreements():
    """Every point whose f32 cell differs from f64 must sit inside the
    epsilon band, with >= 2x headroom below CELL_MARGIN_K."""
    pts = _global_points(150_000)
    res = 9
    c64 = np.asarray(H3.point_to_cell(pts, res))  # host f64 path
    f32 = jnp.asarray(pts, dtype=jnp.float32)
    c32, m = H3.point_to_cell_margin(f32, res)
    c32, m = np.asarray(c32), np.asarray(m)
    dis = c32 != c64
    assert dis.any(), "sanity: f32 must disagree somewhere at res 9"
    worst = m[dis, 0].max() / EPS32
    assert worst <= CELL_MARGIN_K / 2, (
        f"disagreeing point at margin {worst:.2f}·eps — above the "
        f"calibrated headroom ({CELL_MARGIN_K}/2)"
    )
    # the band must stay a small minority of points (recheck cost bound)
    band = (m[:, 0] < CELL_MARGIN_K * EPS32).mean()
    assert band < 0.08, f"cell band too wide: {band:.3f}"


def test_alt_cell_covers_flagged_points():
    """For flagged points the true f64 cell is the primary or the runner-
    up — except near cell corners (margin 2 flags) or where no valid
    alternate exists (alt == -1): those escalate to the host."""
    pts = _global_points(150_000, seed=11)
    res = 9
    c64 = np.asarray(H3.point_to_cell(pts, res))
    f32 = jnp.asarray(pts, dtype=jnp.float32)
    c32, m = H3.point_to_cell_margin(f32, res)
    alt = np.asarray(H3.point_to_cell_alt(f32, res))
    c32, m = np.asarray(c32), np.asarray(m)
    km = CELL_MARGIN_K * EPS32
    flagged = m[:, 0] < km
    vertex = m[:, 1] < km
    dis = c32 != c64
    covered = ~dis | (flagged & ((alt == c64) | vertex | (alt == -1)))
    bad = np.nonzero(~covered)[0]
    assert bad.size == 0, (
        f"{bad.size} disagreements escape the band/alt/vertex cover, "
        f"e.g. point {pts[bad[0]] if bad.size else None}"
    )
    # escalation set (host recheck upper bound) stays tiny
    esc = (flagged & (vertex | (alt == -1))).mean()
    assert esc < 0.005, f"direct-host escalation too wide: {esc:.4f}"


def test_alt_cell_is_a_neighbor():
    """The runner-up is a distinct cell, and (away from face-overage
    geometry, where grid adjacency itself warps) a k-ring-1 neighbor."""
    pts = _global_points(20_000, seed=5)
    res = 7
    f32 = jnp.asarray(pts, dtype=jnp.float32)
    c32 = np.asarray(H3.point_to_cell(f32, res))
    alt = np.asarray(H3.point_to_cell_alt(f32, res))
    ok = alt >= 0
    assert (alt[ok] != c32[ok]).all()
    rings = np.asarray(H3.k_ring(jnp.asarray(c32[ok]), 1))
    neighbor_frac = (rings == alt[ok, None]).any(axis=1).mean()
    assert neighbor_frac > 0.999


def _nyc_zones():
    return wkt.from_wkt(
        [
            "POLYGON ((-74.02 40.70, -73.96 40.70, -73.96 40.76, "
            "-74.02 40.76, -74.02 40.70))",
            "POLYGON ((-73.96 40.70, -73.90 40.70, -73.90 40.76, "
            "-73.96 40.76, -73.96 40.70))",
            "POLYGON ((-74.00 40.77, -73.92 40.77, -73.92 40.80, "
            "-74.00 40.80, -74.00 40.77), (-73.97 40.78, -73.97 40.79, "
            "-73.95 40.79, -73.95 40.78, -73.97 40.78))",
        ]
    )


def test_pip_join_recheck_matches_host_oracle_exactly():
    """f32 cells + f32 probe + recheck == the exact f64 host join,
    row for row (the VERDICT r4 'discrepancies drop to 0' bar)."""
    col = _nyc_zones()
    res = 9
    rng = np.random.default_rng(2)
    pts = np.column_stack(
        [rng.uniform(-74.05, -73.87, 60_000), rng.uniform(40.68, 40.82, 60_000)]
    )
    table = tessellate(col, H3, res, keep_core_geoms=False)
    idx = build_chip_index(table)
    got = pip_join(
        pts, None, H3, res, chip_index=idx,
        recheck=True, cell_dtype=jnp.float32,
    )
    want = host_join(pts, idx.host, H3, res)
    np.testing.assert_array_equal(got, want)


def test_pip_join_recheck_off_still_close():
    """Without recheck the f32 path may differ only inside the band."""
    col = _nyc_zones()
    res = 9
    rng = np.random.default_rng(4)
    pts = np.column_stack(
        [rng.uniform(-74.05, -73.87, 40_000), rng.uniform(40.68, 40.82, 40_000)]
    )
    table = tessellate(col, H3, res, keep_core_geoms=False)
    idx = build_chip_index(table)
    got = pip_join(
        pts, None, H3, res, chip_index=idx,
        recheck=False, cell_dtype=jnp.float32,
    )
    want = host_join(pts, idx.host, H3, res)
    assert (got != want).mean() < 0.005


def test_recheck_config_flag_routes_default(monkeypatch):
    import mosaic_tpu.context as ctx

    col = _nyc_zones()
    res = 8
    rng = np.random.default_rng(6)
    pts = np.column_stack(
        [rng.uniform(-74.05, -73.87, 5_000), rng.uniform(40.68, 40.82, 5_000)]
    )
    table = tessellate(col, H3, res, keep_core_geoms=False)
    idx = build_chip_index(table)
    cfg = ctx.current_config()
    monkeypatch.setattr(
        ctx, "current_config",
        lambda: type(cfg)(**{**cfg.__dict__, "exact_recheck": True}),
    )
    got = pip_join(pts, None, H3, res, chip_index=idx, cell_dtype=jnp.float32)
    want = host_join(pts, idx.host, H3, res)
    np.testing.assert_array_equal(got, want)


def test_host_companion_round_trip():
    """HostRecheck survives an npz round-trip (bench index cache)."""
    import io

    from mosaic_tpu.sql.join import HostRecheck

    col = _nyc_zones()
    idx = build_chip_index(tessellate(col, H3, 8, keep_core_geoms=False))
    buf = io.BytesIO()
    np.savez(buf, **idx.host.save_arrays())
    buf.seek(0)
    back = HostRecheck.from_arrays(np.load(buf))
    assert back.coord_scale == idx.host.coord_scale
    np.testing.assert_array_equal(back.cells, idx.host.cells)
    np.testing.assert_array_equal(back.cell_edges, idx.host.cell_edges)


def test_bng_margin_flags_boundary_points():
    cells, m = BNG.point_to_cell_margin(
        np.array([[100000.0, 200000.0], [123456.7, 254321.9]]), 4
    )
    assert m.shape == (2, 2)
    # first point sits ON a binning boundary: zero margin
    assert m[0, 0] < 1e-12
    assert m[1, 0] > 1e-6


def test_recheck_requires_host_companion():
    import dataclasses as dc

    import pytest

    col = _nyc_zones()
    idx = build_chip_index(tessellate(col, H3, 8, keep_core_geoms=False))
    stripped = dc.replace(idx)  # fresh instance without the attribute
    rng = np.random.default_rng(1)
    pts = np.column_stack(
        [rng.uniform(-74.0, -73.9, 100), rng.uniform(40.7, 40.8, 100)]
    )
    with pytest.raises(ValueError, match="host companion"):
        pip_join(pts, None, H3, 8, chip_index=stripped, recheck=True)


def test_margin_golden_two_x_headroom():
    """The committed calibration sweep (`tools/calibrate_margins.py`)
    pins the measured drift ceiling; the shipped band constants must keep
    >= 2x headroom over it, and the golden must be regenerated whenever
    the defaults change (the tool records them)."""
    with open(GOLDEN) as f:
        g = json.load(f)
    assert g["defaults"] == {
        "CELL_MARGIN_K": CELL_MARGIN_K,
        "EDGE_BAND_K": EDGE_BAND_K,
    }, "constants changed: rerun tools/calibrate_margins.py"
    cell_max = g["cell_margin"]["max_observed_k"]
    edge_max = g["edge_band"]["max_observed_k"]
    assert cell_max > 0, "sweep found no cell disagreements — no signal"
    assert edge_max > 0, "sweep found no edge disagreements — no signal"
    assert 2 * cell_max <= CELL_MARGIN_K, (
        f"cell drift {cell_max}·eps leaves <2x headroom under "
        f"CELL_MARGIN_K={CELL_MARGIN_K}"
    )
    assert 2 * edge_max <= EDGE_BAND_K, (
        f"edge drift {edge_max}·eps·scale leaves <2x headroom under "
        f"EDGE_BAND_K={EDGE_BAND_K}"
    )


def test_margin_golden_matches_fresh_measurement():
    """A fresh (smaller) drift measurement stays under the golden's 2x-
    headroom ceiling — catches silent drift in the cell pipeline."""
    import sys

    sys.path.insert(
        0, os.path.join(os.path.dirname(os.path.dirname(__file__)), "tools")
    )
    from calibrate_margins import global_points, measure_cell_drift

    r = measure_cell_drift(H3, global_points(40_000, seed=21), 9)
    assert 2 * r["max_observed_k"] <= CELL_MARGIN_K


def test_recheck_runs_one_narrow_compacted_rejoin():
    """The recheck issue-path must be ONE band-compacted narrow re-join —
    never a full-width pass: exactly one `recheck_narrow` event per
    batch, its compacted cap strictly below the batch width, its caps
    sized to the band, and the result still exactly equal to f64."""
    col = _nyc_zones()
    res = 9
    rng = np.random.default_rng(13)
    pts = np.column_stack(
        [rng.uniform(-74.05, -73.87, 30_000),
         rng.uniform(40.68, 40.82, 30_000)]
    )
    table = tessellate(col, H3, res, keep_core_geoms=False)
    idx = build_chip_index(table)
    with telemetry.capture() as events:
        got = pip_join(
            pts, None, H3, res, chip_index=idx,
            recheck=True, cell_dtype=jnp.float32,
        )
    want = host_join(pts, idx.host, H3, res)
    np.testing.assert_array_equal(got, want)
    narrow = [e for e in events if e["event"] == "recheck_narrow"]
    assert len(narrow) == 1, narrow
    e = narrow[0]
    assert e["mode"] == "alt_rejoin"
    assert 0 < e["band"] <= e["cap"] < e["n"] == pts.shape[0]
    # the re-join is sized to the band, not the batch
    assert e["caps"][0] <= e["cap"]
    assert e["ties"] >= 0 and e["seconds"] >= 0
    # the event is the band span's own record: made inside it, once, with
    # its numbers and on its clock (`join.recheck.band`, one a chunk)
    band = [s for s in events
            if s["event"] == "span" and s["name"] == "join.recheck.band"]
    assert len(band) == 1 and e["span_id"] == band[0]["span_id"]
    assert (band[0]["mode"], band[0]["band"], band[0]["cap"]) == \
        ("alt_rejoin", e["band"], e["cap"])
    assert e["seconds"] <= band[0]["seconds"]


def test_recheck_narrow_respects_margin_override():
    """cell_margin_k=0 narrows the cell band to the rows whose f32 margin
    is NEGATIVE — the cube-rounding tie-fix picked the other centre
    (`hex_round_margins`), so the row is maximally borderline and the
    exact path must still re-join it: k = 0 clamps nothing away. A wider
    band flags more rows than the default."""
    from mosaic_tpu.sql.join import _assign_cells

    col = _nyc_zones()
    res = 9
    rng = np.random.default_rng(8)
    pts = np.column_stack(
        [rng.uniform(-74.05, -73.87, 8_000),
         rng.uniform(40.68, 40.82, 8_000)]
    )
    idx = build_chip_index(tessellate(col, H3, res, keep_core_geoms=False))
    _, margins = _assign_cells(
        H3, res, jnp.asarray(pts).astype(jnp.float32), "margin"
    )
    negative = int((np.asarray(margins)[:, 0] < 0).sum())
    assert negative >= 1  # this seed holds one; k = 0 must keep it

    def band(**kw):
        with telemetry.capture() as ev:
            pip_join(
                pts, None, H3, res, chip_index=idx, recheck=True,
                cell_dtype=jnp.float32, **kw,
            )
        return sum(e["band"] for e in ev if e["event"] == "recheck_narrow")

    band_0 = band(cell_margin_k=0.0)
    band_def = band()
    band_wide = band(cell_margin_k=4 * CELL_MARGIN_K)
    assert band_0 == negative
    assert band_0 <= band_def < band_wide


def test_pip_join_recheck_bng_no_alt_fallback():
    """BNG has margins but no alternate-rounding: the whole flagged band
    escalates to the host oracle — still exactly equal to f64."""
    from mosaic_tpu.core.tessellate import tessellate

    col = wkt.from_wkt([
        "POLYGON ((400000 200000, 440000 200000, 440000 240000, "
        "400000 240000, 400000 200000))",
        "POLYGON ((440000 200000, 480000 200000, 480000 240000, "
        "440000 240000, 440000 200000))",
    ])
    idx = build_chip_index(tessellate(col, BNG, 3, keep_core_geoms=False))
    rng = np.random.default_rng(4)
    pts = np.column_stack(
        [rng.uniform(395000, 485000, 20000), rng.uniform(195000, 245000, 20000)]
    )
    got = pip_join(
        pts, None, BNG, 3, chip_index=idx,
        recheck=True, cell_dtype=jnp.float32,
    )
    truth = host_join(pts, idx.host, BNG, 3)
    np.testing.assert_array_equal(got, truth)
