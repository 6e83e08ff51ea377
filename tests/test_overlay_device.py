"""Device overlay lane vs the pure-f64 host oracle.

The acceptance contract of the device overlay join: candidates generated
on device (sorted segment equi-join) and measures fused into one program
must be BIT-IDENTICAL to `expr.host_oracle.host_overlay_measures` — the
numpy twin that under x64 IS the pure-f64 oracle — on adversarial
fixtures: self-joins, shared-edge-only contact (touches, not overlaps),
all-border multi-cell spans, empty-intersection candidates, and the
OVERFLOW(-2) cap through the fused expr path.
"""

import numpy as np
import pytest

from mosaic_tpu import expr as E
from mosaic_tpu.core.geometry import wkt
from mosaic_tpu.core.index import CustomIndexSystem, GridConf
from mosaic_tpu.core.tessellate import tessellate
from mosaic_tpu.dispatch import core as dispatch
from mosaic_tpu.sql.join import OVERFLOW
from mosaic_tpu.sql.overlay import (
    overlay_measures,
    prepare_overlay,
    warmup_overlay,
)


def _grid():
    # 1.25-degree cells at res 3: hermetic, fast, no external index dep
    return CustomIndexSystem(GridConf(-180, 180, -90, 90, 2, 10.0, 10.0))


RES = 3


def _squares(specs):
    out = []
    for x0, y0, w, h in specs:
        out.append(
            f"POLYGON (({x0} {y0}, {x0 + w} {y0}, {x0 + w} {y0 + h},"
            f" {x0} {y0 + h}, {x0} {y0}))"
        )
    return wkt.from_wkt(out)


def _assert_bitwise(got, want):
    for field in ("pairs", "value", "valid", "area", "sure"):
        a = np.asarray(getattr(got, field))
        b = np.asarray(getattr(want, field))
        assert a.shape == b.shape and a.dtype == b.dtype, field
        assert a.tobytes() == b.tobytes(), (
            f"{field} diverged from the f64 host oracle"
        )


def _both_lanes(left, right, value=None, **kw):
    grid = _grid()
    dev = overlay_measures(left, right, grid, RES, value, **kw)
    host = overlay_measures(left, right, grid, RES, value,
                            lane="host", **kw)
    return dev, host


def test_device_matches_host_oracle_bitwise():
    left = _squares([(i * 2.9, j * 2.9, 2.7, 2.7)
                     for i in range(4) for j in range(4)])
    right = _squares([(i * 2.9 + 0.9, j * 2.9 + 0.6, 2.4, 2.4)
                      for i in range(4) for j in range(4)])
    dev, host = _both_lanes(left, right, E.overlap_fraction())
    assert dev.lane == "device" and not dev.degraded
    assert host.lane == "host"
    _assert_bitwise(dev, host)
    assert dev.pairs.shape[0] > 0
    assert np.nanmax(dev.value) > 0.0


def test_self_join_symmetry():
    """Identical tables: the pair set is symmetric, the diagonal's
    overlap fraction is ~1.0, and both lanes agree bit for bit."""
    geoms = _squares([(0.2, 0.3, 2.6, 2.6), (2.0, 2.1, 3.1, 2.2),
                      (5.4, 0.7, 1.9, 3.3)])
    dev, host = _both_lanes(geoms, geoms, E.overlap_fraction())
    _assert_bitwise(dev, host)
    pairs = {(int(a), int(b)) for a, b in dev.pairs}
    assert pairs == {(b, a) for a, b in pairs}
    diag = dev.pairs[:, 0] == dev.pairs[:, 1]
    assert set(dev.pairs[diag, 0].tolist()) == {0, 1, 2}
    # the folded per-cell decomposition and the whole-geometry shoelace
    # agree to rounding, not bitwise — allclose is the right contract
    np.testing.assert_allclose(dev.value[diag], 1.0, rtol=1e-12)


def test_shared_edge_only_touches_not_overlaps():
    """Two squares sharing exactly one edge: the shared cell makes them
    candidates, but the overlap measure must be exactly zero."""
    left = _squares([(0.0, 0.0, 1.0, 1.0)])
    right = _squares([(1.0, 0.0, 1.0, 1.0)])
    dev, host = _both_lanes(left, right)
    _assert_bitwise(dev, host)
    assert dev.pairs.shape[0] == 1
    assert float(dev.area[0]) == 0.0
    assert float(dev.value[0]) == 0.0


def test_all_border_multicell_span():
    """A thin rectangle spanning many cells — every chip a border chip,
    no core shortcut anywhere — still folds to the exact area."""
    left = _squares([(0.1, 0.2, 5.9, 0.6)])    # 5 cells, all border
    right = _squares([(0.3, 0.4, 5.2, 0.6)])
    dev, host = _both_lanes(left, right)
    _assert_bitwise(dev, host)
    assert not bool(dev.sure.any())
    assert dev.pairs.shape[0] == 1
    np.testing.assert_allclose(float(dev.area[0]), 5.2 * 0.4,
                               rtol=1e-12)


def test_empty_intersection_candidate_reports_zero():
    """Disjoint polygons sharing a cell are candidates; the fused
    measure must answer 0.0, not drop the pair."""
    left = _squares([(0.0, 0.0, 0.5, 0.5)])
    right = _squares([(0.7, 0.0, 0.5, 0.5)])
    dev, host = _both_lanes(left, right, E.overlap_fraction())
    _assert_bitwise(dev, host)
    assert dev.pairs.shape[0] == 1
    assert float(dev.area[0]) == 0.0
    assert float(dev.value[0]) == 0.0


def test_overflow_cap_through_fused_path():
    """A candidate cap below the stream size must surface as a trailing
    OVERFLOW(-2) row with NaN measures — in BOTH lanes, identically."""
    left = _squares([(i * 2.9, 0.0, 2.7, 2.7) for i in range(4)])
    right = _squares([(i * 2.9 + 0.8, 0.5, 2.4, 2.4) for i in range(4)])
    dev, host = _both_lanes(left, right, E.overlap_fraction(),
                            pair_cap=2)
    assert dev.overflow > 0 and host.overflow == dev.overflow
    assert tuple(dev.pairs[-1]) == (OVERFLOW, OVERFLOW)
    assert np.isnan(dev.value[-1]) and np.isnan(dev.area[-1])
    assert not dev.valid[-1]
    # NaN payloads compare equal at the byte level
    _assert_bitwise(dev, host)


def test_zero_cold_compiles_after_warmup():
    left = _squares([(0.3, 0.1, 2.6, 2.6), (3.3, 0.1, 2.6, 2.6)])
    right = _squares([(1.0, 0.8, 2.6, 2.6), (4.0, 0.8, 2.6, 2.6)])
    grid = _grid()
    value = E.overlap_fraction()
    prep = warmup_overlay(left, right, grid, RES, value)
    c0 = dispatch.backend_compiles()
    out = overlay_measures(left, right, grid, RES, value, prep=prep)
    assert out.lane == "device"
    assert (dispatch.backend_compiles() - c0) == 0


def test_device_failure_degrades_to_host_oracle(monkeypatch):
    """A device fault past the retry budget must degrade the WHOLE lane
    to the host oracle with the result flagged — same numbers, lane and
    flag tell the truth."""
    left = _squares([(0.3, 0.1, 2.6, 2.6)])
    right = _squares([(1.0, 0.8, 2.6, 2.6)])
    grid = _grid()
    want = overlay_measures(left, right, grid, RES, lane="host")

    import mosaic_tpu.expr.compile as _compile

    def boom(*a, **k):
        raise RuntimeError("injected device fault")

    monkeypatch.setattr(_compile, "run_tracked", boom)
    got = overlay_measures(left, right, grid, RES)
    assert got.lane == "host" and got.degraded
    assert "injected device fault" in got.reason
    _assert_bitwise(got, want)


def test_mesh_sharded_bit_identity():
    import jax

    if len(jax.devices()) < 2:
        pytest.skip("single-device runtime (no host platform mesh)")
    left = _squares([(i * 2.9, j * 2.9, 2.7, 2.7)
                     for i in range(3) for j in range(3)])
    right = _squares([(i * 2.9 + 0.9, j * 2.9 + 0.6, 2.4, 2.4)
                      for i in range(3) for j in range(3)])
    grid = _grid()
    value = E.overlap_fraction()
    single = overlay_measures(left, right, grid, RES, value)
    meshed = overlay_measures(left, right, grid, RES, value,
                              mesh=len(jax.devices()))
    assert meshed.lane == "device" and not meshed.degraded
    _assert_bitwise(meshed, single)


def test_the_meshed_program_is_handed_the_resident_columns():
    """`_host_tables` gives the meshed lane, as host arrays, exactly what
    the single-device lane keeps resident: the left side's dense ranks,
    the right side's run offsets, the fused program's tables — and no
    int64 cell column on either."""
    from mosaic_tpu.sql import overlay as ov

    left = _squares([(i * 2.9, 0.2, 2.7, 2.7) for i in range(3)])
    right = _squares([(i * 2.9 + 0.9, 0.6, 2.4, 2.4) for i in range(3)])
    grid = _grid()
    prep = prepare_overlay(tessellate(left, grid, RES),
                           tessellate(right, grid, RES), left, right, grid, RES)
    acc = np.dtype(prep.acc_name)
    for side, column in ((prep.left, "rank"), (prep.right, "roff")):
        host = ov._host_tables(side, acc)
        assert list(host) == list(side.dev) and column in host
        assert "cells" not in host
        for name, table in host.items():
            resident = np.asarray(side.dev[name])
            assert table.dtype == resident.dtype, name
            assert np.array_equal(table, resident), name


def test_function_frontends():
    from mosaic_tpu.functions.geometry import (
        st_intersection_area,
        st_overlap_fraction,
    )

    left = _squares([(0.3, 0.1, 2.6, 2.6)])
    right = _squares([(1.0, 0.8, 2.6, 2.6)])
    grid = _grid()
    area = st_intersection_area(left, right, grid, RES)
    frac = st_overlap_fraction(left, right, grid, RES)
    np.testing.assert_allclose(float(area.area[0]), 1.9 * 1.9,
                               rtol=1e-12)
    np.testing.assert_allclose(
        float(frac.value[0]), (1.9 * 1.9) / (2.6 * 2.6), rtol=1e-12
    )


def test_prepared_overlay_reuse_is_identical():
    """The amortized prep must answer exactly like the from-scratch
    path (same shift frame, same buckets, same programs)."""
    left = _squares([(0.3, 0.1, 2.6, 2.6), (3.3, 0.1, 2.6, 2.6)])
    right = _squares([(1.0, 0.8, 2.6, 2.6)])
    grid = _grid()
    lt = tessellate(left, grid, RES)
    rt = tessellate(right, grid, RES)
    prep = prepare_overlay(lt, rt, left, right, grid, RES)
    a = overlay_measures(left, right, grid, RES, prep=prep)
    b = overlay_measures(left, right, grid, RES)
    _assert_bitwise(a, b)
