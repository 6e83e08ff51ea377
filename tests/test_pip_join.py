"""PIP join tests: chip-index join vs the dense host oracle.

Reference analog: `PointInPolygonJoinTest` — a point lands in polygon P iff
the managed join reports P (`sql/join/PointInPolygonJoin.scala:15-98`).
"""

import functools

import numpy as np
import pytest

from mosaic_tpu.core.geometry import oracle, wkt
from mosaic_tpu.core.index import CustomIndexSystem, GridConf, H3
from mosaic_tpu.sql.join import build_chip_index, pip_join
from mosaic_tpu.core.tessellate import tessellate

CUSTOM = CustomIndexSystem(GridConf(-180, 180, -90, 90, 2, 10.0, 10.0))

# disjoint "zones" with concave shapes and a hole
ZONES = [
    "POLYGON ((1 1, 13 2, 12 11, 6 14, 2 9, 1 1), (5 5, 5 8, 8 8, 8 5, 5 5))",
    "POLYGON ((20 0, 30 0, 30 10, 25 4, 20 10, 20 0))",
    "MULTIPOLYGON (((-20 -20, -12 -20, -12 -12, -20 -12, -20 -20)), ((-8 -8, -2 -8, -2 -2, -8 -2, -8 -8)))",
]


def oracle_match(col, pts):
    """Smallest polygon row containing each point, -1 if none."""
    out = np.full(pts.shape[0], -1, dtype=np.int32)
    for g in reversed(range(len(col))):
        inside = oracle.contains_points(col, g, pts)
        out[inside] = g
    return out


@pytest.mark.parametrize("res", [2, 3])
def test_join_matches_oracle(res):
    col = wkt.from_wkt(ZONES)
    rng = np.random.default_rng(7)
    pts = np.column_stack(
        [rng.uniform(-25, 35, 4000), rng.uniform(-25, 20, 4000)]
    )
    got = pip_join(pts, col, CUSTOM, res)
    want = oracle_match(col, pts)
    # f32 device coords: points within ~1e-5 of any edge may legitimately
    # classify either way — exclude the epsilon band from exact comparison
    diff = np.nonzero(got != want)[0]
    if diff.size:
        for i in diff:
            d = min(
                float(oracle.point_boundary_distance(col, g, pts[i]))
                for g in range(len(col))
            )
            assert d < 1e-4, f"point {i} misjoined at boundary distance {d}"


def test_join_batched_equals_single():
    col = wkt.from_wkt(ZONES)
    rng = np.random.default_rng(3)
    pts = np.column_stack([rng.uniform(-25, 35, 1000), rng.uniform(-25, 20, 1000)])
    a = pip_join(pts, col, CUSTOM, 3)
    b = pip_join(pts, col, CUSTOM, 3, batch_size=137)
    np.testing.assert_array_equal(a, b)


def test_prebuilt_chip_index_reused():
    col = wkt.from_wkt(ZONES)
    table = tessellate(col, CUSTOM, 3, keep_core_geoms=False)
    ci = build_chip_index(table)
    rng = np.random.default_rng(4)
    pts = np.column_stack([rng.uniform(0, 14, 500), rng.uniform(0, 14, 500)])
    got = pip_join(pts, col, CUSTOM, 3, chip_index=ci)
    want = oracle_match(col, pts)
    ok = got == want
    assert ok.mean() > 0.99


def test_join_h3_nyc_box():
    """H3 at res 8 over an NYC-scale box — core-vs-border paths both hit."""
    zones = [
        "POLYGON ((-74.02 40.70, -73.96 40.70, -73.96 40.76, -74.02 40.76, -74.02 40.70))",
        "POLYGON ((-73.96 40.70, -73.90 40.70, -73.90 40.76, -73.96 40.76, -73.96 40.70))",
    ]
    col = wkt.from_wkt(zones)
    rng = np.random.default_rng(5)
    pts = np.column_stack(
        [rng.uniform(-74.05, -73.87, 2000), rng.uniform(40.68, 40.78, 2000)]
    )
    got = pip_join(pts, col, H3, 8)
    want = oracle_match(col, pts)
    # away from shared boundary everything must agree
    off_boundary = np.abs(pts[:, 0] - -73.96) > 1e-3
    np.testing.assert_array_equal(got[off_boundary], want[off_boundary])


@functools.lru_cache(maxsize=None)
def _join_case(heavy: bool):
    """(shifted points, cells, index) of the writeback tests' fixtures:
    the NYC box pair (no heavy cell) or ZONES at edge_cap=8 (heavy)."""
    import jax.numpy as jnp

    from mosaic_tpu.sql import join as J

    rng = np.random.default_rng(2)
    if heavy:
        col, res, n = wkt.from_wkt(ZONES), 3, 10000
        pts = np.column_stack(
            [rng.uniform(-25, 35, n), rng.uniform(-25, 20, n)]
        )
        kw = {"edge_cap": 8}
    else:
        col, res, n = wkt.from_wkt([
            "POLYGON ((-74.02 40.70, -73.96 40.70, -73.96 40.76, "
            "-74.02 40.76, -74.02 40.70))",
            "POLYGON ((-73.96 40.70, -73.90 40.70, -73.90 40.76, "
            "-73.96 40.76, -73.96 40.70))",
        ]), 8, 5000
        pts = np.column_stack(
            [rng.uniform(-74.05, -73.87, n), rng.uniform(40.68, 40.78, n)]
        )
        kw = {}
    idx = J.build_chip_index(
        tessellate(col, H3, res, keep_core_geoms=False), **kw
    )
    assert bool(idx.num_heavy_cells) == heavy
    cells = H3.point_to_cell(jnp.asarray(pts), res)
    shifted = jnp.asarray(
        pts - np.asarray(idx.border.shift, np.float64),
        dtype=idx.border.verts.dtype,
    )
    return shifted, cells, idx


def test_writeback_variants_identical():
    """The gather writeback is an autotuning knob: results must be
    bitwise identical to the scatter path, bands included."""
    import jax.numpy as jnp

    from mosaic_tpu.sql.join import pip_join_points

    shifted, cells, idx = _join_case(heavy=False)
    eps2 = jnp.asarray(1e-10, idx.border.verts.dtype)
    a, na = pip_join_points(shifted, cells, idx, edge_eps2=eps2)
    for wb in ("gather", "direct"):
        g, ng = pip_join_points(
            shifted, cells, idx, edge_eps2=eps2, writeback=wb
        )
        np.testing.assert_array_equal(np.asarray(a), np.asarray(g), wb)
        np.testing.assert_array_equal(np.asarray(na), np.asarray(ng), wb)
    # capped case: overflow marks must agree too (direct has no tier-1
    # cap, so it is exact wherever the capped runs did not overflow)
    a2 = pip_join_points(shifted, cells, idx, found_cap=64)
    g2 = pip_join_points(shifted, cells, idx, found_cap=64, writeback="gather")
    np.testing.assert_array_equal(np.asarray(a2), np.asarray(g2))
    d2 = np.asarray(pip_join_points(shifted, cells, idx, writeback="direct"))
    a2 = np.asarray(a2)
    ok = a2 != -2
    np.testing.assert_array_equal(a2[ok], d2[ok])


@pytest.mark.parametrize("banded", [True, False])
@pytest.mark.parametrize("writeback", ["direct", "scatter", "gather"])
def test_tier1_chunked_path_identical(monkeypatch, writeback, banded):
    """Tier 1 runs its row work in `lax.map` chunks above _TIER1_CHUNK
    rows: direct mode always (XLA's 2 GB buffer limit at 4M on TPU), the
    compacting writebacks on the gather lane (its padded edge rows did not
    fit the chip in a 4M-row stream loop). Shrink the chunk so the chunked
    path runs on a small batch and assert bitwise equality with the
    unchunked scatter path, bands and heavy cells included."""
    import jax
    import jax.numpy as jnp

    from mosaic_tpu.sql import join as J

    # heavy cells: the chunks carry tier 2's row ids too
    shifted, cells, cidx = _join_case(heavy=True)
    eps2 = jnp.asarray(1e-10, cidx.border.verts.dtype) if banded else None
    # a cap one under the batch keeps tier 1 compacting (`tier1_compacts`)
    cap = shifted.shape[0] - 1
    want = J.pip_join_points(
        shifted, cells, cidx, edge_eps2=eps2, found_cap=cap
    )
    monkeypatch.setattr(J, "_TIER1_CHUNK", 1536)  # non-divisor: pads
    got = J.pip_join_points(
        shifted, cells, cidx, edge_eps2=eps2, writeback=writeback,
        found_cap=cap,
    )
    want, got = jax.tree.leaves(want), jax.tree.leaves(got)  # out[, near]
    assert len(want) == len(got) == 1 + banded
    for w, g in zip(want, got):
        np.testing.assert_array_equal(np.asarray(w), np.asarray(g))
    assert (np.asarray(want[0]) >= 0).any()


@pytest.mark.parametrize(
    "n, found_cap, probe, writeback, compacts",
    [
        (4096, None, "scatter", "scatter", False),   # uncapped: K1 = n
        (4096, None, "scatter", "gather", False),
        (4096, 4096, "scatter", "scatter", False),   # the whole batch
        (4096, 8192, "scatter", "scatter", False),   # clipped to n
        (4096, 4095, "scatter", "scatter", True),    # cuts one slot
        (4096, 2048, "scatter", "gather", True),
        (4096, 1, "scatter", "scatter", True),       # K1 = 8 < n
        (4, None, "scatter", "scatter", False),      # K1 = 8 >= n
        (4, 2, "scatter", "scatter", False),
        (4096, 2048, "scatter", "direct", False),    # direct never does
        (4096, None, "adaptive", "scatter", True),   # lanes split rows
        (4096, 4096, "adaptive-light", "scatter", True),
        (4096, None, "adaptive-convex", "gather", True),
    ],
)
def test_tier1_compacts_rule(n, found_cap, probe, writeback, compacts):
    """Tier 1 compacts only where ``K1 = max(8, min(found_cap or n, n))``
    is under the row count, or the probe is adaptive (with or without
    convex cells: the index is not asked)."""
    from mosaic_tpu.sql.join import tier1_compacts

    assert tier1_compacts(n, found_cap, probe, writeback) is compacts


@pytest.mark.parametrize("banded", [True, False])
@pytest.mark.parametrize("heavy", [False, True])
def test_uncompacted_default_equals_direct_and_capped(heavy, banded):
    """A call whose cap cuts no rows tests them in place: its answers,
    ``near`` and tier-2 OVERFLOW marks are those of ``writeback="direct"``
    and of the compacted program (a cap one under the batch), bit for
    bit."""
    import jax
    import jax.numpy as jnp

    from mosaic_tpu.sql.join import OVERFLOW, pip_join_points

    shifted, cells, idx = _join_case(heavy)
    n = shifted.shape[0]
    eps2 = jnp.asarray(1e-10, idx.border.verts.dtype) if banded else None
    hcaps = (None, 8) if heavy else (None,)
    for hcap in hcaps:
        kw = {"edge_eps2": eps2, "heavy_cap": hcap}
        want = jax.tree.leaves(pip_join_points(shifted, cells, idx, **kw))
        assert len(want) == 1 + banded
        out = np.asarray(want[0])
        assert (out >= 0).any() and (out == -1).any()
        assert (out == OVERFLOW).any() == (hcap is not None)
        for other in (
            {"writeback": "direct"},
            {"writeback": "gather"},
            {"found_cap": n},
            {"found_cap": n - 1},
            {"found_cap": n - 1, "writeback": "gather"},
        ):
            got = jax.tree.leaves(
                pip_join_points(shifted, cells, idx, **kw, **other)
            )
            for w, g in zip(want, got, strict=True):
                np.testing.assert_array_equal(
                    np.asarray(w), np.asarray(g), str((hcap, other))
                )


def _join_text(J, shifted, cells, idx, **kw):
    """StableHLO of one `pip_join_points` call (a new function object
    every time: no trace is shared between two texts)."""
    import jax

    return jax.jit(
        lambda p, c, i: J.pip_join_points(p, c, i, **kw)
    ).lower(shifted, cells, idx).as_text()


def test_program_scatters_only_where_a_cap_cuts_rows():
    """The lowered StableHLO of an uncapped default call holds no scatter
    (no compaction, no writeback scatter; the index has no heavy cell);
    a capped one holds them."""
    from mosaic_tpu.sql import join as J

    shifted, cells, idx = _join_case(heavy=False)
    n = shifted.shape[0]
    for kw in ({}, {"found_cap": n}, {"writeback": "gather"},
               {"writeback": "direct"}):
        assert "scatter" not in _join_text(J, shifted, cells, idx, **kw), kw
    for kw in ({"found_cap": n // 2}, {"found_cap": n - 1},
               {"found_cap": n // 2, "writeback": "gather"}):
        assert "stablehlo.scatter" in _join_text(
            J, shifted, cells, idx, **kw
        ), kw


@pytest.mark.parametrize("probe", ["adaptive", "adaptive-light"])
@pytest.mark.parametrize("found_cap", [None, 2048])
def test_adaptive_program_is_the_parents(monkeypatch, probe, found_cap):
    """Under the parent's rule (tier 1 compacts unless the writeback is
    direct) an adaptive program lowers to the same text byte for byte:
    the rule changes nothing for it, capped or not. The same swap does
    change the uncapped scatter-probe program, so the swap is seen."""
    from mosaic_tpu.sql import join as J

    shifted, cells, idx = _join_case(heavy=False)
    kw = {"probe": probe, "found_cap": found_cap}
    mine = _join_text(J, shifted, cells, idx, **kw)
    plain = _join_text(J, shifted, cells, idx)
    monkeypatch.setattr(
        J, "tier1_compacts", lambda n, cap, probe, wb="scatter": wb != "direct"
    )
    assert _join_text(J, shifted, cells, idx, **kw) == mine
    assert _join_text(J, shifted, cells, idx) != plain


@pytest.mark.parametrize("order", ["in-order", "random"])
@pytest.mark.parametrize("heavy", [False, True])
@pytest.mark.parametrize("edge_dtype", ["float32", "float64"])
def test_tier1_rows_gather_is_plain_indexing(edge_dtype, heavy, order):
    """The two-gather tier-1 fetch against numpy indexing of the five
    tables, bit for bit: the component-major edge row comes back as
    (K, E1, 4), ebits survive their int32 bit-cast (top bit included),
    bools travel as 0/1, for either edge dtype."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from mosaic_tpu.core.index import H3
    from mosaic_tpu.core.tessellate import tessellate
    from mosaic_tpu.sql import join as J

    table = tessellate(wkt.from_wkt(ZONES), H3, 3, keep_core_geoms=False)
    cidx = J.build_chip_index(table, **({"edge_cap": 8} if heavy else {}))
    assert bool(cidx.num_heavy_cells) == heavy
    rng = np.random.default_rng(17)
    U = cidx.cell_edges.shape[0]
    ebits = np.asarray(cidx.cell_ebits).copy()
    ebits[:: 3] |= np.uint32(1 << 31)  # a bit-cast must keep the sign bit
    cidx = dataclasses.replace(
        cidx,
        cell_edges=jnp.asarray(
            rng.standard_normal(cidx.cell_edges.shape), edge_dtype
        ),
        cell_ebits=jnp.asarray(ebits),
    )
    us = (
        np.arange(U, dtype=np.int32) if order == "in-order"
        else rng.integers(0, U, 3 * U).astype(np.int32)
    )
    got = jax.jit(J._tier1_rows_gather)(jnp.asarray(us), cidx)
    want = (
        np.asarray(cidx.cell_edges)[us], ebits[us],
        np.asarray(cidx.cell_slot_geom)[us],
        np.asarray(cidx.cell_slot_core)[us],
        np.asarray(cidx.cell_heavy)[us],
    )
    assert np.asarray(cidx.cell_slot_core).any()
    assert (np.asarray(cidx.cell_heavy) >= 0).any() == heavy
    for g, w, name in zip(
        got, want, ("edges", "ebits", "geoms", "cores", "heavy")
    ):
        g = np.asarray(g)
        assert g.dtype == w.dtype and g.shape == w.shape, name
        np.testing.assert_array_equal(g.view(np.uint8), w.view(np.uint8), name)


@pytest.mark.parametrize(
    "case, n, every, cap",
    [
        ("none-set", 1000, 0, 16),
        ("all-set", 1024, 1, 1024),
        ("cap-is-count", 5000, 3, 1667),
        ("cap-one-under", 5000, 3, 1666),
        ("not-a-multiple-of-128", 8191 + 4 * 2048, 3, 4096),
    ],
)
def test_compact_against_flatnonzero(case, n, every, cap):
    """`_compact` against `np.flatnonzero` (every ``every``-th row set):
    ``src`` lists the first ``cap`` flagged rows in order, ``valid``
    counts them, ``pos`` is the exclusive prefix and ``overflow`` names
    exactly the flagged rows past the cap. The last case is long enough
    for the matmul prefix scan."""
    import jax
    import jax.numpy as jnp

    from mosaic_tpu.sql.join import _compact

    flag = np.arange(n) % every == 0 if every else np.zeros(n, bool)
    rows = np.flatnonzero(flag)
    src, valid, over, pos = (
        np.asarray(x)
        for x in jax.jit(_compact, static_argnames="cap")(
            jnp.asarray(flag), cap=cap
        )
    )
    kept = min(rows.size, cap)
    assert src.shape == valid.shape == (cap,)
    np.testing.assert_array_equal(valid, np.arange(cap) < kept)
    np.testing.assert_array_equal(src[:kept], rows[:kept])
    assert not src[kept:].any()  # pad slots hold row 0, masked by valid
    np.testing.assert_array_equal(pos, np.cumsum(flag) - flag)
    np.testing.assert_array_equal(np.flatnonzero(over), rows[cap:])
