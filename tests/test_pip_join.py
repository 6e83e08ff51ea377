"""PIP join tests: chip-index join vs the dense host oracle.

Reference analog: `PointInPolygonJoinTest` — a point lands in polygon P iff
the managed join reports P (`sql/join/PointInPolygonJoin.scala:15-98`).
"""

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


def test_writeback_variants_identical():
    """The gather writeback is an autotuning knob: results must be
    bitwise identical to the scatter path, bands included."""
    import jax.numpy as jnp

    from mosaic_tpu.core.index import H3
    from mosaic_tpu.core.tessellate import tessellate
    from mosaic_tpu.sql.join import build_chip_index, pip_join_points

    col = wkt.from_wkt([
        "POLYGON ((-74.02 40.70, -73.96 40.70, -73.96 40.76, "
        "-74.02 40.76, -74.02 40.70))",
        "POLYGON ((-73.96 40.70, -73.90 40.70, -73.90 40.76, "
        "-73.96 40.76, -73.96 40.70))",
    ])
    idx = build_chip_index(tessellate(col, H3, 8, keep_core_geoms=False))
    rng = np.random.default_rng(2)
    pts = np.column_stack(
        [rng.uniform(-74.05, -73.87, 5000), rng.uniform(40.68, 40.78, 5000)]
    )
    cells = H3.point_to_cell(jnp.asarray(pts), 8)
    shifted = jnp.asarray(
        pts - np.asarray(idx.border.shift, np.float64),
        dtype=idx.border.verts.dtype,
    )
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


def test_mxu_lookup_bit_exact():
    """The one-hot MXU row lookup is an autotuning knob: `_mm_rows` must
    be a bit-exact f32 gather (3-term bf16 split, single one-hot hit per
    row), and the full join must be bitwise identical to the gather
    lookup, bands included."""
    import jax
    import jax.numpy as jnp

    from mosaic_tpu.core.index import H3
    from mosaic_tpu.core.tessellate import tessellate
    from mosaic_tpu.sql.join import _mm_rows, build_chip_index, pip_join_points

    rng = np.random.default_rng(5)
    # exponents spanning the f32 range stress the bf16 split exactness
    tab = jnp.asarray(
        (rng.standard_normal((90, 50))
         * (10.0 ** rng.integers(-20, 20, (90, 50)))).astype(np.float32)
    )
    idx = jnp.asarray(rng.integers(0, 90, 2048).astype(np.int32))
    got = np.asarray(jax.jit(_mm_rows)(idx, tab))
    np.testing.assert_array_equal(got, np.asarray(tab)[np.asarray(idx)])

    col = wkt.from_wkt(ZONES)
    cidx = build_chip_index(tessellate(col, H3, 3, keep_core_geoms=False))
    pts = np.column_stack(
        [rng.uniform(-25, 35, 20000), rng.uniform(-25, 20, 20000)]
    )
    cells = H3.point_to_cell(jnp.asarray(pts, jnp.float32), 3)
    shifted = jnp.asarray(
        pts - np.asarray(cidx.border.shift, np.float64),
        dtype=cidx.border.verts.dtype,
    )
    eps2 = jnp.asarray(1e-10, cidx.border.verts.dtype)
    for wb in ("scatter", "gather"):
        a, na = pip_join_points(
            shifted, cells, cidx, edge_eps2=eps2, writeback=wb
        )
        for lk in ("mxu", "mxu2"):
            m, nm = pip_join_points(
                shifted, cells, cidx, edge_eps2=eps2, writeback=wb, lookup=lk
            )
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(m), f"{wb}/{lk}"
            )
            np.testing.assert_array_equal(
                np.asarray(na), np.asarray(nm), f"{wb}/{lk}"
            )
    assert (np.asarray(a) >= 0).any()


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

    from mosaic_tpu.core.index import H3
    from mosaic_tpu.core.tessellate import tessellate
    from mosaic_tpu.sql import join as J

    col = wkt.from_wkt(ZONES)
    cidx = J.build_chip_index(
        tessellate(col, H3, 3, keep_core_geoms=False), edge_cap=8
    )
    assert cidx.num_heavy_cells  # the chunks carry tier 2's row ids too
    rng = np.random.default_rng(11)
    pts = np.column_stack(
        [rng.uniform(-25, 35, 10000), rng.uniform(-25, 20, 10000)]
    )
    cells = H3.point_to_cell(jnp.asarray(pts, jnp.float32), 3)
    shifted = jnp.asarray(
        pts - np.asarray(cidx.border.shift, np.float64),
        dtype=cidx.border.verts.dtype,
    )
    eps2 = jnp.asarray(1e-10, cidx.border.verts.dtype) if banded else None
    want = J.pip_join_points(shifted, cells, cidx, edge_eps2=eps2)
    monkeypatch.setattr(J, "_TIER1_CHUNK", 1536)  # non-divisor: pads
    got = J.pip_join_points(
        shifted, cells, cidx, edge_eps2=eps2, writeback=writeback
    )
    want, got = jax.tree.leaves(want), jax.tree.leaves(got)  # out[, near]
    assert len(want) == len(got) == 1 + banded
    for w, g in zip(want, got):
        np.testing.assert_array_equal(np.asarray(w), np.asarray(g))
    assert (np.asarray(want[0]) >= 0).any()


def test_mxu_compaction_identical():
    """_compact_mxu (block one-hot int8 matmuls + one small unique
    scatter) must match _compact exactly, including pos, validity and
    both overflow kinds (global cap + block-local s_cap)."""
    import jax
    import jax.numpy as jnp

    from mosaic_tpu.sql.join import _compact, _compact_mxu

    rng = np.random.default_rng(0)
    for n, p, cap, s_cap in [
        (100000, 0.09, 16384, 256),
        (70000, 0.5, 65536, 1280),
        (2048, 1.0, 4096, 2048),
    ]:
        flag = jnp.asarray(rng.random(n) < p)
        a = jax.jit(lambda f, cap=cap: _compact(f, cap))(flag)
        m = jax.jit(
            lambda f, cap=cap, s=s_cap: _compact_mxu(f, cap, s)
        )(flag)
        for x, y, name in zip(a, m, ("src", "valid", "over", "pos")):
            np.testing.assert_array_equal(
                np.asarray(x), np.asarray(y), f"{n}/{p}/{name}"
            )
        # the vals channel (4x6-bit int8 dots) must equal vals[src]
        vals = jnp.asarray(
            rng.integers(0, 1 << 24, n).astype(np.int32)
        )
        mv = jax.jit(
            lambda f, v, cap=cap, s=s_cap: _compact_mxu(f, cap, s, vals=v)
        )(flag, vals)
        got_v = np.asarray(mv[4])
        want_v = np.asarray(vals)[np.asarray(a[0])]
        valid_np = np.asarray(a[1])
        np.testing.assert_array_equal(
            got_v[valid_np], want_v[valid_np], f"{n}/{p}/vals"
        )
    # clustered flags exceeding s_cap in one block: dropped rows must be
    # flagged overflow (never a silently wrong/missing result)
    flag = np.zeros(100000, bool)
    flag[1000:1900] = True
    fm = jnp.asarray(flag)
    a = [np.asarray(x) for x in _compact(fm, 4096)]
    m = [np.asarray(x) for x in _compact_mxu(fm, 4096, 256)]
    np.testing.assert_array_equal(a[3], m[3])
    np.testing.assert_array_equal(m[0][:256], a[0][:256])
    assert m[2][1256:1900].all() and not m[2][:1256].any()
    assert not m[1][256:900].any()


def test_compaction_knob_end_to_end():
    import jax.numpy as jnp

    from mosaic_tpu.core.index import H3
    from mosaic_tpu.core.tessellate import tessellate
    from mosaic_tpu.sql import join as J

    col = wkt.from_wkt(ZONES)
    cidx = J.build_chip_index(tessellate(col, H3, 3, keep_core_geoms=False))
    rng = np.random.default_rng(13)
    n = 1 << 17  # above the mxu-compaction threshold
    pts = np.column_stack(
        [rng.uniform(-25, 35, n), rng.uniform(-25, 20, n)]
    )
    cells = H3.point_to_cell(jnp.asarray(pts, jnp.float32), 3)
    shifted = jnp.asarray(
        pts - np.asarray(cidx.border.shift, np.float64),
        dtype=cidx.border.verts.dtype,
    )
    eps2 = jnp.asarray(1e-10, cidx.border.verts.dtype)
    a, na = J.pip_join_points(shifted, cells, cidx, edge_eps2=eps2)
    m, nm = J.pip_join_points(
        shifted, cells, cidx, edge_eps2=eps2, lookup="mxu",
        compaction="mxu", compact_block=1024,
    )
    np.testing.assert_array_equal(np.asarray(a), np.asarray(m))
    np.testing.assert_array_equal(np.asarray(na), np.asarray(nm))
