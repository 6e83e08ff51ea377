"""BNG + Custom grid index systems: encode/decode/neighbors/polyfill."""

import jax.numpy as jnp
import numpy as np
import pytest

from mosaic_tpu.core.index import (
    BNG,
    CustomIndexSystem,
    GridConf,
    custom_from_name,
)


def _one_box_walk(bounds, resolution):
    """One bbox's covering cells, x-major: the grid's corners clamped to
    its extent, `arange` over the cells' origins, `meshgrid` of their
    centres. Kept here as the independent walk the batch is held to."""
    edge = BNG.edge_size(resolution)
    x0 = max(0, int(np.floor(bounds[0] / edge)) * edge)
    y0 = max(0, int(np.floor(bounds[1] / edge)) * edge)
    x1 = min(700_000, int(np.ceil(bounds[2] / edge)) * edge)
    y1 = min(1_300_000, int(np.ceil(bounds[3] / edge)) * edge)
    xs = np.arange(x0, x1, edge, dtype=np.float64) + edge / 2
    ys = np.arange(y0, y1, edge, dtype=np.float64) + edge / 2
    if not len(xs) or not len(ys):
        return np.zeros(0, dtype=np.int64)
    gx, gy = np.meshgrid(xs, ys, indexing="ij")
    centers = np.stack([gx.ravel(), gy.ravel()], axis=-1)
    return np.asarray(BNG.point_to_cell(jnp.asarray(centers), resolution))


class TestBNG:
    def test_point_roundtrip_all_res(self):
        rng = np.random.default_rng(0)
        pts = np.column_stack(
            [rng.uniform(0, 700_000, 200), rng.uniform(0, 1_300_000, 200)]
        )
        for res in BNG.resolutions():
            cells = np.asarray(BNG.point_to_cell(jnp.asarray(pts), res))
            assert np.asarray(BNG.resolution_of(cells)).tolist() == [res] * 200
            centers = np.asarray(BNG.cell_center(cells))
            # center of the cell must map back to the same cell
            cells2 = np.asarray(BNG.point_to_cell(jnp.asarray(centers), res))
            np.testing.assert_array_equal(cells, cells2)
            # original point within cell bounds
            edge = BNG.edge_size(res)
            assert np.all(np.abs(centers - pts) <= edge)

    def test_known_strings(self):
        # Ben Nevis-ish: eastings 216650 northings 771250 -> NN 16 71 (10km "NN17"?)
        pts = jnp.asarray([[216650.0, 771250.0]])
        c1 = np.asarray(BNG.point_to_cell(pts, 1))[0]
        assert BNG.format([c1]) == ["NN"]
        c2 = np.asarray(BNG.point_to_cell(pts, 2))[0]
        assert BNG.format([c2]) == ["NN17"]
        c4 = np.asarray(BNG.point_to_cell(pts, 4))[0]
        assert BNG.format([c4]) == ["NN166712"]

    def test_quadrant_res(self):
        # 50km quadrants of square TQ (e 5xx, n 1xx): TQ SW corner 500000,100000
        pts = jnp.asarray(
            [
                [510_000.0, 110_000.0],  # SW
                [510_000.0, 160_000.0],  # NW
                [560_000.0, 160_000.0],  # NE
                [560_000.0, 110_000.0],  # SE
            ]
        )
        cells = np.asarray(BNG.point_to_cell(pts, -2))
        assert BNG.format(cells) == ["TQSW", "TQNW", "TQNE", "TQSE"]
        # parse inverse
        np.testing.assert_array_equal(BNG.parse(BNG.format(cells)), cells)

    def test_format_parse_roundtrip(self):
        rng = np.random.default_rng(1)
        pts = np.column_stack(
            [rng.uniform(0, 700_000, 50), rng.uniform(0, 1_300_000, 50)]
        )
        for res in [1, 2, 3, -2, -3, 4, -4, 5, 6]:
            cells = np.asarray(BNG.point_to_cell(jnp.asarray(pts), res))
            strs = BNG.format(cells)
            np.testing.assert_array_equal(BNG.parse(strs), cells)

    def test_k_ring_loop(self):
        pts = jnp.asarray([[400_000.0, 400_000.0]])
        c = BNG.point_to_cell(pts, 3)
        ring = np.asarray(BNG.k_ring(c, 1))[0]
        assert (ring >= 0).sum() == 9
        loop = np.asarray(BNG.k_loop(c, 1))[0]
        assert (loop >= 0).sum() == 8
        assert int(np.asarray(c)[0]) not in loop.tolist()
        # edge of grid: fewer valid neighbors
        edge_c = BNG.point_to_cell(jnp.asarray([[500.0, 500.0]]), 3)
        ring_e = np.asarray(BNG.k_ring(edge_c, 1))[0]
        assert (ring_e >= 0).sum() == 4

    def test_grid_distance(self):
        a = BNG.point_to_cell(jnp.asarray([[100_500.0, 100_500.0]]), 3)
        b = BNG.point_to_cell(jnp.asarray([[103_500.0, 104_500.0]]), 3)
        # Chebyshev: consistent with the square k_loop rings
        assert int(np.asarray(BNG.grid_distance(a, b))[0]) == 4

    def test_distance_consistent_with_kloop(self):
        c = BNG.point_to_cell(jnp.asarray([[400_000.0, 400_000.0]]), 3)
        for k in [1, 2, 3]:
            loop = np.asarray(BNG.k_loop(c, k))[0]
            loop = loop[loop >= 0]
            cc = jnp.broadcast_to(c, (len(loop),))
            d = np.asarray(BNG.grid_distance(cc, jnp.asarray(loop)))
            assert (d == k).all()

    def test_boundary(self):
        c = BNG.point_to_cell(jnp.asarray([[216_650.0, 771_250.0]]), 2)
        b = np.asarray(BNG.cell_boundary(c))[0]
        np.testing.assert_allclose(b[0], [210_000, 770_000])
        np.testing.assert_allclose(b[2], [220_000, 780_000])
        np.testing.assert_allclose(b[0], b[4])

    def test_polyfill_candidates(self):
        cand = BNG.polyfill_candidates(
            np.array([100_000, 100_000, 130_000, 120_000]), 2
        )
        assert len(cand) == 3 * 2
        assert len(set(cand.tolist())) == 6

    def test_host_arrays_are_answered_on_the_host_like_the_device(self):
        """A numpy input computes in numpy (tessellation asks for the
        cells of host arrays of every size, and each new size of an eager
        device op is a compile of its own); the answer is the device's."""
        import jax

        rng = np.random.default_rng(3)
        pts = np.column_stack(
            [rng.uniform(0, 700_000, 300), rng.uniform(0, 1_300_000, 300)]
        )
        for res in BNG.resolutions():
            host = BNG.point_to_cell(pts, res)
            dev = BNG.point_to_cell(jnp.asarray(pts), res)
            assert isinstance(host, np.ndarray) and isinstance(dev, jax.Array)
            np.testing.assert_array_equal(host, np.asarray(dev))
            for fn in (BNG.cell_boundary, BNG.cell_center):
                a, b = fn(host), fn(jnp.asarray(host))
                assert isinstance(a, np.ndarray) and isinstance(b, jax.Array)
                np.testing.assert_array_equal(a, np.asarray(b))
        # inside jit the tracer takes the device path
        jitted = jax.jit(lambda p: BNG.point_to_cell(p, 4))(pts)
        np.testing.assert_array_equal(
            np.asarray(jitted), BNG.point_to_cell(pts, 4))

    @pytest.mark.parametrize("res", BNG.resolutions())
    def test_polyfill_candidates_batch_is_the_one_box_walk(self, res):
        """The batch against an independent walk of one box (`_one_box_walk`,
        the arange/meshgrid code the batch replaced), at every resolution:
        boxes the size of a few cells everywhere on the grid, over its
        corners and edges, and in the partial 500 km blocks T, O, H and J
        at its east and north edge."""
        edge = BNG.edge_size(res)
        rng = np.random.default_rng(4)
        lo = np.column_stack(
            [rng.uniform(0, 690_000, 50), rng.uniform(0, 1_290_000, 50)]
        )
        boxes = np.column_stack([lo, lo + rng.uniform(0.01, 3.0, (50, 2)) * edge])
        # (anchor, extent in cells): the named boxes keep a few cells at
        # every resolution
        for i, (x, y, w, h) in enumerate([
            (-0.5 * edge, -0.2 * edge, 2.5, 1.3),   # over the south-west corner
            (700_000 - 0.9 * edge, 5.0, 1.4, 0.01),  # over the east edge
            (1_010_000.0, 5.0, 1.0, 0.01),          # outside: nothing
            (530_000.0, 180_000.0, 1.2, 1.0),       # T (London)
            (600_000.0, 600_000.0, 1.0, 1.5),       # O
            (100_000.0, 1_100_000.0, 0.8, 0.3),     # H
            (650_000.0, 1_250_000.0, 0.7, 0.1),     # J
            (700_000 - 0.5 * edge, 1_300_000 - 0.5 * edge, 1.0, 1.0),  # far corner
        ]):
            boxes[i] = [x, y, x + w * edge, y + h * edge]
        got = BNG.polyfill_candidates_batch(boxes, res)
        assert len(got) == 50 and got[2].shape == (0,)
        for box, cells in zip(boxes, got):
            np.testing.assert_array_equal(cells, _one_box_walk(box, res))
            np.testing.assert_array_equal(
                cells, BNG.polyfill_candidates(box, res))
            if box[0] < 700_000:
                assert cells.size
            if not cells.size:
                continue
            assert len(set(cells.tolist())) == cells.size
            b = BNG.cell_boundary(cells)
            # every cell meets the box, and the box's corners are covered
            assert (b[..., 0].max(1) > box[0]).all() and (b[..., 0].min(1) < box[2] + edge).all()
            assert (b[..., 1].max(1) > box[1]).all() and (b[..., 1].min(1) < box[3] + edge).all()
            for corner in (box[[0, 1]], box[[2, 3]] - 1e-6):
                inside = np.clip(corner, 0, [699_999.0, 1_299_999.0])
                assert BNG.point_to_cell(inside[None], res)[0] in cells

    def test_polyfill_reaches_the_partial_500km_blocks(self):
        """London at '500km': 500 km blocks do not divide the grid's 700 x
        1,300 km, and the blocks at its east and north edge are cells."""
        london = np.array([530_000.0, 180_000.0, 542_000.0, 190_000.0])
        assert BNG.format(BNG.polyfill_candidates(london, -1)) == ["T"]
        north = np.array([100_000.0, 1_100_000.0, 650_000.0, 1_250_000.0])
        assert BNG.format(BNG.polyfill_candidates(north, -1)) == ["H", "J"]

    def test_500km_blocks(self):
        pts = jnp.asarray([[100.0, 100.0], [600_000.0, 100.0], [100.0, 1_200_000.0]])
        cells = np.asarray(BNG.point_to_cell(pts, -1))
        assert BNG.format(cells) == ["S", "T", "H"]
        np.testing.assert_array_equal(BNG.parse(["S", "T", "H"]), cells)


class TestCustom:
    conf = GridConf(-180, 180, -90, 90, 2, 360, 180)

    def test_factory_name_roundtrip(self):
        ix = CustomIndexSystem(self.conf)
        ix2 = custom_from_name(ix.name)
        assert ix2.conf == ix.conf

    def test_roundtrip(self):
        ix = CustomIndexSystem(self.conf)
        rng = np.random.default_rng(2)
        pts = np.column_stack([rng.uniform(-180, 180, 100), rng.uniform(-90, 90, 100)])
        for res in [0, 1, 2, 5, 8]:
            cells = np.asarray(ix.point_to_cell(jnp.asarray(pts), res))
            assert np.all(np.asarray(ix.resolution_of(cells)) == res)
            centers = np.asarray(ix.cell_center(cells))
            cells2 = np.asarray(ix.point_to_cell(jnp.asarray(centers), res))
            np.testing.assert_array_equal(cells, cells2)
            assert np.asarray(ix.is_valid(cells)).all()

    def test_cell_counts(self):
        ix = CustomIndexSystem(self.conf)
        assert ix.cells_x(0) == 1 and ix.cells_y(0) == 1
        assert ix.cells_x(3) == 8 and ix.cells_y(3) == 8

    def test_neighbors(self):
        ix = CustomIndexSystem(self.conf)
        c = ix.point_to_cell(jnp.asarray([[0.1, 0.1]]), 4)
        ring = np.asarray(ix.k_ring(c, 1))[0]
        assert (ring >= 0).sum() == 9
        loop = np.asarray(ix.k_loop(c, 2))[0]
        assert (loop >= 0).sum() == 16

    def test_polyfill(self):
        ix = CustomIndexSystem(self.conf)
        cand = ix.polyfill_candidates(np.array([-10.0, -10.0, 10.0, 10.0]), 5)
        centers = np.asarray(ix.cell_center(jnp.asarray(cand)))
        assert np.all(centers[:, 0] > -12) and np.all(centers[:, 0] < 12)
        w, h = ix.cell_size(5)
        assert len(cand) >= (20 / w - 1) * (20 / h - 1)
