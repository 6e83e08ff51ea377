"""The benchmark's own arithmetic: percentiles, spreads, bytes per row,
the peaks table, the zone generator's copy and the plain reference."""

import json
import os

import numpy as np
import pytest

from bh_fixtures import REPO

from benchmark.generators import zones
from benchmark.harness import cost, peaks, stats
from benchmark.references import pip_bruteforce


@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4], 0.50, 2),          # nearest rank, not banker's rounding
    ([1, 2, 3, 4], 0.95, 4),
    ([5], 0.95, 5),
    (list(range(1, 101)), 0.95, 95),
    (list(range(1, 101)), 0.50, 50),
    (list(range(1, 101)), 0.99, 99),
    ([3, 1, 2], 0.34, 2),
    (list(range(1, 21)), 0.95, 19),
])
def test_percentile_is_nearest_rank(values, q, want):
    assert stats.percentile(values, q) == want


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        stats.percentile([], 0.5)


def test_iqr_share_is_statistics_quantiles():
    vals = [10.0, 11.0, 12.0, 13.0, 14.0, 15.0]
    # statistics.quantiles(n=4) (exclusive): q1 = 10.75, q3 = 14.25
    assert stats.iqr_share(vals) == pytest.approx(3.5 / 12.5)


def test_bytes_per_row_from_shapes():
    shapes = {"hash_bucket": 2, "tier1_edges": 20, "tier1_slots": 3,
              "edge_itemsize": 4}
    # point 16 + cell 16 + bucket 2*12 + answer 8 = 64; tier-1 row
    # 20*(16+4) + 3*5 = 415 for a found row
    assert cost.bytes_per_row(shapes, 0.0) == 64
    assert cost.bytes_per_row(shapes, 1.0) == 64 + 415
    assert cost.bytes_per_row(shapes, 0.5) == 64 + 207.5
    # three u32 words an entry on every index: whether the ids once packed
    # into int64 entries changes nothing (PR 34 stores them one way)
    for packed in (True, False, None):
        assert cost.bytes_per_row(dict(shapes, hash_packed=packed), 0.0) == 64
    # the taxi index's row (B 2, E1 24 in f32, M1 4) at match share 0.811
    taxi = {"hash_bucket": 2, "tier1_edges": 24, "tier1_slots": 4,
            "edge_itemsize": 4}
    assert cost.bytes_per_row(taxi, 0.811) == pytest.approx(64 + 0.811 * 500)
    with pytest.raises(ValueError):
        cost.bytes_per_row(shapes, 1.5)


@pytest.mark.parametrize("bucket", [1, 2, 3])
def test_index_shapes_read_the_table_the_probe_reads(bucket):
    """`hash_bucket` is `table_rows.shape[1] // 3`, and an index that has
    dropped `table_cell` and `table_pack` (read by no device program since
    PR 34) still gives every shape the byte count needs."""
    from types import SimpleNamespace

    import numpy as np

    index = SimpleNamespace(
        table_rows=np.zeros((16, 3 * bucket), np.uint32),
        cell_edges=np.zeros((5, 24, 4), np.float32),
        cell_slot_geom=np.zeros((5, 4), np.int32),
    )
    shapes = cost.index_shapes(index)
    assert shapes["hash_bucket"] == bucket and shapes["hash_packed"] is None
    assert (shapes["tier1_edges"], shapes["tier1_slots"],
            shapes["edge_itemsize"]) == (24, 4, 4)
    # a dead field that is still there is reported, never counted
    index.table_pack = np.zeros((0,), np.int64)
    assert cost.index_shapes(index)["hash_packed"] is False


def test_peaks_table_knows_the_v5e_and_refuses_a_guess():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["flops_bf16"] == 197e12
    with pytest.raises(KeyError, match="not in the benchmark's peaks"):
        peaks.peaks_for("TPU v9 imaginary")


def test_zone_generator_is_a_faithful_copy():
    from mosaic_tpu.datasets import synthetic_zones

    cfg = json.load(open(os.path.join(
        REPO, "benchmark", "configs", "taxi-zones-h3r9.json")))
    z = cfg["zones"]
    rings = zones.star_lattice(z["nx"], z["ny"], tuple(z["bbox"]),
                               seed=z["seed"], verts=z["verts"],
                               jitter=z["jitter"])
    packed = synthetic_zones(16, 16)
    assert len(rings) == len(packed) == 256
    b = packed.bounds()
    for i in (0, 17, 255):
        assert np.allclose(
            [rings[i][:, 0].min(), rings[i][:, 1].min(),
             rings[i][:, 0].max(), rings[i][:, 1].max()], b[i], rtol=0, atol=0)


def test_reference_smallest_containing_zone():
    sq = lambda x, y, s: np.array(  # noqa: E731
        [[x, y], [x + s, y], [x + s, y + s], [x, y + s]], float)
    rings = [sq(0, 0, 2), sq(1, 1, 2), sq(10, 10, 1)]
    pts = np.array([[0.5, 0.5], [1.5, 1.5], [2.5, 2.5], [10.5, 10.5],
                    [5.0, 5.0], [-1.0, 0.5]])
    assert pip_bruteforce.answers(rings, pts).tolist() == [0, 0, 1, 2, -1, -1]
    # a concave ring: the notch is outside
    notch = np.array([[0, 0], [4, 0], [4, 4], [2, 1], [0, 4]], float)
    got = pip_bruteforce.answers([notch], np.array([[2.0, 3.0], [2.0, 0.5]]))
    assert got.tolist() == [-1, 0]


def test_reference_agrees_with_the_programs_f64_oracle():
    """Independent code, same semantics: on the tiny fixture the plain
    reference and `host_join` give the same answers."""
    import mosaic_tpu
    from mosaic_tpu.core.tessellate import tessellate
    from mosaic_tpu.datasets import synthetic_zones
    from mosaic_tpu.sql.join import build_chip_index, host_join

    bbox = (-25.0, -25.0, 35.0, 20.0)
    grid = mosaic_tpu.enable_mosaic(
        "CUSTOM(-180,180,-90,90,2,10,10)").index_system
    index = build_chip_index(tessellate(
        synthetic_zones(3, 3, bbox=bbox), grid, 2, keep_core_geoms=False))
    rings = zones.star_lattice(3, 3, bbox)
    pts = np.random.default_rng(3).uniform((-30, -30), (40, 25), (20000, 2))
    assert np.array_equal(
        pip_bruteforce.answers(rings, pts),
        host_join(pts, index.host, grid, 2),
    )
