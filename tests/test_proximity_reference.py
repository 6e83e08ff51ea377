"""The distance join's plain reference (`benchmark/references/
dwithin_bruteforce.py`) against distances known in closed form, and the
deployment tied to the source's literal operation: on seeded tracks,
``intersects_join(st_buffer(a, r), st_buffer(b, r))`` gives the frontend's
pairs but for the polygonisation's sliver, and the lattice cover with a
reach holds every cell ``tessellate(st_buffer(track))`` makes."""

import importlib.util
import os

import numpy as np
import pytest

from mosaic_tpu.core.index.h3 import H3IndexSystem
from mosaic_tpu.core.tessellate import tessellate
from mosaic_tpu.core.types import GeometryType, PackedGeometry
from mosaic_tpu.functions import geometry as F
from mosaic_tpu.knn.index import expand_ranges, reach_cover
from mosaic_tpu.sql.overlay import intersects_join
from mosaic_tpu.sql.proximity import dwithin_join

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(kind, name):
    path = os.path.join(REPO, "benchmark", kind, name + ".py")
    spec = importlib.util.spec_from_file_location("_s2s_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("references", "dwithin_bruteforce")
GEN = _load("generators", "ais_tracks")

#: a small fleet in a small box: every kind of vessel, dense enough to meet
FLEET = {
    "vessels": 96, "window_minutes": 15, "ping_slots": 15, "pings": [5, 15],
    "one_metre_deg": 9e-06, "buffer_m": 200,
    "box": [-90.5, 28.0, -90.2, 28.2],
    "moored": {"share": 0.4, "places": 3, "zipf_s": 1.1,
               "sigma_km": [0.4, 1.0], "jitter_m": [10, 30],
               "speed_kn": [0.0, 0.5]},
    "lanes": {"share": 0.45, "count": 2, "speed_kn": [8, 16],
              "lateral_sigma_m": 300, "min_length_deg": 0.1},
    "free": {"speed_kn": [5, 14]},
    "transfers": {"pairs": 2, "windows": [1, 2], "gap_m": [30, 90],
                  "closing_kn": 2.0, "drift_kn": [0.2, 0.8],
                  "offset_km": [1, 2]},
    "layout_seed": 7,
}


def lines(xy, offsets):
    n = offsets.shape[0] - 1
    part = np.arange(n + 1)
    return PackedGeometry(
        xy=xy, ring_offsets=offsets, part_offsets=part, geom_offsets=part,
        geom_type=np.full(n, int(GeometryType.LINESTRING), np.uint8),
        srid=np.full(n, 4326, np.int32),
    )


def csr(tracks):
    return (np.concatenate(tracks).astype(float),
            np.concatenate([[0], np.cumsum([len(t) for t in tracks])]))


def test_reference_distances_in_closed_form():
    tracks = [
        np.array([[0.0, 0.0], [4.0, 0.0]]),              # 0 a segment
        np.array([[0.0, 3.0], [4.0, 3.0]]),              # 1 parallel, 3 off
        np.array([[2.0, -1.0], [2.0, 1.0]]),             # 2 crosses 0
        np.array([[7.0, 4.0]]),                          # 3 a point, 3-4-5 off 0's end
        np.array([[5.0, 0.0], [6.0, 0.0], [6.0, 5.0]]),  # 4 collinear with 0, 1 off
        np.array([[4.0, 0.0], [4.0, -2.0]]),             # 5 touches 0's end
    ]
    xy, off = csr(tracks)
    a = np.array([0, 0, 0, 0, 0, 1, 3, 2])
    b = np.array([1, 2, 3, 4, 5, 2, 4, 5])
    d = REF.distances(xy, off, a, b)
    assert d.tolist() == [3.0, 0.0, 5.0, 1.0, 0.0, 2.0, 1.0, 2.0]


def test_reference_answer_keys_boxes_and_the_undecidable_pair():
    tracks = [np.array([[0.0, 0.0], [1.0, 0.0]]),
              np.array([[0.0, 0.5], [1.0, 0.5]]),    # exactly r + r off 0
              np.array([[0.0, 0.25], [1.0, 0.25]]),
              np.array([[9.0, 9.0]])]
    xy, off = csr(tracks)
    pairs, unsure = REF.within(xy, off, 0.25)
    assert unsure.tolist() == [[0, 1]]  # |d - thr| within 1e-12: neither
    assert pairs.tolist() == [[0, 2], [1, 2]]
    # a key: only equal keys are compared
    pairs, _ = REF.within(xy, off, 0.25, key=np.array([0, 0, 1, 1]))
    assert pairs.tolist() == []
    pairs, _ = REF.within(xy, off, np.array([0.1, 0.1, 0.2, 0.1]))
    assert pairs.tolist() == [[0, 2], [1, 2]]
    # the box test drops no pair the distance keeps
    t = GEN.table(FLEET, 1, 3)
    a, b = REF.box_candidates(t["xy"], t["offsets"], np.arange(96), t["radius"])
    i, j = np.triu_indices(96, 1)
    d = REF.distances(t["xy"], t["offsets"], i, j)
    close = d <= t["radius"][i] + t["radius"][j]
    assert close.sum() > 50
    assert set(zip(i[close], j[close])) <= set(zip(a, b))


def test_generator_makes_the_sources_shapes():
    t = GEN.table(FLEET, 3, 11)
    assert len(t["pings"]) == 3 * 96 and t["offsets"][-1] == t["xy"].shape[0]
    assert t["pings"].min() >= 5 and t["pings"].max() <= 15
    assert np.allclose(t["radius"], 200 * 9e-06 * 15 / t["pings"])
    assert t["window"].tolist() == np.repeat(np.arange(3), 96).tolist()
    # the same seed, the same table; another seed, another
    again = GEN.table(FLEET, 3, 11)
    assert np.array_equal(t["xy"], again["xy"])
    assert not np.array_equal(t["xy"][:50], GEN.table(FLEET, 3, 12)["xy"][:50])
    # a planted pair lies under 100 m apart in every window it is planted in
    assert len(t["planted"]) >= 2
    rows = np.stack([t["planted"][:, 0] * 96 + t["planted"][:, 1],
                     t["planted"][:, 0] * 96 + t["planted"][:, 2]], axis=1)
    d = REF.distances(t["xy"], t["offsets"], rows[:, 0], rows[:, 1])
    assert (d < 130 * 9e-06).all() and (rows[:, 0] < rows[:, 1]).all()
    # pings a window are in time order: under way, a vessel never turns back
    big = GEN.table(dict(FLEET, vessels=256), 2, 5)
    assert big["xy"].shape[0] == big["pings"].sum()


@pytest.fixture(scope="module")
def tracks():
    t = GEN.table(dict(FLEET, vessels=160), 2, 29)
    return t, lines(t["xy"], t["offsets"])


def test_buffered_intersects_join_gives_the_frontends_pairs_but_for_the_sliver(
        tracks, capsys):
    """The source's literal operation on one radius: `intersects_join(
    st_buffer(a, r), st_buffer(a, r))`, a < b and equal windows kept, is
    the frontend's answer — but for pairs whose f64 distance lies in the
    sliver ``((1 - 0.0049) 2r, 2r]`` the inscribed 32-gon arcs fall short
    of, which the test enumerates. The tracks are vessels under way: this
    repo's native `st_buffer` unions a moored vessel's jumble of 20 m
    segments up to 3.5% of r short of its round buffer (one pair in 4,226
    on the mixed fleet read 0.965: the polygon path's defect, not the
    sliver's)."""
    del tracks
    fleet = dict(FLEET, vessels=160, moored=dict(FLEET["moored"], share=0.0),
                 lanes=dict(FLEET["lanes"], share=0.7))
    t = GEN.table(fleet, 2, 29)
    col = lines(t["xy"], t["offsets"])
    grid = H3IndexSystem()
    r = 300 * 9e-06
    got = dwithin_join(col, radius=r, key=t["window"], index_system=grid,
                       resolution=9).pairs
    buf = F.st_buffer(col, r)
    raw = intersects_join(buf, buf, grid, 9)
    raw = raw[(raw[:, 0] < raw[:, 1])
              & (t["window"][raw[:, 0]] == t["window"][raw[:, 1]])]
    d = REF.distances(t["xy"], t["offsets"], got[:, 0], got[:, 1])
    sliver = got[(d > (1 - 0.0049) * 2 * r) & (d <= 2 * r)]
    with capsys.disabled():
        print(f"\n[sliver] pairs={len(got)} buffered={len(raw)} "
              f"in the sliver={len(sliver)}: {sliver.tolist()}")
    a, b = set(map(tuple, got)), set(map(tuple, raw))
    assert len(a) > 100
    assert b <= a                       # a polygonised buffer is inscribed
    assert a - b <= set(map(tuple, sliver))


def test_capsule_cover_holds_every_cell_of_the_tessellated_buffer(tracks):
    t, col = tracks
    grid = H3IndexSystem()
    pick = np.arange(0, 160, 3)
    starts, ends = t["offsets"][:-1][pick], t["offsets"][1:][pick]
    for r in (200 * 9e-06, 600 * 9e-06):
        ok, face, line, a, b, n = reach_cover(grid, 9, t["xy"], starts, ends, r)
        assert ok.all()
        keys = grid.lattice_pack(
            np.repeat(face[line], n), np.repeat(a, n), expand_ranges(b, n))
        mine = set(zip(np.repeat(line, n).tolist(), keys.tolist()))
        chips = tessellate(F.st_buffer(col.take(pick), r), grid, 9,
                           keep_core_geoms=False)
        theirs = set(zip(
            np.asarray(chips.geom_id).tolist(),
            grid.lattice_keys(np.asarray(chips.cell_id, np.int64))[0].tolist()))
        assert theirs <= mine
        # a superset, not a blanket: under twice the corridor's own cells
        assert len(mine) < 2.0 * len(theirs)
