"""The resident KNN index: everything a serve frontend needs to answer
nearest-neighbour queries against a fixed candidate column.

Built ONCE per candidate set (the serving analog of
`SpatialKNN.transform`'s per-call tessellation):

- the candidate chips in a sorted-cell CSR (`cells`/`rows`), the exact
  structure the batch model probes with ``searchsorted`` every ring
  iteration;
- the device geometry column ``dc``, recentered by a shift derived from
  the CANDIDATE column bounds alone — for queries inside the candidate
  bounding box this is bit-for-bit the shift `functions.geometry._pair_pack`
  derives in the batch path, which is what makes served distances
  bit-identical to batch `SpatialKNN` distances;
- a host f64 twin of the candidate column in the SAME shifted frame
  (the `sql.join.HostRecheck` idiom) — the brute-force oracle's data
  and the degradation fallback's;
- the candidate :class:`~mosaic_tpu.sql.join.ChipIndex` (polygonal
  candidates only), whose build precomputed the Voronoi adjacency of
  convex chip sites (``chip_index.voronoi``) that the frontend's convex
  fast path walks.
"""

from __future__ import annotations

import dataclasses
import hashlib

import numpy as np

from ..core.geometry import affine as _affine
from ..core.geometry.device import DeviceGeometry, pack_to_device
from ..dispatch import cells_prog
from ..core.index.base import IndexSystem
from ..core.tessellate import tessellate
from ..core.types import GeometryType, PackedGeometry
from ..functions._coerce import to_packed
from ..utils import get_logger

logger = get_logger(__name__)


#: candidates a resident point block holds: one TPU lane row. A cell's
#: candidates fill whole blocks, so a (query, block) chunk is one row
#: gather; 32 and 512 read slower on the chip (PERF.md section 6, PR 33)
BLOCK_WIDTH = 128
#: rows of one cell-assignment launch of the index build
_BUILD_ROWS = 1 << 18


@dataclasses.dataclass
class HostCandidates:
    """Host f64 twin of the device candidate column, shifted frame.

    Per candidate geometry: the real vertices, the type-aware boundary
    edges (closed rings for polygons, open runs for lines, none for
    points), and the closed polygon rings for the containment parity
    test — exactly the three masked terms of
    `core/geometry/predicates.min_distance` / `crossing_number`. A column
    of points alone keeps one (N, 2) array, ``xy``, and no lists.
    """

    verts: "list | None"  # g -> (V, 2) f64
    edges: "list | None"  # g -> ((E, 2), (E, 2)) f64 boundary edge endpoints
    poly_edges: "list | None"  # g -> ((E, 2), (E, 2)) closed polygon edges or None
    xy: "np.ndarray | None" = None  # (N, 2) f64: an all-point column


@dataclasses.dataclass
class PointBlocks:
    """An all-point candidate column laid out for the ring search's
    device path: the candidates of one cell fill ``ceil(count / width)``
    blocks of ``width`` slots, cell after cell in cell order, so a ring
    cell is a run of whole blocks and a (query, block) chunk one row
    gather. Block ``n_blocks`` holds no candidate: padding chunks point
    at it."""

    width: int
    ucells: np.ndarray  # (U,) int64 occupied cells, ascending
    blk_start: np.ndarray  # (U+1,) int64 first block of each cell
    count: np.ndarray  # (U,) int64 candidates of each cell
    n_blocks: int
    x: object  # (n_blocks+1, width) device dtype, shifted frame
    y: object
    rid: object  # (n_blocks+1, width) int32 candidate row, -1 = empty


@dataclasses.dataclass
class KNNIndex:
    """Resident candidate-side state for served KNN."""

    candidates: PackedGeometry
    index_system: IndexSystem
    resolution: int
    #: (T,) int64 probe keys of the chips, sorted: the chips' cell ids,
    #: or (``lattice``) their `IndexSystem.lattice_keys`, which a ring
    #: search steps over with integer adds
    cells: np.ndarray
    rows: np.ndarray  # (T,) int64 candidate row per chip, key-sorted
    shift: np.ndarray  # (2,) f64 recenter origin of dc and the twin
    #: radius every completed ring is sure to cover (`IndexSystem.ring_width`)
    cell_width: float
    host: HostCandidates
    chip_index: object  # ChipIndex | None (non-polygonal candidates)
    fingerprint: str  # restart-stable identity for AOT program keys
    dtype: np.dtype  # device dtype of the candidate coordinates
    #: the block layout of an all-point column, else None
    points: "PointBlocks | None" = None
    #: ``cells`` holds lattice keys (every chip's cell lies on the grid's
    #: lattice), not cell ids
    lattice: bool = False
    _dc: "DeviceGeometry | None" = None

    @property
    def n(self) -> int:
        return len(self.candidates)

    @property
    def dc(self) -> DeviceGeometry:
        """The shifted device candidate column the pair programs gather
        from. An all-point index answers point queries from its blocks
        and packs this only when a pair program first asks for it."""
        if self._dc is None:
            self._dc = pack_to_device(
                _affine.translate(
                    self.candidates, -self.shift[0], -self.shift[1]
                ),
                dtype=self.dtype,
            )
        return self._dc

    @property
    def voronoi(self):
        """`sql.join.VoronoiTables` of the convex chip sites, or None
        (non-polygonal candidates / no convex-eligible cells)."""
        return getattr(self.chip_index, "voronoi", None)

    def candidate_rows(self, cells: np.ndarray) -> np.ndarray:
        """Distinct candidate rows whose chips land in the cells
        ``cells`` (cell ids; the searchsorted CSR probe), ascending."""
        if not cells.size:
            return np.zeros(0, dtype=np.int64)
        keys = self.probe_keys(cells)[0]
        lo = np.searchsorted(self.cells, keys, side="left")
        hi = np.searchsorted(self.cells, keys, side="right")
        return np.unique(self.rows[expand_ranges(lo, hi - lo)])

    def probe_keys(self, cells: np.ndarray):
        """``(keys, margin)`` of cell ids in the table's own key space:
        the ids themselves (margin None), or their lattice keys with the
        rings each is sure to keep on its face's lattice."""
        cells = np.asarray(cells, dtype=np.int64)
        if not self.lattice:
            return cells, None
        uniq, inv = np.unique(cells, return_inverse=True)
        keys, margin = self.index_system.lattice_keys(uniq)
        return keys[inv], margin[inv]

    def ring_keys(self, cells, keys, margin, it: int) -> np.ndarray:
        """(S, M) probe keys of iteration ``it``'s ring around each seed
        (cell ids ``cells``, their `probe_keys`): k-ring(1) at ``it ==
        1``, the k-loop(it) shell after it; -1 pads. On a lattice the
        ring is the seed's key plus the ring's offsets; a seed too near
        its face's edge for that (or off the lattice) has its ring
        walked by the grid and looked up."""
        if not self.lattice:
            return self.index_system.ring_cells(cells, it)
        out = keys[:, None] + self.index_system.lattice_ring(it)[None, :]
        walk = np.flatnonzero((keys < 0) | (margin < it + 1))
        if walk.size:
            ring = self.index_system.ring_cells(cells[walk], it)
            out[walk] = -1
            got = np.where(
                ring >= 0, self.probe_keys(ring.ravel())[0].reshape(ring.shape),
                -1,
            )
            out[walk, : got.shape[1]] = got[:, : out.shape[1]]
        return out


@dataclasses.dataclass
class LandmarkSeeds:
    """The cells polygon landmarks search from, CSR by landmark: a superset
    of each landmark's cover (every point of the polygon lies in one of its
    seed cells), which is all the ring search's rest criterion asks."""

    ptr: np.ndarray  # (L+1,) int64
    #: (S,) int64 cell ids; -1 where the seed was made on the lattice and
    #: never needs its id (`KNNIndex.ring_keys` walks only short margins)
    cells: np.ndarray
    keys: "np.ndarray | None"  # (S,) lattice keys (a lattice index), else None
    margin: "np.ndarray | None"
    tessellated: int = 0  # landmarks whose cover the grid's clipper made


#: edge slots of one table of the polygon block program (`LandmarkRings`):
#: a table's rows are these over its pad whatever the landmark column
#: holds, so the program that takes it is one shape an edge rung
TABLE_SLOTS = 1 << 17


def table_rows(vpad: int) -> int:
    """Rows of a `LandmarkRings` table whose rows pad to ``vpad`` edges."""
    return TABLE_SLOTS // vpad


@dataclasses.dataclass
class LandmarkRings:
    """Polygon landmarks' edges, laid out for the block program: landmark
    ``l`` is row ``row[l]`` of table ``table[l]`` — ``(table_rows(vpad),
    5, vpad)`` in the index's dtype and frame: an edge's ``ax, ay, bx, by``
    and ``1 / length^2`` (0 for a pad or a point), every ring closed, holes
    in the same row, pads repeating the landmark's first vertex (an edge of
    no length: it crosses nothing and is no nearer than the vertex). A
    table's shape is its pad's alone (`table_rows`): the landmarks of one
    edge rung fill as many tables as they need, in their order, and the
    program compiled for a rung serves every column. The last row of a
    table is the padding chunks'. ``table`` is -1 where a landmark has no
    edge (it has no seed either) or more than the ladder's top rung: the
    host answers that one from ``flat``, the f64 edges of every landmark
    (``start``, ``edges``)."""

    edges: np.ndarray  # (L,) int64 real edges (one a vertex)
    start: np.ndarray  # (L,) int64 first edge in ``flat``
    flat: np.ndarray  # (5, V) f64
    table: np.ndarray  # (L,) int64 index into ``tables``, -1 = host
    row: np.ndarray  # (L,) int64
    pads: np.ndarray  # (T,) int64 the edges a row of each table pads to
    tables: list  # (T,) device arrays
    nbytes: int = 0


def _geom_vertex_runs(land: PackedGeometry):
    """(lo, hi) vertex runs of each geometry in ``land.xy``."""
    first = land.ring_offsets[land.part_offsets[land.geom_offsets]]
    return first[:-1], first[1:]


def pack_landmark_rings(kx: "KNNIndex", land: PackedGeometry, ladder) -> LandmarkRings:
    """`LandmarkRings` of a polygon column, recentred on ``kx.shift`` in
    f64 on the host and put on the device in ``kx.dtype``; array code."""
    import jax.numpy as jnp

    lo, hi = _geom_vertex_runs(land)
    nv = land.xy.shape[0]
    # every vertex starts an edge to the next of its ring, the last to the first
    nxt = np.arange(1, nv + 1, dtype=np.int64)
    ends = land.ring_offsets[1:]
    full = ends > land.ring_offsets[:-1]
    nxt[ends[full] - 1] = land.ring_offsets[:-1][full]
    a = land.xy - kx.shift
    b = a[nxt]
    d = b - a
    len2 = (d * d).sum(axis=1)
    inv = np.divide(1.0, len2, out=np.zeros(nv), where=len2 > 0)
    flat = np.stack([a[:, 0], a[:, 1], b[:, 0], b[:, 1], inv])
    edges = (hi - lo).astype(np.int64)
    vpads = np.asarray(ladder.buckets)
    rung = np.searchsorted(vpads, edges)
    rung[(rung >= vpads.size) | (edges == 0)] = -1
    table = np.full(edges.shape[0], -1, dtype=np.int64)
    row = np.zeros(edges.shape[0], dtype=np.int64)
    tables, pads, nbytes = [], [], 0
    owner = np.repeat(np.arange(edges.shape[0]), edges)  # landmark of each edge
    within = np.arange(nv, dtype=np.int64) - np.repeat(lo, edges)
    for r, vpad in enumerate(vpads.tolist()):
        mine = np.flatnonzero(rung == r)
        held = table_rows(vpad) - 1  # (the last row is the pads')
        table[mine] = len(tables) + np.arange(mine.size) // held
        row[mine] = np.arange(mine.size) % held
        sel = np.flatnonzero(rung[owner] == r)
        for t in range(-(-mine.size // held)):
            part = mine[t * held : (t + 1) * held]
            tab = np.zeros((held + 1, 5, vpad))
            tab[: part.size, 0] = tab[: part.size, 2] = a[lo[part], 0][:, None]
            tab[: part.size, 1] = tab[: part.size, 3] = a[lo[part], 1][:, None]
            e = sel[table[owner[sel]] == len(tables)]
            tab[row[owner[e]], :, within[e]] = flat[:, e].T
            dev = jnp.asarray(tab, dtype=kx.dtype)
            nbytes += int(dev.nbytes)
            tables.append(dev)
            pads.append(vpad)
    return LandmarkRings(
        edges=edges, start=lo.astype(np.int64), flat=flat, table=table,
        row=row, pads=np.asarray(pads, dtype=np.int64), tables=tables,
        nbytes=nbytes,
    )


def edge_terms(px, py, ax, ay, bx, by, inv, xp=np):
    """A point against an edge ``a -> b``, elementwise over broadcast
    shapes: ``(squared distance to the segment, whether a ray from the
    point towards +x crosses it)`` — the even-odd rule's crossing, half
    open in y so a vertex counts once. ``inv`` is ``1 / |b - a|^2`` (0
    for an edge of no length: the distance is the vertex's)."""
    dx, dy = bx - ax, by - ay
    rx, ry = px - ax, py - ay
    t = xp.clip((rx * dx + ry * dy) * inv, 0.0, 1.0)
    cx, cy = rx - t * dx, ry - t * dy
    crossing = ((ay > py) != (by > py)) & ((rx * dy < ry * dx) == (dy > 0))
    return cx * cx + cy * cy, crossing


def host_polygon_distances(rings: LandmarkRings, qi, cxy) -> np.ndarray:
    """(P,) f64 `st_distance(polygon, point)` of landmark ``qi[p]`` and the
    shifted point ``cxy[p]``: 0.0 inside or on the boundary (even-odd over
    all rings, so a courtyard is outside), else the nearest edge's — the
    block program's arithmetic in numpy on the f64 edges, a slice of pairs
    at a time."""
    out = np.empty(qi.shape[0], dtype=np.float64)
    if not qi.size:
        return out
    width = int(rings.edges[qi].max())
    if not width:
        out[:] = np.inf
        return out
    step = max((1 << 21) // width, 1)
    lane = np.arange(width)
    for s in range(0, qi.shape[0], step):
        q = qi[s : s + step]
        n = rings.edges[q][:, None]
        # (a pad lane reads the landmark's first edge again: min and parity
        # are masked below)
        at = rings.start[q][:, None] + np.where(lane < n, lane, 0)
        e = rings.flat[:, at]  # (5, p, width)
        p = cxy[s : s + step]
        d2, cross = edge_terms(p[:, :1], p[:, 1:], e[0], e[1], e[2], e[3], e[4])
        real = lane < n
        odd = (np.count_nonzero(cross & real, axis=1) & 1) == 1
        d = np.sqrt(np.where(real, d2, np.inf).min(axis=1))
        out[s : s + step] = np.where(odd, 0.0, d)
    return out


#: a lattice hexagon reaches 2/3 of a step along each axis from its centre
_HEX_REACH = 2.0 / 3.0
#: what a cover's ranges give way for an edge that is straight in lon/lat
#: and not on the face's plane (under 4e-3 of a cell at `_COVER_SPAN`)
_COVER_SLACK = 0.01
#: cells a landmark may span along an axis and still be covered on the lattice
_COVER_SPAN = 32


def lattice_forms(xa, xb, at, ends):
    """The ranges ``(lo, hi)`` of the lattice's six linear forms — the
    axial coordinates ``a, b, c = -a - b`` and their differences ``a - b,
    b - c, c - a`` — over runs of points given by their continuous axial
    coordinates: run ``i`` is ``at[i]:ends[i]`` (runs may overlap or
    leave points out; each must hold a point)."""
    xc = -xa - xb
    # (an odd slot reduces nothing that is read; the last one may not name
    # the end of the array)
    idx = np.stack([at, ends], axis=1).reshape(-1)
    if idx[-1] >= xa.shape[0]:
        idx = idx[:-1]

    def span(v):
        return (np.minimum.reduceat(v, idx)[::2],
                np.maximum.reduceat(v, idx)[::2])

    return [span(v) for v in (xa, xb, xc, xa - xb, xb - xc, xc - xa)]


def _cover_box(forms, reach):
    """``(a_lo, na, nb)``: the box of lattice positions within ``reach``
    (six values, each a number or one a run) of the forms' ranges along
    the two axes — where it starts along ``a`` and its two widths."""
    a_lo = np.ceil(forms[0][0] - reach[0]).astype(np.int64)
    a_hi = np.floor(forms[0][1] + reach[0]).astype(np.int64)
    b_lo = np.ceil(forms[1][0] - reach[1]).astype(np.int64)
    b_hi = np.floor(forms[1][1] + reach[1]).astype(np.int64)
    return a_lo, a_hi - a_lo + 1, b_hi - b_lo + 1


def cover_scanlines(forms, reach, pick, a_lo, na):
    """``(run, a, b_lo, n)``: the lattice positions whose six forms all
    lie within ``reach`` of a run's ranges, for the runs ``pick``, as
    SCANLINES — run ``run`` holds the positions ``(a, b_lo) .. (a, b_lo +
    n - 1)``; run by run, ``a`` ascending. With ``a`` fixed each of the
    other five forms bounds ``b`` to an interval, and a scanline is the
    integers of their intersection (no position is made that a later test
    would drop; ``n`` may be 0)."""
    def at(v):
        return v[pick] if isinstance(v, np.ndarray) else v

    lo = [at(f[0]) - at(r) for f, r in zip(forms, reach)]
    hi = [at(f[1]) + at(r) for f, r in zip(forms, reach)]
    lines = na[pick]
    s = np.repeat(np.arange(pick.size), lines)  # scanline -> run of `pick`
    a = a_lo[pick][s] + expand_ranges(np.zeros_like(lines), lines)

    def of(v):
        return v[s] if isinstance(v, np.ndarray) else v

    # b itself; c = -a - b; a - b; b - c = a + 2b; c - a = -2a - b
    b_lo = np.maximum.reduce([
        np.broadcast_to(of(lo[1]), a.shape), -a - of(hi[2]), a - of(hi[3]),
        (of(lo[4]) - a) / 2.0, -2 * a - of(hi[5]),
    ])
    b_hi = np.minimum.reduce([
        np.broadcast_to(of(hi[1]), a.shape), -a - of(lo[2]), a - of(lo[3]),
        (of(hi[4]) - a) / 2.0, -2 * a - of(lo[5]),
    ])
    b_lo = np.ceil(b_lo).astype(np.int64)
    n = np.maximum(np.floor(b_hi).astype(np.int64) - b_lo + 1, 0)
    return pick[s], a, b_lo, n


#: the longest run of a line, in lattice steps along it, that one cover
#: piece holds: the six forms bound a run's hull by a 12-gon, which for a
#: straight run of length L is up to L sin 15 degrees wider than the run
#: (a quarter of L), so a piece is kept to a few cells
_PIECE_STEPS = 4.0
#: what a reach in coordinate units gives way for the lattice not being
#: affine over a piece (the gnomonic projection bends under 1e-4 a cell
#: over `_COVER_SPAN` cells)
_REACH_SLACK = 1.0 + 1e-3


#: a line whose box's half diagonal is under this share of its reach is
#: covered as one point, the box's centre, the reach made longer by that
#: half diagonal: a moored vessel's pings, placed on the lattice once
_POINT_SHARE = 0.25


def reach_cover(index_system, resolution: int, xy, starts, ends, reach):
    """The cells within ``reach`` of polylines, as array code with no
    buffer and no clipping: `polygon_cover`'s lattice arithmetic with the
    ranges widened by a radius.

    Line ``i`` is the vertices ``xy[starts[i]:ends[i]]`` (at least one;
    one vertex is a point), ``reach[i]`` its radius in coordinate units
    (planar, as `st_buffer` reads it). A line is cut into PIECES of a few
    cells (`_PIECE_STEPS` along the line; a segment is never cut), a
    piece's vertices are placed on the lattice, and the hexagons kept are
    those whose centre is within a hexagon's reach PLUS the radius of
    every one of the six forms' ranges over the piece — the radius in
    lattice units a form, ``|grad form| * reach`` with the gradients taken
    at the line's first vertex by differences. Every point within
    ``reach`` of the line lies in a kept cell (a superset of
    ``tessellate(st_buffer(line))``'s cells).

    Returns ``(ok (N,) bool, face (N,), line (S,), a (S,), b (S,), n
    (S,))``: the lines covered here (the rest — nearer their face's edge
    than their cover, wider than `_COVER_SPAN` cells a piece, or all of
    them on a grid with no lattice — are the caller's to tessellate),
    each line's face, and the cover as scanlines: line ``line[s]`` holds
    the lattice positions ``(a[s], b[s]) .. (a[s], b[s] + n[s] - 1)`` of
    its face, line by line. A line's pieces overlap where they meet: a
    position may come twice."""
    n = int(np.asarray(starts).shape[0])
    none = np.zeros(0, dtype=np.int64)
    if not n or index_system.lattice_keys(none) is None:
        return np.zeros(n, dtype=bool), none, none, none, none, none
    starts = np.asarray(starts, dtype=np.int64)
    count = np.asarray(ends, dtype=np.int64) - starts
    reach = np.broadcast_to(np.asarray(reach, dtype=np.float64), (n,))
    pts = xy[expand_ranges(starts, count)]
    first = np.cumsum(count) - count             # line -> its first vertex
    lo = np.minimum.reduceat(pts, first, axis=0)
    hi = np.maximum.reduceat(pts, first, axis=0)
    half = 0.5 * np.hypot(*(hi - lo).T)
    dot = half <= _POINT_SHARE * reach
    if dot.any():
        keep = np.repeat(~dot, count)
        keep[first[dot]] = True
        pts[first[dot]] = 0.5 * (lo[dot] + hi[dot])
        pts = pts[keep]
        count = np.where(dot, 1, count)
        first = np.cumsum(count) - count
        reach = reach + np.where(dot, half, 0.0)
    line = np.repeat(np.arange(n), count)        # vertex -> line
    p0 = pts[first]
    face = index_system.lattice_coords(p0, resolution)[0]
    _, xa, xb, edge = index_system.lattice_coords(
        pts, resolution, face=face[line]
    )
    # the lattice's gradients at each line's first vertex, by differences
    # over a step of the reach's own size
    h = np.maximum(reach, 1e-9)
    zero = np.zeros(n)
    _, ax, bx, _ = index_system.lattice_coords(
        p0 + np.stack([h, zero], axis=1), resolution, face=face)
    _, ay, by, _ = index_system.lattice_coords(
        p0 + np.stack([zero, h], axis=1), resolution, face=face)
    ga = np.stack([ax - xa[first], ay - xa[first]], axis=1) / h[:, None]
    gb = np.stack([bx - xb[first], by - xb[first]], axis=1) / h[:, None]

    # pieces: a line's segments bucketed by the lattice steps walked
    # before them (hex2d's metric in axial coordinates); a line of one
    # vertex is one piece of one vertex
    nseg = count - 1
    seg = np.flatnonzero(
        np.arange(pts.shape[0]) + 1 < np.repeat(first + count, count)
    )
    da, db = xa[seg + 1] - xa[seg], xb[seg + 1] - xb[seg]
    steps = np.sqrt(da * da + da * db + db * db)
    before = np.cumsum(steps) - steps
    sl = line[seg]
    bucket = np.floor(
        (before - before[(np.cumsum(nseg) - nseg)[sl]]) / _PIECE_STEPS
    ).astype(np.int64)
    head = np.ones(seg.size, dtype=bool)
    head[1:] = (sl[1:] != sl[:-1]) | (bucket[1:] != bucket[:-1])
    head = np.flatnonzero(head)
    tail = np.concatenate([head[1:], [seg.size]])[: head.size] - 1
    dots = np.flatnonzero(count == 1)
    p_lo = np.concatenate([seg[head], first[dots]])
    p_hi = np.concatenate([seg[tail] + 2, first[dots] + 1])
    order = np.argsort(p_lo, kind="stable")
    p_lo, p_hi = p_lo[order], p_hi[order]
    p_line = line[p_lo]

    forms = lattice_forms(xa, xb, p_lo, p_hi)
    r = reach[p_line] * _REACH_SLACK
    cell = (_HEX_REACH + _COVER_SLACK,) * 3 + (1.0 + _COVER_SLACK,) * 3
    widen = [
        np.hypot(g[p_line, 0], g[p_line, 1]) * r + c
        for g, c in zip(
            (ga, gb, -ga - gb, ga - gb, ga + 2.0 * gb, -2.0 * ga - gb), cell
        )
    ]
    a_lo, na, nb = _cover_box(forms, widen)
    room = np.minimum.reduceat(edge, first)[p_line] - np.maximum(na, nb) - 1
    ok = np.ones(n, dtype=bool)
    ok[p_line[(np.maximum(na, nb) > _COVER_SPAN) | (room < 0)]] = False
    piece, a, b, width = cover_scanlines(
        forms, widen, np.flatnonzero(ok[p_line]), a_lo, na
    )
    return ok, face, p_line[piece], a, b, width


def polygon_cover(kx: "KNNIndex", land: PackedGeometry, rings: int) -> LandmarkSeeds:
    """The seed cells of polygon landmarks as array code, with no clipping.

    On a lattice index: a landmark's vertices are placed on the lattice
    (`IndexSystem.lattice_coords`, continuous); a polygon lies in the hull
    of its vertices, so each of the lattice's six linear forms — the axial
    coordinates ``a, b, c = -a - b`` and their differences — stays within
    its range over the vertices, and a hexagon meets the polygon only if
    its centre is within a hexagon's reach of every range (2/3 along an
    axis, 1 along a difference: the hexagon IS ``|da - db|, |db - dc|,
    |dc - da| <= 1``). The cells that pass are a superset of the cover —
    exactly the cover for a footprint small against a cell. ``rings`` is
    how many rings the search may ask of a seed: a landmark nearer its
    face's edge than that (or past it, or spanning more than
    `_COVER_SPAN` cells, or on a grid with no lattice) has its cover made
    by `tessellate` instead."""
    # (a landmark's vertices are one run of ``land.xy``, landmark after
    # landmark: `_geom_vertex_runs`)
    n = len(land)
    lo, hi = _geom_vertex_runs(land)
    some = np.flatnonzero(hi > lo)
    regular = np.zeros(n, dtype=bool)
    own = np.zeros(0, dtype=np.int64)
    cells = keys = margin = own
    if kx.lattice and some.size:
        # every vertex on the face of its landmark's first: one beyond that
        # face's triangle reads a negative margin below
        at = lo[some]
        face = kx.index_system.lattice_coords(land.xy[at], kx.resolution)[0]
        _, xa, xb, edge = kx.index_system.lattice_coords(
            land.xy[at[0] :], kx.resolution,
            face=np.repeat(face, np.diff(np.r_[at, land.xy.shape[0]])),
        )
        at = at - at[0]
        xc = -xa - xb

        def span(v):
            return np.minimum.reduceat(v, at), np.maximum.reduceat(v, at)

        forms = [span(v) for v in (xa, xb, xc, xa - xb, xb - xc, xc - xa)]
        reach = _HEX_REACH + _COVER_SLACK
        a_lo = np.ceil(forms[0][0] - reach).astype(np.int64)
        a_hi = np.floor(forms[0][1] + reach).astype(np.int64)
        b_lo = np.ceil(forms[1][0] - reach).astype(np.int64)
        b_hi = np.floor(forms[1][1] + reach).astype(np.int64)
        na, nb = a_hi - a_lo + 1, b_hi - b_lo + 1
        room = np.minimum.reduceat(edge, at) - np.maximum(na, nb) - 1
        ok = (np.maximum(na, nb) <= _COVER_SPAN) & (room >= rings)
        regular[some[ok]] = True
        pick = np.flatnonzero(ok)
        box = (na * nb)[pick]  # lattice positions in each landmark's a x b box
        o = np.repeat(pick, box)
        t = expand_ranges(np.zeros_like(box), box)
        a = a_lo[o] + t // nb[o]
        b = b_lo[o] + t % nb[o]
        c = -a - b
        keep = np.ones(o.size, dtype=bool)
        for (v_lo, v_hi), v, r in zip(
            forms, (a, b, c, a - b, b - c, c - a), (reach,) * 3 + (1.0 + _COVER_SLACK,) * 3
        ):
            keep &= (v >= v_lo[o] - r) & (v <= v_hi[o] + r)
        o, a, b = o[keep], a[keep], b[keep]
        own = some[o]
        keys = kx.index_system.lattice_pack(face[o], a, b)
        margin = room[o]
        cells = np.full(own.size, -1, dtype=np.int64)
    rest = np.flatnonzero(~regular & (hi > lo))
    if rest.size:
        table = tessellate(
            land if rest.size == n else land.take(rest), kx.index_system,
            kx.resolution, keep_core_geoms=False,
        )
        cover = np.unique(
            np.stack([rest[table.geom_id.astype(np.int64)],
                      np.asarray(table.cell_id, dtype=np.int64)]), axis=1,
        )
        t_keys, t_margin = kx.probe_keys(cover[1])
        own = np.concatenate([own, cover[0]])
        cells = np.concatenate([cells, cover[1]])
        if kx.lattice:
            keys = np.concatenate([keys, t_keys])
            margin = np.concatenate([margin, t_margin])
        order = np.argsort(own, kind="stable")
        own, cells = own[order], cells[order]
        if kx.lattice:
            keys, margin = keys[order], margin[order]
    ptr = np.concatenate([[0], np.cumsum(np.bincount(own, minlength=n))])
    return LandmarkSeeds(
        ptr=ptr.astype(np.int64), cells=cells,
        keys=keys if kx.lattice else None,
        margin=margin if kx.lattice else None, tessellated=int(rest.size),
    )


def expand_ranges(start: np.ndarray, count: np.ndarray) -> np.ndarray:
    """``concatenate([arange(s, s + c) for s, c in zip(start, count)])``
    as array code."""
    count = np.asarray(count, dtype=np.int64)
    total = int(count.sum())
    if not total:
        return np.zeros(0, dtype=np.int64)
    first = np.cumsum(count) - count  # output offset of each range
    return np.arange(total, dtype=np.int64) + np.repeat(
        np.asarray(start, dtype=np.int64) - first, count
    )


def point_coords(data) -> "np.ndarray | None":
    """(N, 2) f64 coordinates where ``data`` is a column of single
    points — a float (N, 2) array, or a geometry column whose every row
    is one POINT — else None."""
    if isinstance(data, np.ndarray) and data.ndim == 2 and data.shape[1] == 2 \
            and data.dtype.kind == "f":
        return np.asarray(data, dtype=np.float64)
    if (
        isinstance(data, PackedGeometry)
        and len(data)
        and data.num_vertices == len(data)
        and bool(np.all(data.geom_type == int(GeometryType.POINT)))
        and bool(np.all(np.diff(data.geom_offsets) == 1))
    ):
        return data.xy
    return None


def points_column(xy: np.ndarray, srid: int = 4326) -> PackedGeometry:
    """(N, 2) coordinates as a column of POINTs, in array code
    (`functions.st_point` builds the same column a row at a time)."""
    n = xy.shape[0]
    off = np.arange(n + 1, dtype=np.int64)
    return PackedGeometry(
        xy=xy, ring_offsets=off, part_offsets=off, geom_offsets=off,
        geom_type=np.full(n, int(GeometryType.POINT), dtype=np.uint8),
        srid=np.full(n, srid, dtype=np.int32),
    )


def assign_cells(index_system, resolution: int, xy: np.ndarray) -> np.ndarray:
    """(N, 2) -> (N,) int64 cells through the shared jitted
    `dispatch.cells_prog`, a power-of-two launch at a time."""
    n = xy.shape[0]
    out = np.empty(n, dtype=np.int64)
    prog = cells_prog(index_system, resolution, "cells")
    for c0 in range(0, n, _BUILD_ROWS):
        chunk = xy[c0 : c0 + _BUILD_ROWS]
        m = chunk.shape[0]
        b = max(64, 1 << (m - 1).bit_length())
        padded = np.concatenate([chunk, np.broadcast_to(chunk[:1], (b - m, 2))])
        out[c0 : c0 + m] = np.asarray(prog(padded))[:m]
    return out


def _lattice_of(index_system, cells: np.ndarray):
    """The chips' lattice keys where the grid offers them and every chip's
    cell lies on the lattice (a table with a pentagon's children keeps
    cell ids: their rings are walked by the grid), else None."""
    uniq, inv = np.unique(cells, return_inverse=True)
    got = index_system.lattice_keys(uniq)
    if got is None or (got[0] < 0).any():
        return None
    return got[0][inv]


def _point_blocks(cells, rows, xs, dtype, width: int) -> PointBlocks:
    """Lay cell-sorted candidates (``rows`` under sorted ``cells``, shifted
    coordinates ``xs`` by row) out in blocks of ``width``."""
    import jax.numpy as jnp

    ucells, first, count = np.unique(
        cells, return_index=True, return_counts=True
    )
    nblk = -(-count // width)
    blk_start = np.concatenate([[0], np.cumsum(nblk)]).astype(np.int64)
    n_blocks = int(blk_start[-1])
    # slot of every candidate: its cell's first block, then its rank there
    rank = np.arange(cells.shape[0], dtype=np.int64) - np.repeat(first, count)
    slot = np.repeat(blk_start[:-1] * width, count) + rank
    shape = ((n_blocks + 1) * width,)
    x = np.zeros(shape, dtype=np.float64)
    y = np.zeros(shape, dtype=np.float64)
    rid = np.full(shape, -1, dtype=np.int32)
    x[slot], y[slot], rid[slot] = xs[rows, 0], xs[rows, 1], rows
    to = (n_blocks + 1, width)
    return PointBlocks(
        width=width, ucells=ucells.astype(np.int64), blk_start=blk_start,
        count=count.astype(np.int64), n_blocks=n_blocks,
        x=jnp.asarray(x.reshape(to), dtype=dtype),
        y=jnp.asarray(y.reshape(to), dtype=dtype),
        rid=jnp.asarray(rid.reshape(to)),
    )


def _candidate_shift(cand: PackedGeometry) -> np.ndarray:
    """Midpoint of the candidate column's finite bounds — equals
    `_pair_pack(queries, cand)`'s union-bounds shift whenever the query
    bbox sits inside the candidate bbox (the served-traffic contract the
    bit-identity tests pin)."""
    bb = cand.bounds()
    finite = bb[np.isfinite(bb[:, 0])]
    if not finite.size:
        return np.zeros(2)
    lo = finite[:, :2].min(axis=0)
    hi = finite[:, 2:].max(axis=0)
    return (lo + hi) / 2.0


def _host_twin(cand: PackedGeometry, shift: np.ndarray) -> HostCandidates:
    verts, edges, poly_edges = [], [], []
    for g in range(len(cand)):
        base = cand.geometry_type(g).base
        polygonal = base == GeometryType.POLYGON
        linear = base == GeometryType.LINESTRING
        v_list, ea, eb, pa, pb = [], [], [], [], []
        for p in cand.geom_parts(g):
            for r in cand.part_rings(p):
                ring = cand.ring_xy(r) - shift  # open form, f64
                v_list.append(ring)
                if polygonal and ring.shape[0] >= 2:
                    closed = np.vstack([ring, ring[:1]])
                    ea.append(closed[:-1])
                    eb.append(closed[1:])
                    pa.append(closed[:-1])
                    pb.append(closed[1:])
                elif linear and ring.shape[0] >= 2:
                    ea.append(ring[:-1])
                    eb.append(ring[1:])
        verts.append(
            np.concatenate(v_list) if v_list else np.zeros((0, 2))
        )
        edges.append(
            (np.concatenate(ea), np.concatenate(eb))
            if ea
            else (np.zeros((0, 2)), np.zeros((0, 2)))
        )
        poly_edges.append(
            (np.concatenate(pa), np.concatenate(pb)) if pa else None
        )
    return HostCandidates(verts=verts, edges=edges, poly_edges=poly_edges)


def _fingerprint(cells, rows, shift, resolution, index_system) -> str:
    h = hashlib.sha256()
    h.update(np.ascontiguousarray(cells).tobytes())
    h.update(np.ascontiguousarray(rows).tobytes())
    h.update(np.ascontiguousarray(shift).tobytes())
    h.update(str(int(resolution)).encode())
    h.update(type(index_system).__name__.encode())
    return "knn-" + h.hexdigest()[:32]


def build_knn_index(
    candidates,
    index_system: "IndexSystem | None" = None,
    resolution: "int | None" = None,
    dtype=None,
) -> KNNIndex:
    """Tessellate + pack + twin the candidate column into a
    :class:`KNNIndex` that `KNNFrontend` and `SpatialKNN.transform` hold
    resident. ``candidates`` is any geometry input, or a float (N, 2)
    array of points. A column of points alone is built in array code —
    a point's chip is its cell, assigned on the device — and laid out in
    blocks for the ring search's device path. ``dtype`` is the device
    dtype of the candidate coordinates and of the distances (the
    package's default: float64 where x64 is on)."""
    if index_system is None:
        from ..context import current_context

        index_system = current_context().index_system
    xy = point_coords(candidates)
    cand = points_column(xy) if xy is not None and not isinstance(
        candidates, PackedGeometry
    ) else to_packed(candidates)
    if xy is None:
        xy = point_coords(cand)
    if resolution is not None:
        res = index_system.resolution_arg(resolution)
    else:
        from ..sql.analyzer import MosaicAnalyzer

        res = MosaicAnalyzer(index_system).get_optimal_resolution(cand)
    from ..functions.geometry import _device_dtype

    dtype = np.dtype(_device_dtype() if dtype is None else dtype)

    if xy is not None:
        finite = xy[np.isfinite(xy).all(axis=1)]
        shift = (
            (finite.min(axis=0) + finite.max(axis=0)) / 2.0
            if finite.size else np.zeros(2)
        )
        pcells = assign_cells(index_system, res, xy)
        cell_width = index_system.ring_width(res, pcells)
        keys = _lattice_of(index_system, pcells)
        pcells = pcells if keys is None else keys
        order = np.argsort(pcells, kind="stable")
        cells = pcells[order]
        rows = order.astype(np.int64)
        xs = xy - shift
        return KNNIndex(
            candidates=cand, index_system=index_system, resolution=res,
            cells=cells, rows=rows, shift=shift, cell_width=cell_width,
            host=HostCandidates(None, None, None, xy=xs), chip_index=None,
            fingerprint=_fingerprint(cells, rows, shift, res, index_system),
            dtype=dtype, lattice=keys is not None,
            points=_point_blocks(cells, rows, xs, dtype, BLOCK_WIDTH),
        )

    table = tessellate(cand, index_system, res, keep_core_geoms=False)
    tcells = np.asarray(table.cell_id, dtype=np.int64)
    cell_width = index_system.ring_width(res, tcells)
    keys = _lattice_of(index_system, tcells)
    tcells = tcells if keys is None else keys
    order = np.argsort(tcells, kind="stable")
    cells = tcells[order]
    rows = table.geom_id[order].astype(np.int64)
    shift = _candidate_shift(cand)

    chip_index = None
    if bool(np.isin(
        cand.geom_type,
        (int(GeometryType.POLYGON), int(GeometryType.MULTIPOLYGON)),
    ).all()):
        from ..sql.join import build_chip_index

        chip_index = build_chip_index(table)

    return KNNIndex(
        candidates=cand,
        index_system=index_system,
        resolution=res,
        cells=cells,
        rows=rows,
        shift=np.asarray(shift, dtype=np.float64),
        cell_width=cell_width,
        host=_host_twin(cand, shift),
        chip_index=chip_index,
        fingerprint=_fingerprint(cells, rows, shift, res, index_system),
        dtype=dtype, lattice=keys is not None,
    )
