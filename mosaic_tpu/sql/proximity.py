"""Distance join of lines and points: ``dist(a, b) <= r_a + r_b``.

Reference analog: the ship-to-ship-transfer workload
(`notebooks/examples/python/Ship2ShipTransfers/`, notebook 03): vessel
tracks are buffered (``st_buffer``), the buffers tessellated, and the
chips joined on ``(window, cell)`` with ``is_core || st_intersects``.
A polygonised buffer is the reference's stand-in for a distance:
``buffer(A, r_a)`` meets ``buffer(B, r_b)`` exactly where ``dist(A, B)
<= r_a + r_b``. :func:`dwithin_join` answers that question directly,
with no buffer and no clipper:

- **Cover** (`knn.index.reach_cover`): a line's cells within its radius,
  from its vertices' places on the grid's lattice — `polygon_cover`'s
  arithmetic with the ranges widened by the radius. A superset of
  ``tessellate(st_buffer(line))``'s cells. Lines it cannot cover (near
  a face's edge, a grid with no lattice) take that polygon path and are
  counted (``tessellated``).
- **Candidates**: the cover's rows ``(key, cell, piece)`` are sorted once
  (one ``np.sort`` of packed words, which drops a line's repeated cells
  too), ranked densely over their distinct ``(key, cell)`` and joined by
  the overlay's own segment equi-join — `kernels.overlay.rank_spans` and
  `emit_spans`, the programs `overlay_measures` launches, here keyed by a
  composite and, for ONE table joined with itself, with every row's span
  starting after the row (``after_self``: each unordered pair once).
  The emission runs a slice of ``CHUNK_PAIRS`` candidate rows a launch,
  so a stream of any length compiles one bucket. On the device a slot of
  the slice finds its left row with no search: every row's span offset
  is scattered onto the slice's slots as a mark and the marks are summed
  along them (`kernels.overlay._rows_by_marks`; the spans carry
  ``form="marks"``); the numpy twin (``lane="host"``) searches the
  offsets, and is what the device form is tested against.
- **Predicate** (`kernels.proximity`): a candidate row's two PIECES — a
  line cut into runs of ``PIECE_VERTS`` vertices, the source's tracks
  being one piece each — gathered from the resident piece table into the
  frame of the first one's first vertex, their least segment-segment
  distance against ``r_a + r_b``, in float32 on the TPU and float64
  under x64 elsewhere (`sql.overlay.overlay_acc_dtype`'s rule). A row
  within the band of the threshold (:func:`dwithin_band`) is not the
  device's to answer: the f64 host lane re-answers the pair from the
  whole lines.
- **Fold**: a pair of lines that shares N cells has N candidate rows;
  the rows' answers come back as one byte a row, the host decodes the
  hits' rows from the spans it holds too and folds them to distinct
  ``(left, right)`` pairs (``rows_per_pair`` counts the repeats).

Everything a call launches sits on a ladder — sorted rows
(`sql.overlay.TABLE_LADDER`), ranks (`RANK_LADDER`), pieces
(``PIECE_LADDER``), candidate rows a launch (`PAIR_LADDER`'s rungs up to
``CHUNK_PAIRS``) — so a second table of another size compiles nothing
that :func:`warmup_dwithin` has met. A ``pair_cap`` that cuts the
candidate stream yields the structural ``OVERFLOW`` row, never a silent
truncation; past the retry budget the call degrades to the numpy twin of
the same pipeline (``lane="host"``), flagged.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from ..core.index.base import IndexSystem
from ..core.types import GeometryType, PackedGeometry
from ..dispatch import core as _dispatch
from ..kernels import overlay as _k
from ..kernels import proximity as _kp
from ..knn.index import _geom_vertex_runs, expand_ranges, reach_cover
from ..obs import trace as _trace
from ..runtime import platform as _platform
from ..runtime import telemetry as _telemetry
from .join import EDGE_BAND_K, OVERFLOW
from .overlay import (
    PAIR_LADDER,
    RANK_LADDER,
    TABLE_LADDER,
    _count_program,
    _emit_program,
    _register_stages,
    overlay_acc_dtype,
)

__all__ = [
    "CHUNK_PAIRS",
    "PIECE_VERTS",
    "DWithinPairs",
    "ProximityPrep",
    "dwithin_band",
    "dwithin_join",
    "host_line_distances",
    "prepare_dwithin",
    "warmup_dwithin",
]

#: vertices a piece holds (so 15 segments): the source's tracks are 5-15
#: pings of a 15-minute window, one piece each; a longer line is cut into
#: pieces that share their end vertices. The pad enters every program's
#: signature, so it is one number and not a ladder.
PIECE_VERTS = 16
#: candidate rows a launch: a rung of `PAIR_LADDER`. A call's stream is
#: emitted and answered a slice of this many rows at a time (the
#: predicate's working set is 16 x this many values a temporary)
CHUNK_PAIRS = 1 << 20
#: piece-table ladder (rows of the resident line table)
PIECE_LADDER = _dispatch.BucketLadder(min_bucket=64, max_bucket=1 << 21)
#: rounding steps of the frame's extent the band allows a distance:
#: `EDGE_BAND_K` for each of the four places a rounding enters it — the
#: stored coordinates, the two origins' difference, the projection onto
#: the segment and the root (a chip's divide and root are a few steps off
#: the correctly rounded ones)
BAND_K = 4.0 * EDGE_BAND_K
#: what the polygon path's buffer is made wider by, so that it holds the
#: round buffer: its inscribed 32-gon arcs (``quad_segs`` 8) fall short by
#: 1 - cos(pi / 32) = 0.5%, and the native union of edge capsules has been
#: seen 3.5% of r short on a moored vessel's jumble of 20 m segments
#: (`tests/test_proximity_reference.py`)
_BUFFER_GROW = 1.05


def dwithin_band(acc_name: str, platform: str | None = None) -> float:
    """The recheck band as a share of a candidate row's frame extent:
    ``BAND_K`` rounding steps of the arithmetic the device REALLY
    computes ``acc_name`` in (`runtime.platform.arithmetic_eps`). A row
    whose distance lies within ``band * extent`` of ``r_a + r_b`` is the
    f64 host lane's to answer."""
    return float(BAND_K * _platform.arithmetic_eps(acc_name, platform))


# ---------------------------------------------------------------- the prep


@dataclass(frozen=True)
class _Side:
    """One table of a prep: its lines, their pieces and the sorted cover
    rows. ``dev`` holds what the device programs read."""

    col: PackedGeometry
    lo: np.ndarray          # (G,) first vertex of each line in col.xy
    hi: np.ndarray          # (G,) one past the last
    radius: np.ndarray      # (G,) f64
    owner: np.ndarray       # (T,) piece -> line
    start: np.ndarray       # (T,) piece -> first vertex in col.xy
    length: np.ndarray      # (T,) piece -> vertices (1..PIECE_VERTS)
    table: np.ndarray       # (Tb, 2V + 5) f64 piece rows (see `_piece_table`)
    n: int                  # sorted cover rows
    bucket: int             # Lb
    rank: np.ndarray        # (Lb,) i32 dense (key, cell) rank, pad = ranks + 1
    row_piece: np.ndarray   # (Lb,) i32 sorted row -> piece
    tessellated: int
    dev: dict = field(default_factory=dict)


@dataclass(frozen=True)
class ProximityPrep:
    """Amortised prep of a distance join: the sorted, ranked cover rows
    and the resident piece tables of both sides (one side, twice, for a
    self-join), the run offsets the spans are read from, and the static
    pieces of every program's signature."""

    left: _Side
    right: _Side
    self_join: bool
    roff: np.ndarray        # (Tk,) i32 run offsets of the right rows' ranks
    ranks: int
    acc_name: str
    band: float
    index_system: IndexSystem
    resolution: int
    dev_roff: object = None


def _line_runs(col: PackedGeometry):
    """``(lo, hi)`` vertex runs of a column of LINESTRING and POINT rows."""
    gt = np.asarray(col.geom_type)
    ok = np.isin(gt, (int(GeometryType.POINT), int(GeometryType.LINESTRING)))
    if not ok.all() or np.any(np.diff(col.geom_offsets) > 1) \
            or np.any(np.diff(col.part_offsets) > 1):
        raise ValueError(
            "dwithin_join takes LINESTRING and POINT rows (one part each); "
            f"row {int(np.flatnonzero(~ok)[0]) if (~ok).any() else '?'} is not"
        )
    return _geom_vertex_runs(col)


def _pieces(lo, hi):
    """Lines cut into pieces of at most `PIECE_VERTS` vertices that share
    their end vertices: ``(owner, start, length)``. A line with no vertex
    has no piece (it is within reach of nothing)."""
    nv = hi - lo
    step = PIECE_VERTS - 1
    count = np.where(nv > 1, -(-(nv - 1) // step), (nv > 0).astype(np.int64))
    owner = np.repeat(np.arange(nv.shape[0]), count)
    start = lo[owner] + expand_ranges(np.zeros_like(count), count) * step
    return owner, start, np.minimum(PIECE_VERTS, hi[owner] - start)


def _piece_table(xy, start, length, radius, shift, bucket: int):
    """(bucket, 2V + 5) f64: a row a piece — its ``V`` x then ``V`` y
    coordinates relative to its own first vertex (the pad repeats the
    last), that vertex relative to ``shift`` on each axis as a word and a
    zero (`_to_acc` splits it into a high and a low word of the
    accelerated dtype), the radius. Pad rows are zero."""
    V = PIECE_VERTS
    out = np.zeros((bucket, 2 * V + 5), np.float64)
    t = start.shape[0]
    if t:
        idx = start[:, None] + np.minimum(np.arange(V)[None, :], length[:, None] - 1)
        origin = xy[start]
        local = xy[idx] - origin[:, None, :]
        out[:t, :V], out[:t, V : 2 * V] = local[..., 0], local[..., 1]
        out[:t, 2 * V], out[:t, 2 * V + 2] = (origin - shift).T
        out[:t, 2 * V + 4] = radius
    return out


def _to_acc(table: np.ndarray, acc: np.dtype) -> np.ndarray:
    """The piece table in the accelerated dtype, each origin word split
    into a high word and the low word the rounding left."""
    V = PIECE_VERTS
    out = table.astype(acc)
    for c in (2 * V, 2 * V + 2):
        out[:, c + 1] = (table[:, c] - out[:, c].astype(np.float64)).astype(acc)
    return out


def _run_heads(column: np.ndarray) -> np.ndarray:
    """Mask of a sorted column's rows that start a run of equal values."""
    if not column.size:
        return np.zeros(0, bool)
    return np.concatenate([[True], column[1:] != column[:-1]])


class _NoLattice(Exception):
    """A polygon-path cell has no place on the lattice."""


def _polygon_cover(col, lines, radius, index_system, resolution):
    """``(line, cell)`` rows of the polygon path: ``tessellate(st_buffer(
    line, r))`` of the lines the lattice cover refused, a radius at a
    time (`st_buffer` takes one a column), the buffer made wider by what
    its polygonised arcs fall short of the round one."""
    from ..core.tessellate import tessellate
    from ..functions.geometry import st_buffer

    own, cells = [], []
    for r in np.unique(radius[lines]):
        rows = lines[radius[lines] == r]
        chips = tessellate(
            st_buffer(col.take(rows), float(r) * _BUFFER_GROW), index_system,
            resolution, keep_core_geoms=False,
        )
        own.append(rows[np.asarray(chips.geom_id, np.int64)])
        cells.append(np.asarray(chips.cell_id, np.int64))
    if not own:
        z = np.zeros(0, np.int64)
        return z, z
    return np.concatenate(own), np.concatenate(cells)


def _cover_side(col, lo, hi, radius, index_system, resolution, lattice: bool):
    """The cover of one table as scanlines ``(piece, face, a, b, n)`` —
    on a lattice grid the positions ``(face, a, b) .. (face, a, b + n -
    1)``; else ``a`` is the raw cell id, one a scanline — with the
    pieces and the count of lines that took the polygon path."""
    owner, start, length = _pieces(lo, hi)
    xy = col.xy
    G = lo.shape[0]
    todo = np.zeros(G, bool)
    z = np.zeros(0, np.int64)
    piece, face, a, b, n = z, z, z, z, z
    if lattice:
        ok, pface, piece, a, b, n = reach_cover(
            index_system, resolution, xy, start, start + length, radius[owner]
        )
        # a line with a refused piece takes the polygon path whole
        todo[owner[~ok]] = True
        keep = ~todo[owner[piece]]
        piece, a, b, n = piece[keep], a[keep], b[keep], n[keep]
        face = pface[piece]
    else:
        todo[hi > lo] = True
    lines = np.flatnonzero(todo)
    if lines.size:
        t_line, t_cell = _polygon_cover(col, lines, radius, index_system, resolution)
        # every piece of the line gets the line's cells
        first = np.searchsorted(owner, t_line, side="left")
        cnt = np.searchsorted(owner, t_line, side="right") - first
        t_piece = expand_ranges(first, cnt)
        t_cell = np.repeat(t_cell, cnt)
        if lattice:
            keys = index_system.lattice_keys(t_cell)[0]
            if (keys < 0).any():
                raise _NoLattice
            t_face, t_a, t_b = index_system.lattice_unpack(keys)
        else:
            t_face, t_a, t_b = np.zeros_like(t_cell), t_cell, np.zeros_like(t_cell)
        piece = np.concatenate([piece, t_piece])
        face = np.concatenate([face, t_face])
        a, b = np.concatenate([a, t_a]), np.concatenate([b, t_b])
        n = np.concatenate([n, np.ones_like(t_piece)])
    return (owner, start, length), (piece, face, a, b, n), int(lines.size)


def _sorted_rows(scans, krank, owner, dims, pbits: int):
    """One table's cover rows, sorted by ``(key, cell, piece)`` and with a
    piece's repeated cells dropped, as ``(code, piece)``: one ``np.sort``
    of words that pack the row's composite code above its piece."""
    piece, face, a, b, n = scans
    (f0, nf), (a0, na), (b0, nb) = dims
    base = (
        ((krank[owner[piece]] * nf + (face - f0)) * na + (a - a0)) * nb + (b - b0)
    )
    word = np.repeat((base << pbits) | piece, n)
    word += expand_ranges(np.zeros_like(n), n) << pbits
    word.sort()
    word = word[_run_heads(word)]
    return word >> pbits, word & ((1 << pbits) - 1)


def _span(parts, widths=None):
    """``(min, count)`` of the integers in ``parts`` (with ``widths``:
    each reaches ``widths - 1`` further)."""
    lo = min((int(p.min()) for p in parts if p.size), default=0)
    hi = max(
        (int((p if w is None else p + w - 1).max())
         for p, w in zip(parts, widths or [None] * len(parts)) if p.size),
        default=0,
    )
    return lo, hi - lo + 1


def prepare_dwithin(
    left: PackedGeometry,
    right: PackedGeometry | None = None,
    *,
    radius,
    index_system: IndexSystem,
    resolution: int,
    key=None,
) -> ProximityPrep:
    """Build the prep of a distance join: cover both tables, sort and
    rank the cover's rows, lay the lines out as piece tables and put what
    the programs read on the device. ``right`` None: ``left`` joined with
    itself. ``radius`` / ``key``: a scalar or one value a row (``key``
    None: no equality); with two tables a pair ``(left's, right's)`` or
    one scalar for both."""
    resolution = index_system.resolution_arg(resolution)
    self_join = right is None
    cols = [left] if self_join else [left, right]

    def per_side(v, dtype):
        if v is None:
            return [None] * len(cols)
        if isinstance(v, tuple) and not self_join:
            vs = v
        else:
            vs = (v,) * len(cols)
        return [
            np.ascontiguousarray(np.broadcast_to(np.asarray(x, dtype), (len(c),)))
            for x, c in zip(vs, cols)
        ]

    radii = per_side(radius, np.float64)
    if radii[0] is None or any((r < 0).any() or not np.isfinite(r).all() for r in radii):
        raise ValueError("dwithin_join needs a finite radius >= 0 a row")
    keys = per_side(key, np.int64)
    runs = [_line_runs(c) for c in cols]
    lattice = index_system.lattice_keys(np.zeros(0, np.int64)) is not None
    while True:
        try:
            covers = [
                _cover_side(c, lo, hi, r, index_system, resolution, lattice)
                for c, (lo, hi), r in zip(cols, runs, radii)
            ]
            break
        except _NoLattice:  # a pentagon's cell: every line by the polygon path
            lattice = False
    scans = [c[1] for c in covers]
    # the composite code's frame: key ranks, and the cells' box (or, for a
    # grid with no lattice or a box too wide to pack, the distinct cells'
    # ranks)
    if keys[0] is None:
        kranks, nk = [np.zeros(len(c), np.int64) for c in cols], 1
    else:
        ukeys = np.unique(np.concatenate(keys))
        kranks, nk = [np.searchsorted(ukeys, k) for k in keys], max(ukeys.size, 1)
    pbits = max(max(c[0][0].shape[0] for c in covers) - 1, 1).bit_length()

    def dims_of(scans):
        return (
            _span([s[1] for s in scans]), _span([s[2] for s in scans]),
            _span([s[3] for s in scans], [s[4] for s in scans]),
        )

    dims = dims_of(scans)
    if not lattice or (nk * dims[0][1] * dims[1][1] * dims[2][1]).bit_length() + pbits > 62:
        # cells by rank: a scanline a position, each the rank of its cell
        # (on a lattice: of its packed key) among the distinct ones
        flat = []
        for piece, face, a, b, n in scans:
            cell = np.repeat(a, n)
            if lattice:
                cell = index_system.lattice_pack(
                    np.repeat(face, n), cell,
                    np.repeat(b, n) + expand_ranges(np.zeros_like(n), n),
                )
            flat.append((np.repeat(piece, n), cell))
        ucell = np.unique(np.concatenate([c for _, c in flat]))
        scans = [
            (piece, np.zeros_like(piece), np.searchsorted(ucell, cell),
             np.zeros_like(piece), np.ones_like(piece))
            for piece, cell in flat
        ]
        dims = dims_of(scans)
    sides_rows = [
        _sorted_rows(s, kr, c[0][0], dims, pbits)
        for s, kr, c in zip(scans, kranks, covers)
    ]
    # dense ranks over the distinct (key, cell) codes of both sides: a
    # sorted side's distinct codes are where its column changes
    firsts = [_run_heads(code) for code, _ in sides_rows]
    ucodes = [code[first] for (code, _), first in zip(sides_rows, firsts)]
    ucode = ucodes[0] if self_join else np.union1d(*ucodes)
    ranks = int(ucode.size)
    roff_len = RANK_LADDER.bucket_for(ranks + 3)
    acc = overlay_acc_dtype()
    acc_dt = np.dtype(acc)
    origins = [c.xy[cv[0][1]] for c, cv in zip(cols, covers) if cv[0][1].size]
    if origins:
        every = np.concatenate(origins)
        shift = 0.5 * (every.min(axis=0) + every.max(axis=0))
    else:
        shift = np.zeros(2)

    sides = []
    for col, (lo, hi), r, cv, (code, piece), first, mine in zip(
            cols, runs, radii, covers, sides_rows, firsts, ucodes):
        owner, start, length = cv[0]
        n = int(code.shape[0])
        Lb = TABLE_LADDER.bucket_for(max(n, 1))
        rank = np.full(Lb, ranks + 1, np.int32)
        # (the side's own distinct codes stand in the pair's at `place`)
        place = np.arange(ranks) if self_join else np.searchsorted(ucode, mine)
        rank[:n] = place[np.cumsum(first) - 1]
        row_piece = np.zeros(Lb, np.int32)
        row_piece[:n] = piece
        table = _piece_table(
            col.xy, start, length, r[owner], shift,
            PIECE_LADDER.bucket_for(max(owner.shape[0], 1)),
        )
        sides.append(_Side(
            col=col, lo=lo, hi=hi, radius=r, owner=owner, start=start,
            length=length, table=table, n=n, bucket=Lb, rank=rank,
            row_piece=row_piece, tessellated=cv[2],
            dev={
                "rank": jax.device_put(rank),
                "row_piece": jax.device_put(row_piece),
                "table": jax.device_put(_to_acc(table, acc_dt)),
            },
        ))
    R = sides[-1]
    # the right rows' run offsets, from where its runs start: a rank the
    # right side lacks reads the start of the next it has
    roff = np.full(roff_len, R.n, np.int32)
    roff[place] = np.flatnonzero(first)
    roff = np.minimum.accumulate(roff[::-1])[::-1].copy()
    return ProximityPrep(
        left=sides[0], right=R, self_join=self_join, roff=roff, ranks=ranks,
        acc_name=acc, band=dwithin_band(acc), index_system=index_system,
        resolution=resolution, dev_roff=jax.device_put(roff),
    )


# ------------------------------------------------------------ the programs


@_dispatch.bounded_cache("proximity_gather_programs", 8)
def _gather_program():
    def proximity_gather(li, ri, left_piece, right_piece, left_table, right_table):
        # field-major out: the predicate reads a field of every row at once
        with jax.named_scope("proximity.gather"):
            return left_table[left_piece[li]].T, right_table[right_piece[ri]].T

    return jax.jit(proximity_gather)


@_dispatch.bounded_cache("proximity_segpair_programs", 8)
def _segpair_program():
    """The predicate over gathered piece rows: its shapes are the
    candidate bucket's alone, so the one costly compile does not multiply
    by the tables' buckets."""
    def proximity_segpairs(ta, tb, valid, band):
        ax, ay, bx, by, thr, extent = _kp.pair_frame(ta, tb, PIECE_VERTS, xp=jnp)
        d2, crosses = _kp.piece_distance(ax, ay, bx, by, xp=jnp)
        return _kp.classify(d2, crosses, thr, band * extent, valid, xp=jnp)

    return jax.jit(proximity_segpairs)


def _chunk_plan(total: int, pair_cap: int | None):
    """``(Pb, emit_limit, overflow, starts)``: the candidate stream cut at
    ``pair_cap`` (the rest is structural OVERFLOW) and emitted a slice of
    one bucket a launch."""
    total = int(total)
    emit_limit = total if pair_cap is None else min(total, int(pair_cap))
    Pb = PAIR_LADDER.bucket_for(max(min(emit_limit, CHUNK_PAIRS), 1))
    return Pb, emit_limit, total - emit_limit, list(range(0, max(emit_limit, 1), Pb))


# ------------------------------------------------------------- the answers


@dataclass(frozen=True)
class DWithinPairs:
    """A distance join's answer: ``pairs`` (P, 2) int64, the distinct
    ``(left_row, right_row)`` with ``dist <= r_left + r_right`` and equal
    key, in order (``left_row < right_row`` for a self-join) — plus, when
    a ``pair_cap`` cut the candidate stream, a trailing ``(OVERFLOW,
    OVERFLOW)`` row: structural truncation, never a silent one.
    ``overflow`` counts the candidate rows cut, ``lane`` says which lane
    answered (``degraded`` True when the device lane failed past its
    retry budget and the numpy twin answered instead), ``metrics`` holds
    the call's counters (the root span's)."""

    pairs: np.ndarray
    overflow: int
    lane: str
    degraded: bool = False
    reason: str = ""
    metrics: dict = field(default_factory=dict)


def host_line_distances(left: _Side, right: _Side, a, b) -> np.ndarray:
    """(P,) f64 least distance of whole lines ``left[a[p]]`` and
    ``right[b[p]]``, each pair in the frame of its left line's first
    vertex: `kernels.proximity.piece_distance` under numpy, the lines
    padded to the longest of the block."""
    a, b = np.asarray(a, np.int64), np.asarray(b, np.int64)
    out = np.empty(a.shape[0], np.float64)
    la, lb = (left.hi - left.lo)[a], (right.hi - right.lo)[b]
    order = np.argsort(np.maximum(la, lb), kind="stable")
    block = 1 << 12
    for s in range(0, order.size, block):
        sel = order[s : s + block]
        W = max(int(np.maximum(la[sel], lb[sel]).max()), 2)
        j = np.arange(W)[None, :]
        va = left.col.xy[left.lo[a[sel]][:, None] + np.minimum(j, la[sel][:, None] - 1)]
        vb = right.col.xy[right.lo[b[sel]][:, None] + np.minimum(j, lb[sel][:, None] - 1)]
        origin = va[:, :1]
        va, vb = va - origin, vb - origin
        d2, crosses = _kp.piece_distance(
            va[..., 0].T, va[..., 1].T, vb[..., 0].T, vb[..., 1].T, xp=np
        )
        out[sel] = np.where(crosses, 0.0, np.sqrt(d2))
    return out


def _host_spans(prep: ProximityPrep):
    """The spans the count program reads, on the host: ``(lo, cnt, off,
    total)``."""
    lo, cnt = _k.rank_spans(
        prep.left.rank, prep.roff, prep.left.n, xp=np,
        after_self=prep.self_join,
    )
    lo, cnt = lo.astype(np.int64), cnt.astype(np.int64)
    return lo, cnt, np.cumsum(cnt) - cnt, int(cnt.sum())


def _stream_lines(prep: ProximityPrep, spans):
    """The candidate stream as lines: ``(left line, right line)`` of row
    ``k``, the ``k``-th pair `kernels.overlay.emit_spans` resolves —
    written as repeats over the whole stream (most rows of a dense fleet
    are hits: a search a hit would cost more than the stream's repeats)."""
    lo, cnt, off, total = spans
    L, R = prep.left, prep.right
    rows = np.repeat(np.arange(cnt.shape[0]), cnt)
    ri = np.arange(total) + np.repeat(lo - off, cnt)
    if not total:
        return rows, ri
    return (L.owner[L.row_piece[: L.n]][rows],
            R.owner[R.row_piece[: R.n]][ri])


def _pair_words(prep: ProximityPrep, a, b) -> np.ndarray:
    """Line pairs as sorted distinct words ``a * width + b``. (A
    self-join's rows come ``a <= b``: its rows are sorted by piece inside
    a run and a piece's number grows with its line's; a line against
    itself — two pieces of one long line — is dropped.)"""
    if prep.self_join:
        keep = a != b
        a, b = a[keep], b[keep]
    word = a * np.int64(len(prep.right.col) + 1) + b
    word.sort()
    return word[_run_heads(word)]


def _fold(prep: ProximityPrep, codes: np.ndarray, lines, call):
    """The rows' answers -> the distinct pairs: the hit and band rows'
    lines (``lines``: `_stream_lines`), folded to line pairs, the band's
    re-answered in f64 on the host from the whole lines."""
    left, right = lines
    width = np.int64(len(prep.right.col) + 1)
    with _trace.span("proximity.glue", rows=int(codes.shape[0])):
        hit_rows = np.flatnonzero(codes == _kp.HIT)
        hits = _pair_words(prep, left[hit_rows], right[hit_rows])
        band_rows = np.flatnonzero(codes == _kp.BAND)
        band = _pair_words(prep, left[band_rows], right[band_rows])
        # a pair some other row has already answered needs no recheck
        band = band[~np.isin(band, hits, assume_unique=True)]
    answered = int(hits.size + band.size)
    with _trace.span("proximity.host_band", pairs=int(band.size)) as hspan:
        if band.size:
            a, b = band // width, band % width
            d = host_line_distances(prep.left, prep.right, a, b)
            inside = d <= prep.left.radius[a] + prep.right.radius[b]
            hspan.set(inside=int(inside.sum()))
            hits = np.sort(np.concatenate([hits, band[inside]]))
    call.set(
        pairs=answered, hits=int(hits.size), band_pairs=int(band.size),
        rows_per_pair=round(hit_rows.size / max(answered - band.size, 1), 4),
    )
    return np.stack([hits // width, hits % width], axis=-1).astype(np.int64)


def _host_codes(prep: ProximityPrep, spans, emit_limit: int):
    """The numpy twin of the device lane's launches: every candidate
    row's answer, in float64, a block at a time."""
    lo, cnt = spans[:2]
    L, R = prep.left, prep.right
    V = PIECE_VERTS
    band = dwithin_band("float64", "cpu")
    codes = np.zeros(emit_limit, np.int8)
    block = 1 << 16
    for s in range(0, emit_limit, block):
        li, ri, valid = _k.emit_spans(lo, cnt, emit_limit, block, xp=np, start=s)
        li, ri = li[valid], ri[valid]
        ta, tb = L.table[L.row_piece[li]].T, R.table[R.row_piece[ri]].T
        ax, ay, bx, by, thr, extent = _kp.pair_frame(ta, tb, V, xp=np)
        d2, crosses = _kp.piece_distance(ax, ay, bx, by, xp=np)
        codes[s : s + li.size] = _kp.classify(
            d2, crosses, thr, band * extent, np.ones(li.size, bool), xp=np
        )
    return codes


def _package(pairs, overflow, lane, call, degraded=False, reason=""):
    if overflow > 0:
        pairs = np.concatenate(
            [pairs, np.asarray([[OVERFLOW, OVERFLOW]], np.int64)]
        )
    return DWithinPairs(
        pairs=pairs, overflow=int(overflow), lane=lane, degraded=degraded,
        reason=reason, metrics=dict(call.attrs),
    )


def dwithin_join(
    left: PackedGeometry,
    right: PackedGeometry | None = None,
    *,
    radius=None,
    index_system: IndexSystem | None = None,
    resolution: int | None = None,
    key=None,
    prep: ProximityPrep | None = None,
    pair_cap: int | None = None,
    lane: str = "device",
) -> DWithinPairs:
    """The distinct ``(left_row, right_row)`` pairs with ``dist(left,
    right) <= radius_left + radius_right`` and equal ``key``; with
    ``right`` None, ``left`` joined with itself, ``left_row <
    right_row``.

    LINESTRING and POINT rows, float64; the distance is planar, in the
    column's own units (as `st_buffer` and `st_distance` read them).
    ``radius``: a scalar or one value a row (two tables: a pair of such,
    or one scalar); ``key``: None, or one int64 a row (two tables: a
    pair) — only rows of equal key are compared (the source's 15-minute
    window). ``prep``: a :func:`prepare_dwithin` result to reuse (then
    the call makes no cover). ``pair_cap`` bounds the candidate rows
    answered; the excess is reported as an OVERFLOW row.

    One call records, under its root span ``proximity.call`` (counters
    ``tracks``, ``segments``, ``cover_rows``, ``tessellated``,
    ``raw_candidates``, ``pairs``, ``hits``, ``rows_per_pair``,
    ``band_pairs``, ``bucket``, ``vpad``, ``acc``): ``proximity.cover``
    (the prep, where the call makes it), ``proximity.count`` (the count
    program's launch and the blocking read of the candidate total),
    ``proximity.emit`` and ``proximity.launch`` (the enqueue of each
    slice's emission and of its gather + predicate), ``proximity.pull``
    (the blocking pull of the rows' answers), ``proximity.glue`` (hit
    rows -> distinct pairs) and ``proximity.host_band``.

    ``lane="host"`` routes to the numpy twin of the same pipeline in
    float64; the device lane degrades there (result flagged) when the
    device path fails past its retry budget.
    """
    if lane not in ("device", "host"):
        raise ValueError(f"unknown dwithin lane {lane!r}")
    with _trace.span("proximity.call", vpad=PIECE_VERTS) as call:
        if prep is None:
            if index_system is None or resolution is None:
                raise ValueError("dwithin_join needs index_system and resolution")
            with _trace.span("proximity.cover") as cspan:
                prep = prepare_dwithin(
                    left, right, radius=radius, index_system=index_system,
                    resolution=resolution, key=key,
                )
                cspan.set(rows=prep.left.n, ranks=prep.ranks)
        L, R = prep.left, prep.right
        sides = (L,) if prep.self_join else (L, R)
        call.set(
            tracks=sum(len(s.col) for s in sides),
            segments=int(sum((s.length - 1).sum() for s in sides)),
            cover_rows=sum(s.n for s in sides),
            tessellated=sum(s.tessellated for s in sides),
            acc=prep.acc_name if lane == "device" else "float64",
        )
        spans = _host_spans(prep)

        def host_lane(**flags):
            Pb, emit_limit, overflow, _ = _chunk_plan(spans[3], pair_cap)
            call.set(raw_candidates=spans[3], bucket=Pb, overflow=overflow)
            codes = _host_codes(prep, spans, emit_limit)
            pairs = _fold(prep, codes, _stream_lines(prep, spans), call)
            return _package(pairs, overflow, "host", call, **flags)

        if lane == "host":
            return host_lane()
        try:
            pull, overflow = _device_launch(prep, pair_cap, call)
            lines = _stream_lines(prep, spans)  # while the chip works
            pairs = _fold(prep, pull(), lines, call)
            return _package(pairs, overflow, "device", call)
        except Exception as e:  # lint: broad-except-ok (degradation seam: past the retry budget the numpy twin answers instead; the result is flagged, parity with every other DispatchCore frontend)
            _telemetry.record(
                "degraded", label="proximity.segpairs", error=repr(e)[:200]
            )
            return host_lane(
                degraded=True, reason=f"proximity.segpairs: {e!r}"[:300]
            )


def _device_launch(prep: ProximityPrep, pair_cap, call):
    """The device lane: count, then a slice of the candidate stream a
    launch — emission, gather, predicate — all enqueued; returns
    ``(pull, overflow)``, ``pull()`` the blocking pull of the rows'
    answers, one byte a row."""
    L, R = prep.left, prep.right
    acc = np.dtype(prep.acc_name)

    def device_candidates():
        with _trace.span("proximity.count", ranks=prep.ranks):
            count = _count_program(prep.self_join)
            args = (L.dev["rank"], prep.dev_roff, L.n)
            _register_stages(count, args, L.bucket)
            dtotal, dlo, dcnt = count(*args)
            return int(dtotal), dlo, dcnt

    total, dlo, dcnt = _dispatch.guarded_call(
        "proximity.device_candidates", device_candidates
    )
    Pb, emit_limit, overflow, starts = _chunk_plan(total, pair_cap)
    call.set(raw_candidates=total, bucket=Pb, overflow=overflow,
             launches=len(starts))
    emit = _emit_program(Pb)
    gather = _gather_program()
    segpairs = _segpair_program()
    band = acc.type(prep.band)

    def launches():
        out = []
        for start in starts:
            with _trace.span("proximity.emit", bucket=Pb, form="marks"):
                args = (dlo, dcnt, emit_limit, np.int32(start))
                _register_stages(emit, args, Pb)
                dli, dri, dvalid = emit(*args)
            with _trace.span("proximity.launch", bucket=Pb):
                args = (dli, dri, L.dev["row_piece"], R.dev["row_piece"],
                        L.dev["table"], R.dev["table"])
                _register_stages(gather, args, Pb)
                ta, tb = gather(*args)
                args = (ta, tb, dvalid, band)
                _register_stages(segpairs, args, Pb)
                out.append(segpairs(*args))
        return out

    out = _dispatch.guarded_call("proximity.segpairs", launches)

    def pull():
        with _trace.span("proximity.pull", launches=len(out)):
            codes = np.concatenate([np.asarray(c) for c in out])
        return codes[:emit_limit]

    return (lambda: _dispatch.guarded_call("proximity.segpairs", pull)), overflow


def warmup_dwithin(
    left: PackedGeometry,
    right: PackedGeometry | None = None,
    *,
    radius=None,
    index_system: IndexSystem | None = None,
    resolution: int | None = None,
    key=None,
    prep: ProximityPrep | None = None,
    pair_cap: int | None = None,
) -> ProximityPrep:
    """Execute the device pipeline once on a sample table, then every
    program again on zero tables a rung above and below the sample's
    buckets (rows, ranks, pieces) and at every candidate bucket up to
    ``CHUNK_PAIRS``: a later table of another size then launches nothing
    that has not been compiled. Returns the sample's prep."""
    if prep is None:
        prep = prepare_dwithin(
            left, right, radius=radius, index_system=index_system,
            resolution=resolution, key=key,
        )
    dwithin_join(left, right, prep=prep, pair_cap=pair_cap)
    acc = np.dtype(prep.acc_name)

    def rung(ladder, b, d):
        rungs = ladder.buckets
        return rungs[min(max(rungs.index(b) + d, 0), len(rungs) - 1)]

    def around(ladder, b):
        return sorted({rung(ladder, b, d) for d in (-1, 0, 1)})

    width = 2 * PIECE_VERTS + 5
    count = _count_program(prep.self_join)
    gather = _gather_program()
    segpairs = _segpair_program()
    own = _chunk_plan(_host_spans(prep)[3], pair_cap)[0]
    chunk = sorted(set(around(PAIR_LADDER, own))
                   | {PAIR_LADDER.bucket_for(CHUNK_PAIRS)})
    L, R = prep.left, prep.right
    # the two sides' buckets move together: tables of one source grow and
    # shrink alike (a self-join's are one table)
    for d in (-1, 0, 1):
        lrank = jnp.zeros(rung(TABLE_LADDER, L.bucket, d), jnp.int32)
        rrank = jnp.zeros(rung(TABLE_LADDER, R.bucket, d), jnp.int32)
        for Tk in around(RANK_LADDER, prep.roff.shape[0]):
            _t, lo, cnt = count(lrank, jnp.zeros(Tk, jnp.int32), 0)
        for Pb in chunk:
            li, ri, _v = _emit_program(Pb)(lo, cnt, 0, np.int32(0))
            for dt in (-1, 0, 1):
                gather(
                    li, ri, lrank, rrank,
                    jnp.zeros((rung(PIECE_LADDER, L.table.shape[0], dt), width), acc),
                    jnp.zeros((rung(PIECE_LADDER, R.table.shape[0], dt), width), acc),
                )
    for Pb in chunk:
        rows = jnp.zeros((width, Pb), acc)
        jax.block_until_ready(
            segpairs(rows, rows, jnp.zeros(Pb, bool), acc.type(prep.band))
        )
    return prep
