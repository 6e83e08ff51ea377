"""Polygon-polygon overlay join: device candidates + fused overlap measures.

Reference analog: the BNG overlay workload
(`notebooks/examples/python/BritishNationalGrid.py`) — both polygon tables
are tessellated into grid chips, the equi-join on cell id produces candidate
pairs, and the exact work runs only on pairs whose chips are both border
chips (a core chip covers its whole cell, so any other geometry touching
that cell intersects it by construction — the chip-table shortcut the
reference's `is_core || st_intersects` predicate expresses).

Two lanes share one contract:

- **Device lane** (:func:`overlay_measures`): both chip tables are
  exploded into ring rows, sorted by int64 cell id and put on the device
  once (:func:`prepare_overlay`, amortized like the chip index build);
  candidate generation runs on device as a sorted segment equi-join
  whose spans are READ, once a call, through the prep's dense cell
  ranks and the right column's run offsets
  (`kernels.overlay.rank_spans`, then `emit_spans` against a static
  pair bucket: a slot finds its left row by a scatter of the rows' span
  offsets and a running sum, not by a search), and the overlap measures
  — per-row intersection areas, folded per geometry pair, with an
  `expr/` pair tree evaluated over the folded tables — run as ONE fused
  program per ``(tree-hash, buckets, index, mesh)`` signature through
  `DispatchCore` (compile cache, warmup tripwire, watchdog/retry,
  ``mesh=`` sharding, graceful degradation).
- **Host lane** (`expr.host_oracle.host_overlay_measures`): the numpy
  twin of the same kernels (``xp=np``) — off the TPU, under x64, the
  pure-f64 oracle the device lane matches bit for bit, and the
  degradation target when the device path fails past its retry budget.

**The frame.** Both sides of a candidate row lie in ONE cell, so every
ring is stored relative to its OWN cell's corner (subtracted in f64 on
the host, once: :func:`_pack_rings`). A coordinate is then at most a
cell's extent, wherever on the grid the data stands, and the
accelerated dtype keeps its whole mantissa for the cell: float32 on the
TPU, float64 under x64 elsewhere (:func:`overlay_acc_dtype`).

**The band.** ``EDGE_BAND_K · eps · cell²`` with ``eps`` the rounding
step of the arithmetic the device REALLY computes in
(`runtime.platform.arithmetic_eps`: the TPU emulates float64 at about 46
bits, 64 times coarser than ``np.finfo`` says) — :func:`overlay_band`.
A clipped area is the device's to answer where it is exactly 0.0 (the
clip left nothing inside the window, or collapsed a touch onto a line)
or at least the band; strictly in between, the f64 host lane re-answers
the WHOLE geometry pair, and reads anything under ITS band as exactly
0.0. So a pair that only touches reports 0.0, and a positive device
area is positive in truth.

**The three clip routes** of a border × border row
(:func:`pair_routes`; `kernels.overlay.clip_rows` / `fan_rows`): the
Sutherland–Hodgman clip against the right ring where it is convex; the
same clip SWAPPED — the right ring against the left — where only the
left one is (the area is symmetric); and where neither is, the signed
fan over the window's triangles from one apex, which is right for any
simple ring and for the zero-width bridges a clipped concave ring
carries. A ring with a hole is two rows of opposite sign, so holed and
many-ring chips fold like any other.

**What the host lane still answers**: pairs with a row in the band
(slivers; touches the clip could not collapse exactly; fans whose
pieces cancel), pairs with a ring over the vertex pad
(``MAX_CHIP_VERTS``), pairs with a clip that spilled its buffer, and
pairs whose ROWS cancel (a subject inside a hole is the shell's row less
the hole's: `expr.host_oracle.cancelled_pairs`) —
by the numpy twins of the same three routes, vectorized, in f64
(`expr.host_oracle.host_row_areas`).

Caps are full-bucket and structural: when the candidate count exceeds
``pair_cap`` (or the top pair bucket), the emission truncates and the
result carries an OVERFLOW(-2) pair row — never a silent wrong answer,
never an escalation.

The boolean `ST_Intersects` join (:func:`intersects_join`) keeps its host
columnar candidate generator, now deduplicated by geometry pair
(core-beats-border precedence) so a pair sharing N cells is emitted once.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..core.index.base import IndexSystem
from ..core.tessellate import ChipTable, _dedupe_boundaries_batch, tessellate
from ..core.types import GeometryType, PackedGeometry
from ..dispatch import core as _dispatch
from ..kernels import overlay as _k
from ..obs import stages as _stages
from ..obs import trace as _trace
from ..runtime import platform as _platform
from ..runtime import telemetry as _telemetry
from ..runtime.errors import DegradedResult
from .join import EDGE_BAND_K, OVERFLOW

__all__ = [
    "MAX_CHIP_VERTS",
    "OverlayMeasures",
    "OverlayPrep",
    "OverlaySide",
    "candidate_pairs",
    "chip_candidate_rows",
    "intersects_join",
    "overlay_acc_dtype",
    "overlay_band",
    "overlay_join",
    "overlay_measures",
    "pair_glue",
    "pair_plan",
    "pair_routes",
    "prepare_overlay",
    "warmup_overlay",
]

#: vertex pad ceiling for device-clippable chips — a border chip whose
#: outer ring needs more vertices is routed to the f64 host lane (the
#: pad enters the program signature, so it must stay small and stable)
MAX_CHIP_VERTS = 32

#: candidate-pair bucket ladder: min 8 so tiny caps exercise OVERFLOW
#: semantics without a dedicated program population, top bucket 4M pairs
PAIR_LADDER = _dispatch.BucketLadder(min_bucket=8, max_bucket=1 << 22)

#: sorted side-table ladder (chip rows) and geometry-pair segment ladder
TABLE_LADDER = _dispatch.BucketLadder(min_bucket=64, max_bucket=1 << 21)
SEG_LADDER = _dispatch.BucketLadder(min_bucket=64, max_bucket=1 << 21)

#: the run-offset table's ladder (`OverlaySide.roff`), on
#: `TABLE_LADDER`'s rungs and two beyond: the table has an entry a
#: distinct cell of the PAIR — two full side tables with no cell in
#: common hold twice the top bucket — and three more (the two pad
#: sentinels' ranks and the end of the last run)
RANK_LADDER = _dispatch.BucketLadder(
    min_bucket=TABLE_LADDER.min_bucket,
    max_bucket=4 * TABLE_LADDER.max_bucket,
)


def pair_plan(total: int, pair_cap: int | None = None):
    """``(Pb, emit_limit, overflow)`` for a candidate count — full-bucket
    cap semantics: emission truncates at ``min(total, pair_cap, top
    bucket)`` and the remainder is booked as structural OVERFLOW."""
    total = int(total)
    cap = PAIR_LADDER.max_bucket if pair_cap is None else int(pair_cap)
    emit_limit = min(total, cap, PAIR_LADDER.max_bucket)
    Pb = PAIR_LADDER.bucket_for(max(emit_limit, 1))
    return Pb, emit_limit, total - emit_limit


# ------------------------------------------------ host candidate columns


def _group_spans(cells_sorted: np.ndarray):
    """(uniq, start, stop) run-length spans of a sorted int64 array."""
    if not cells_sorted.shape[0]:
        return (
            np.zeros(0, np.int64),
            np.zeros(0, np.int64),
            np.zeros(0, np.int64),
        )
    change = np.nonzero(np.diff(cells_sorted))[0] + 1
    start = np.concatenate([[0], change])
    stop = np.concatenate([change, [cells_sorted.shape[0]]])
    return cells_sorted[start], start, stop


def chip_candidate_rows(
    left: ChipTable, right: ChipTable
) -> tuple[np.ndarray, np.ndarray]:
    """Raw chip-row candidate pairs sharing a cell (host columnar set
    algebra). A geometry pair sharing N cells appears N times here — the
    per-shared-cell stream the area fold consumes; use
    :func:`candidate_pairs` for the deduplicated geometry-pair view."""
    lc = np.asarray(left.cell_id)
    rc = np.asarray(right.cell_id)
    lo = np.argsort(lc, kind="stable")
    ro = np.argsort(rc, kind="stable")
    lu, ls, le_ = _group_spans(lc[lo])
    ru, rs, re_ = _group_spans(rc[ro])
    common, li, ri = np.intersect1d(lu, ru, return_indices=True)
    if not common.shape[0]:
        z = np.zeros(0, np.int64)
        return z, z
    # vectorized per-cell cross join: left rows repeat by the right
    # group size, right rows tile within each (cell, left-row) block
    ln = le_[li] - ls[li]  # left group size per common cell
    rn = re_[ri] - rs[ri]  # right group size per common cell
    pair_n = ln * rn
    cell_of = np.repeat(np.arange(common.shape[0]), pair_n)
    off = np.concatenate([[0], np.cumsum(pair_n)])[:-1]
    k = np.arange(int(pair_n.sum())) - off[cell_of]  # rank within cell
    lrows = lo[ls[li][cell_of] + k // rn[cell_of]]
    rrows = ro[rs[ri][cell_of] + k % rn[cell_of]]
    return lrows, rrows


def _dedup_pairs(left: ChipTable, right: ChipTable,
                 lrows: np.ndarray, rrows: np.ndarray):
    """Chip-row candidates → unique geometry pairs with core-beats-border
    precedence: ``sure[p]`` is True when ANY shared cell of pair ``p``
    has a core chip on either side (intersection certain there, no
    predicate needed anywhere for the pair)."""
    lgeom = np.asarray(left.geom_id)[lrows]
    rgeom = np.asarray(right.geom_id)[rrows]
    either = (
        np.asarray(left.is_core)[lrows] | np.asarray(right.is_core)[rrows]
    )
    uniq, pair_id = np.unique(
        np.stack([lgeom, rgeom], axis=-1), axis=0, return_inverse=True
    )
    sure = np.zeros(uniq.shape[0], bool)
    np.logical_or.at(sure, pair_id, either)
    return uniq, pair_id, either, sure


def _candidate_stats(span, sure: np.ndarray) -> None:
    """Record the profileable candidate statistics (deduplicated
    geometry-pair counts) on the span and the telemetry stream."""
    n = int(sure.shape[0])
    sure_fraction = float(sure.sum()) / max(1, n)
    stats = {
        "candidates": n,
        "sure_fraction": round(sure_fraction, 6),
        "border_fraction": round(1.0 - sure_fraction, 6),
    }
    span.set(**stats)
    _telemetry.record("overlay_candidates", **stats)


def candidate_pairs(
    left: ChipTable, right: ChipTable
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Deduplicated geometry-pair candidates sharing at least one cell.

    Returns ``(lgeom, rgeom, sure)`` — one row per (left geometry, right
    geometry) pair regardless of how many cells the pair shares, with
    ``sure`` True where some shared cell has a core chip on either side
    (core beats border: the pair is accepted without a predicate).

    Emits an ``overlay.candidates`` span (and matching
    ``overlay_candidates`` telemetry) with the candidate count, the
    sure-fraction (pairs accepted without a predicate), and the
    border-pair fraction (pairs that will pay exact work) — the
    statistics that make overlay workloads profileable like the point
    frontends.
    """
    with _trace.span(
        "overlay.candidates",
        left_chips=int(np.asarray(left.cell_id).shape[0]),
        right_chips=int(np.asarray(right.cell_id).shape[0]),
    ) as span:
        lrows, rrows = chip_candidate_rows(left, right)
        if not lrows.shape[0]:
            _candidate_stats(span, np.zeros(0, bool))
            z = np.zeros(0, np.int64)
            return z, z, np.zeros(0, bool)
        uniq, _, _, sure = _dedup_pairs(left, right, lrows, rrows)
        _candidate_stats(span, sure)
        return uniq[:, 0], uniq[:, 1], sure


def intersects_join(
    left: PackedGeometry,
    right: PackedGeometry,
    index_system: IndexSystem,
    resolution: int,
    left_chips: ChipTable | None = None,
    right_chips: ChipTable | None = None,
    backend: str = "oracle",
) -> np.ndarray:
    """(P, 2) int64 — distinct (left_row, right_row) pairs that intersect.

    Both sides tessellate at ``resolution`` (pass prebuilt chip tables to
    amortize); pairs sharing a cell where either chip is core are accepted
    without a predicate, the rest run one row-wise st_intersects over the
    border-chip geometry pairs (chips are clipped to their cell, so
    chip-level intersection within a shared cell is exact for the
    geometry-level predicate). Refinement defaults to the f64 ``oracle``
    backend — exact boundary touches (shared edges) are below f32
    resolution; pass ``backend="device"`` to trade that edge case for
    batched device evaluation of huge pair lists.

    Known degenerate case (cell-equality joins generally, including the
    reference's): a pair whose intersection has zero area and lies
    EXACTLY on a cell boundary of an axis-aligned grid (BNG/CUSTOM) can
    tessellate into disjoint cell sets and produce no candidate.
    """
    lt = (
        left_chips
        if left_chips is not None
        else tessellate(left, index_system, resolution)
    )
    rt = (
        right_chips
        if right_chips is not None
        else tessellate(right, index_system, resolution)
    )
    with _trace.span(
        "overlay.candidates",
        left_chips=int(np.asarray(lt.cell_id).shape[0]),
        right_chips=int(np.asarray(rt.cell_id).shape[0]),
    ) as span:
        lrows, rrows = chip_candidate_rows(lt, rt)
        if not lrows.shape[0]:
            _candidate_stats(span, np.zeros(0, bool))
            return np.zeros((0, 2), np.int64)
        uniq_pairs, pair_id, either, psure = _dedup_pairs(
            lt, rt, lrows, rrows
        )
        _candidate_stats(span, psure)
    hit = either.copy()
    # a geometry pair already accepted via a core chip in ANY shared cell
    # needs no predicate for its remaining border-border candidates
    need = np.nonzero(~either & ~psure[pair_id])[0]
    degraded: DegradedResult | None = None
    if need.shape[0]:
        from ..functions.geometry import st_intersects

        # every undecided candidate chip pair is evaluated: a geometry
        # pair intersects iff ANY of its shared-cell chip pairs does
        a = lt.chips.take(lrows[need])
        b = rt.chips.take(rrows[need])

        def predicate():
            return np.asarray(st_intersects(a, b, backend=backend))

        # transient device failures retry with backoff; past the budget a
        # non-oracle backend degrades to the exact f64 host oracle (result
        # flagged), an oracle run raises typed RetryExhausted — the
        # watchdog/retry composition (and the "overlay.predicate" fault
        # plan) lives in dispatch.guarded_call
        res = _dispatch.guarded_call(
            "overlay.predicate",
            predicate,
            fallback=(
                (lambda: np.asarray(st_intersects(a, b, backend="oracle")))
                if backend != "oracle"
                else None
            ),
        )
        if isinstance(res, DegradedResult):
            degraded = res
        hit[need] = np.asarray(res)
    pairs = uniq_pairs[np.unique(pair_id[hit])]
    if degraded is not None:
        return DegradedResult.wrap(
            pairs, reason=degraded.reason, attempts=degraded.attempts,
        )
    return pairs


#: the managed overlay entry point under its workload name (the BNG
#: overlay notebook's join) — same callable, resilience included
overlay_join = intersects_join


# ------------------------------------------------------- device-lane prep


@dataclass(frozen=True)
class OverlaySide:
    """One cell-sorted, bucket-padded side table of an overlay prep.

    A ROW is one ring of a border chip (a chip with a hole has two rows,
    ``sign`` +1 for the shell and -1 for the hole: ``1_chip = Σ sign ·
    1_ring``, so the fold over a geometry pair's rows is the chips'
    intersection whatever the ring count) or one core chip (no ring).
    All per-row arrays are in sorted-by-cell order, padded to ``bucket``
    rows (pad cells carry a per-side sentinel that sorts above every
    real cell and can never equi-join the other side's sentinel).
    ``rows`` maps sorted row → original chip row; ``geom_area`` is
    indexed by ORIGINAL geometry id.

    ``rank`` is the cell column again, as dense ranks: a row's cell's
    place among the distinct cells of BOTH sides of the pair, the pad
    rows ranked as their sentinel sorts (all real cells, then the right
    sentinel, then the left one). On the right side ``roff`` holds the
    rank column's run offsets (`kernels.overlay.run_offsets`, on
    `RANK_LADDER`): the storage form the device's equi-join reads its
    spans from, where ``cells`` (int64, host only) is what the numpy
    twin searches. ``dev`` holds what the device programs read, put on
    the device once by `prepare_overlay`: the left side's ``rank`` or
    the right side's ``roff`` for the candidate programs, and the
    fused measure program's tables in the prep's ``acc`` dtype.
    """

    table: ChipTable
    n: int
    bucket: int
    cells: np.ndarray      # (Lb,) i64 sorted ascending, sentinel tail
    rank: np.ndarray       # (Lb,) i32 dense rank of ``cells`` in the pair
    geom: np.ndarray       # (Lb,) i64 geometry id, -1 pad
    core: np.ndarray       # (Lb,) bool
    ok: np.ndarray         # (Lb,) bool border ring within the vertex pad
    convex: np.ndarray     # (Lb,) bool ring usable as a convex window
    star: np.ndarray       # (Lb,) bool fan from vertex 0 has no negative triangle
    sign: np.ndarray       # (Lb,) f64 +1 shell / core, -1 hole
    verts: np.ndarray      # (Lb, V, 2) f64 CELL-LOCAL CCW open rings
    vlen: np.ndarray       # (Lb,) i32 left-packed vertex counts
    chip_area: np.ndarray  # (Lb,) f64 signed ring area (core: cell area)
    cell_area: np.ndarray  # (Lb,) f64 area of the row's cell
    origin: np.ndarray     # (Lb, 2) f64 the row's cell corner (frame)
    ring_start: np.ndarray  # (Lb,) i64 ring span in ``table.chips.xy``
    ring_len: np.ndarray   # (Lb,) i64
    rows: np.ndarray       # (n,) i64 sorted row -> original chip row
    geom_area: np.ndarray  # (G,) f64 |geometry|
    roff: np.ndarray | None = None  # (T,) i32 run offsets of ``rank`` (right)
    dev: dict | None = None


@dataclass(frozen=True)
class OverlayPrep:
    """Amortized overlay prep: both sorted side tables, the frame
    (every ring is stored relative to its OWN cell's corner; ``scale``
    is the largest cell extent, ``shift`` the data's centre, kept for
    callers that want one), the accelerated dtype by
    :func:`overlay_acc_dtype`, the epsilon band in area units and the
    vertex pad — every static piece of the fused program's signature —
    and ``ranks``, the pair's distinct cells (what the sides' ``rank``
    columns count in)."""

    left: OverlaySide
    right: OverlaySide
    shift: np.ndarray
    scale: float
    index_system: IndexSystem
    resolution: int
    acc_name: str
    band: float
    vpad: int
    ranks: int


def _csr_geom_areas(col: PackedGeometry, shift: np.ndarray) -> np.ndarray:
    """(G,) f64 polygon areas (|shells| − |holes|), vectorized over the
    CSR offsets — the columnar twin of `core.geometry.oracle.area`
    (shell = first ring of its part, open rings, wraparound shoelace).
    Non-polygon rows report 0.0; coordinates are shifted first (one
    ``(2,)`` shift, or ``(G, 2)``: one a geometry) so the products stay
    small."""
    G = len(col)
    out = np.zeros(G, np.float64)
    nv = int(np.asarray(col.xy).shape[0])
    if not G or not nv:
        return out
    ro = np.asarray(col.ring_offsets, np.int64)
    po = np.asarray(col.part_offsets, np.int64)
    go = np.asarray(col.geom_offsets, np.int64)
    R = ro.shape[0] - 1
    ring_of = np.repeat(np.arange(R), np.diff(ro))
    part_of_ring = np.repeat(np.arange(po.shape[0] - 1), np.diff(po))
    geom_of_part = np.repeat(np.arange(G), np.diff(go))
    shift = np.asarray(shift, np.float64)
    if shift.ndim == 2:
        shift = shift[geom_of_part[part_of_ring[ring_of]]]
    xy = np.asarray(col.xy, np.float64)[:, :2] - shift
    x, y = xy[:, 0], xy[:, 1]
    nxt = np.arange(nv) + 1
    nxt = np.where(nxt == ro[1:][ring_of], ro[:-1][ring_of], nxt)
    ring_area = np.zeros(R, np.float64)
    np.add.at(ring_area, ring_of, x * y[nxt] - x[nxt] * y)
    ring_area *= 0.5
    is_shell = np.arange(R) == po[:-1][part_of_ring]
    signed = np.where(is_shell, np.abs(ring_area), -np.abs(ring_area))
    np.add.at(out, geom_of_part[part_of_ring], signed)
    gt = np.asarray(col.geom_type, np.int64)
    base = np.where(gt > 3, gt - 3, gt)
    return np.where(base == int(GeometryType.POLYGON), out, 0.0)


def _first_vertices(col: PackedGeometry) -> np.ndarray:
    """(G, 2) f64: each geometry's first vertex (zeros where it has
    none) — a frame of its own for its area."""
    G = len(col)
    xy = np.asarray(col.xy, np.float64).reshape(-1, np.asarray(col.xy).shape[-1])[:, :2]
    out = np.zeros((G, 2), np.float64)
    if not G or not xy.shape[0]:
        return out
    ro = np.asarray(col.ring_offsets, np.int64)
    po = np.asarray(col.part_offsets, np.int64)
    go = np.asarray(col.geom_offsets, np.int64)
    has = (go[1:] > go[:-1])
    fp = np.minimum(go[:-1], max(po.shape[0] - 2, 0))
    fr = np.minimum(po[fp], max(ro.shape[0] - 2, 0))
    v0 = np.minimum(ro[fr], xy.shape[0] - 1)
    has &= ro[fr + 1] > ro[fr]
    out[has] = xy[v0[has]]
    return out


def _masked_shoelace(verts: np.ndarray, vlen: np.ndarray) -> np.ndarray:
    """(N,) f64 signed shoelace areas of left-packed open rings."""
    x, y = verts[:, :, 0], verts[:, :, 1]
    j = np.arange(verts.shape[1])[None, :]
    nxt = np.where(j + 1 < vlen[:, None], j + 1, 0)
    xn = np.take_along_axis(x, nxt, axis=1)
    yn = np.take_along_axis(y, nxt, axis=1)
    contrib = np.where(j < vlen[:, None], x * yn - xn * y, 0.0)
    return 0.5 * contrib.sum(axis=1)


def _ring_rows(table: ChipTable):
    """The side table's rows before sorting: ``(chip, start, length,
    sign)`` — one row a ring of every border polygon chip (the shell of
    each part +1, its holes -1; rings of under three vertices are left
    out), one row with no ring for every other chip (a core chip; a
    border chip that is no polygon or stores no geometry, which then
    clips to nothing)."""
    ch = table.chips
    C = len(ch)
    z = np.zeros(0, np.int64)
    if not C:
        return z, z, z, np.zeros(0, np.float64)
    go = np.asarray(ch.geom_offsets, np.int64)
    po = np.asarray(ch.part_offsets, np.int64)
    ro = np.asarray(ch.ring_offsets, np.int64)
    gt = np.asarray(ch.geom_type, np.int64)
    base = np.where(gt > 3, gt - 3, gt)
    ringed = (
        np.asarray(table.has_geom, bool)
        & ~np.asarray(table.is_core, bool)
        & (base == int(GeometryType.POLYGON))
    )
    R = ro.shape[0] - 1
    part_of_ring = np.repeat(np.arange(po.shape[0] - 1), np.diff(po))
    chip_of_ring = np.repeat(np.arange(C), np.diff(go))[part_of_ring]
    rlen = np.diff(ro)
    keep = ringed[chip_of_ring] & (rlen >= 3)
    shell = np.arange(R) == po[:-1][part_of_ring]
    has_ring = np.zeros(C, bool)
    has_ring[chip_of_ring[keep]] = True
    bare = np.nonzero(~has_ring)[0]
    chip = np.concatenate([chip_of_ring[keep], bare])
    start = np.concatenate([ro[:-1][keep], np.zeros(bare.shape[0], np.int64)])
    length = np.concatenate([rlen[keep], np.zeros(bare.shape[0], np.int64)])
    sign = np.concatenate([
        np.where(shell[keep], 1.0, -1.0), np.ones(bare.shape[0]),
    ])
    order = np.argsort(chip, kind="stable")
    return chip[order], start[order], length[order], sign[order]


def _fan_negative(verts: np.ndarray, vlen: np.ndarray, tol: float):
    """(N,) f64: the area a ring's fan from vertex 0 counts NEGATIVE
    (0.0: the fan is a partition of the ring)."""
    w0 = verts[:, :1]
    a = verts[:, 1:-1] - w0
    b = verts[:, 2:] - w0
    cr = a[..., 0] * b[..., 1] - a[..., 1] * b[..., 0]
    i = np.arange(1, verts.shape[1] - 1)[None, :]
    live = i + 1 < vlen[:, None]
    return np.where(live & (cr < -tol), -cr, 0.0).sum(axis=1)


def _pack_rings(xy: np.ndarray, start, length, V: int, origin, scale: float):
    """``(ok, convex, star, verts, vlen)`` for ring spans of ``xy``:
    left-packed CCW open rings padded by repeating the last vertex, each
    RELATIVE TO ITS CELL'S CORNER (``origin``, subtracted in f64 here,
    once: both rings of a candidate row lie in one cell, so the frame
    costs nothing and a coordinate is at most a cell's extent); a ring
    over the pad gets ``ok`` False and no vertices. A ring that is not
    convex is turned so that the apex whose fan counts the least
    negative area comes first (``star``: none at all)."""
    N = int(np.asarray(start).shape[0])
    if not N:
        return (
            np.zeros(0, bool), np.zeros(0, bool), np.zeros(0, bool),
            np.zeros((0, V, 2), np.float64), np.zeros(0, np.int32),
        )
    ok = (length >= 3) & (length <= V)
    safe_len = np.maximum(length, 1)
    j = np.arange(V)[None, :]
    idx = start[:, None] + np.minimum(j, safe_len[:, None] - 1)
    idx = np.clip(idx, 0, max(xy.shape[0] - 1, 0))
    verts = xy[idx] - np.asarray(origin, np.float64)[:, None, :]
    vlen = np.where(ok, length, 0).astype(np.int32)
    verts = np.where(ok[:, None, None], verts, 0.0)
    # orient CCW (reverse the valid prefix where the ring is CW)
    sa = _masked_shoelace(verts, vlen)
    rev = np.where(j < vlen[:, None],
                   np.maximum(vlen[:, None] - 1 - j, 0), j)
    flipped = np.take_along_axis(verts, rev[:, :, None], axis=1)
    verts = np.where((sa < 0)[:, None, None], flipped, verts)
    # convex-window test: every pair of consecutive edges turns left
    # (cross ≥ -tol), wraparound included
    nxt = np.where(j + 1 < vlen[:, None], j + 1, 0)
    nxy = np.take_along_axis(verts, nxt[:, :, None], axis=1)
    e = nxy - verts
    en = np.take_along_axis(e, nxt[:, :, None], axis=1)
    cross = e[:, :, 0] * en[:, :, 1] - e[:, :, 1] * en[:, :, 0]
    tol = _k.CLIP_EPS * scale * scale
    convex = ok & np.all(
        np.where(j < vlen[:, None], cross, 0.0) >= -tol, axis=1
    )
    star = convex.copy()
    bent = np.nonzero(ok & ~convex)[0]
    if bent.size:
        bv, bl = verts[bent], vlen[bent]
        best = _fan_negative(bv, bl, tol)
        best_at = np.zeros(bent.shape[0], np.int64)
        for a in range(1, V):
            turn = np.where(j < bl[:, None], (j + a) % np.maximum(bl, 1)[:, None], j)
            cand = np.take_along_axis(bv, turn[:, :, None], axis=1)
            neg = np.where(a < bl, _fan_negative(cand, bl, tol), np.inf)
            better = neg < best
            best = np.where(better, neg, best)
            best_at = np.where(better, a, best_at)
        turn = np.where(
            j < bl[:, None], (j + best_at[:, None]) % np.maximum(bl, 1)[:, None], j
        )
        verts[bent] = np.take_along_axis(bv, turn[:, :, None], axis=1)
        star[bent] = best == 0.0
    # the pad repeats the last vertex (a turned ring's tail is re-made)
    last = np.take_along_axis(
        verts, np.maximum(vlen - 1, 0)[:, None, None].astype(np.int64)
        * np.ones((1, 1, 2), np.int64), axis=1,
    )
    verts = np.where((j >= vlen[:, None])[:, :, None] & ok[:, None, None],
                     last, verts)
    return ok, convex, star, verts, vlen


def overlay_acc_dtype(platform: str | None = None) -> str:
    """THE rule for the overlay's accelerated dtype (a rule with its
    reason, no argument — `sql.stream.stream_cell_dtype` is the model).

    Rings are stored relative to their own cell's corner, so a
    coordinate is at most a cell's extent and float32 holds it to
    ``1.2e-7`` of that: the band, ``EDGE_BAND_K · eps · cell²``, is a
    five-hundredth of a percent of the cell. On the TPU that is the
    clip's dtype: the chip has no float64 unit, emulates it at about 46
    bits (`runtime.platform.arithmetic_eps`) and pays for it several
    times over in every half-plane round, to narrow a band under which
    the f64 host lane answers anyway (both readings: ``PERF.md``
    section 6, PR 40). Everywhere else float64 under x64 — there the
    device lane IS the oracle's arithmetic and matches the numpy twin
    bit for bit — and float32 without it."""
    if platform is None:
        platform = jax.devices()[0].platform
    if platform == "tpu" or not jax.config.jax_enable_x64:
        return "float32"
    return "float64"


def overlay_band(acc_name: str, scale: float,
                 platform: str | None = None) -> float:
    """The recheck band in area units: ``EDGE_BAND_K`` rounding steps of
    the arithmetic the device REALLY computes ``acc_name`` in
    (`runtime.platform.arithmetic_eps`: the chip's float64 is not
    numpy's) times the square of the largest coordinate a ring holds,
    which in the cell-local frame is a cell's extent."""
    return float(
        EDGE_BAND_K * _platform.arithmetic_eps(acc_name, platform)
        * scale * scale
    )


def prepare_overlay(
    left_chips: ChipTable,
    right_chips: ChipTable,
    left: PackedGeometry,
    right: PackedGeometry,
    index_system: IndexSystem,
    resolution: int,
) -> OverlayPrep:
    """Build the amortized device-lane prep for an overlay table pair.

    One host pass per table pair: explode both chip tables into ring
    rows, sort them by cell id, pad to ladder buckets with per-side
    sentinels, rank every row's cell among the pair's distinct cells and
    lay the right column out as its runs' offsets over those ranks (the
    index the device's equi-join reads its spans from), precompute the
    f64 area tables (ring, cell, whole-geometry), pack every border ring within the vertex pad in
    its own cell's frame (:func:`_pack_rings`), derive the epsilon band
    from the arithmetic the device really computes in
    (:func:`overlay_band`) and put what the fused program reads on the
    device, once, in the accelerated dtype. Everything here is reused
    across measures, caps and meshes — only the fused program varies
    per signature, and a call moves the candidate rows, the segments
    and the answers.
    """
    with _trace.span(
        "overlay.prepare",
        left_chips=int(np.asarray(left_chips.cell_id).shape[0]),
        right_chips=int(np.asarray(right_chips.cell_id).shape[0]),
    ) as span:
        lcells_raw = np.asarray(left_chips.cell_id, np.int64)
        rcells_raw = np.asarray(right_chips.cell_id, np.int64)
        ucells = np.unique(np.concatenate([lcells_raw, rcells_raw]))
        if ucells.shape[0]:
            bnds = np.asarray(
                index_system.cell_boundary(ucells), np.float64
            )
        else:
            bnds = np.zeros((0, 4, 2), np.float64)
        if bnds.shape[0]:
            corner = bnds.min(axis=1)
            scale = float(max(
                np.max(bnds.max(axis=1) - corner), np.finfo(np.float64).tiny
            ))
            allxy = bnds.reshape(-1, 2)
            shift = 0.5 * (allxy.min(axis=0) + allxy.max(axis=0))
        else:
            corner = np.zeros((0, 2), np.float64)
            scale = 1.0
            shift = np.zeros(2, np.float64)
        cell_polys, klen = _dedupe_boundaries_batch(bnds)
        ucell_area = np.abs(_masked_shoelace(
            cell_polys - corner[:, None, :], klen.astype(np.int64)
        ))

        lrows = _ring_rows(left_chips)
        rrows = _ring_rows(right_chips)

        def _longest_ring(rows):
            length = rows[2]
            return int(length.max()) if length.shape[0] else 0

        # the pad is part of every program's signature: the longest border
        # ring, rounded up to a multiple of four so that layers which
        # differ by a vertex share their programs
        longest = max(4, _longest_ring(lrows), _longest_ring(rrows))
        V = int(min(MAX_CHIP_VERTS, -(-longest // 4) * 4))

        acc = overlay_acc_dtype()
        band = overlay_band(acc, scale)
        acc_dt = np.dtype(acc)
        # the column the ranks count in: the sentinels keep their order
        ranked = np.concatenate(
            [ucells, [_k.RIGHT_PAD_CELL, _k.LEFT_PAD_CELL]]
        )
        roff_len = RANK_LADDER.bucket_for(ranked.shape[0] + 1)

        def _side(table, col, cells_raw, rows, pad_cell, probed):
            # probed: the side whose column the other's rows look their
            # spans up in (the right); it carries the run offsets
            chip, start, length, sign = rows
            n = int(chip.shape[0])
            cells_row = cells_raw[chip]
            order = np.argsort(cells_row, kind="stable")
            Lb = TABLE_LADDER.bucket_for(max(n, 1))
            pos = np.searchsorted(ucells, cells_row)
            origin = corner[pos] if n else np.zeros((0, 2), np.float64)
            xy = np.asarray(table.chips.xy, np.float64)
            xy = xy.reshape(-1, xy.shape[-1])[:, :2] if xy.size else np.zeros((0, 2))
            ok, convex, star, verts, vlen = _pack_rings(
                xy, start, length, V, origin, scale
            )
            core = np.asarray(table.is_core, bool)[chip]
            row_cell_area = (
                ucell_area[pos] if n else np.zeros(0, np.float64)
            )
            # a ring's signed area in its own frame; a core chip covers
            # its cell exactly — use the cell table so the core
            # branches and the area tables agree bit-for-bit. A ring
            # over the pad is measured by the host lane when a row
            # needs it, so its table entry is its own shoelace too.
            ring_area = sign * np.abs(_masked_shoelace(verts, vlen))
            big = np.nonzero((length > V))[0]
            if big.size:
                Vb = int(length[big].max())
                _o, _c, _s, bverts, bvlen = _pack_rings(
                    xy, start[big], length[big], Vb, origin[big], scale
                )
                ring_area[big] = sign[big] * np.abs(
                    _masked_shoelace(bverts, bvlen)
                )
            chip_area = np.where(core, row_cell_area, ring_area)

            def pad(a, fill=0):
                out = np.full((Lb,) + a.shape[1:], fill, a.dtype)
                out[:n] = a[order]
                return out

            side = dict(
                cells=pad(cells_row, pad_cell),
                rank=pad(
                    pos.astype(np.int32),
                    np.searchsorted(ranked, pad_cell),
                ),
                geom=pad(np.asarray(table.geom_id, np.int64)[chip], -1),
                core=pad(core),
                ok=pad(ok & ~core),
                convex=pad(convex & ~core),
                star=pad(star & ~core),
                sign=pad(sign, 1.0),
                verts=pad(verts),
                vlen=pad(vlen),
                chip_area=pad(chip_area),
                cell_area=pad(row_cell_area),
                origin=pad(origin),
                ring_start=pad(start),
                ring_len=pad(length),
            )
            if probed:
                side["roff"] = _k.run_offsets(side["rank"], roff_len)
            dev = {
                k: jax.device_put(
                    side[k].astype(acc_dt)
                    if side[k].dtype == np.float64 else side[k]
                )
                for k in ("roff" if probed else "rank", "core", "sign",
                          "verts", "vlen", "chip_area", "cell_area")
            }
            return OverlaySide(
                table=table, n=n, bucket=Lb,
                rows=chip[order].astype(np.int64),
                geom_area=_csr_geom_areas(col, _first_vertices(col)),
                dev=dev, **side,
            )

        prep = OverlayPrep(
            left=_side(left_chips, left, lcells_raw, lrows,
                       _k.LEFT_PAD_CELL, False),
            right=_side(right_chips, right, rcells_raw, rrows,
                        _k.RIGHT_PAD_CELL, True),
            shift=np.asarray(shift, np.float64),
            scale=scale,
            index_system=index_system,
            resolution=resolution,
            acc_name=acc,
            band=float(band),
            vpad=V,
            ranks=int(ucells.shape[0]),
        )
        span.set(
            left_rows=prep.left.n, right_rows=prep.right.n, vpad=V,
            ranks=prep.ranks,
            acc=acc, band=float(band), scale=scale,
            resident_bytes=sum(
                int(a.nbytes) for s in (prep.left, prep.right)
                for a in s.dev.values()
            ),
        )
        return prep


def pair_glue(prep: OverlayPrep, li, ri, valid):
    """Candidate stream → geometry-pair segments (host glue, shared by
    the device lane and its numpy twin so both see identical segment
    ids): ``(uniq (U, 2) i64, seg (Pb,) i32 with -1 for dead slots,
    sure (U,), Sb, seg_larea (Sb,) f64, seg_rarea (Sb,) f64)``."""
    L, R = prep.left, prep.right
    li = np.asarray(li)
    ri = np.asarray(ri)
    valid = np.asarray(valid, bool)
    lg = L.geom[li]
    rg = R.geom[ri]
    valid = valid & (lg >= 0) & (rg >= 0)
    seg = np.full(li.shape[0], -1, np.int32)
    if valid.any():
        # one int64 key a pair, in (left, right) order: the unique of a
        # 1-d key is a sort of words, of an (N, 2) table a sort of rows
        width = np.int64(R.geom_area.shape[0] + 1)
        key, inv = np.unique(
            lg[valid] * width + rg[valid], return_inverse=True
        )
        uniq = np.stack([key // width, key % width], axis=-1)
        seg[valid] = inv.astype(np.int32)
    else:
        uniq = np.zeros((0, 2), np.int64)
    U = uniq.shape[0]
    sure = np.zeros(U, bool)
    either = L.core[li] | R.core[ri]
    if valid.any():
        sure[seg[valid & either]] = True
    Sb = SEG_LADDER.bucket_for(max(U, 1))
    seg_larea = np.zeros(Sb, np.float64)
    seg_rarea = np.zeros(Sb, np.float64)
    if U:
        seg_larea[:U] = L.geom_area[uniq[:, 0]]
        seg_rarea[:U] = R.geom_area[uniq[:, 1]]
    return uniq, seg, sure, Sb, seg_larea, seg_rarea


def pair_routes(prep: OverlayPrep, li, ri, seg):
    """Which border × border candidate rows take which clip — host
    flags, shared by both lanes: ``(clip_rows, clip_swap, fan_rows,
    fan_swap, shape_rows)``, the rows ascending int32 indices into the
    candidate stream, the swaps by `kernels.overlay.window_swaps`.

    - ``clip_rows``: one of the two rings is convex, and is the window.
    - ``fan_rows``: neither is — the signed fan
      (`kernels.overlay.fan_area`).
    - ``shape_rows``: a ring over the vertex pad; the f64 host lane
      answers the whole geometry pair.
    """
    L, R = prep.left, prep.right
    li = np.asarray(li)
    ri = np.asarray(ri)
    bb = (np.asarray(seg) >= 0) & ~L.core[li] & ~R.core[ri]
    ringed = (L.ring_len[li] >= 3) & (R.ring_len[ri] >= 3)
    okk = L.ok[li] & R.ok[ri]
    conv = L.convex[li] | R.convex[ri]
    clip_r, fan_r, shape_r = (
        np.nonzero(m)[0].astype(np.int32)
        for m in (bb & okk & conv, bb & okk & ~conv, bb & ringed & ~okk)
    )

    def swaps(rows):
        lk, rk = li[rows], ri[rows]
        return _k.window_swaps(
            L.convex[lk], R.convex[rk], L.star[lk], R.star[rk]
        )

    return clip_r, swaps(clip_r)[0], fan_r, swaps(fan_r)[1], shape_r


def _host_tables(side: OverlaySide, acc: np.dtype) -> dict:
    """What ``side.dev`` holds, as host arrays in ``acc``."""
    out = {}
    for k in side.dev:
        a = getattr(side, k)
        out[k] = a.astype(acc) if a.dtype == np.float64 else a
    return out


def _padded(rows: np.ndarray, bucket: int) -> np.ndarray:
    out = np.zeros(bucket, rows.dtype)
    out[: rows.shape[0]] = rows
    return out


# --------------------------------------------------- device-lane programs


@_dispatch.bounded_cache("overlay_count_programs", 8)
def _count_program(after_self: bool = False):
    def overlay_count(rank, roff, n_left):
        lo, cnt = _k.rank_spans(
            rank, roff, n_left, xp=jnp, after_self=after_self
        )
        with jax.named_scope("overlay.spans"):  # a trace books the sum there
            total = cnt.sum()
        return total, lo, cnt

    return jax.jit(overlay_count)


@_dispatch.bounded_cache("overlay_emit_programs", 32)
def _emit_program(pair_bucket: int):
    """The emission at ``pair_bucket``, from the pair rank ``start`` on:
    0 for a stream one bucket holds (`overlay_measures`), a slice a launch
    for a longer one (`sql.proximity`)."""
    def overlay_emit(lo, cnt, emit_limit, start=0):
        return _k.emit_spans(
            lo, cnt, emit_limit, pair_bucket, xp=jnp, start=start
        )

    return jax.jit(overlay_emit)


_STAGES_SEEN: set = set()


def _register_stages(fn, args: tuple, rows: int) -> None:
    """Tell `obs.stages` how to lower ``fn(*args)`` again (shapes only;
    nothing is lowered here), once a (program, shapes) signature."""
    key = (id(fn), rows, tuple(
        (getattr(a, "shape", None), str(getattr(a, "dtype", type(a))))
        for a in args
    ))
    if key in _STAGES_SEEN:
        return
    if len(_STAGES_SEEN) >= 256:
        _STAGES_SEEN.clear()
    _STAGES_SEEN.add(key)
    _stages.register(fn, _stages.shapes_of(args), rows=rows)


@dataclass(frozen=True)
class OverlayMeasures:
    """Fused overlay measure result — one row per unique geometry pair
    sharing at least one cell (plus, when the candidate stream was
    capped, a trailing ``(OVERFLOW, OVERFLOW)`` row with NaN measures:
    structural truncation, never a silent wrong answer).

    ``value`` is the evaluated pair tree (f64), ``valid`` its mask lane,
    ``area`` the folded intersection area, ``sure`` the core-chip
    certainty flag, ``host_overridden`` how many pairs the f64 host lane
    re-answered (epsilon band / over-pad ring / spill), and ``lane``
    which lane produced the numbers (``degraded`` True when the device
    lane failed past its retry budget and the host oracle answered
    instead)."""

    pairs: np.ndarray
    value: np.ndarray
    valid: np.ndarray
    area: np.ndarray
    sure: np.ndarray
    overflow: int
    lane: str
    host_overridden: int
    degraded: bool = False
    reason: str = ""


def _package(out: dict, lane: str, degraded: bool = False,
             reason: str = "") -> OverlayMeasures:
    """Lane output dict → :class:`OverlayMeasures`, appending the
    OVERFLOW(-2) row when the emission was capped."""
    pairs = out["pairs"]
    value = out["value"]
    vmask = out["valid"]
    area = out["area"]
    sure = out["sure"]
    overflow = int(out["overflow"])
    if overflow > 0:
        pairs = np.concatenate(
            [pairs, np.asarray([[OVERFLOW, OVERFLOW]], np.int64)]
        )
        value = np.concatenate([value, [np.nan]])
        area = np.concatenate([area, [np.nan]])
        vmask = np.concatenate([vmask, [False]])
        sure = np.concatenate([sure, [False]])
    return OverlayMeasures(
        pairs=pairs, value=value, valid=vmask, area=area, sure=sure,
        overflow=overflow, lane=lane,
        host_overridden=int(out["host_overridden"]),
        degraded=degraded, reason=reason,
    )


def overlay_measures(
    left: PackedGeometry,
    right: PackedGeometry,
    index_system: IndexSystem,
    resolution: int,
    value=None,
    *,
    left_chips: ChipTable | None = None,
    right_chips: ChipTable | None = None,
    prep: OverlayPrep | None = None,
    pair_cap: int | None = None,
    mesh=None,
    lane: str = "device",
) -> OverlayMeasures:
    """Fused overlap measures per intersecting geometry pair.

    ``value`` is an `expr/` PAIR tree over :func:`expr.ast.overlap_area`
    / ``left_area`` / ``right_area`` (default: the raw intersection
    area); ``st_intersection_area`` and ``st_overlap_fraction`` are the
    canned frontends. Candidate generation runs on device as a sorted
    segment equi-join over the prep's resident rank column and run
    offsets, the measures as ONE fused program per ``(tree-hash,
    buckets, index, mesh)`` signature — warm it with :func:`warmup_overlay` before
    `expr.compile.freeze`.

    One call records, under its root span ``overlay.call`` (the pair's
    ``left_rows``, ``right_rows``, ``acc`` and ``vpad``; counters
    ``raw_candidates``, ``pairs``, ``bucket``, ``clip_rows``,
    ``swapped_rows``, ``fan_rows``, ``fan_triangles``,
    ``host_overridden`` and its split ``host_band`` / ``host_shape`` /
    ``host_spill`` / ``host_cancel``): ``overlay.count`` (the launch of the
    program that READS every left row's span of right rows — two gathers
    through the dense ranks, ``spans="rank"`` over ``ranks`` distinct
    cells, the call's only pass over the cell columns — and the blocking
    read of their total; the spans stay on the device), ``overlay.emit``
    (the launch that turns those spans into candidate rows at the pair
    bucket the total picked — ``form="marks"``: no search — and the pull
    of the rows),
    ``overlay.glue`` (`pair_glue`, `pair_routes`), ``overlay.launch``,
    ``overlay.pull`` and ``overlay.host_override``.

    ``lane="host"`` routes to the pure-f64 numpy twin (the oracle); the
    device lane degrades there automatically (result flagged) when the
    device path fails past its retry budget. ``pair_cap`` bounds the
    candidate emission — the excess is reported as an OVERFLOW(-2) row,
    never silently dropped.
    """
    from ..expr import ast as _ast
    from ..expr import compile as _compile
    from ..expr.host_oracle import host_overlay_measures, splice_override

    value = _ast.overlap_area() if value is None else value
    _ast.validate_pair(value)
    mesh = _dispatch.resolve_mesh(mesh)
    if prep is None:
        lt = (
            left_chips
            if left_chips is not None
            else tessellate(left, index_system, resolution)
        )
        rt = (
            right_chips
            if right_chips is not None
            else tessellate(right, index_system, resolution)
        )
        prep = prepare_overlay(
            lt, rt, left, right, index_system, resolution
        )
    if lane == "host":
        out = host_overlay_measures(prep, value, pair_cap=pair_cap)
        return _package(out, lane="host")
    if lane != "device":
        raise ValueError(f"unknown overlay lane {lane!r}")

    L, R = prep.left, prep.right
    acc = np.dtype(prep.acc_name)
    meshed = _dispatch.mesh_key(mesh) is not None
    # a meshed program takes host tables (it places them itself); the
    # single-device one reads the resident copies
    lt_, rt_ = (
        (_host_tables(L, acc), _host_tables(R, acc)) if meshed
        else (L.dev, R.dev)
    )
    try:
        with _trace.span(
            "overlay.call", left_rows=L.n, right_rows=R.n, acc=prep.acc_name,
            vpad=prep.vpad,
        ) as call:
            def device_candidates():
                with _trace.span(
                    "overlay.count", spans="rank", ranks=prep.ranks,
                ):
                    count = _count_program()
                    args = (lt_["rank"], rt_["roff"], L.n)
                    _register_stages(count, args, L.bucket)
                    dtotal, dlo, dcnt = count(*args)
                    total = int(dtotal)
                Pb, emit_limit, overflow = pair_plan(total, pair_cap)
                with _trace.span("overlay.emit", bucket=Pb, form="marks"):
                    emit = _emit_program(Pb)
                    args = (dlo, dcnt, emit_limit, np.int32(0))
                    _register_stages(emit, args, Pb)
                    dli, dri, dvalid = emit(*args)
                    li = np.asarray(dli)
                    ri = np.asarray(dri)
                return (
                    dli, dri, dvalid, li, ri,
                    np.arange(Pb) < emit_limit,
                    total, Pb, emit_limit, overflow,
                )

            (dli, dri, dvalid, li, ri, valid, total, Pb,
             emit_limit, overflow) = _dispatch.guarded_call(
                "overlay.device_candidates", device_candidates
            )
            with _trace.span("overlay.glue", rows=emit_limit):
                uniq, seg, sure, Sb, seg_l64, seg_r64 = pair_glue(
                    prep, li, ri, valid
                )
                clip_r, clip_swap, fan_r, fan_swap, shape_r = (
                    pair_routes(prep, li, ri, seg)
                )
                Cb = PAIR_LADDER.bucket_for(max(clip_r.shape[0], 1))
                Fb = PAIR_LADDER.bucket_for(max(fan_r.shape[0], 1))

            sig = _compile.overlay_signature_of(
                value, L.bucket, R.bucket, Pb, Cb, Fb, Sb, prep.vpad,
                prep.acc_name, index_system, resolution, mesh,
            )
            prog = _compile.overlay_program(
                value, L.bucket, R.bucket, Pb, Cb, Fb, Sb, prep.vpad,
                prep.acc_name, mesh,
            )
            if meshed:
                dli, dri, dvalid = li, ri, valid
            args = (
                dli, dri, dvalid, seg,
                _padded(clip_r, Cb), _padded(clip_swap, Cb),
                np.int32(clip_r.shape[0]),
                _padded(fan_r, Fb), _padded(fan_swap, Fb),
                np.int32(fan_r.shape[0]),
                lt_["core"], lt_["sign"], lt_["verts"], lt_["vlen"],
                lt_["chip_area"], lt_["cell_area"],
                rt_["core"], rt_["sign"], rt_["verts"], rt_["vlen"],
                rt_["chip_area"],
                seg_l64.astype(acc), seg_r64.astype(acc),
                acc.type(prep.band),
            )
            if not meshed:
                _register_stages(prog, args, Pb)

            def measures():
                with _trace.span(
                    "overlay.launch", clip_bucket=Cb, fan_bucket=Fb,
                ):
                    raw = _compile.run_tracked(sig, prog, *args)
                with _trace.span("overlay.pull"):
                    return tuple(np.asarray(x) for x in raw)

            val, vok, s, cnt, host_c, host_f, spill_c, spill_f = (
                _dispatch.guarded_call("overlay.measures", measures)
            )
            val = val.astype(np.float64).copy()
            vok = vok.astype(bool).copy()
            area64 = s.astype(np.float64).copy()
            nc, nf = clip_r.shape[0], fan_r.shape[0]
            flagged_rows = np.concatenate([
                clip_r[host_c[:nc]], fan_r[host_f[:nf]], shape_r,
            ])
            with _trace.span("overlay.host_override") as hspan:
                (val, vok, area64, overridden, hrows,
                 cancelled) = splice_override(
                    prep, value, li, ri, seg, flagged_rows, cnt,
                    seg_l64, seg_r64, val, vok, area64,
                )
                hspan.set(pairs=overridden, rows=hrows)
            wlen = np.where(fan_swap, L.vlen[li[fan_r]], R.vlen[ri[fan_r]])

            def pairs_of(*rows):  # geometry pairs that hold such a row
                return int(np.unique(seg[np.concatenate(rows)]).shape[0])

            call.set(
                raw_candidates=total, pairs=int(uniq.shape[0]), bucket=Pb,
                clip_rows=int(nc + nf + shape_r.shape[0]),
                swapped_rows=int(clip_swap.sum()), fan_rows=int(nf),
                fan_triangles=int(np.maximum(wlen - 2, 0).sum()),
                host_overridden=overridden, host_cancel=cancelled,
                host_shape=pairs_of(shape_r),
                host_spill=pairs_of(
                    clip_r[spill_c[:nc]], fan_r[spill_f[:nf]]
                ),
                host_band=pairs_of(
                    clip_r[host_c[:nc] & ~spill_c[:nc]],
                    fan_r[host_f[:nf] & ~spill_f[:nf]],
                ),
                overflow=overflow,
            )
        U = uniq.shape[0]
        return _package(
            {
                "pairs": uniq, "value": val[:U], "valid": vok[:U],
                "area": area64[:U], "sure": sure,
                "overflow": overflow, "host_overridden": overridden,
            },
            lane="device",
        )
    except Exception as e:  # lint: broad-except-ok (degradation seam: past the retry budget the f64 host oracle answers instead; the result is flagged, parity with every other DispatchCore frontend)
        _telemetry.record(
            "degraded", label="overlay.measures", error=repr(e)[:200]
        )
        out = host_overlay_measures(prep, value, pair_cap=pair_cap)
        return _package(
            out, lane="host", degraded=True,
            reason=f"overlay.measures: {e!r}"[:300],
        )


def warmup_overlay(
    left: PackedGeometry,
    right: PackedGeometry,
    index_system: IndexSystem,
    resolution: int,
    value=None,
    *,
    left_chips: ChipTable | None = None,
    right_chips: ChipTable | None = None,
    prep: OverlayPrep | None = None,
    pair_cap: int | None = None,
    mesh=None,
) -> OverlayPrep:
    """Execute the device overlay pipeline once so its signature joins
    the warm set (`expr.compile.freeze` afterwards arms the cold-compile
    tripwire) and return the prep for amortized reuse."""
    if prep is None:
        lt = (
            left_chips
            if left_chips is not None
            else tessellate(left, index_system, resolution)
        )
        rt = (
            right_chips
            if right_chips is not None
            else tessellate(right, index_system, resolution)
        )
        prep = prepare_overlay(
            lt, rt, left, right, index_system, resolution
        )
    overlay_measures(
        left, right, index_system, resolution, value,
        prep=prep, pair_cap=pair_cap, mesh=mesh,
    )
    return prep
