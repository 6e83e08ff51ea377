"""Index-assisted point-in-polygon join — the north-star workload.

Reference analog: `sql/join/PointInPolygonJoin.scala:15-98` and the
Quickstart benchmark (`notebooks/examples/scala/QuickstartNotebook.scala:
204-216`): points get a cell id, polygons are tessellated into chips, the
join is an equi-join on cell id, and the exact `st_contains` predicate runs
only on border-chip matches (`is_core || st_contains(wkb, point)`).

TPU-native redesign: there is no shuffle. The chip table is compiled into a
device-resident :class:`ChipIndex` which is small enough to replicate
(all-gather over ICI) on every chip of a mesh, while the billion-point side
shards over devices.

The per-point probe is designed around TPU gather latency and HBM bandwidth:

    key  = (cell * A) >> (64 - log2 T)    multiply-shift hash, no search
    bkt  = table[key]                     1 gather: B candidate (cell, u)
    u    = bucket row whose cell matches  parallel compare, no loop
    edges= cell_edges[u]                  1 flat gather: the cell's chip
                                          edges, capped at EDGE_CAP
    par  = xor-reduce(crossing ? bit : 0) one parity bit per chip slot
    hit  = core | parity bit              fused vector math

The edge table is FLAT per cell (not per-chip padded): every cell row holds
at most ``EDGE_CAP`` edges, each tagged with the parity bit of the chip it
belongs to. This kills the max-verts padding blow-up that a per-chip
``(U, M, R, V, 2)`` layout suffers (one 309-vertex coastline chip would
force every cell row to carry V=309 — ~10 GB of gather per 1M points, which
made every >=1M batch fail TPU compilation in round 2). Cells whose chips
carry more than ``EDGE_CAP`` edges (<8% of NYC cells) divert to a HEAVY side
table (tier 2). Under a ``heavy_cap`` below the row count the points landing
in them are stream-compacted (cumsum + scatter, all static shapes) and only
that compacted subset pays the wide heavy gather; without one (the stream,
full-bucket caps) compaction would move every row into as many slots and
back, so every row fetches a wide row in place and the rows of no heavy cell
are masked (:func:`tier2_compacts`, as :func:`tier1_compacts` one tier up).
"""

from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import (
    Layout as _Layout,
    with_layout_constraint as _with_layout,
)

from ..core.geometry.device import (
    DeviceGeometry,
    recenter_shift,
    to_device,
)
from ..core.index.base import IndexSystem
from ..core.tessellate import ChipTable, tessellate
from ..core.types import PackedGeometry
from ..dispatch import core as _dispatch
from ..obs import stages as _stages
from ..obs import trace as _obs_trace
from ..runtime import (
    faults as _faults,
    telemetry as _telemetry,
)
from ..runtime.errors import DegradedResult, RetryExhausted
from ..runtime.platform import interpret_kernels
from ..runtime.escalate import run_escalating
from ..utils import get_logger

_SENTINEL = jnp.iinfo(jnp.int32).max
_I32_MAX = np.iinfo(np.int32).max
_OVF_MARK = _SENTINEL - 1  # in-probe marker: tier-2 capacity exceeded

#: per-cell flat edge capacity of the tier-1 probe; cells with more edges
#: divert to the heavy table (measured on NYC res-9: cap 32 keeps 93% of
#: cells in tier 1 and the heavy table holds <8k edges)
EDGE_CAP = 32

#: parity bits are uint32 — at most 32 chip slots per cell and per heavy row
MAX_SLOTS = 32

#: result code for points whose heavy-cell probe exceeded ``heavy_cap``
#: (unknown result; raise the cap — `pip_join` sizes it exactly)
OVERFLOW = -2

#: tier-1 chunk rows where the gathered-row work runs under `lax.map`
#: (`_map_rows`), which bounds its intermediates (direct mode's
#: un-compacted (N, E1, 4) edges crossed XLA's 2 GB buffer limit at 4M;
#: see `pip_join_points` for the compacted rows). A multiple of 128; tests
#: shrink it to run the chunked paths small
_TIER1_CHUNK = 1 << 20

#: epsilon-band multipliers (SURVEY §7 precision strategy): a point is
#: borderline when its cell-rounding margin (`IndexSystem.
#: point_to_cell_margin`) is below CELL_MARGIN_K·eps(dtype) — calibrated
#: against exhaustive f32-vs-f64 disagreement sets (max observed ≈ 2.8·eps
#: globally at res 5/9; tests/test_recheck.py pins the 2x headroom) — or
#: within EDGE_BAND_K·eps·coord_scale of a probed chip edge.
CELL_MARGIN_K = 6.0
EDGE_BAND_K = 16.0

#: convex-lane table shape (adaptive router): y-scanline buckets per
#: convex cell and the per-bucket edge capacity. A cell only qualifies
#: when every pad-inflated bucket fits CONVEX_EDGE_CAP edges, so the lane
#: reads at most EB edges/point against tier 1's full E1 row.
CONVEX_BUCKETS = 8
CONVEX_EDGE_CAP = 16


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ChipIndex:
    """Device-resident join index over a tessellated polygon table.

    Per-chip layout (kept for oracles, tests and host inspection):

    cells:     (U,) int64 — sorted unique cell ids present in the chip table.
    chip_rows: (U, M) int32 — chip-row ids per cell, -1 padded (M = max
               chips per cell, static).
    chip_geom: (C,) int32 — source polygon row per chip.
    chip_core: (C,) bool — core chips skip the predicate.
    border:    DeviceGeometry over all C chip rows (core rows are empty and
               never evaluated).

    Probe fast path (see module docstring):

    hash_mult:  (1,) uint64 — multiply-shift hash multiplier.
    table_rows: (T, 3B) uint32 — the bucketed hash table as the device
                probes it, one row a bucket: the B entries' cell ids' low
                words, their high words, their cell slots u (an empty
                entry is 0xFFFFFFFF in all three: cell -1, slot -1). T is
                a power of two, B the max bucket occupancy. The chip has
                no 64-bit lanes, so a table held as u32 words is fetched
                by ONE row gather and is never split per launch.
    table_cell: (T, B) int64, table_slot: (T, B) int32, table_pack: (T, B)
                int64 or (0, 0) — the same table as `_build_hash` returns
                it (cell ids, slots, the slot-packed entries where the ids
                share enough low bits). No device program reads them (jit
                drops an unused argument): they stay for host inspection,
                tests, and the benchmark's byte count, which reads B and
                whether the index packs off their shapes.

    Tier-1 flat edge probe (light cells):

    cell_edges:     (U, E1, 4) — ax, ay, bx, by per edge, zero pad (inert:
                    a zero-length edge never straddles any scanline).
    cell_ebits:     (U, E1) uint32 — parity bit ``1 << slot`` of the owning
                    chip, 0 for pad edges.
    cell_slot_geom: (U, M1) int32 — geom id per tier-1 chip slot, -1 pad.
    cell_slot_core: (U, M1) bool — core chips hit without any edge test.
    cell_heavy:     (U,) int32 — heavy-table row of this cell, -1 if light.

    Tier-2 heavy table (cells whose border chips exceed EDGE_CAP edges):

    heavy_edges:     (H, E2, 4); heavy_ebits: (H, E2) uint32.
    heavy_slot_geom: (H, M2) int32 — geom per heavy chip slot, -1 pad.
    H == 0 when no cell is heavy (tier 2 compiles away entirely).

    Convex lane (adaptive router, ``probe="adaptive"``): single-chip
    light cells whose border chip is one closed convex ring get a
    reduced-edge test — edges are binned by y into ``KB`` scanline
    buckets so a point touches only its bucket's ``EB`` edges instead of
    the cell's full E1 row:

    cell_convex: (U,) int32 — convex-table row of this cell, -1 otherwise.
    convex_edges: (Cv, KB, EB, 4) — y-bucketed edges (the same f32 values
                  as the cell's tier-1 row; zero pad is inert).
    convex_ebits: (Cv, KB, EB) uint32 — 1 for real edges, 0 pad.
    convex_geom:  (Cv,) int32 — the single chip's geom id.
    convex_ybin:  (Cv, 3) f32 — [y_min, buckets/height, band_guard²];
                  buckets overlap by a pad of 4·EDGE_BAND_K·eps·scale so
                  bucket-boundary rounding can never drop a straddling
                  edge, and the epsilon band stays exact while the
                  runtime eps² <= band_guard² (the router checks).
    Cv == 0 when no cell qualifies (the lane compiles away).

    Instances built by :func:`build_chip_index` additionally carry a
    ``host`` attribute (:class:`HostRecheck`, f64 host twin of the edge
    tables) — not a dataclass field, so it stays out of the pytree.
    """

    cells: jax.Array
    chip_rows: jax.Array
    chip_geom: jax.Array
    chip_core: jax.Array
    border: DeviceGeometry
    hash_mult: jax.Array
    table_rows: jax.Array
    table_cell: jax.Array
    table_slot: jax.Array
    table_pack: jax.Array
    cell_edges: jax.Array
    cell_ebits: jax.Array
    cell_slot_geom: jax.Array
    cell_slot_core: jax.Array
    cell_heavy: jax.Array
    heavy_edges: jax.Array
    heavy_ebits: jax.Array
    heavy_slot_geom: jax.Array
    cell_convex: jax.Array
    convex_edges: jax.Array
    convex_ebits: jax.Array
    convex_geom: jax.Array
    convex_ybin: jax.Array

    @property
    def num_cells(self) -> int:
        return int(self.cells.shape[0])

    @property
    def max_chips_per_cell(self) -> int:
        return int(self.chip_rows.shape[1])

    @property
    def num_heavy_cells(self) -> int:
        return int(self.heavy_edges.shape[0])

    @property
    def num_convex_cells(self) -> int:
        return int(self.convex_edges.shape[0])


@dataclasses.dataclass
class HostRecheck:
    """Host-side f64 companion of a :class:`ChipIndex`: the same flat
    edge layout in full precision and the same recentring shift, built
    from the pre-narrowing chip coordinates. This is the exact oracle the
    epsilon-band recheck evaluates borderline points against (and a
    standalone f64 reference join for tests/benchmarks via
    :func:`host_join_with_cells`). Not a pytree — never crosses to device.
    """

    cells: np.ndarray  # (U,) int64 sorted
    cell_edges: np.ndarray  # (U, E1, 4) float64
    cell_ebits: np.ndarray
    cell_slot_geom: np.ndarray
    cell_slot_core: np.ndarray
    cell_heavy: np.ndarray
    heavy_edges: np.ndarray  # (H, E2, 4) float64
    heavy_ebits: np.ndarray
    heavy_slot_geom: np.ndarray
    shift: np.ndarray  # (2,) float64
    coord_scale: float  # max |recentered edge coordinate|

    _FIELDS = (
        "cells", "cell_edges", "cell_ebits", "cell_slot_geom",
        "cell_slot_core", "cell_heavy", "heavy_edges", "heavy_ebits",
        "heavy_slot_geom", "shift",
    )

    def save_arrays(self) -> dict:
        """{name: array} for npz round-trips (bench index cache)."""
        d = {f"hr_{n}": getattr(self, n) for n in self._FIELDS}
        d["hr_coord_scale"] = np.asarray(self.coord_scale)
        return d

    @classmethod
    def from_arrays(cls, z) -> "HostRecheck":
        kw = {n: np.asarray(z[f"hr_{n}"]) for n in cls._FIELDS}
        return cls(coord_scale=float(z["hr_coord_scale"]), **kw)


def _np_parity(px, py, e, bits):
    """Host twin of :func:`_ray_parity` (float64 numpy)."""
    ax, ay, bx, by = e[..., 0], e[..., 1], e[..., 2], e[..., 3]
    st = (ay > py[:, None]) != (by > py[:, None])
    den = np.where(by == ay, 1.0, by - ay)
    xc = ax + (py[:, None] - ay) * (bx - ax) / den
    cr = st & (px[:, None] < xc)
    return np.bitwise_xor.reduce(
        np.where(cr, bits, np.uint32(0)).astype(np.uint32), axis=1
    )


def host_join_with_cells(
    points: np.ndarray, cells: np.ndarray, host: HostRecheck
) -> np.ndarray:
    """(N,) int32 — exact f64 host evaluation of the join contract for
    pre-assigned ``cells`` (raw, unshifted ``points``; same smallest-
    matching-row semantics as :func:`pip_join_points`)."""
    p = np.asarray(points, np.float64) - host.shift
    out = np.full(p.shape[0], -1, dtype=np.int32)
    U = host.cells.shape[0]
    if U == 0:
        return out
    u = np.clip(np.searchsorted(host.cells, cells), 0, U - 1)
    fi = np.nonzero(host.cells[u] == cells)[0]
    if fi.size == 0:
        return out
    uf = u[fi]
    px, py = p[fi, 0], p[fi, 1]
    par = _np_parity(px, py, host.cell_edges[uf], host.cell_ebits[uf])
    M = host.cell_slot_geom.shape[1]
    inside = ((par[:, None] >> np.arange(M, dtype=np.uint32)) & 1).astype(bool)
    g = host.cell_slot_geom[uf]
    hit = (g >= 0) & (host.cell_slot_core[uf] | inside)
    best = np.where(hit, g, _I32_MAX).min(axis=1)
    if host.heavy_edges.shape[0]:
        hrow = host.cell_heavy[uf]
        hi_ = np.nonzero(hrow >= 0)[0]
        if hi_.size:
            h = hrow[hi_]
            par2 = _np_parity(
                px[hi_], py[hi_], host.heavy_edges[h], host.heavy_ebits[h]
            )
            M2 = host.heavy_slot_geom.shape[1]
            in2 = (
                (par2[:, None] >> np.arange(M2, dtype=np.uint32)) & 1
            ).astype(bool)
            g2 = host.heavy_slot_geom[h]
            b2 = np.where((g2 >= 0) & in2, g2, _I32_MAX).min(axis=1)
            best[hi_] = np.minimum(best[hi_], b2)
    out[fi] = np.where(best == _I32_MAX, -1, best).astype(np.int32)
    return out


def host_join(
    points: np.ndarray,
    host: HostRecheck,
    index_system: IndexSystem,
    resolution: int,
) -> np.ndarray:
    """Exact f64 host join: f64 cell assignment (numpy host path, pentagon-
    exact) + f64 flat-edge probe. Ground truth for the epsilon-band
    recheck and the f32/f64 agreement metrics."""
    cells = np.asarray(
        index_system.point_to_cell(np.asarray(points, np.float64), resolution)
    )
    return host_join_with_cells(points, cells, host)


def _build_hash(cells: np.ndarray, max_bucket: int = 8):
    """Host: bucketed multiply-shift hash over the unique cell ids.

    Returns (mult, table_cell (T, B), table_slot (T, B), table_pack (T, B)
    or (0, 0), pack_low (2,)). T is sized ~4x the cell count (power of
    two); the multiplier is retried (growing the table each time) until
    the fullest bucket holds <= max_bucket entries, then B shrinks to the
    realized max. The fallback keeps ``keys`` consistent with
    the final ``bits`` even if every retry clusters: the last computed keys
    are used as-is with a (possibly larger) realized B.
    """
    U = cells.shape[0]
    bits = max(4, int(np.ceil(np.log2(max(4 * U, 16)))))
    bits_cap = bits + 6  # bound table growth (and host memory) at 64x
    rng = np.random.default_rng(0xC0FFEE)
    # NOTE: do not chase smaller B by growing T — measured on v5e, gather
    # cost is dominated by table footprint (a 262k-row table probes ~8x
    # slower per element than an 8k-row one), so T ~= 4U beats a larger
    # table. The probe's row gather is paid per index, not per word: the
    # ledger reads 15.76 ms / 4M points at B=2 and 15.74 at B=3 (PR 33),
    # and the device fetches a bucket as ONE (3B)-word row (`_hash_rows`),
    # so a smaller B no longer buys device time. The hunt below for a
    # B<=2 multiplier at the SAME T predates that reading (success odds
    # per multiplier are ~1% at T=4U, Poisson tail, so a few hundred
    # tries, microseconds each over U keys, usually land one); whether
    # it still earns its set-up time is open (PERF.md §7).
    cells_u64 = cells.astype(np.uint64)
    counts = np.zeros(1, dtype=np.int64)
    mult = np.uint64(1)
    found = False
    for b2 in (bits, bits + 1):  # one doubling: T=8U at B=2 still beats
        # Poisson estimate of >=3-entry buckets: when e^-E(count) is
        # negligible the hunt cannot succeed — skip instead of burning
        # 400 futile tries (3.7 s at U=200k)
        lam = U / float(1 << b2)
        if (1 << b2) * lam**3 / 6.0 * np.exp(-lam) > 7.0:
            continue
        for _ in range(400):     # T=4U at B=4 (same bytes, half the rows)
            cand = np.uint64(
                rng.integers(0, 2**64, dtype=np.uint64) | np.uint64(1)
            )
            k = (cells_u64 * cand) >> np.uint64(64 - b2)
            c = np.bincount(k.astype(np.int64), minlength=1 << b2)
            if c.max() <= 2:
                mult, keys, counts, found = cand, k, c, True
                bits = b2
                break
        if found:
            break
    if not found:
        for attempt in range(32):
            mult = np.uint64(
                rng.integers(0, 2**64, dtype=np.uint64) | np.uint64(1)
            )
            keys = (cells_u64 * mult) >> np.uint64(64 - bits)
            counts = np.bincount(keys.astype(np.int64), minlength=1 << bits)
            if counts.max() <= max_bucket:
                break
            if attempt < 31 and bits < bits_cap:
                bits += 1  # grow the table if this multiplier clusters
    B = int(counts.max()) if U else 1
    T = 1 << bits
    table_cell = np.full((T, B), -1, dtype=np.int64)
    table_slot = np.full((T, B), -1, dtype=np.int32)
    fill = np.zeros(T, dtype=np.int64)
    for u, (c, k) in enumerate(zip(cells, keys.astype(np.int64))):
        table_cell[k, fill[k]] = c
        table_slot[k, fill[k]] = u
        fill[k] += 1

    # slot-packed variant: if all cells share their low k bits (H3 at a
    # fixed res keeps the unused finer digits constant) and slot+1 fits in
    # k bits, one int64 entry carries both the cell and the slot. The
    # device probe reads `_hash_rows` whether or not an index packs; what
    # still reads this is the benchmark's byte count (`hash_packed`)
    table_pack = np.zeros((0, 0), dtype=np.int64)
    pack_low = np.zeros(2, dtype=np.int64)
    if U:
        diff = np.bitwise_or.reduce(cells ^ cells[0])
        k_bits = int(diff & -diff).bit_length() - 1 if diff else 63
        k_bits = min(k_bits, 62)
        if k_bits > 0 and (U + 1) < (1 << k_bits):
            low = np.int64((1 << k_bits) - 1)
            table_pack = np.where(
                table_slot >= 0,
                (table_cell & ~low) | (table_slot.astype(np.int64) + 1),
                np.int64(0),
            )
            pack_low = np.asarray([low, cells[0] & low], dtype=np.int64)
    return mult, table_cell, table_slot, table_pack, pack_low


def _hash_rows(table_cell: np.ndarray, table_slot: np.ndarray) -> np.ndarray:
    """Host: the (T, B) hash table as the (T, 3B) uint32 rows the device
    probes — per bucket the B cell ids' low words, their high words and
    their slots, so one row gather fetches a bucket whole."""
    cell = table_cell.astype(np.int64, copy=False)
    return np.concatenate(
        [
            (cell & 0xFFFFFFFF).astype(np.uint32),
            (cell >> 32).astype(np.uint32),
            table_slot.astype(np.uint32),  # -1 wraps to 0xFFFFFFFF
        ],
        axis=1,
    )


def _round8(n: int, lo: int = 8) -> int:
    return max(lo, (n + 7) // 8 * 8)


def build_chip_index(
    table: ChipTable,
    dtype=jnp.float32,
    max_chips_per_cell: int | None = None,
    recenter: bool = True,
    edge_cap: int = EDGE_CAP,
) -> ChipIndex:
    """Host: compile a ChipTable into the device join index."""
    if len(table) == 0:
        raise ValueError("empty chip table")
    with _obs_trace.span("index.build", chips=len(table)) as sp:
        idx = _build_chip_index(
            table, dtype, max_chips_per_cell, recenter, edge_cap
        )
        sp.set(
            cells=idx.num_cells, heavy=idx.num_heavy_cells,
            convex=idx.num_convex_cells,
            E1=int(idx.cell_edges.shape[1]),
            M1=int(idx.cell_slot_geom.shape[1]),
            E2=int(idx.heavy_edges.shape[1]),
            M2=int(idx.heavy_slot_geom.shape[1]),
            # a cell past MAX_SLOTS chips a tier is refused, not spilled
            spilled=0,
            # the probe fetches one row of this many u32 words a point
            hash_row_words=int(idx.table_rows.shape[1]),
            hash_gathers=1,
            table_bytes=sum(
                int(getattr(a, "nbytes", 0))
                for a in jax.tree_util.tree_leaves(idx)
            ),
        )
    return idx


def _build_chip_index(
    table, dtype, max_chips_per_cell, recenter, edge_cap
) -> ChipIndex:
    C = len(table)
    order = np.argsort(table.cell_id, kind="stable")
    sorted_cells = table.cell_id[order]
    uniq, starts, counts = np.unique(
        sorted_cells, return_index=True, return_counts=True
    )
    M = int(max_chips_per_cell or counts.max())
    if counts.max() > M:
        raise ValueError(
            f"cell with {counts.max()} chips exceeds max_chips_per_cell={M}"
        )
    U = uniq.size
    rows = np.full((U, M), -1, dtype=np.int32)
    chip_cell_slot = np.full(C, -1, dtype=np.int64)  # chip -> cell row u
    cell_of_sorted = np.repeat(np.arange(U), counts)
    rows[cell_of_sorted, np.arange(C) - np.repeat(starts, counts)] = order
    chip_cell_slot[order] = cell_of_sorted
    # only border rows need vertices: blank core chip geometries before
    # padding so V is set by the clipped border chips, not the cell polygons
    chips = table.chips
    if table.is_core.any() and table.has_geom[table.is_core].any():
        # rebuild with empty geometry for core rows
        from ..core.types import GeometryBuilder, GeometryType

        b = GeometryBuilder()
        for g in range(C):
            if table.is_core[g]:
                b.add_geometry(GeometryType.POLYGON, [[np.zeros((0, 2))]], 0)
            else:
                b.append_from(chips, g)
        chips = b.build()
    # recenter: chips span a city/region, so subtracting the f64 midpoint
    # before narrowing to f32 shrinks the coordinate ulp by ~1e3 (the
    # SURVEY §7 precision strategy) — points are shifted to match in
    # pip_join before they are narrowed. The padded host f64 coordinates
    # are kept (HostRecheck) so the epsilon-band recheck evaluates against
    # the TRUE chips, not their narrowed images; the device tables below
    # narrow from these same host arrays (bitwise-identical to narrowing
    # on device, no device round-trip).
    padded = chips.to_padded(dtype=np.float64)
    shift64 = recenter_shift(padded) if recenter else np.zeros(2)
    bverts64 = np.where(
        (np.asarray(padded.ring_len)[:, :, None] > 0)[..., None],
        np.asarray(padded.verts, dtype=np.float64) - shift64,
        0.0,
    )
    border = to_device(
        padded, dtype=dtype, shifted_verts=bverts64, shift=shift64
    )

    # probe fast path: hash table + flat per-cell edge rows
    mult, table_cell, table_slot, table_pack, _ = _build_hash(uniq)

    from ..core.types import GeometryType

    bverts = bverts64.astype(np.dtype(dtype))  # (C, R, V, 2), recentered
    blen = np.asarray(padded.ring_len)  # (C, R)
    btype = np.asarray(padded.geom_type)
    is_poly = (btype == GeometryType.POLYGON) | (btype == GeometryType.MULTIPOLYGON)
    contributes = is_poly & ~table.is_core  # chips whose edges are probed

    # flat edge extraction: one (chip, ring, e) triple per real edge, in
    # chip-major order (closed rings: vertex ring_len repeats vertex 0)
    Rr, V = bverts.shape[1], bverts.shape[2]
    e_idx = np.arange(V - 1)
    emask = (
        contributes[:, None, None]
        & (e_idx[None, None, :] < blen[:, :, None])
    )  # (C, R, V-1)
    ec, er, ee = np.nonzero(emask)
    e_a = bverts[ec, er, ee]  # (E, 2)
    e_b = bverts[ec, er, ee + 1]
    edges_all = np.concatenate([e_a, e_b], axis=1).astype(bverts.dtype)  # (E,4)
    edges_all64 = np.concatenate(
        [bverts64[ec, er, ee], bverts64[ec, er, ee + 1]], axis=1
    )  # (E, 4) f64 twin, same row order
    e_cell = chip_cell_slot[ec]  # (E,) cell row u per edge

    # per-cell edge totals decide light vs heavy
    epc = np.bincount(e_cell, minlength=U)
    heavy_mask = epc > edge_cap
    heavy_u = np.nonzero(heavy_mask)[0]
    H = heavy_u.size
    cell_heavy = np.full(U, -1, dtype=np.int32)
    cell_heavy[heavy_u] = np.arange(H, dtype=np.int32)

    # chip slot assignment per tier: tier-1 keeps every chip of light cells
    # plus core/non-polygonal chips of heavy cells; heavy border chips get
    # tier-2 slots. Slot numbers are per-cell-local (parity bit positions).
    # Vectorized: per-tier rank within each cell via cumsum-of-flags minus
    # the cumsum at the cell's start (chips in `order` are cell-grouped).
    chip_heavy_tier = contributes & heavy_mask[chip_cell_slot]
    f2 = chip_heavy_tier[order]
    f1 = ~f2
    c1 = np.cumsum(f1)
    c2 = np.cumsum(f2)
    start_pos = np.repeat(starts, counts)  # sorted-pos of each chip's cell start
    base1 = np.concatenate([[0], c1])[start_pos]
    base2 = np.concatenate([[0], c2])[start_pos]
    rank1 = c1 - 1 - base1  # valid where f1
    rank2 = c2 - 1 - base2  # valid where f2
    t1_slot = np.full(C, -1, dtype=np.int64)
    t2_slot = np.full(C, -1, dtype=np.int64)
    t1_slot[order[f1]] = rank1[f1]
    t2_slot[order[f2]] = rank2[f2]
    n1_per_cell = np.bincount(chip_cell_slot[~chip_heavy_tier], minlength=U)
    n2_per_cell = np.bincount(chip_cell_slot[chip_heavy_tier], minlength=U)
    M1 = max(1, int(n1_per_cell.max(initial=0)))
    M2 = max(1, int(n2_per_cell.max(initial=0)))
    if M1 > MAX_SLOTS or M2 > MAX_SLOTS:
        # a step finer cuts a cell into 7 (H3) or 4 (a square grid), and a
        # cell's chips with it at best: the coarsest resolution that can
        # hold the layer is `lo` steps finer, `hi` at the thinner rate
        over = max(M1, M2) / MAX_SLOTS
        lo = int(np.ceil(np.log(over) / np.log(7.0)))
        hi = int(np.ceil(np.log(over) / np.log(4.0)))
        raise ValueError(
            f"a cell holds more than {MAX_SLOTS} chips per probe tier (the "
            f"fullest cell: {M1} chips in tier 1, {M2} in tier 2); parity "
            f"bits are uint32 — merge chips, or tessellate {lo} "
            f"resolution{'s' if lo > 1 else ''} finer at the least "
            f"({hi} on a square grid)"
        )
    slot_geom = np.full((U, M1), -1, dtype=np.int32)
    slot_core = np.zeros((U, M1), dtype=bool)
    ch1 = np.nonzero(~chip_heavy_tier)[0]
    slot_geom[chip_cell_slot[ch1], t1_slot[ch1]] = table.geom_id[ch1].astype(
        np.int32
    )
    slot_core[chip_cell_slot[ch1], t1_slot[ch1]] = table.is_core[ch1]

    # pack tier-1 edges: light-tier edges only, grouped per cell
    t1_edge = t1_slot[ec] >= 0
    E1 = _round8(min(int(epc.max(initial=0)), edge_cap))
    cell_edges = np.zeros((U, E1, 4), dtype=bverts.dtype)
    cell_edges64 = np.zeros((U, E1, 4), dtype=np.float64)
    cell_ebits = np.zeros((U, E1), dtype=np.uint32)
    if t1_edge.any():
        cu = e_cell[t1_edge]
        ord1 = np.argsort(cu, kind="stable")
        cu = cu[ord1]
        ed = edges_all[t1_edge][ord1]
        bits = np.uint32(1) << t1_slot[ec][t1_edge][ord1].astype(np.uint32)
        pos = np.arange(cu.size) - np.searchsorted(cu, cu)
        cell_edges[cu, pos] = ed
        cell_edges64[cu, pos] = edges_all64[t1_edge][ord1]
        cell_ebits[cu, pos] = bits

    # pack tier-2 heavy rows
    if H:
        t2_edge = t2_slot[ec] >= 0
        hrow = cell_heavy[e_cell[t2_edge]].astype(np.int64)
        ord2 = np.argsort(hrow, kind="stable")
        hrow = hrow[ord2]
        ed2 = edges_all[t2_edge][ord2]
        bits2 = np.uint32(1) << t2_slot[ec][t2_edge][ord2].astype(np.uint32)
        eph = np.bincount(hrow, minlength=H)
        E2 = _round8(int(eph.max(initial=1)))
        heavy_edges = np.zeros((H, E2, 4), dtype=bverts.dtype)
        heavy_edges64 = np.zeros((H, E2, 4), dtype=np.float64)
        heavy_ebits = np.zeros((H, E2), dtype=np.uint32)
        pos2 = np.arange(hrow.size) - np.searchsorted(hrow, hrow)
        heavy_edges[hrow, pos2] = ed2
        heavy_edges64[hrow, pos2] = edges_all64[t2_edge][ord2]
        heavy_ebits[hrow, pos2] = bits2
        hgeom = np.full((H, M2), -1, dtype=np.int32)
        ch2 = np.nonzero(chip_heavy_tier)[0]
        hgeom[
            cell_heavy[chip_cell_slot[ch2]], t2_slot[ch2]
        ] = table.geom_id[ch2].astype(np.int32)
    else:
        heavy_edges = np.zeros((0, 8, 4), dtype=bverts.dtype)
        heavy_edges64 = np.zeros((0, 8, 4), dtype=np.float64)
        heavy_ebits = np.zeros((0, 8), dtype=np.uint32)
        hgeom = np.zeros((0, 1), dtype=np.int32)

    coord_scale = (
        float(np.abs(edges_all64).max()) if edges_all64.size else 1.0
    )
    (
        cell_convex, convex_edges, convex_ebits, convex_geom, convex_ybin,
    ) = _build_convex_tables(
        U, epc, heavy_mask, cell_edges, slot_geom, slot_core, coord_scale
    )

    idx = ChipIndex(
        cells=jnp.asarray(uniq, dtype=jnp.int64),
        chip_rows=jnp.asarray(rows),
        chip_geom=jnp.asarray(table.geom_id.astype(np.int32)),
        chip_core=jnp.asarray(table.is_core),
        border=border,
        hash_mult=jnp.asarray(np.asarray([mult], dtype=np.uint64)),
        table_rows=jnp.asarray(_hash_rows(table_cell, table_slot)),
        table_cell=jnp.asarray(table_cell),
        table_slot=jnp.asarray(table_slot),
        table_pack=jnp.asarray(table_pack),
        cell_edges=jnp.asarray(cell_edges),
        cell_ebits=jnp.asarray(cell_ebits),
        cell_slot_geom=jnp.asarray(slot_geom),
        cell_slot_core=jnp.asarray(slot_core),
        cell_heavy=jnp.asarray(cell_heavy),
        heavy_edges=jnp.asarray(heavy_edges),
        heavy_ebits=jnp.asarray(heavy_ebits),
        heavy_slot_geom=jnp.asarray(hgeom),
        cell_convex=jnp.asarray(cell_convex),
        convex_edges=jnp.asarray(convex_edges),
        convex_ebits=jnp.asarray(convex_ebits),
        convex_geom=jnp.asarray(convex_geom),
        convex_ybin=jnp.asarray(convex_ybin),
    )
    # host f64 companion for the epsilon-band recheck — a plain attribute,
    # deliberately OUTSIDE the pytree (jit must never device-put it);
    # absent on indexes reconstructed from flattened pytrees or plain
    # deserialization (see HostRecheck.save_arrays for npz round-trips)
    idx.host = HostRecheck(
        cells=uniq.astype(np.int64),
        cell_edges=cell_edges64,
        cell_ebits=cell_ebits,
        cell_slot_geom=slot_geom,
        cell_slot_core=slot_core,
        cell_heavy=cell_heavy,
        heavy_edges=heavy_edges64,
        heavy_ebits=heavy_ebits,
        heavy_slot_geom=hgeom,
        shift=shift64,
        coord_scale=coord_scale,
    )
    # Voronoi adjacency of the convex chip sites — same non-pytree
    # discipline as ``host`` above; consumed by the KNN serve frontend's
    # convex fast path (mosaic_tpu/knn/frontend.py)
    idx.voronoi = _build_voronoi_tables(
        uniq, cell_convex, epc, cell_edges64, convex_geom, shift64
    )
    return idx


@dataclasses.dataclass
class VoronoiTables:
    """Host-side Voronoi adjacency of the convex chip sites (PAPERS.md:
    *A Novel Point Inclusion Test for Convex Polygons Based on Voronoi
    Tessellations*): one site per convex-lane cell (the single chip's
    vertex centroid), with the Delaunay-dual neighbour lists that make
    "move to the adjacent site closer to the query" walks possible.

    The KNN serve frontend (`mosaic_tpu/knn`) uses the walk twice: to
    order ring expansion by neighbour-of-current-nearest, and to derive
    a kth-distance upper bound that collapses the iterative ring loop
    into one guaranteed-cover dispatch. Correctness never depends on the
    adjacency (the ring cover guarantee is what is exact) — adjacency
    quality only affects how tight the bound is, which is why the
    scipy-less fallback (nearest-``DEG`` sites) is sound.

    Like :class:`HostRecheck` this is a plain attribute on the built
    index, deliberately OUTSIDE the pytree — the walk is host work.

    sites:    (Cv, 2) f64 — convex chip vertex centroids (recentred frame).
    adjacency:(Cv, DEG) int32 — neighbouring convex rows, -1 padded.
    geom:     (Cv,) int32 — the site's source polygon row (== convex_geom).
    cell:     (Cv,) int64 — the site's cell id.
    shift:    (2,) f64 — the recenter origin of ``sites`` (same frame as
              :class:`HostRecheck`); walks subtract it from raw queries.
    method:   "delaunay" | "nearest" — how adjacency was derived.
    """

    sites: np.ndarray
    adjacency: np.ndarray
    geom: np.ndarray
    cell: np.ndarray
    shift: np.ndarray
    method: str

    @property
    def num_sites(self) -> int:
        return int(self.sites.shape[0])


def _voronoi_adjacency(sites: np.ndarray):
    """(Cv, DEG) int32 neighbour lists. Prefers the true Delaunay dual
    (scipy, when the container has it); degrades to the nearest-DEG
    heuristic — a superset-free approximation that only loosens the
    walk's bound, never the exactness of the ring cover pass."""
    Cv = sites.shape[0]
    if Cv <= 1:
        return np.full((Cv, 1), -1, dtype=np.int32), "nearest"
    neigh = [set() for _ in range(Cv)]
    method = "nearest"
    if Cv >= 4:
        try:
            from scipy.spatial import Delaunay  # noqa: PLC0415

            tri = Delaunay(sites)
            for simplex in tri.simplices:
                for i in simplex:
                    for j in simplex:
                        if i != j:
                            neigh[i].add(int(j))
            method = "delaunay"
        except Exception:  # lint: broad-except-ok (scipy absent or degenerate site set — the nearest-neighbour fallback below is always available)
            method = "nearest"
    if method == "nearest":
        deg = min(8, Cv - 1)
        d2 = ((sites[:, None, :] - sites[None, :, :]) ** 2).sum(axis=-1)
        np.fill_diagonal(d2, np.inf)
        nearest = np.argsort(d2, axis=1, kind="stable")[:, :deg]
        for i in range(Cv):
            neigh[i].update(int(j) for j in nearest[i])
            # symmetrize so walks can traverse in both directions
            for j in nearest[i]:
                neigh[int(j)].add(i)
    deg = max(1, max(len(s) for s in neigh))
    adj = np.full((Cv, deg), -1, dtype=np.int32)
    for i, s in enumerate(neigh):
        row = sorted(s)
        adj[i, : len(row)] = row
    return adj, method


def _build_voronoi_tables(
    uniq, cell_convex, epc, cell_edges, convex_geom, shift
) -> VoronoiTables:
    """Host: site + adjacency tables over the convex-lane cells, built
    next to the y-bucketed convex tables from the same edge rows."""
    rows = np.nonzero(cell_convex >= 0)[0]
    Cv = rows.size
    sites = np.zeros((Cv, 2), dtype=np.float64)
    cell = np.zeros(Cv, dtype=np.int64)
    for u in rows:
        r = int(cell_convex[u])
        k = int(epc[u])
        # one closed convex ring: the edge 'a' endpoints enumerate the
        # ring's vertices exactly once
        sites[r] = cell_edges[u, :k, 0:2].astype(np.float64).mean(axis=0)
        cell[r] = uniq[u]
    adj, method = _voronoi_adjacency(sites)
    return VoronoiTables(
        sites=sites, adjacency=adj,
        geom=np.asarray(convex_geom, dtype=np.int32), cell=cell,
        shift=np.asarray(shift, dtype=np.float64), method=method,
    )


def _build_convex_tables(
    U, epc, heavy_mask, cell_edges, slot_geom, slot_core, coord_scale
):
    """Host: classify convex-eligible cells and y-bucket their edges.

    A cell qualifies when it is light, holds exactly one non-core chip
    whose edges form one closed convex ring, and every pad-inflated y
    bucket fits CONVEX_EDGE_CAP edges. The bucketed edges are the SAME
    f32 values as the cell's tier-1 row (bit-identity: the lane evaluates
    the identical crossing arithmetic on a subset of edges that provably
    contains every edge the point's scanline can straddle). Buckets are
    inflated by ``pad = 4·EDGE_BAND_K·eps(f32)·coord_scale``: f32
    bucket-index rounding moves a point across a boundary by at most a
    few ulps (< pad), and the epsilon band reaches at most sqrt(eps²)
    <= pad/2 beyond the straddle set while the runtime guard
    ``eps² <= band_guard² = (pad/2)²`` holds.
    """
    KB = CONVEX_BUCKETS
    pad = 4.0 * EDGE_BAND_K * float(np.finfo(np.float32).eps) * coord_scale
    cell_convex = np.full(U, -1, dtype=np.int32)
    picked = []  # (u, (KB, EB) edge-index lists, ymin, inv)
    n_slots = (slot_geom >= 0).sum(axis=1)
    cand = np.nonzero(
        (~heavy_mask)
        & (n_slots == 1)
        & (slot_geom[:, 0] >= 0)
        & (~slot_core[:, 0])
        & (epc >= 3)
    )[0]
    for u in cand:
        k = int(epc[u])
        ef = cell_edges[u, :k].astype(np.float64)  # the probed f32 values
        # one closed ring: each edge's b is the next edge's a (cyclic);
        # multi-ring chips (holes) break the chain and fall out here
        if not np.array_equal(ef[:, 2:4], np.roll(ef[:, 0:2], -1, axis=0)):
            continue
        d = ef[:, 2:4] - ef[:, 0:2]
        cr = d[:, 0] * np.roll(d[:, 1], -1) - d[:, 1] * np.roll(d[:, 0], -1)
        if not (np.all(cr >= 0) or np.all(cr <= 0)):
            continue
        ys = np.concatenate([ef[:, 1], ef[:, 3]])
        ymin, ymax = float(ys.min()), float(ys.max())
        height = ymax - ymin
        if not height > 4.0 * pad:  # degenerate: buckets would alias
            continue
        hb = height / KB
        elo = np.minimum(ef[:, 1], ef[:, 3])
        ehi = np.maximum(ef[:, 1], ef[:, 3])
        buckets = []
        for b in range(KB):
            blo = ymin + b * hb - pad
            bhi = ymin + (b + 1) * hb + pad
            sel = np.nonzero((ehi >= blo) & (elo <= bhi))[0]
            if sel.size > CONVEX_EDGE_CAP:
                buckets = None
                break
            buckets.append(sel)
        if buckets is None:
            continue
        picked.append((u, buckets, np.float32(ymin), np.float32(KB / height)))
    Cv = len(picked)
    if not Cv:
        return (
            cell_convex,
            np.zeros((0, KB, 8, 4), dtype=cell_edges.dtype),
            np.zeros((0, KB, 8), dtype=np.uint32),
            np.zeros((0,), dtype=np.int32),
            np.zeros((0, 3), dtype=np.float32),
        )
    EB = _round8(max(max(s.size for s in bk) for _, bk, _, _ in picked))
    convex_edges = np.zeros((Cv, KB, EB, 4), dtype=cell_edges.dtype)
    convex_ebits = np.zeros((Cv, KB, EB), dtype=np.uint32)
    convex_geom = np.zeros(Cv, dtype=np.int32)
    convex_ybin = np.zeros((Cv, 3), dtype=np.float32)
    for row, (u, buckets, ymin, inv) in enumerate(picked):
        cell_convex[u] = row
        convex_geom[row] = slot_geom[u, 0]
        convex_ybin[row] = (ymin, inv, np.float32((pad / 2.0) ** 2))
        for b, sel in enumerate(buckets):
            convex_edges[row, b, : sel.size] = cell_edges[u, sel]
            convex_ebits[row, b, : sel.size] = 1
    return cell_convex, convex_edges, convex_ebits, convex_geom, convex_ybin


def _probe_slot(pcells: jax.Array, index: ChipIndex) -> jax.Array:
    """(N,) cell ids -> (N,) cell row u, -1 on miss — the multiply-shift
    hash probe: ONE row gather fetches a point's bucket whole."""
    T, B = index.table_rows.shape[0], index.table_rows.shape[1] // 3
    pu = pcells.astype(jnp.uint64)
    key = (
        (pu * index.hash_mult[0]) >> jnp.uint64(64 - int(np.log2(T)))
    ).astype(jnp.int32)
    # the chip writes the gathered (N, 3B) block row-major, each row padded
    # to 128 lanes, and a compare that slices it reads all of it once a
    # slice (2.76 ms a pass at 4M rows). Held point-minor it is dense: one
    # transposing copy, then the compare costs nothing (PERF.md §6, PR 34)
    words = _with_layout(
        index.table_rows[key].T, _Layout(major_to_minor=(0, 1))
    )  # (3B, N): low words | high words | slots
    slot = jax.lax.bitcast_convert_type(words[2 * B:], jnp.int32)
    match = (
        (words[:B] == pu.astype(jnp.uint32))
        & (words[B:2 * B] == (pu >> jnp.uint64(32)).astype(jnp.uint32))
        & (slot >= 0)
    )
    return jnp.max(jnp.where(match, slot, -1), axis=0)  # (N,)


@jax.named_scope("pip.counts")
def _probe_counts(pcells: jax.Array, index: ChipIndex, probe: str = "scatter"):
    """The probe that sizes `pip_join`'s caps, and answers: ``(counts, u)``.

    ``counts`` is one (3,) array of (found count, heavy-cell count,
    convex-cell count) — `pip_join` pulls these ints in a single transfer
    instead of the whole cell column (32 MB at 4M points) and sizes its
    compaction caps from them. ``u`` is the (N,) int32 slot column the
    counts were taken on (`_probe_slot`: a row's cell row, -1 on a miss).
    It stays on the device: `pip_join` hands it to the join program as
    ``slots=``, which then holds no probe of its own — the table is read
    once a chunk, here.

    The heavy count gathers ``cell_heavy`` over all rows where the index
    has heavy cells. The convex count gathers ``cell_convex`` only where a
    lane reads it: under an adaptive ``probe`` (static) on an index with
    convex cells; under ``probe="scatter"`` no cap is sized from it, so it
    is not taken and reads 0 (26.5 ms of a 4M-row call on v5e: PERF.md
    section 6, PR 50).

    Scopes: the probe sits under ``pip.hash_probe`` (the innermost scope
    names an op's stage in `obs.stages`), the sums and the heavy / convex
    gathers under ``pip.counts``."""
    with jax.named_scope("pip.hash_probe"):
        u = _probe_slot(pcells, index)
    found = u >= 0
    nf = found.sum()
    us = jnp.maximum(u, 0)
    if index.heavy_edges.shape[0]:
        nh = (jnp.where(found, index.cell_heavy[us], -1) >= 0).sum()
    else:
        nh = jnp.zeros((), nf.dtype)
    if probe != "scatter" and index.convex_edges.shape[0]:
        nc = (jnp.where(found, index.cell_convex[us], -1) >= 0).sum()
    else:
        nc = jnp.zeros((), nf.dtype)
    return jnp.stack([nf, nh, nc]), u


def _ray_parity(px, py, edges, bits, eps2=None):
    """XOR-accumulated crossing parity bits.

    px, py: (...,); edges: (..., E, 4) ax/ay/bx/by; bits: (..., E) uint32
    (0 for pad edges — a zero edge has ay == by so it never straddles).
    Returns (...,) uint32 where bit m is the ray-crossing parity of chip
    slot m. With ``eps2`` (scalar, squared length), additionally returns
    the epsilon-band mask: True where the point lies within sqrt(eps2) of
    any real edge segment — the only geometry where the f32 crossing
    decision can disagree with f64 (fused into the same pass so the edge
    gather is paid once).
    """
    ax, ay = edges[..., 0], edges[..., 1]
    bx, by = edges[..., 2], edges[..., 3]
    pyb, pxb = py[..., None], px[..., None]
    straddle = (ay > pyb) != (by > pyb)
    denom = jnp.where(by == ay, jnp.ones_like(by), by - ay)
    xcross = ax + (pyb - ay) * (bx - ax) / denom
    crossed = straddle & (pxb < xcross)
    vals = jnp.where(crossed, bits, jnp.zeros_like(bits))
    par = jax.lax.reduce(
        vals, np.uint32(0), jax.lax.bitwise_xor, (vals.ndim - 1,)
    )
    if eps2 is None:
        return par
    ex, ey = bx - ax, by - ay
    qx, qy = pxb - ax, pyb - ay
    dd = ex * ex + ey * ey
    t = jnp.clip((qx * ex + qy * ey) / jnp.where(dd == 0, 1.0, dd), 0.0, 1.0)
    rx, ry = qx - t * ex, qy - t * ey
    near = jnp.any((rx * rx + ry * ry <= eps2) & (bits != 0), axis=-1)
    return par, near


def _slot_best(parity, geoms, cores=None):
    """Smallest geom id among hit slots (SENTINEL if none).

    parity: (...,) uint32; geoms: (..., M) int32 (-1 pad);
    cores: (..., M) bool or None.
    """
    Mn = geoms.shape[-1]
    m = jnp.arange(Mn, dtype=jnp.uint32)
    inside = ((parity[..., None] >> m) & jnp.uint32(1)).astype(bool)
    hit = inside if cores is None else (cores | inside)
    hit = hit & (geoms >= 0)
    return jnp.min(jnp.where(hit, geoms, _SENTINEL), axis=-1)


_SCAN_COLS = 2048


def _prefix_inclusive(flag_i32: jax.Array) -> jax.Array:
    """Inclusive prefix sum of (N,) 0/1 int32, N >= 1.

    `jnp.cumsum` lowers to an XLA reduce-window that costs ~22 ms for 4M
    elements on v5e; a row-reshaped prefix by upper-triangular-ones matmul
    runs on the MXU in ~2 ms. f32 HIGHEST keeps counts exact only below
    2^24, so batches that could overflow fall back to the exact cumsum
    (as do small batches, where the matmul setup dominates).
    """
    n = flag_i32.shape[0]
    if n < 4 * _SCAN_COLS or n >= (1 << 24):
        return jnp.cumsum(flag_i32)
    c = _SCAN_COLS
    r = (n + c - 1) // c
    # device-built mask: a module-level numpy constant would bake 16 MB
    # into every executable that traces this
    tri = (
        jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
        <= jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    ).astype(jnp.float32)
    x = jnp.zeros(r * c, jnp.float32).at[: n].set(flag_i32.astype(jnp.float32))
    x2 = x.reshape(r, c)
    p = jax.lax.dot(x2, tri, precision=jax.lax.Precision.HIGHEST)
    rowsum = p[:, -1]
    rowoff = jnp.cumsum(rowsum) - rowsum
    return (p + rowoff[:, None]).reshape(-1)[:n].astype(jnp.int32)


@jax.named_scope("pip.compact")
def _compact(flag: jax.Array, cap: int):
    """Stream-compact: indices of up-to-``cap`` True rows (static shape).

    Returns (src (cap,) int32, valid (cap,) bool, overflow (N,) bool,
    pos (N,) int32): ``src`` lists the first ``cap`` flagged row ids
    (padded with 0, masked by ``valid``); ``overflow`` marks flagged rows
    beyond ``cap``; ``pos`` is each row's compacted slot (exclusive
    prefix — meaningful where ``flag``), which lets callers invert the
    compaction by GATHER instead of scatter.

    The scatter destinations are *globally unique*: flagged rows write
    their row id to their exclusive-prefix slot (all distinct, < cap);
    non-flagged rows aim at ``cap + (i - pos_i)`` — strictly increasing
    out-of-bounds slots that ``mode="drop"`` discards. A unique
    no-combiner scatter is the cheapest XLA can lower on TPU: 18.8 ms at
    4M points vs 35.2 ms for the previous sorted min-combiner
    formulation (the single largest op in the traced join step; the
    sorted-add variant also measures 35 ms).
    """
    n = flag.shape[0]
    incl = _prefix_inclusive(flag.astype(jnp.int32))
    pos = incl - flag.astype(jnp.int32)  # exclusive prefix
    iota = jnp.arange(n, dtype=jnp.int32)
    # flagged rows land on pos (<= n); non-flagged on cap+n+(i-pos_i),
    # strictly increasing from cap+n — the two ranges cannot collide, so
    # every index is globally unique even for dropped overflow rows
    dest = jnp.where(flag, pos, cap + n + (iota - pos))
    src = (
        jnp.zeros(cap, dtype=jnp.int32)
        .at[dest]
        .set(iota, unique_indices=True, mode="drop")
    )
    count = incl[-1]
    valid = jnp.arange(cap, dtype=jnp.int32) < count
    return src, valid, flag & (pos >= cap), pos


def _tier1_rows_gather(us: jax.Array, index: "ChipIndex"):
    """All tier-1 per-cell rows for slots ``us`` in TWO row gathers: the
    edge row, and one int32 row of everything else. Exact by construction
    (rows are moved, never converted: ebits travel bit-cast, bools as
    0/1), for any edge dtype. Returns (edges (K, E1, 4), ebits (K, E1)
    u32, geoms (K, M1) i32, cores (K, M1) bool, heavy (K,) i32).

    A gather is paid per gather, not per byte (traced on v5e, each of
    the four separate gathers cost 17-26 ms a 4M-row step and the 4-byte
    slot_core row the most: PERF.md section 6, PR 25), so the four small
    tables travel as one packed row. The edge row is fetched
    component-major ([ax.. | ay.. | bx.. | by..], a transposed copy built
    in-program: 13 MB at 33,898 cells), so each coordinate the parity
    test reads is a contiguous lane range of the fetched row; gathered
    as (K, E1, 4), XLA laid the rows out E1-minor and padded to 128
    lanes: 15.3 GB at K = 4M, and 57 ms of parity where this form takes
    13.5.
    """
    U, E1 = index.cell_ebits.shape
    M1 = index.cell_slot_geom.shape[1]
    edges = jnp.moveaxis(index.cell_edges, 2, 1).reshape(U, 4 * E1)[us]
    rest = jnp.concatenate(
        [
            jax.lax.bitcast_convert_type(index.cell_ebits, jnp.int32),
            index.cell_slot_geom.astype(jnp.int32),
            index.cell_slot_core.astype(jnp.int32),
            index.cell_heavy.astype(jnp.int32)[:, None],
        ],
        axis=1,
    )[us]
    return (
        jnp.moveaxis(edges.reshape(-1, 4, E1), 1, 2),
        jax.lax.bitcast_convert_type(rest[:, :E1], jnp.uint32),
        rest[:, E1 : E1 + M1],
        rest[:, E1 + M1 : E1 + 2 * M1] != 0,
        rest[:, E1 + 2 * M1],
    )


@jax.named_scope("pip.tier2")
def _heavy_tier(px, py, hs, index, heavy_cap, eps2, engine="gather"):
    """Tier 2, shared by every probe plumbing mode: probe the wide rows of
    the points whose cell is heavy (``hs >= 0``, a heavy-table row each).

    Where ``heavy_cap`` is under the row count (:func:`tier2_compacts`)
    those rows are compacted into ``heavy_cap`` slots, probed there and
    scattered back; rows beyond the cap overflow. Where it is not (no cap,
    or a cap of all the rows: the stream, full-bucket caps) compaction
    would move every row into as many slots and back, so each row probes
    the wide row of ``max(hs, 0)`` in place and rows with ``hs < 0`` are
    masked; no row can overflow. Rows are independent and nothing is
    converted: the answers are the same bit for bit.

    ``engine="pallas"`` runs the probe through the tiled
    :func:`~mosaic_tpu.kernels.pip.pip_heavy_tiled` kernel (heavy tables
    pinned in VMEM, bit-identical crossing arithmetic) instead of the
    row-gather + `_ray_parity` pipeline; the kernel is interpreted only
    on the CPU platform (`runtime.platform.interpret_kernels`), so CPU
    tests exercise the same kernel and every chip compiles it.

    Returns (best2, over2 overflow mask, near2 | None when ``eps2`` is
    None), each of ``hs``'s length."""
    rows = hs.shape[0]

    def _tier2(px_c, py_c, h_c, valid_c):
        """The wide rows' probe for rows ``h_c``. Row-wise."""
        if engine == "pallas":
            from ..kernels.pip import pip_heavy_tiled

            best_c, near_c = pip_heavy_tiled(
                px_c, py_c, jnp.where(valid_c, h_c, -1),
                index.heavy_edges, index.heavy_ebits, index.heavy_slot_geom,
                eps2=eps2, interpret=interpret_kernels(),
            )
            if near_c is None and eps2 is not None:  # pragma: no cover
                near_c = jnp.zeros(px_c.shape[0], bool)
            return best_c, near_c
        hedges, hebits = index.heavy_edges[h_c], index.heavy_ebits[h_c]
        hgeoms = index.heavy_slot_geom[h_c]
        r2 = _ray_parity(px_c, py_c, hedges, hebits, eps2=eps2)
        par2, near_c = r2 if eps2 is not None else (r2, None)
        # invalid rows never land (dropped, or masked in place)
        return _slot_best(par2, hgeoms), near_c

    def _tier2_rows(*cols):
        # rows are independent, so chunks are exact (see `_tier1_rows`); a
        # stream passes no cap, so tier 2 sees the batch, and 4M gathered
        # rows of E2 = 80 edges are 5 GB before lane padding. The chunk
        # holds as many edges as tier 1's holds at its widest row
        chunk2 = max(
            128,
            _TIER1_CHUNK * EDGE_CAP
            // int(index.heavy_edges.shape[1]) // 128 * 128,
        )
        if cols[0].shape[0] > chunk2:
            return _map_rows(_tier2, chunk2, *cols)
        return _tier2(*cols)

    if not tier2_compacts(rows, heavy_cap):
        # in place: every row fetches a wide row, the rows of no heavy
        # cell (row 0, masked) included; no row can overflow tier 2
        heavy = hs >= 0
        best2, near2 = _tier2_rows(px, py, jnp.maximum(hs, 0), heavy)
        return (
            jnp.where(heavy, best2, _SENTINEL),
            jnp.zeros(rows, bool),
            near2 & heavy if eps2 is not None else None,
        )

    K2 = _cap_rows(heavy_cap, rows)
    src2, valid2, over2, _ = _compact(hs >= 0, K2)
    h2 = jnp.maximum(hs[src2], 0)
    # one (K2, 2) gather, not two serialized column gathers (see tier 1)
    pq2 = jnp.stack([px, py], axis=1)[src2]
    best2k, near2 = _tier2_rows(pq2[:, 0], pq2[:, 1], h2, valid2)
    # unique no-combiner scatter back (see _compact): valid src2 row ids
    # are unique; invalid slots drop via distinct out-of-bounds dests
    dest2 = jnp.where(
        valid2, src2, rows + jnp.arange(src2.shape[0], dtype=jnp.int32)
    )
    best2 = (
        jnp.full(rows, _SENTINEL, dtype=jnp.int32)
        .at[dest2]
        .set(best2k, unique_indices=True, mode="drop")
    )
    near_sc = (
        jnp.zeros(rows, bool)
        .at[dest2]
        .set(near2, unique_indices=True, mode="drop")
        if eps2 is not None
        else None
    )
    return best2, over2, near_sc


#: lanes a forced-adaptive probe can pin (MOSAIC_PROBE_FORCE_LANE)
_PROBE_LANES = ("light", "heavy", "convex")


def _probe_modes():
    return ("scatter", "adaptive") + tuple(
        f"adaptive-{ln}" for ln in _PROBE_LANES
    )


def resolve_probe_mode(probe: str) -> str:
    """Normalize a ``probe`` argument, folding in the force-lane env knob.

    ``MOSAIC_PROBE_FORCE_LANE=light|heavy|convex`` rewrites ``adaptive``
    to the pinned variant ``adaptive-<lane>`` HERE — before the value
    reaches any jit static argument — so the knob can never be baked
    stale into a compiled program's cache entry.
    """
    if probe not in _probe_modes():
        raise ValueError(
            f"probe must be one of {_probe_modes()}, got {probe!r}"
        )
    if probe == "adaptive":
        lane = os.environ.get("MOSAIC_PROBE_FORCE_LANE", "").strip().lower()
        if lane:
            if lane not in _PROBE_LANES:
                raise ValueError(
                    f"MOSAIC_PROBE_FORCE_LANE must be one of "
                    f"{_PROBE_LANES}, got {lane!r}"
                )
            return f"adaptive-{lane}"
    return probe


def _cap_rows(cap: "int | None", rows: int) -> int:
    """Slots a tier compacts ``rows`` rows into under ``cap`` (None or 0:
    no cap): at least 8, at most ``rows`` — unless ``rows`` is under 8."""
    return max(8, min(int(cap) if cap else rows, rows))


def tier1_compacts(
    n: int, found_cap: "int | None", probe: str, writeback: str = "scatter"
) -> bool:
    """Whether an ``n``-row `pip_join_points` program compacts its found
    rows before tier 1: the one rule, from static facts, at trace time.

    Compaction reorders the found rows into ``K1 = max(8, min(found_cap
    or n, n))`` slots so that tier 1 tests ``K1`` rows instead of ``n``.
    With ``K1 >= n`` (no cap, or a cap of the whole batch: the stream,
    `DispatchCore`'s full-bucket caps, `pip_join` on a batch more than
    half found) it shortens nothing and no row can overflow, so the
    program tests the rows in place, as ``writeback="direct"`` always
    does. An adaptive probe always compacts: its lanes split the found
    rows between them. Read on v5e in PERF.md section 6, PR 30.
    """
    if probe != "scatter":
        return True
    if writeback == "direct":
        return False
    return _cap_rows(found_cap, n) < n


def tier2_compacts(rows: int, heavy_cap: "int | None") -> bool:
    """Whether tier 2 compacts the ``rows`` rows it is handed (the batch
    where tier 1 ran in place, tier 1's ``K1`` slots where it compacted)
    before it probes the wide rows: :func:`tier1_compacts`' rule one tier
    down. With ``K2 = max(8, min(heavy_cap or rows, rows)) >= rows`` (no
    cap: the stream; `DispatchCore`'s full-bucket caps; `pip_join` where
    its count sizes the cap at the batch) compaction selects nothing, so
    `_heavy_tier` probes every row in place. Read on v5e in PERF.md
    section 6, PR 32."""
    return _cap_rows(heavy_cap, rows) < rows


def tier2_compacted(
    n: int, num_heavy_cells: int, found_cap: "int | None",
    heavy_cap: "int | None", probe: str, writeback: str = "scatter",
) -> bool:
    """:func:`tier2_compacts` of an ``n``-row `pip_join_points` program,
    for the counters beside ``compacted``: False where the index has no
    heavy cell (no tier 2 is compiled)."""
    if not num_heavy_cells:
        return False
    rows = (
        _cap_rows(found_cap, n)
        if tier1_compacts(n, found_cap, probe, writeback)
        else n
    )
    return tier2_compacts(rows, heavy_cap)


def _map_rows(fn, chunk: int, *cols):
    """``fn(*cols)`` over row chunks of at most ``chunk`` by `lax.map`, for
    a row-wise ``fn`` (row i of every output depends on row i of the
    inputs alone): bounds ``fn``'s intermediates by the chunk, results
    unchanged. ``cols`` share their leading length; ``fn`` returns a
    pytree of arrays of that leading length (``None`` leaves pass)."""
    n = cols[0].shape[0]
    n_ch = -(-n // chunk)
    # equal lane-aligned chunks: 4M rows are 4 x 1,000,064, not 4 x 2^20
    ch = -(-(-(-n // n_ch)) // 128) * 128
    pad = n_ch * ch - n
    res = jax.lax.map(
        lambda c: fn(*c),
        tuple(
            jnp.pad(c, [(0, pad)] + [(0, 0)] * (c.ndim - 1)).reshape(
                (n_ch, ch) + c.shape[1:]
            )
            for c in cols
        ),
    )
    return jax.tree.map(
        lambda r: r.reshape((n_ch * ch,) + r.shape[2:])[:n], res
    )


def pip_join_points(
    points: jax.Array,
    pcells: jax.Array,
    index: ChipIndex,
    heavy_cap: int | None = None,
    found_cap: int | None = None,
    edge_eps2: jax.Array | None = None,
    writeback: str = "scatter",
    probe: str = "scatter",
    convex_cap: int | None = None,
    slots: jax.Array | None = None,
) -> jax.Array:
    """(N,) int32 — smallest matching polygon row per point, -1 if none.

    Jittable (``heavy_cap``/``found_cap`` static); shard the point axis over
    a mesh and replicate ``index``. Probe = hash lookup (1 gather), then a
    flat bounded edge gather + XOR crossing parity (tier 1); points in heavy
    cells also probe the heavy table's wide rows (tier 2: compacted into
    ``heavy_cap`` slots first where that is under the rows tier 1 hands
    over, in place where it is not — :func:`tier2_compacts`, the same rule
    one tier down, the same answers). Where ``found_cap`` is under
    the row count, the points whose cell exists in the index are
    stream-compacted into ``found_cap`` slots first, so tier 1 tests that
    many rows and the misses skip all edge work. Where it is not (no cap,
    or a cap of the whole batch) compaction would reorder N rows into N
    slots and back, so tier 1 tests every row in place and masks the
    misses: :func:`tier1_compacts` is the rule, static, evaluated at
    trace time, and the answers are the same bit for bit.

    ``found_cap`` bounds how many points per call may hit an indexed cell
    and ``heavy_cap`` how many may land in heavy cells. Both default to
    their exact upper bound (N / found_cap), so an uncapped call is always
    exact — tighter caps are a performance knob. If a cap is exceeded the
    excess points return :data:`OVERFLOW` (-2) instead of a wrong answer;
    `pip_join` sizes both caps exactly from device-side counts.

    ``edge_eps2`` (scalar array, squared length) switches on the epsilon
    band: returns ``(out, near)`` where ``near`` marks points within
    sqrt(edge_eps2) of any probed chip edge — the set whose f32 parity may
    disagree with f64 (`pip_join` rechecks them on the host oracle).

    ``writeback`` picks the probe plumbing under a cap that cuts rows —
    identical results: ``"scatter"`` compacts found points then returns
    results via a unique-destination set scatter; ``"gather"`` compacts
    but inverts by per-point gather of the prefix slot; ``"direct"`` never
    compacts tier 1, whatever the cap — every point gathers its own edge
    rows (wasted gathers on misses, but no prefix scan, no point
    permutation and no writeback; ``found_cap`` is ignored and tier-1
    overflow is impossible). On v5e at 4M rows and E1 = 24 (PERF.md
    section 6, PR 30) the join in place takes 93.5 ms at every found
    share; compacted into N - 1 slots 183.0 (``gather`` 186.1), into N/2
    121.2 (136.0), into N/4 89.8 (110.6), into N/16 68.5 (93.2):
    compaction costs 57 ms that scale with N plus 126 ms x cap / N, and
    pays under a cap of about 0.29 N. Tier 2's readings (E2 = 88, a third
    of the rows heavy): PERF.md section 6, PR 32.

    ``probe="adaptive"`` switches on per-cell density routing inside this
    one jitted program: light cells keep the tier-1 path above, heavy
    cells run tier 2 through the tiled Pallas kernel
    (:func:`~mosaic_tpu.kernels.pip.pip_heavy_tiled`, interpreted on the
    CPU platform only), and convex single-chip cells divert to a y-bucketed
    reduced-edge test sized by ``convex_cap`` (default exact: N).
    Results are bit-identical to ``probe="scatter"`` — the kernel
    reproduces `_ray_parity`'s evaluation order and the convex tables
    hold the same f32 edge values as tier 1. ``adaptive-light`` /
    ``adaptive-heavy`` / ``adaptive-convex`` pin one lane for isolation
    (benchmarks, the CI probe-smoke gate); `resolve_probe_mode` folds
    the ``MOSAIC_PROBE_FORCE_LANE`` env knob into these pinned values
    before jit ever sees the argument. Convex-lane overflow returns
    :data:`OVERFLOW`, exactly like the other caps.

    ``slots`` is for the caller that has probed already: the (N,) int32
    slot column of ``pcells`` on ``index`` (`_probe_slot`: a row's cell
    row, -1 on a miss), as `_probe_counts` returns it beside the counts.
    Given it, the program reads it where it would have probed — no hash,
    no gather of ``table_rows`` — and ``pcells`` is not read and may be
    None; the rows returned are the probing call's bit for bit. `pip_join`
    hands each chunk's column from its count sync to the join (and to
    every escalation of it, and the band's from the recheck's own count to
    the narrow re-join). Without it (the stream, `DispatchCore`, the raster
    probe, the sharded lanes: callers with no count sync) the program
    probes, as ever.
    """
    out, near, _ = _join_points(
        points, pcells, index, heavy_cap, found_cap, edge_eps2, writeback,
        probe, convex_cap, with_heavy=False, slots=slots,
    )
    return out if edge_eps2 is None else (out, near)


def pip_join_points_heavy(
    points, pcells, index, heavy_cap=None, found_cap=None, probe="scatter",
    convex_cap=None,
):
    """:func:`pip_join_points` and, beside the rows, the (N,) bool mask of
    the points whose cell is heavy (the rows that need tier 2) — the
    stream folds its count. For an index with heavy cells only."""
    out, _, heavy = _join_points(
        points, pcells, index, heavy_cap, found_cap, None, "scatter", probe,
        convex_cap, with_heavy=True,
    )
    return out, heavy


def _join_points(
    points, pcells, index, heavy_cap, found_cap, edge_eps2, writeback, probe,
    convex_cap, *, with_heavy, slots=None,
):
    """The join behind :func:`pip_join_points`: ``(out, near | None,
    heavy | None)``; ``heavy`` only where ``with_heavy`` and H > 0. With
    ``slots`` the probe's answer is an input and ``pcells`` is not read."""
    if writeback not in ("scatter", "gather", "direct"):
        raise ValueError(
            f"writeback must be scatter|gather|direct, got {writeback!r}"
        )
    # validate only — no env fold here: this function is jit-traced
    # (`dispatch.jit_join` keys its compile cache on the UNRESOLVED
    # `probe` static arg), so reading MOSAIC_PROBE_FORCE_LANE at this point
    # would bake the first-seen lane into the cached program. Host-side
    # entry points (pip_join, stream, serve, dist_join) fold the knob
    # via `resolve_probe_mode` before staging.
    if probe not in _probe_modes():
        raise ValueError(
            f"probe must be one of {_probe_modes()}, got {probe!r}"
        )
    adaptive = probe != "scatter"
    if adaptive and writeback == "direct":
        raise ValueError(
            "probe='adaptive' routes through compaction; it composes "
            "with writeback scatter|gather, not direct"
        )
    if pcells is None and slots is None:
        raise ValueError("pip_join_points needs pcells to probe, or slots")
    N = points.shape[0]
    # named scopes mark the probe stages: every instruction of the join
    # sits under exactly one innermost pip.* scope, which
    # `obs.stages` reads back from the optimized HLO to name the device
    # trace's ops (scopes are HLO metadata only — results do not change)
    with jax.named_scope("pip.hash_probe"):
        u = _probe_slot(pcells, index) if slots is None else slots
        found = u >= 0
    banded = edge_eps2 is not None
    H = int(index.heavy_edges.shape[0])
    CV = int(index.convex_edges.shape[0])
    # adaptive per-cell routing: the density class is a table lookup, so
    # the route costs one extra (N,) gather. Convex cells leave the light
    # lane; heavy POINTS stay in it (their tier-1 row holds the cell's
    # core/light chips — the Pallas lane replaces only the tier-2 probe).
    use_convex = adaptive and CV > 0 and probe in ("adaptive", "adaptive-convex")
    heavy_engine = (
        "pallas"
        if adaptive
        and probe in ("adaptive", "adaptive-heavy")
        and index.heavy_edges.dtype == jnp.float32
        else "gather"
    )
    if use_convex:
        with jax.named_scope("pip.route"):
            cvrow = jnp.where(
                found, index.cell_convex[jnp.maximum(u, 0)], -1
            )
            conv = cvrow >= 0
            if banded:
                # band exactness holds only while eps² fits under the
                # bucket pad guard; wider bands fall back to tier 1
                guard2 = index.convex_ybin[jnp.maximum(cvrow, 0), 2]
                conv = conv & (edge_eps2 <= guard2)
    else:
        conv = None

    def _tier1(px_c, py_c, us_c):
        """Tier 1 for rows already matched to cell slots ``us_c``: fetch
        the cell's row, test the crossings, pick the slot. Row-wise."""
        edges1, ebits1, geoms1, cores1, heavy1 = _tier1_rows_gather(
            us_c, index
        )
        r1 = _ray_parity(px_c, py_c, edges1, ebits1, eps2=edge_eps2)
        parity, near1 = r1 if banded else (r1, None)
        return (
            _slot_best(parity, geoms1, cores1), near1,
            heavy1 if H else None,
        )

    def _tier1_rows(px_c, py_c, us_c):
        if us_c.shape[0] > _TIER1_CHUNK:
            # rows are independent, so chunks are exact; they bound the
            # fetched rows' footprint (128-lane padded): a 4M-row stream
            # loop holds 3.9 GB of temporaries in 1M-row chunks against
            # 11.1 GB whole, at the same step time on v5e (PERF.md
            # section 6, PR 25). Direct mode's un-compacted rows crossed
            # XLA's 2 GB buffer limit above ~2M points unchunked
            # (tpu_compile_helper crash, observed at 4M on v5e)
            return _map_rows(_tier1, _TIER1_CHUNK, px_c, py_c, us_c)
        return _tier1(px_c, py_c, us_c)

    if not tier1_compacts(N, found_cap, probe, writeback):
        # in place: every row fetches its own tier-1 row, misses (slot 0,
        # masked by `found`) included; no row can overflow tier 1
        with jax.named_scope("pip.tier1"):
            us = jnp.maximum(u, 0)
            best, near1, heavy_d = _tier1_rows(
                points[:, 0], points[:, 1], us
            )
            best = jnp.where(found, best, _SENTINEL)
            if H:
                hs = jnp.where(found, heavy_d, -1)
                best2, over2, near_sc = _heavy_tier(
                    points[:, 0], points[:, 1], hs, index, heavy_cap,
                    edge_eps2,
                )
                best = jnp.minimum(best, best2)
                best = jnp.where(over2, _OVF_MARK, best)
                if banded:
                    near1 = near1 | near_sc
        with jax.named_scope("pip.writeback"):
            out = jnp.where(best == _SENTINEL, -1, best).astype(jnp.int32)
            out = jnp.where(best == _OVF_MARK, OVERFLOW, out)
            return (
                out, near1 & found if banded else None,
                hs >= 0 if H and with_heavy else None,
            )

    with jax.named_scope("pip.compact"):
        light = found if conv is None else (found & ~conv)
        K1 = _cap_rows(found_cap, N)
        src1, valid1, over1, pos1 = _compact(light, K1)
        us = jnp.maximum(u[src1], 0)  # (K1,)
        # ONE (K1, 2) row gather: indexing the columns separately makes XLA
        # emit two serialized point gathers (traced at ~14 ms EACH at 4M/640k)
        pxy = points[src1]
        px, py = pxy[:, 0], pxy[:, 1]

    with jax.named_scope("pip.tier1"):
        best1, near1, heavy1 = _tier1_rows(px, py, us)
        best1 = jnp.where(valid1, best1, _SENTINEL)

    if H:
        with jax.named_scope("pip.tier2"):
            # tier 2: the slots whose cell is heavy (compacted again
            # where `heavy_cap` is under K1: `tier2_compacts`)
            hs = jnp.where(valid1, heavy1, -1)
            best2, over2, near_sc = _heavy_tier(
                px, py, hs, index, heavy_cap, edge_eps2, engine=heavy_engine,
            )
            best1 = jnp.minimum(best1, best2)
            # an overflowed tier-2 point has an unknown answer even if tier 1
            # hit: mark it (each compacted row writes its own unique slot, so
            # the mark survives the writeback scatter verbatim)
            best1 = jnp.where(over2, _OVF_MARK, best1)
            if banded:
                near1 = near1 | near_sc

    if use_convex:
        # convex lane: compact, y-bucket, probe at most EB edges/point.
        # The single-chip eligibility contract makes `parity bit 0 set ->
        # that chip's geom` exactly _slot_best on the cell's tier-1 row.
        K3 = _cap_rows(convex_cap, N)
        with jax.named_scope("pip.convex"):
            src3, valid3, over3, pos3 = _compact(conv, K3)
            cv3 = jnp.maximum(cvrow[src3], 0)
            pq3 = points[src3]
            px3, py3 = pq3[:, 0], pq3[:, 1]
            yb = index.convex_ybin[cv3]
            KB = int(index.convex_edges.shape[1])
            EB = int(index.convex_edges.shape[2])
            b3 = jnp.clip(
                jnp.floor((py3 - yb[:, 0]) * yb[:, 1]).astype(jnp.int32),
                0, KB - 1,
            )
            flat3 = cv3 * KB + b3
            ce = index.convex_edges.reshape(CV * KB, EB, 4)[flat3]
            cb = index.convex_ebits.reshape(CV * KB, EB)[flat3]
            r3 = _ray_parity(px3, py3, ce, cb, eps2=edge_eps2)
            par3, near3 = r3 if banded else (r3, None)
            g3 = index.convex_geom[cv3]
            hit3 = ((par3 & jnp.uint32(1)) == 1) & (g3 >= 0) & valid3
            best3 = jnp.where(hit3, g3, _SENTINEL)
    else:
        best3 = near3 = over3 = None

    # return compacted results to the full point axis. Valid src1 row ids
    # are unique by construction; invalid slots divert to distinct
    # out-of-bounds destinations that mode="drop" discards — a unique
    # no-combiner scatter (see _compact for the measured win over
    # combiner scatters). The convex lane's rows are disjoint from the
    # light lane's, so its scatter chains onto the same buffer.
    with jax.named_scope("pip.writeback"):
        if writeback == "gather":
            slot = jnp.clip(pos1, 0, K1 - 1)
            best = jnp.where(light, best1[slot], _SENTINEL)
            if use_convex:
                slot3 = jnp.clip(pos3, 0, K3 - 1)
                best = jnp.where(conv, best3[slot3], best)
        else:
            wdest = jnp.where(
                valid1, src1, N + jnp.arange(K1, dtype=jnp.int32)
            )
            best = (
                jnp.full(N, _SENTINEL, dtype=jnp.int32)
                .at[wdest]
                .set(best1, unique_indices=True, mode="drop")
            )
            if use_convex:
                wdest3 = jnp.where(
                    valid3, src3, N + jnp.arange(K3, dtype=jnp.int32)
                )
                best = best.at[wdest3].set(
                    best3, unique_indices=True, mode="drop"
                )
        out = jnp.where(best == _SENTINEL, -1, best).astype(jnp.int32)
        out = jnp.where(best == _OVF_MARK, OVERFLOW, out)
        out = jnp.where(over1, OVERFLOW, out)
        if use_convex:
            out = jnp.where(over3, OVERFLOW, out)
        if banded:
            if writeback == "gather":
                near = light & ~over1 & near1[slot]
                if use_convex:
                    near = jnp.where(conv, ~over3 & near3[slot3], near)
            else:
                near = (
                    jnp.zeros(N, bool)
                    .at[wdest]
                    .set(near1, unique_indices=True, mode="drop")
                )
                if use_convex:
                    near = near.at[wdest3].set(
                        near3, unique_indices=True, mode="drop"
                    )
        else:
            near = None
        heavy = None
        if H and with_heavy:
            heavy = found & (index.cell_heavy[jnp.maximum(u, 0)] >= 0)
        return out, near, heavy


# the jitted join/counts/compact executables and the cell-assignment
# program cache are owned by the dispatch core (`dispatch/core.py`) —
# one compile cache shared by batch, stream, serve, raster, and the
# sharded lane. `_dispatch.jit_join()` et al. hand back the process-wide
# wrappers; this module keeps only thin legacy views below.


def _next_pow2(n: int, lo: int = 16) -> int:
    return max(lo, 1 << int(np.ceil(np.log2(max(n, 1)))))


def join_cache_stats(emit: bool = True) -> dict:
    """Legacy view over the unified dispatch cache registry
    (`dispatch.cache_stats` is the full surface).

    ``{"cells_prog": {hits, misses, maxsize, currsize}, "jit_join":
    n_cached, "jit_compact": n_cached}`` — the `cells_prog` lru entry
    count is the number of live (index system, resolution, variant)
    program keys (each PINS its index-system object for the cache's
    lifetime), and the jit sizes count compiled (shape, static-args)
    specializations. Emits one ``join_cache_stats`` telemetry event
    (``emit=False`` reads silently) so long-running servers can chart
    growth and decide when to call :func:`clear_join_caches`.
    """
    stats = _dispatch.join_cache_view()
    if emit:
        _telemetry.record("join_cache_stats", **stats)
    return stats


def clear_join_caches() -> dict:
    """Release the join-owned slice of the dispatch caches (cell
    programs plus the shared join/compact compile caches — they regrow
    on next use; the next call per shape pays one recompile); returns
    the pre-clear :func:`join_cache_stats`. `dispatch.clear_caches`
    drops EVERY dispatch cache. Emits ``join_caches_cleared`` telemetry.
    """
    stats = join_cache_stats(emit=False)
    _dispatch.clear_caches(
        names=("cells_prog", "jit_join", "jit_counts", "jit_compact"),
        emit=False,
    )
    _telemetry.record("join_caches_cleared", **stats)
    return stats


#: below this batch size on CPU, eager per-op dispatch of the cell
#: pipeline beats its XLA compile (measured ~1 min+ for the unrolled H3
#: digit pipeline on CPU x64). On accelerators always jit: eager would pay
#: one dispatch round trip per op, and the compile caches across batches.
_JIT_CELLS_MIN = 65536


#: what `pip_join` has told `obs.stages` about: (program, rows, argument
#: dtypes — an index by identity — and keywords). Bounded like every
#: program cache: full, it starts over and a program registers once more
_STAGES_SEEN: set = set()
_STAGES_SEEN_MAX = 256


def _register_stages(fn, args: tuple, kw: dict, rows: int) -> None:
    """Tell `obs.stages` how to lower ``fn(*args, **kw)`` again (shapes
    only; nothing is lowered here), so that a device trace can name the
    batch path's ops by stage. A signature seen before costs one set
    lookup and no `shapes_of`."""
    key = (
        id(fn), rows,
        tuple(a.dtype if hasattr(a, "dtype") else id(a) for a in args),
        tuple(
            (k, v.dtype if hasattr(v, "dtype") else v)
            for k, v in sorted(kw.items())
        ),
    )
    if key in _STAGES_SEEN:
        return
    if len(_STAGES_SEEN) >= _STAGES_SEEN_MAX:
        _STAGES_SEEN.clear()
    _STAGES_SEEN.add(key)
    _stages.register(
        fn, _stages.shapes_of(args), _stages.shapes_of(kw), rows=rows
    )


def _launch_counts(cells: jax.Array, index: ChipIndex, probe: str) -> tuple:
    """Enqueue the counts program (:func:`_probe_counts`) over ``cells``,
    not waited for: ``(counts, slots)``, both device arrays. ``counts``,
    the (found, heavy-cell, convex-cell) row counts, is for
    :func:`_pull_counts`; ``slots``, the (N,) slot column they were
    counted on, never leaves the device: it is the join's ``slots=``."""
    prog = _dispatch.jit_counts()
    _register_stages(prog, (cells, index), {"probe": probe}, cells.shape[0])
    return prog(cells, index, probe=probe)


def _pull_counts(counts: jax.Array) -> tuple:
    """The three counts of :func:`_launch_counts` as ints: a blocking
    pull (of 24 bytes; the slot column beside them is not touched)."""
    return tuple(int(v) for v in np.asarray(counts))


def _assign_cells(index_system, resolution: int, dev: jax.Array, variant: str):
    if (
        dev.shape[0] >= _JIT_CELLS_MIN
        or jax.devices()[0].platform != "cpu"
    ):
        prog = _dispatch.cells_prog(index_system, resolution, variant)
        _register_stages(prog, (dev,), {}, dev.shape[0])
        return prog(dev)
    if variant == "margin":
        return index_system.point_to_cell_margin(dev, resolution)
    if variant == "alt":
        return index_system.point_to_cell_alt(dev, resolution)
    return index_system.point_to_cell(dev, resolution)


def pip_join(
    points: np.ndarray | jax.Array,
    polygons: PackedGeometry | None,
    index_system: IndexSystem,
    resolution: "int | None" = None,
    chip_index: ChipIndex | None = None,
    batch_size: int | None = None,
    recheck: bool | None = None,
    cell_dtype=None,
    writeback: "str | None" = None,
    cell_margin_k: float | None = None,
    edge_band_k: float | None = None,
    probe: "str | None" = None,
    mesh=None,
    profile=None,
) -> np.ndarray:
    """Managed join (reference: `PointInPolygonJoin.join` auto-indexes both
    sides, `sql/join/PointInPolygonJoin.scala:86-97`).

    Tessellates ``polygons`` (unless a prebuilt ``chip_index`` is passed),
    assigns cells to ``points`` on device and returns the matched polygon
    row per point (-1 = no polygon). ``batch_size`` chunks the point axis
    to bound the probe intermediates. Compaction caps are sized exactly
    from two device-side scalar counts (no cell column ever crosses back
    to the host), so no point can overflow. The counts program is
    launched, not waited for, before the host's f64 ``chunk - shift``;
    its scalars are pulled after the shifted put, so the subtract runs
    while the device transfers the batch, assigns cells and counts.
    The count's hash probe is the join's: the counts program returns the
    slot column it counted on (:func:`_probe_counts`), which stays on the
    device, and every join launched for the chunk — each escalation
    attempt too — reads it as ``slots=`` and holds no probe of its own;
    the recheck's narrow re-join reads the band's column from the band's
    own count the same way. ``join.launch`` says ``slots="handed"``
    (``"probed"`` where the sync is skipped: ``writeback="direct"`` on an
    index with no heavy cell) and the call's ``join.pip`` span counts the
    ``probes``: programs holding a hash probe launched for the call.
    Should a cap overflow anyway (shrunken by `runtime.faults`
    injection, or user-adversarial inputs), the bounded escalation
    engine (`runtime/escalate.py`) regrows every cap geometrically until
    the answer is exact or raises a typed
    :class:`~mosaic_tpu.runtime.CapacityOverflow` — :data:`OVERFLOW`
    rows never escape this API. Transient device failures retry with
    backoff (`runtime/retry.py`); past the budget the call degrades to
    the exact f64 host oracle and the result is flagged
    :class:`~mosaic_tpu.runtime.DegradedResult`.

    ``recheck`` (default: the ``exact_recheck`` config flag) switches on
    the epsilon-band borderline recheck — the SURVEY §7 precision
    contract: points whose cell-rounding margin or chip-edge distance is
    within a few ulps of flipping are re-evaluated exactly. Escalation is
    tiered so the exact host oracle only sees genuine ties: borderline
    cell assignments first re-join against the runner-up cell ON DEVICE
    (`IndexSystem.point_to_cell_alt`); only points where the two
    candidate answers differ — plus cell-corner neighborhoods, invalid
    alternates, and edge-band points — go to the f64 host path
    (:func:`host_join`). Requires the index's ``host`` companion (present
    on any `build_chip_index` product).

    ``cell_dtype`` forces the dtype cells are computed in (default: the
    input device array's dtype — f32 on TPU) — lets CPU/x64 tests
    reproduce TPU f32 behavior exactly.

    ``writeback`` selects the probe plumbing (``scatter``/``gather``/
    ``direct`` — see :func:`pip_join_points`); results are identical.

    ``cell_margin_k`` / ``edge_band_k`` override the calibrated band
    constants :data:`CELL_MARGIN_K` / :data:`EDGE_BAND_K` for this call —
    the `tools/calibrate_margins.py` sweep knob (wider bands stay exact
    but recheck more; narrower bands below the measured drift ceiling
    lose the exactness contract).

    ``probe="adaptive"`` turns on per-cell density routing (light cells
    on the tier-1 path, heavy cells through the tiled Pallas kernel,
    convex single-chip cells through the y-bucketed reduced-edge test) —
    bit-identical results, a throughput knob. ``adaptive-light`` /
    ``adaptive-heavy`` / ``adaptive-convex`` pin a single lane (also
    reachable via ``MOSAIC_PROBE_FORCE_LANE`` when ``probe="adaptive"``);
    requires a compaction writeback (not ``direct``).

    ``mesh`` routes each chunk through the dispatch core's bucketed
    data-parallel lane (`dispatch.DispatchCore`): points padded to the
    ladder bucket and sharded over a 1-D mesh with the ChipIndex
    replicated, full per-shard caps (no count sync, no escalation — the
    serve path's compile discipline), bit-identical to single-device.
    Accepts a device count, a 1-D `jax.sharding.Mesh`, or None (the
    ``MOSAIC_MESH`` env knob, resolved once per call). Requires
    ``recheck=False`` — the epsilon-band path stays single-device.

    ``profile`` takes a `tune.TuningProfile`; its knobs apply with the
    one documented precedence — explicit argument > env knob > profile >
    built-in default (`mosaic_tpu/tune/resolve.py`). Profile-consumed
    knobs here: ``resolution``, ``probe``, ``writeback``,
    ``batch_size`` (pass ``batch_size=0`` to explicitly force the
    unbatched path past a profile's recommendation).
    """
    from ..tune.resolve import resolve_knobs

    # profile-consumed knobs fold HERE, at the host entry point, before
    # anything is staged (env-read-after-staging discipline)
    knobs = resolve_knobs(
        "pip_join", profile,
        explicit={
            "resolution": resolution, "probe": probe,
            "writeback": writeback, "batch_size": batch_size,
        },
        defaults={
            "resolution": None, "probe": "scatter", "writeback": "scatter",
            "batch_size": None,
        },
    )
    resolution, writeback = knobs["resolution"], knobs["writeback"]
    batch_size = knobs["batch_size"] or None  # 0 = explicitly unbatched
    if resolution is None:
        raise ValueError(
            "pip_join needs a resolution — pass it explicitly or via a "
            "profile that recommends one"
        )
    resolution = index_system.resolution_arg(resolution)
    probe = resolve_probe_mode(knobs["probe"])
    if probe != "scatter" and writeback == "direct":
        raise ValueError(
            "probe='adaptive' requires writeback scatter|gather"
        )
    if chip_index is None:
        table = tessellate(polygons, index_system, resolution, keep_core_geoms=False)
        chip_index = build_chip_index(table)
    if recheck is None:
        from ..context import current_config

        recheck = current_config().exact_recheck
    mesh = _dispatch.resolve_mesh(mesh)
    if mesh is not None and recheck:
        raise ValueError(
            "pip_join(mesh=...) runs the bucketed sharded dispatch lane, "
            "which does not support the epsilon-band recheck yet — pass "
            "recheck=False (or drop the mesh for the exact-recheck path)"
        )
    host: HostRecheck | None = getattr(chip_index, "host", None)
    if recheck and host is None:
        raise ValueError(
            "exact_recheck needs the index's f64 host companion — present "
            "on build_chip_index products; rebuild the index in-process "
            "or restore it via HostRecheck.from_arrays"
        )
    raw = np.asarray(points, dtype=np.float64)
    # shift in f64 first, narrow after (keeps f32 ulp small near the data)
    shift = (
        host.shift
        if host is not None
        else np.asarray(chip_index.border.shift, dtype=np.float64)
    )
    dtype = chip_index.border.verts.dtype
    n = raw.shape[0]
    core = (
        None
        if mesh is None
        else _dispatch.core_for(
            chip_index, index_system, resolution,
            writeback=writeback, probe=probe,
            cell_dtype=cell_dtype, mesh=mesh,
        )
    )

    def run(chunk: np.ndarray) -> np.ndarray:
        if core is not None:
            # the sharded bucketed lane: pad to the ladder, dispatch
            # data-parallel with full per-shard caps (overflow
            # structurally impossible — no count sync, no escalation),
            # slice the pad off. RetryExhausted falls through to
            # `run_resilient`'s host-oracle degradation like every lane.
            padded, nn = core.ladder.pad(chunk)
            # (`sp`: the call's `join.pip` span, open around every `run`)
            sp.attrs["probes"] += 1  # the sharded join program's own
            sp.attrs["compacted"] |= core.compacted(padded.shape[0])
            sp.attrs["tier2_compacted"] |= core.tier2_compacted(
                padded.shape[0]
            )
            return _dispatch.guarded_call(
                "pip_join.device", core.execute_padded, padded
            )[:nn]
        rows = chunk.shape[0]
        with _obs_trace.span(
            "join.put", rows=rows, nbytes=int(chunk.nbytes),
            dtype=str(chunk.dtype),
        ):
            dev = jnp.asarray(chunk)
            if cell_dtype is not None:
                dev = dev.astype(cell_dtype)
        variant = "margin" if recheck else "cells"
        with _obs_trace.span("join.cells", variant=variant):
            assigned = _assign_cells(index_system, resolution, dev, variant)
        cells, margins = assigned if recheck else (assigned, None)
        # exact cap sizing from two scalars (pow2-bucketed to bound the
        # number of distinct compiled programs) — overflow impossible.
        # Direct mode has no tier-1 compaction: found_cap is unused, so
        # None keeps the jit static key stable across batches (and with
        # no heavy cells the count sync is skipped entirely). The counts
        # program is only enqueued here; its scalars are pulled after the
        # host's subtract, which needs none of them
        synced = writeback != "direct" or bool(chip_index.num_heavy_cells)
        # the count's probe is the join's: `slots` is the column it counted
        # on, still on the device, and the join handed it holds no probe
        # (and no longer reads `cells`). With no sync the join probes
        slots = None
        sp.attrs["probes"] += 1
        if synced:
            with _obs_trace.span("join.counts_launch") as sl:
                launched, slots = _launch_counts(cells, chip_index, probe)
                launched_at = sl.elapsed()  # `hidden_s` runs on this clock
        # the host's f64 subtract and the narrowing to the index's dtype
        # (numpy's cast, IEEE round-to-nearest, bit-identical to XLA's
        # convert: `DispatchCore.execute_padded`), then a plain put — both
        # while the device transfers the batch, assigns cells and counts
        with _obs_trace.span("join.shift", rows=rows):
            narrowed = np.asarray(chunk - shift, dtype=dtype)
        with _obs_trace.span("join.put_shifted", nbytes=int(narrowed.nbytes)):
            shifted = jnp.asarray(narrowed)
        del narrowed  # 32 MB at 4M rows: not held through the join and pull
        fcap = hcap = ccap = None
        if synced:
            # the blocking pull of the three scalars; the span carries what
            # the sync exists to produce, beside the call span's
            # `compacted` / `tier2_compacted`, and `hidden_s`: the host
            # work done while the device had the sync's work queued
            with _obs_trace.span(
                "join.counts",
                hidden_s=round(sl.elapsed() - launched_at, 6),
            ) as sc:
                nf, nh, nc = _pull_counts(launched)
                if writeback == "direct":
                    hcap = min(_next_pow2(nh + 1), rows)
                else:
                    fcap = min(_next_pow2(nf + 1), rows)
                    if chip_index.num_heavy_cells:
                        hcap = min(_next_pow2(nh + 1), fcap)
                    if probe != "scatter" and chip_index.num_convex_cells:
                        ccap = min(_next_pow2(nc + 1), rows)
                # (`convex`: None under `probe="scatter"`, where the
                # program does not take the count and nothing reads it)
                sc.set(
                    found=nf, heavy=nh,
                    convex=nc if probe != "scatter" else None,
                    found_cap=fcap, heavy_cap=hcap, convex_cap=ccap,
                )
        # fault injection may clamp the exactly-sized caps (no-op
        # without an active plan); the escalation loop grows them back
        if writeback == "direct":
            caps = _faults.clamp_caps({"heavy_cap": hcap})
            hcap = caps["heavy_cap"]
        else:
            caps = _faults.clamp_caps(
                {"found_cap": fcap, "heavy_cap": hcap, "convex_cap": ccap}
            )
            fcap, hcap, ccap = (
                caps["found_cap"], caps["heavy_cap"], caps["convex_cap"]
            )
            if probe != "scatter":
                # lane populations for trails/dashboards: how the router
                # splits this chunk (convex leaves the light lane; heavy
                # points pay both tier 1 and the Pallas tier 2)
                _telemetry.record(
                    "probe_route", n=rows, probe=probe,
                    found=nf, heavy=nh, convex=nc,
                    light=nf - nc,
                )
        sp.attrs["compacted"] |= tier1_compacts(rows, fcap, probe, writeback)
        sp.attrs["tier2_compacted"] |= tier2_compacted(
            rows, chip_index.num_heavy_cells, fcap, hcap, probe, writeback,
        )
        # every cap that exists escalates together toward the row-count
        # ceiling, at which overflow is structurally impossible
        grow = {k: v for k, v in caps.items() if v is not None}
        ceilings = {k: rows for k in grow}
        banded = bool(recheck)
        band_kw = {}
        if banded:
            # --- epsilon-band recheck (SURVEY §7) ---------------------
            ebk = EDGE_BAND_K if edge_band_k is None else float(edge_band_k)
            band_kw["edge_eps2"] = jnp.asarray(
                (ebk * float(np.finfo(np.dtype(dtype)).eps)
                 * host.coord_scale) ** 2,
                dtype=dtype,
            )

        def attempt(c):
            kw = dict(
                band_kw,
                heavy_cap=c.get("heavy_cap", hcap),
                found_cap=c.get("found_cap", fcap),
                writeback=writeback, probe=probe,
                convex_cap=c.get("convex_cap", ccap),
            )
            if slots is None:
                args = (shifted, cells, chip_index)
            else:  # every attempt reads the one slot column
                args, kw["slots"] = (shifted, None, chip_index), slots
            prog = _dispatch.jit_join()
            _register_stages(prog, args, kw, rows)
            with _obs_trace.span(
                "join.launch", banded=banded, found_cap=kw["found_cap"],
                heavy_cap=kw["heavy_cap"], convex_cap=kw["convex_cap"],
                slots="probed" if slots is None else "handed",
            ):
                res = prog(*args, **kw)
            # waits for the device, then D2H (the banded pair as writable
            # host copies: the recheck patches them in place)
            with _obs_trace.span("join.pull") as spull:
                if banded:
                    res = np.array(res[0]), np.array(res[1])
                    spull.set(nbytes=int(res[0].nbytes + res[1].nbytes))
                else:
                    res = np.asarray(res)
                    spull.set(nbytes=int(res.nbytes))
            return res

        # `guarded_call` evaluates the fault hooks (maybe_fail +
        # planned stalls) on this thread, then runs the blocking
        # dispatch under the site's watchdog deadline with transient
        # retry: a hung device surfaces as a typed
        # StalledDeviceError on the same retry path as a dropped
        # connection, never a silent hang
        res, _ = run_escalating(
            lambda c: _dispatch.guarded_call("pip_join.device", attempt, c),
            grow, ceilings,
            overflow_count=lambda r: int(
                ((r[0] if banded else r) == OVERFLOW).sum()
            ),
            stage="pip_join.recheck" if banded else "pip_join",
        )
        if not banded:
            return res
        out, host_mask = res  # PIP-boundary band -> host (host_mask)
        # ONE span a chunk, whether or not the cell band is empty, and one
        # `recheck_narrow` event from inside it, on the span's clock
        with _obs_trace.span("join.recheck.band") as sb:
            n_flag = 0
            if margins is not None:
                meps = float(np.finfo(np.dtype(margins.dtype)).eps)
                cmk = (
                    CELL_MARGIN_K if cell_margin_k is None
                    else float(cell_margin_k)
                )
                km = cmk * meps
                flagged = margins[..., 0] < km
                n_flag = int(flagged.sum())
            narrow = {"band": n_flag, "cap": 0, "ties": 0, "mode": "empty"}
            if n_flag:
                # band-compacted narrow re-join: the epsilon band is
                # compacted ONCE (the probe tiers' own `_compact`
                # machinery) and a single re-join over just the compacted
                # band — sized exactly from its own device-side counts —
                # resolves the runner-up cell. Only result TIES (plus cell
                # corners and invalid alternates) escalate to the host
                # oracle; the full point axis is never re-probed.
                cap = min(_next_pow2(n_flag), rows)
                prog = _dispatch.jit_compact()
                _register_stages(prog, (flagged,), {"cap": cap}, rows)
                src, _, _, _ = prog(flagged, cap=cap)
                alt = _assign_cells(
                    index_system, resolution, dev[src], "alt"
                )
                src_np = np.asarray(src)[:n_flag]
                if alt is None:  # system without alternate-rounding
                    host_mask[src_np] = True
                    narrow.update(cap=cap, ties=n_flag, mode="host_all")
                else:
                    # exact caps for the narrow join from the band's own
                    # scalar counts (pad rows duplicate row 0, so the
                    # counts upper-bound the real band — still exact; the
                    # rejoin runs the scatter path, so no convex count is
                    # taken), and the slot column they were counted on
                    sp.attrs["probes"] += 1
                    counts2, slots2 = _launch_counts(
                        alt, chip_index, "scatter"
                    )
                    nf2, nh2, _ = _pull_counts(counts2)
                    fcap2 = min(_next_pow2(nf2 + 1), cap)
                    hcap2 = (
                        min(_next_pow2(nh2 + 1), fcap2)
                        if chip_index.num_heavy_cells
                        else None
                    )
                    args2 = (shifted[src], None, chip_index)
                    kw2 = {
                        "heavy_cap": hcap2, "found_cap": fcap2,
                        "slots": slots2,
                    }
                    prog = _dispatch.jit_join()
                    _register_stages(prog, args2, kw2, cap)
                    r_alt = np.asarray(prog(*args2, **kw2))[:n_flag]
                    vertex = np.asarray(margins[src, 1])[:n_flag] < km
                    alt_np = np.asarray(alt)[:n_flag]
                    tie = (
                        (r_alt != out[src_np]) | vertex | (alt_np < 0)
                    )
                    host_mask[src_np[tie]] = True
                    narrow.update(
                        cap=cap, caps=[fcap2, hcap2], ties=int(tie.sum()),
                        mode="alt_rejoin",
                    )
            sb.set(**narrow)
            _telemetry.record(
                "recheck_narrow", n=rows, seconds=round(sb.elapsed(), 6),
                **narrow,
            )
        with _obs_trace.span("join.recheck.host", n=rows) as sh:
            host_rows = np.nonzero(host_mask)[0]
            sh.set(rows=int(host_rows.size))
            if host_rows.size:
                out[host_rows] = host_join(
                    chunk[host_rows], host, index_system, resolution
                )
        return out

    def run_resilient(chunk: np.ndarray) -> np.ndarray:
        """`run`, degrading to the exact f64 host oracle when the device
        path fails past the transient-retry budget (result flagged
        :class:`DegradedResult` — never a silent zero/wrong answer)."""
        try:
            return run(chunk)
        except RetryExhausted as e:
            if host is None:
                raise
            _telemetry.record(
                "degraded", label="pip_join", attempts=e.attempts,
                error=repr(e.last)[:200],
            )
            get_logger("mosaic_tpu.runtime").warning(
                "pip_join: device path failed %d times (%r); answering "
                "from the f64 host oracle", e.attempts, e.last,
            )
            return DegradedResult.wrap(
                host_join(chunk, host, index_system, resolution),
                reason=f"pip_join device retries exhausted ({e.last!r})"[:300],
                attempts=e.attempts,
            )

    def run_spanned(chunk: np.ndarray) -> np.ndarray:
        """One lane span per device dispatch when routing is pinned:
        `join.probe.<lane>` wraps the whole forced-lane dispatch so a
        trail attributes its wall clock to that lane (the fused
        `adaptive` program is one dispatch — its lane populations ride
        the `probe_route` event instead)."""
        if probe.startswith("adaptive-"):
            with _obs_trace.span(
                f"join.probe.{probe.removeprefix('adaptive-')}",
                n=chunk.shape[0],
            ):
                return run_resilient(chunk)
        return run_resilient(chunk)

    # one span per pip_join call: escalation/retry/degradation/recheck
    # events inside attach to it, so a trail shows WHICH join they hit
    # (`compacted`, `tier2_compacted`: whether any chunk's program, as
    # first dispatched, compacts before tier 1 — `tier1_compacts` — and
    # before tier 2 — `tier2_compacted`; `probes`: the programs holding a
    # hash probe launched for the call — one a chunk, the count sync's or,
    # with no sync, the join's, and one more for a band re-joined on its
    # runner-up cells; no escalation adds one)
    with _obs_trace.span(
        "join.pip", n=n, recheck=bool(recheck), probe=probe, compacted=False,
        tier2_compacted=False, probes=0,
    ) as sp:
        if batch_size is None or n <= batch_size:
            return run_spanned(raw)
        out = np.empty(n, dtype=np.int32)
        degraded: list[DegradedResult] = []
        for s in range(0, n, batch_size):
            r = run_spanned(raw[s : s + batch_size])
            if isinstance(r, DegradedResult):
                degraded.append(r)
            out[s : s + batch_size] = r
        if degraded:
            return DegradedResult.wrap(
                out,
                reason=degraded[0].reason,
                attempts=max(d.attempts for d in degraded),
                detail={"degraded_batches": len(degraded)},
            )
        return out
