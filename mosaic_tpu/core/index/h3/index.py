"""H3IndexSystem: the IndexSystem contract over the from-scratch H3 core.

Reference analog: `core/index/H3IndexSystem.scala:22-221` (which calls the
H3 C core over JNI per row). Here `point_to_cell` is one fused array program
(numpy on host, jax.numpy under jit on device) — the billion-point
`grid_longlatascellid` hot path of SURVEY.md §3.4.

Coordinates are (lng, lat) degrees in xy order, matching GeoJSON and the
rest of the framework.

Pentagon handling (round 3, HOST path — numpy and eager jax arrays): cell
centers on the 12 pentagon base cells are round-trip exact
(`core._pentagon_unfold_repair` — verified for all 12 base cells at res
0-9 in tests), pentagon boundaries emit the 5 true vertices
(`_pentagon_boundary`), and pentagon neighbor stepping yields the 5
adjacent cells. Values traced under `jit` keep the unrepaired lattice
approximation for pentagon children (hexagon base cells are exact on both
paths).
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..base import IndexSystem
from . import constants as C
from . import core
from . import hexmath as hm
from .tables import derive


def _cell_radius_rad(res: int) -> float:
    """Approximate hexagon circumradius in radians at a resolution."""
    return float(np.arctan(C.RES0_U_GNOMONIC / np.sqrt(3.0) / (C.SQRT7**res)))


#: bits of one axial lattice coordinate in a lattice key
_AXIS_BITS = 26
_SIN60 = float(np.sqrt(3.0) / 2.0)


class H3IndexSystem(IndexSystem):
    name = "H3"
    boundary_max_verts = 7  # 6 + closing vertex

    def resolutions(self) -> Sequence[int]:
        return list(range(C.MAX_RES + 1))

    def resolution_of(self, cells) -> jax.Array:
        xp = jnp if isinstance(cells, jax.Array) else np
        return core.resolution(xp.asarray(cells), xp).astype(xp.int32)

    def buffer_radius(self, resolution: int) -> float:
        return float(np.degrees(_cell_radius_rad(resolution)))

    def cell_area_approx(self, resolution: int) -> float:
        """Mean cell area in square degrees (CRS units of EPSG:4326)."""
        sphere_sq_deg = 4 * np.pi * (180 / np.pi) ** 2
        n_cells = 2 + 120 * (7**resolution)
        return float(sphere_sq_deg / n_cells)

    # ---------------------------------------------------------------- core
    def point_to_cell(self, xy, resolution: int) -> jax.Array:
        xp = jnp if isinstance(xy, jax.Array) else np
        xy = xp.asarray(xy)
        lng = xp.radians(xy[..., 0])
        lat = xp.radians(xy[..., 1])
        return core.geo_to_cell(lat, lng, resolution, xp)

    def point_to_cell_margin(self, xy, resolution: int):
        """Cells plus the (..., 2) relative margins of the finest-res hex
        rounding (nearest and second-nearest boundary; see
        `core._rel_margin`) — the epsilon-band input for the f64
        borderline recheck in `sql.join`."""
        xp = jnp if isinstance(xy, jax.Array) else np
        xy = xp.asarray(xy)
        lng = xp.radians(xy[..., 0])
        lat = xp.radians(xy[..., 1])
        return core.geo_to_cell(lat, lng, resolution, xp, with_margin=True)

    def point_to_cell_alt(self, xy, resolution: int) -> jax.Array:
        """Runner-up cell of the finest-res rounding: for a point flagged
        borderline (small first margin, ample second), the true f64 cell
        is the primary or this one. -1 where no valid alternate exists
        (face-overage corner) — callers escalate those to the host path."""
        xp = jnp if isinstance(xy, jax.Array) else np
        xy = xp.asarray(xy)
        lng = xp.radians(xy[..., 0])
        lat = xp.radians(xy[..., 1])
        return core.geo_to_cell(lat, lng, resolution, xp, alt=True)

    def cell_center(self, cells) -> jax.Array:
        # eager jax arrays route through the host path so pentagon centers
        # get the round-trip-exact repair; only traced values stay on the
        # (pentagon-approximate) device path
        if isinstance(cells, jax.Array) and not isinstance(cells, jax.core.Tracer):
            return jnp.asarray(self.cell_center(np.asarray(cells)))
        xp = jnp if isinstance(cells, jax.Array) else np
        cells = xp.asarray(cells)
        lat, lng = core.cell_to_geo(cells, xp)
        return xp.stack([xp.degrees(lng), xp.degrees(lat)], axis=-1)

    def cell_boundary(self, cells) -> jax.Array:
        if isinstance(cells, jax.Array) and not isinstance(cells, jax.core.Tracer):
            return jnp.asarray(self.cell_boundary(np.asarray(cells)))
        if not isinstance(cells, jax.Array) and np.ndim(cells) == 0:
            return self.cell_boundary(np.asarray(cells).reshape(1))[0]
        xp = jnp if isinstance(cells, jax.Array) else np
        cells = xp.asarray(cells)
        lats, lngs = core.cell_boundary(cells, xp)
        # close the ring: repeat first vertex
        lats = xp.concatenate([lats, lats[..., :1]], axis=-1)
        lngs = xp.concatenate([lngs, lngs[..., :1]], axis=-1)
        out = xp.stack([xp.degrees(lngs), xp.degrees(lats)], axis=-1)
        if xp is np and out.ndim == 3:
            pent = np.asarray(core.is_pentagon_cell(cells, np), dtype=bool)
            if pent.any():
                out = out.copy()
                out[pent] = self._pentagon_boundary(
                    np.asarray(cells)[pent].reshape(-1)
                )
        return out

    def _pentagon_boundary(self, cells: np.ndarray) -> np.ndarray:
        """(P,) pentagon cells -> (P, 7, 2) lng/lat deg: 5 TRUE vertices
        (each the spherical circumcenter of the cell center and two
        azimuth-adjacent neighbor centers — the point where three cells
        meet), closed and padded by repeating the first vertex.

        Reference behavior: the H3 C core emits 5 distinct vertices for
        pentagons (`core/index/H3IndexSystem.scala:93-100` closes the ring
        the same way)."""
        P = cells.shape[0]
        nb = self.neighbors(cells)  # (P, 6), -1 pads (pentagons have 5)
        ctr = self.cell_center(cells)  # (P, 2) lng/lat deg
        out = np.zeros((P, 7, 2))
        for p in range(P):
            ns = nb[p][nb[p] >= 0]
            nctr = self.cell_center(ns)  # (K, 2)
            clng, clat = np.radians(ctr[p, 0]), np.radians(ctr[p, 1])
            nlng, nlat = np.radians(nctr[:, 0]), np.radians(nctr[:, 1])
            az = np.arctan2(
                np.sin(nlng - clng) * np.cos(nlat),
                np.cos(clat) * np.sin(nlat)
                - np.sin(clat) * np.cos(nlat) * np.cos(nlng - clng),
            )
            # ascending compass bearing sweeps CW; reverse for CCW rings
            # (hexagon boundaries from the lattice path are CCW)
            order = np.argsort(az)[::-1]
            nlat, nlng = nlat[order], nlng[order]
            c3 = np.array(
                [np.cos(clat) * np.cos(clng), np.cos(clat) * np.sin(clng), np.sin(clat)]
            )
            n3 = np.stack(
                [np.cos(nlat) * np.cos(nlng), np.cos(nlat) * np.sin(nlng), np.sin(nlat)],
                -1,
            )  # (K, 3)
            K = n3.shape[0]
            verts = []
            for m in range(K):
                a, b = n3[m], n3[(m + 1) % K]
                v = np.cross(b - c3, a - c3)
                v /= max(np.linalg.norm(v), 1e-15)
                if np.dot(v, c3) < 0:
                    v = -v
                verts.append((np.arctan2(v[1], v[0]), np.arcsin(v[2])))
            ring = np.asarray(verts)  # (K, 2) lng/lat rad
            row = np.degrees(
                np.concatenate([ring, ring[:1], ring[:1]], axis=0)
            )[:7]
            out[p, : row.shape[0]] = row
            out[p, row.shape[0] :] = row[-1]
        return out

    def is_valid(self, cells) -> jax.Array:
        xp = jnp if isinstance(cells, jax.Array) else np
        return core.is_valid_cell(xp.asarray(cells), xp)

    def is_pentagon(self, cells) -> jax.Array:
        xp = jnp if isinstance(cells, jax.Array) else np
        return core.is_pentagon_cell(xp.asarray(cells), xp)

    # ----------------------------------------------------------- neighbors
    def neighbors_raw(self, cells) -> np.ndarray:
        """(N,) -> (N, 6) raw neighbor candidates — vectorized, MAY contain
        duplicates and the cell itself (pentagon distortion); no -1s.

        Table-free: steps from each cell center past each edge midpoint in
        the owning face's exact grid frame, then re-rounds — the geometric
        equivalent of the C library's h3NeighborRotations tables.
        """
        xp = np
        cells = np.asarray(cells, dtype=np.int64).reshape(-1)
        face, cx, cy, res_arr = core.cell_center_frame(cells, xp)
        N = len(cells)
        # all 6 directions in one flattened projection/round-trip
        ang = np.arange(6) * (np.pi / 3)
        nx = (cx[:, None] + np.cos(ang)[None, :]).reshape(-1)  # (N*6,)
        ny = (cy[:, None] + np.sin(ang)[None, :]).reshape(-1)
        face6 = np.repeat(face, 6)
        res6 = np.repeat(res_arr, 6)
        lat, lng = core._per_res_geo(face6, nx, ny, res6, xp)
        ncell = np.full(N * 6, -1, dtype=np.int64)
        for r in np.unique(res6):
            sel = res6 == r
            ncell[sel] = core.geo_to_cell(lat[sel], lng[sel], int(r), xp)
        out = ncell.reshape(N, 6)

        # pentagon-distorted rows: 6 lattice steps from a (repaired,
        # non-lattice-aligned) center can miss an adjacent cell — re-derive
        # those rows from a dense unit circle around the center. Applies to
        # pentagons, rows that stepped onto themselves (distortion), and
        # hexagons adjacent to a pentagon (their ring is distorted too).
        pent = np.asarray(core.is_pentagon_cell(cells, xp), dtype=bool)
        srt = np.sort(out, axis=1)
        has_dup = (srt[:, 1:] == srt[:, :-1]).any(1)
        nb_pent = np.asarray(
            core.is_pentagon_cell(out.reshape(-1), xp), dtype=bool
        ).reshape(N, 6).any(1)
        # pentagon rows at res >= 1 are EXACT by construction (the center
        # child's neighbors are its parent's 5 other children, K deleted).
        # Sibling membership is checked for EVERY row (cheap cached isin),
        # not only when a pentagon is in the same batch — results must not
        # depend on batch composition.
        sib_flag = np.zeros(N, dtype=bool)
        for r in np.unique(res_arr):
            if int(r) < 1:
                continue
            rows = dict(self._pentagon_rows(int(r)))
            m = res_arr == r
            for p in np.nonzero(m & pent)[0]:
                sibs = rows.get(int(cells[p]))
                if sibs is not None:
                    row = np.full(6, -1, dtype=np.int64)
                    s = sorted(sibs)[:6]
                    row[: len(s)] = s
                    out[p] = row
            # hexagons that are pentagon siblings must list the pentagon
            all_sibs = set()
            for pc, ss in rows.items():
                all_sibs |= ss
            sib_flag |= m & np.isin(cells, np.asarray(sorted(all_sibs)))
        near_pent = (
            (pent & (res_arr == 0))
            | sib_flag
            | nb_pent
            | has_dup
            | (out == cells[:, None]).any(1)
        ) & ~(pent & (res_arr >= 1))  # sibling rows are exact: keep them
        flagged = np.nonzero(near_pent)[0]
        counts = {}
        for p in flagged:
            out[p], counts[p] = self._boundary_walk_neighbors(
                int(cells[p]), int(face[p]), cx[p], cy[p], int(res_arr[p])
            )
        # symmetry patch: a pentagon-sibling hexagon whose boundary only
        # grazes the pentagon in a wedge the ray walk straddled still must
        # list it
        for p in flagged:
            if pent[p]:
                continue
            r = int(res_arr[p])
            for pcell, prow in self._pentagon_rows(r):
                if int(cells[p]) in prow and pcell not in out[p]:
                    row = out[p]
                    free = np.nonzero(row < 0)[0]
                    if free.size:
                        row[free[0]] = pcell
                    else:
                        # drop the least ray-supported entry
                        cnt = counts.get(p, {})
                        weakest = min(
                            range(6), key=lambda m2: cnt.get(int(row[m2]), 0)
                        )
                        row[weakest] = pcell
        return out

    def _pentagon_rows(self, res: int):
        """[(pentagon cell id, set of its 5 neighbors)] at ``res`` (cached).

        res >= 1: exact by construction — the pentagon is the center child
        of a pentagon parent, so its neighbors are the parent's children at
        digits {2..6} (digit 1, the K axis, is deleted on pentagons).
        res 0: derived by the boundary walk over base cells."""
        cache = getattr(self, "_pent_row_cache", {})
        if res not in cache:
            t = derive()
            rows = []
            for bc in np.nonzero(t.is_pentagon)[0]:
                digits = np.full((1, C.MAX_RES), C.INVALID_DIGIT, np.int64)
                digits[:, :res] = 0
                pcell = int(hm.pack(np.asarray([bc]), digits, res, np)[0])
                if res >= 1:
                    sibs = set()
                    for d in (2, 3, 4, 5, 6):
                        dd = digits.copy()
                        dd[:, res - 1] = d
                        sibs.add(int(hm.pack(np.asarray([bc]), dd, res, np)[0]))
                    rows.append((pcell, sibs))
                else:
                    f, px, py, rr = core.cell_center_frame(
                        np.asarray([pcell], dtype=np.int64), np
                    )
                    row, _ = self._boundary_walk_neighbors(
                        pcell, int(f[0]), px[0], py[0], res
                    )
                    rows.append((pcell, set(int(v) for v in row if v >= 0)))
            cache[res] = rows
            self._pent_row_cache = cache
        return cache[res]

    @staticmethod
    def _boundary_walk_neighbors(cell, face, cx, cy, res, n_rays: int = 36):
        """Edge-sharing neighbors of one (distorted) cell by walking its
        region boundary: in each direction, bisect the largest t with
        geo_to_cell(center + t*dir) == cell, then step just beyond — the
        cell found there shares boundary with ours. Exact for the
        pentagon-distorted regions where fixed lattice steps mis-hit.
        Returns (row (6,) int64 -1-padded, {cell: ray count})."""
        ang = np.arange(n_rays) * (2 * np.pi / n_rays)
        dx, dy = np.cos(ang), np.sin(ang)

        def assign(t):
            la, lo = core._per_res_geo(
                np.full(n_rays, face), cx + t * dx, cy + t * dy,
                np.full(n_rays, res), np,
            )
            return core.geo_to_cell(la, lo, res, np)

        lo_t = np.zeros(n_rays)
        hi_t = np.full(n_rays, 2.5)
        # ensure hi is outside (region radius is ~<1.2 grid units)
        for _ in range(3):
            on_cell = assign(hi_t) == cell
            if not on_cell.any():
                break
            hi_t = np.where(on_cell, hi_t * 2, hi_t)
        for _ in range(20):
            mid = (lo_t + hi_t) / 2
            inside = assign(mid) == cell
            lo_t = np.where(inside, mid, lo_t)
            hi_t = np.where(inside, hi_t, mid)
        nb = assign(lo_t + (hi_t - lo_t) * 2 + 1e-6)
        uniq = [c for c in dict.fromkeys(nb.tolist()) if c != cell]
        expected = 5 if bool(core.is_pentagon_cell(np.asarray([cell]), np)[0]) else 6
        if len(uniq) < expected and n_rays < 288:
            return H3IndexSystem._boundary_walk_neighbors(
                cell, face, cx, cy, res, n_rays * 4
            )
        cnt = {}
        for c in nb.tolist():
            if c != cell:
                cnt[c] = cnt.get(c, 0) + 1
        row = np.full(6, -1, dtype=np.int64)
        row[: min(6, len(uniq))] = uniq[:6]
        return row, cnt

    def neighbors(self, cells) -> np.ndarray:
        """(N,) -> (N, 6) adjacent cells (edge-sharing), -1 pads for
        pentagons/duplicates (first occurrence kept, order preserved)."""
        cells = np.asarray(cells, dtype=np.int64).reshape(-1)
        out = self.neighbors_raw(cells)
        for m in range(6):
            dup = out[:, m] == cells
            if m:
                dup |= (out[:, m : m + 1] == out[:, :m]).any(axis=1)
            out[dup, m] = -1
        return out

    @staticmethod
    def _row_unique(a: np.ndarray, width: int | None = None) -> np.ndarray:
        """Per-row sorted unique of an int64 array; -1 entries dropped,
        result left-packed ascending and -1-padded to ``width`` columns."""
        big = np.iinfo(np.int64).max
        s = np.sort(np.where(a < 0, big, a), axis=1)
        dup = np.zeros_like(s, dtype=bool)
        dup[:, 1:] = s[:, 1:] == s[:, :-1]
        s[dup] = big
        s = np.sort(s, axis=1)
        used = int((s != big).sum(axis=1).max()) if s.size else 0
        w = max(width if width is not None else used, 1)
        if s.shape[1] < w:
            s = np.pad(s, ((0, 0), (0, w - s.shape[1])), constant_values=big)
        s = s[:, :w]
        return np.where(s == big, np.int64(-1), s)

    def k_ring(self, cells, k: int) -> np.ndarray:
        """(N,) -> (N, 1+3k(k+1)) filled disk, sorted ascending, -1 pads.

        Vectorized level-wise expansion: each round takes raw neighbors of
        the whole current disk in ONE batched call and row-uniques — no
        per-row Python sets (reference does this in C via JNI,
        `core/index/H3IndexSystem.scala:152-166`)."""
        cells = np.asarray(cells, dtype=np.int64).reshape(-1)
        N = cells.shape[0]
        m_out = 1 + 3 * k * (k + 1)
        disk = cells[:, None].copy()
        if N == 0 or k == 0:
            return self._row_unique(disk, width=m_out)
        for _ in range(k):
            # -1 pads would corrupt the geometric step: substitute each
            # row's own center (its neighbors are already in the disk)
            safe = np.where(disk >= 0, disk, disk[:, :1])
            nb = self.neighbors_raw(safe.reshape(-1)).reshape(N, -1)
            disk = self._row_unique(np.concatenate([disk, nb], axis=1))
        return self._row_unique(disk, width=m_out)

    def k_loop(self, cells, k: int) -> np.ndarray:
        """Hollow ring: k_ring(k) minus k_ring(k-1); sorted, -1 pads."""
        cells = np.asarray(cells, dtype=np.int64).reshape(-1)
        full = self.k_ring(cells, k)
        if k == 0:
            return full
        inner = self.k_ring(cells, k - 1)
        m_out = 6 * k
        # membership test: both sides sorted per row; chunk the broadcast
        N = full.shape[0]
        keep = np.zeros_like(full, dtype=bool)
        chunk = max(1, int(2e7 // max(full.shape[1] * inner.shape[1], 1)))
        for s in range(0, N, chunk):
            sl = slice(s, s + chunk)
            keep[sl] = (full[sl] >= 0) & ~(
                full[sl][:, :, None] == inner[sl][:, None, :]
            ).any(axis=2)
        out = np.where(keep, full, np.int64(-1))
        return self._row_unique(out, width=m_out)

    def ring_width(self, resolution: int, cells=None) -> float:
        """Hexagons: a point at a vertex of its cell has unvisited ground
        ONE EDGE away after ring 1 (the next vertex out, where the edge
        between two ring-1 cells ends, belongs to a ring-2 cell) — the
        cell's circumradius, 0.62 of ``sqrt(area)``, and less than the
        ``sqrt(area) / 1.5`` a grid of squares may credit; after ``j``
        rings it is ``(1.5 j - 0.5)`` circumradii, so the first ring's
        reach is safe for every ``j``. Measured on up to 64 of ``cells``:
        the distance from each to the nearest of its ring-2 cells, as
        polygons, in the cell's own local frame (longitude scaled by the
        cosine of the latitude: a distance in degrees is never shorter
        than that frame's); the smallest over the sample, less 3% for the
        cells not sampled. Without cells: the mean hexagon's circumradius,
        less the 26% by which the grid's smallest hexagons are narrower."""
        cells = np.zeros(0, np.int64) if cells is None else np.asarray(cells)
        cells = cells[cells >= 0]
        if not cells.size:
            return float(
                0.74 * np.sqrt(self.cell_area_approx(resolution) / 2.598)
            )
        pick = np.unique(cells)
        pick = pick[np.linspace(0, pick.size - 1, min(64, pick.size)).astype(int)]
        centre = np.asarray(self.cell_center(pick))  # (S, 2) lon, lat
        scale = np.stack(
            [np.cos(np.radians(centre[:, 1])), np.ones(pick.size)], axis=-1
        )

        def outline(c):  # (S, M) cells -> (S, M, 7, 2) closed, local frame
            d = np.asarray(self.cell_boundary(c.ravel())).reshape(
                c.shape + (-1, 2)
            ) - centre[:, None, None, :]
            d[..., 0] = (d[..., 0] + 180.0) % 360.0 - 180.0
            return d * scale[:, None, None, :]

        def gap(pts, poly):  # (S, P, 2) points, (S, E + 1, 2) closed rings
            a, b = poly[:, None, :-1], poly[:, None, 1:]
            ab, ap = b - a, pts[:, :, None] - a
            t = np.clip(
                (ap * ab).sum(-1) / np.maximum((ab * ab).sum(-1), 1e-300), 0, 1
            )
            return np.hypot(*np.moveaxis(ap - t[..., None] * ab, -1, 0)).min((1, 2))

        ring2 = np.asarray(self.k_loop(pick, 2))
        mine = outline(pick[:, None])[:, 0]
        reach = np.full(pick.size, np.inf)
        for m in range(ring2.shape[1]):  # at most 12 ring-2 cells
            ok = ring2[:, m] >= 0
            theirs = outline(np.where(ok, ring2[:, m], pick)[:, None])[:, 0]
            d = np.minimum(gap(mine, theirs), gap(theirs, mine))
            reach = np.minimum(reach, np.where(ok, d, np.inf))
        return float(0.97 * reach.min())

    def lattice_keys(self, cells):
        """(N,) cells -> ``(keys (N,) int64, margin (N,) int64)``: each
        cell's place on its owning face's hexagon lattice, packed so that
        the cell ``(da, db)`` axial steps away on the same face has the key
        ``key + lattice_step(da, db)`` — a ring search steps over keys
        with integer adds and never rounds a coordinate (the reference
        calls the H3 C core's ``kRing`` a row). ``margin`` is how many
        rings around the cell are sure to stay on that face's plain
        lattice (the centre's distance to the face triangle's nearest
        edge, in cells, less one); a key is -1 where the cell's centre is
        not on the lattice at all (a pentagon base cell's children, whose
        host frame is repaired to round-trip and is not aligned)."""
        cells = np.asarray(cells, dtype=np.int64).reshape(-1)
        if not cells.size:
            return cells.copy(), cells.copy()
        face, x, y, res = core.cell_center_frame(cells, np)
        b = np.rint(y / _SIN60)
        a = np.rint(x - b / 2.0)
        pent = core._tables_for(np)[0].is_pentagon[hm.unpack(cells, np)[1]]
        ok = (
            (np.abs(x - (a + b / 2.0)) < 1e-3)
            & (np.abs(y - b * _SIN60) < 1e-3) & ~pent
        )
        keys = self.lattice_pack(face, a, b)
        corners = core._corners_by_res(np)[res]  # (N, 3, 2)
        p = np.stack([x, y], axis=-1)
        dist = np.full(cells.shape, np.inf)

        def cross(u, v):
            return u[:, 0] * v[:, 1] - u[:, 1] * v[:, 0]

        for i in range(3):
            c0, c1, c2 = corners[:, i], corners[:, (i + 1) % 3], corners[:, (i + 2) % 3]
            e = c1 - c0
            inward = np.sign(cross(e, c2 - c0))
            dist = np.minimum(
                dist, inward * cross(e, p - c0) / np.hypot(e[:, 0], e[:, 1])
            )
        margin = np.floor(dist).astype(np.int64) - 1
        return np.where(ok, keys, np.int64(-1)), np.where(ok, margin, -1)

    def lattice_coords(self, xy, resolution: int, face=None):
        """(N, 2) lon/lat -> ``(face, xa, xb, margin)``: the nearest face
        (or the ``face`` given: a point beyond its triangle reads a
        negative margin) and the point's continuous axial coordinates on that face's
        lattice at ``resolution`` — hex2d ``x = xa + xb / 2``, ``y = xb
        sin 60``, so the cell `lattice_keys` packs as ``(face, a, b)`` is
        the hexagon of the points whose cube rounding is ``(a, b)`` — and
        ``margin``, the point's distance to the face triangle's nearest
        edge in cells, less two, rounded down. Host f64 numpy."""
        xy = np.asarray(xy, dtype=np.float64).reshape(-1, 2)
        res = int(resolution)
        face, x, y = hm.geo_to_hex2d(
            np.radians(xy[:, 1]), np.radians(xy[:, 0]), res, face=face
        )
        xb = y / _SIN60
        corners = core._corners_by_res(np)[res]  # (3, 2)
        dist = np.full(x.shape, np.inf)
        for i in range(3):
            c0, c1, c2 = corners[i], corners[(i + 1) % 3], corners[(i + 2) % 3]
            e, q = c1 - c0, c2 - c0
            inward = np.sign(e[0] * q[1] - e[1] * q[0])
            side = e[0] * (y - c0[1]) - e[1] * (x - c0[0])
            dist = np.minimum(dist, inward * side / np.hypot(e[0], e[1]))
        return (
            face.astype(np.int64), x - xb / 2.0, xb,
            np.floor(dist).astype(np.int64) - 2,
        )

    def lattice_pack(self, face, a, b) -> np.ndarray:
        half = 1 << (_AXIS_BITS - 1)
        return (
            (np.asarray(face, np.int64) << (2 * _AXIS_BITS))
            + ((np.asarray(a, np.int64) + half) << _AXIS_BITS)
            + (np.asarray(b, np.int64) + half)
        )

    def lattice_unpack(self, keys):
        keys = np.asarray(keys, np.int64)
        half, mask = 1 << (_AXIS_BITS - 1), (1 << _AXIS_BITS) - 1
        return (
            keys >> (2 * _AXIS_BITS),
            ((keys >> _AXIS_BITS) & mask) - half, (keys & mask) - half,
        )

    def lattice_ring(self, k: int) -> np.ndarray:
        """(M,) key offsets of the lattice positions a ring search visits
        at iteration ``k``: the centre and its six neighbours at ``k ==
        1``, the ``6k`` positions at hex distance ``k`` after it (side
        ``s`` starts ``k`` steps along direction ``s`` and walks ``k``
        steps along direction ``s + 2``)."""
        u = np.array([[1, 0], [0, 1], [-1, 1], [-1, 0], [0, -1], [1, -1]])
        j = np.arange(k)[None, :, None]
        ab = (k * u[:, None, :] + j * u[(np.arange(6) + 2) % 6][:, None, :])
        ab = ab.reshape(-1, 2)
        if k == 1:
            ab = np.concatenate([np.zeros((1, 2), dtype=ab.dtype), ab])
        return (ab[:, 0].astype(np.int64) << _AXIS_BITS) + ab[:, 1]

    def grid_distance(self, cells_a, cells_b) -> np.ndarray:
        """Hex grid distance via planar ijk on a common face projection.

        Exact when both cells project onto one face. When the pair spans
        icosahedron faces (either cell's owning face differs from the
        common projection face) the planar unfold is unreliable, so -1 is
        returned — the same flagged-failure contract as the reference's
        `h3Distance` (`core/index/H3IndexSystem.scala`)."""
        xp = np
        a = np.asarray(cells_a, dtype=np.int64)
        b = np.asarray(cells_b, dtype=np.int64)
        fa, xa_, ya_, res_a = core.cell_center_frame(a, xp)
        fb, xb0, yb0, res_b = core.cell_center_frame(b, xp)
        lat_b, lng_b = core._per_res_geo(fb, xb0, yb0, res_b, xp)
        res_arr = core.resolution(a, xp)
        # project both on a's owning face: exact for same-face pairs, and
        # cross-face pairs are flagged -1 below anyway
        out = np.zeros(len(a), dtype=np.int64)
        for r in np.unique(res_arr):
            sel = res_arr == r
            xa, ya = xa_[sel], ya_[sel]
            _, xb, yb = hm.geo_to_hex2d(lat_b[sel], lng_b[sel], int(r), face=fa[sel])
            ia, ja = hm.hex2d_to_axial(xa, ya)
            ib, jb = hm.hex2d_to_axial(xb, yb)
            di = ia - ib
            dj = ja - jb
            # hex distance in the (i at 0deg, j at 120deg) basis where the
            # six unit steps are +-(1,0), +-(0,1), +-(1,1)
            out[sel] = np.maximum.reduce(
                [np.abs(di), np.abs(dj), np.abs(di - dj)]
            )
        return np.where(fa != fb, np.int64(-1), out)

    # ------------------------------------------------------------ polyfill
    def _bbox_sample_points(
        self, bounds: np.ndarray, resolution: int
    ) -> np.ndarray:
        """(M, 2) lng/lat sample lattice covering one bbox densely enough
        that every cell intersecting it is hit or is a neighbor of a hit."""
        rad = np.degrees(_cell_radius_rad(resolution))
        lat_mid = np.clip((bounds[1] + bounds[3]) / 2, -89.0, 89.0)
        step_lat = max(rad * 0.8, 1e-7)
        step_lng = max(rad * 0.8 / max(np.cos(np.radians(lat_mid)), 0.05), 1e-7)
        xs = np.arange(bounds[0] - step_lng, bounds[2] + 2 * step_lng, step_lng)
        ys = np.arange(bounds[1] - step_lat, bounds[3] + 2 * step_lat, step_lat)
        ys = ys[(ys >= -90) & (ys <= 90)]
        gx, gy = np.meshgrid(xs, ys, indexing="ij")
        return np.stack([gx.ravel(), gy.ravel()], axis=-1)

    def polyfill_candidates(self, bounds: np.ndarray, resolution: int) -> np.ndarray:
        """Sample-grid candidates covering a lng/lat bbox, plus a 1-ring."""
        pts = self._bbox_sample_points(np.asarray(bounds, dtype=np.float64), resolution)
        if pts.size == 0:
            return np.zeros(0, np.int64)
        cells = np.unique(self.point_to_cell(pts, resolution))
        nb = self.neighbors_raw(cells)
        return np.unique(np.concatenate([cells, nb.reshape(-1)]))

    def _bbox_sample_points_batch(
        self, bounds: np.ndarray, resolution: int
    ) -> tuple[np.ndarray, np.ndarray]:
        """`_bbox_sample_points` for (G, 4) bboxes at once: the same points
        in the same order, concatenated, and how many each bbox has.

        The lattice axes are `np.arange(start, stop, step)` value for
        value: ``ceil((stop - start) / step)`` entries, entry i equal to
        ``start + i * ((start + step) - start)`` (numpy fills a float range
        from its first two entries)."""
        rad = np.degrees(_cell_radius_rad(resolution))
        lat_mid = np.clip((bounds[:, 1] + bounds[:, 3]) / 2, -89.0, 89.0)
        step_lat = np.full(bounds.shape[0], max(rad * 0.8, 1e-7))
        step_lng = np.maximum(
            rad * 0.8 / np.maximum(np.cos(np.radians(lat_mid)), 0.05), 1e-7
        )

        def axis(start, stop, step):
            n = np.maximum(np.ceil((stop - start) / step), 0).astype(np.int64)
            off = np.concatenate([[0], np.cumsum(n)])
            i = np.arange(off[-1]) - np.repeat(off[:-1], n)
            s0 = np.repeat(start, n)
            vals = s0 + i * np.repeat((start + step) - start, n)
            return np.where(i == 1, s0 + np.repeat(step, n), vals), n, off

        xs, nx, xoff = axis(
            bounds[:, 0] - step_lng, bounds[:, 2] + 2 * step_lng, step_lng
        )
        ys, ny, _ = axis(
            bounds[:, 1] - step_lat, bounds[:, 3] + 2 * step_lat, step_lat
        )
        keep = (ys >= -90) & (ys <= 90)
        gy = np.repeat(np.arange(bounds.shape[0]), ny)[keep]
        ys = ys[keep]
        ny = np.bincount(gy, minlength=bounds.shape[0])
        yoff = np.concatenate([[0], np.cumsum(ny)])
        # x-major per bbox: every x paired with all of the bbox's ys
        sizes = nx * ny
        poff = np.concatenate([[0], np.cumsum(sizes)])
        g = np.repeat(np.arange(bounds.shape[0]), sizes)
        k = np.arange(poff[-1]) - poff[:-1][g]
        ix = xoff[:-1][g] + k // np.maximum(ny[g], 1)
        iy = yoff[:-1][g] + k % np.maximum(ny[g], 1)
        return np.stack([xs[ix], ys[iy]], axis=-1), sizes

    def polyfill_candidates_batch(
        self, bounds: np.ndarray, resolution: int
    ) -> list[np.ndarray]:
        """Batched `polyfill_candidates` over (G, 4) bboxes in TWO fused
        array calls total (one point->cell, one neighbor step) instead of
        2G — the per-geometry overhead dominates tessellation otherwise."""
        bounds = np.asarray(bounds, dtype=np.float64).reshape(-1, 4)
        G = bounds.shape[0]
        pts, sizes = self._bbox_sample_points_batch(bounds, resolution)
        if sizes.sum() == 0:
            return [np.zeros(0, np.int64) for _ in range(G)]
        gid = np.repeat(np.arange(G), sizes)
        cells = np.asarray(self.point_to_cell(pts, resolution))

        def unique_pairs(g, c):
            # distinct (g, c) rows, by g then c (np.unique(axis=0) sorts
            # the rows as records, ten times slower at a million rows)
            order = np.lexsort((c, g))
            g, c = g[order], c[order]
            first = np.ones(g.shape[0], dtype=bool)
            first[1:] = (g[1:] != g[:-1]) | (c[1:] != c[:-1])
            return g[first], c[first]

        # distinct (gid, cell) pairs, then ONE neighbor expansion over the
        # distinct cells (small neighbouring bboxes share most of theirs:
        # a layer of building footprints hits each cell ~17 times)
        pg, pc = unique_pairs(gid, cells)
        ucell, inv = np.unique(pc, return_inverse=True)
        nb = self.neighbors_raw(ucell)[inv]  # (P, 6)
        all_gid, all_cell = unique_pairs(
            np.concatenate([pg, np.repeat(pg, 6)]),
            np.concatenate([pc, nb.reshape(-1)]),
        )
        split = np.searchsorted(all_gid, np.arange(G + 1))
        return [all_cell[split[g] : split[g + 1]] for g in range(G)]

    # ------------------------------------------------------------- strings
    def format(self, cells: np.ndarray) -> list[str]:
        return ["%x" % int(c) for c in np.asarray(cells)]

    def parse(self, strs: Sequence[str]) -> np.ndarray:
        return np.asarray([int(s, 16) for s in strs], dtype=np.int64)
