"""IndexSystem contract: pluggable grid indexes, batch-first.

Reference analog: `core/index/IndexSystem.scala:13-221` — a per-cell OO
contract (pointToIndex, polyfill, kRing, indexToGeometry ...). The TPU-native
contract is *columnar*: every operation takes and returns arrays so it can be
vmapped/jitted and sharded over device meshes. Cell IDs are always int64 on
device; string formatting happens only at the host edge (the reference's
Long/String cell-id duality, `functions/MosaicContext.scala:41-48`, becomes a
pair of host codec methods).
"""

from __future__ import annotations

import abc
from typing import Sequence

import jax
import numpy as np


class IndexSystem(abc.ABC):
    """Grid index systems map points/geometries <-> integer cell ids.

    All array methods accept numpy or jax arrays and are jit-compatible
    (static resolution argument) unless documented host-only.
    """

    name: str = "?"
    #: number of vertices of a cell boundary polygon (4 for squares, up to 10
    #: for H3 cells with distortion vertices; boundaries are padded to this).
    boundary_max_verts: int = 4
    #: CRS the grid's coordinates live in (0 = abstract/unknown). H3 is
    #: WGS84 lon/lat; BNG is EPSG:27700 eastings/northings.
    crs_srid: int = 4326

    # ------------------------------------------------------------- metadata
    @abc.abstractmethod
    def resolutions(self) -> Sequence[int]: ...

    def min_resolution(self) -> int:
        return min(self.resolutions())

    def max_resolution(self) -> int:
        return max(self.resolutions())

    @abc.abstractmethod
    def resolution_of(self, cells: jax.Array) -> jax.Array:
        """(N,) int32 resolution of each cell id."""

    # ------------------------------------------------------------ core math
    @abc.abstractmethod
    def point_to_cell(self, xy: jax.Array, resolution: int) -> jax.Array:
        """(N, 2) coords -> (N,) int64 cell ids. Jittable, vmapped inside."""

    def point_to_cell_margin(self, xy: jax.Array, resolution: int):
        """(N, 2) coords -> (cells, rel_margins | None).

        ``rel_margins`` is (N, 2): each point's distance to the nearest
        and second-nearest cell-assignment decision boundaries, divided by
        the coordinate noise scale — compare against k·eps(dtype) to flag
        points whose cell id may differ under higher precision, and whose
        neighborhood has a third candidate (both margins small = near a
        cell corner), for the `sql.join` epsilon-band recheck. Systems
        without a margin implementation return None: callers then skip
        the cell-band part of the recheck."""
        return self.point_to_cell(xy, resolution), None

    def point_to_cell_alt(self, xy: jax.Array, resolution: int):
        """(N, 2) coords -> (N,) runner-up cell ids, or None when the
        system has no alternate-rounding implementation. For borderline
        points (first margin small, second ample) the exact-precision
        cell is the primary or this alternate; -1 entries mean no valid
        alternate (callers escalate those rows to the exact host path)."""
        return None

    @abc.abstractmethod
    def cell_center(self, cells: jax.Array) -> jax.Array:
        """(N,) int64 -> (N, 2) cell center coordinates."""

    @abc.abstractmethod
    def cell_boundary(self, cells: jax.Array) -> jax.Array:
        """(N,) int64 -> (N, boundary_max_verts, 2) boundary polygons (CCW,
        padded by repeating the last vertex)."""

    @abc.abstractmethod
    def k_ring(self, cells: jax.Array, k: int) -> jax.Array:
        """(N,) -> (N, M) filled disk of radius k (cell itself included).
        M is static for the system/k; invalid slots are -1."""

    @abc.abstractmethod
    def k_loop(self, cells: jax.Array, k: int) -> jax.Array:
        """(N,) -> (N, M) hollow ring at exactly distance k; -1 pads."""

    def ring_cells(self, cells, k: int) -> np.ndarray:
        """(N,) -> (N, M) the cells a ring search visits at iteration
        ``k``, for all seeds at once: the disk ``k_ring(cells, 1)`` at
        ``k == 1``, the hollow ring ``k_loop(cells, k)`` after it; -1
        pads (reference: `GridRingNeighbours.leftTransform`). A system
        whose ``k_loop`` is costly at scale offers `lattice_keys`."""
        fn = self.k_ring if k == 1 else self.k_loop
        return np.asarray(fn(cells, k), dtype=np.int64)

    def ring_width(self, resolution: int, cells=None) -> float:
        """The radius, in coordinate units, a ring search may credit every
        completed ring: once rings 1..j around a point's cell are visited,
        every point within ``j * ring_width`` of it lies in a visited
        cell. On a grid of squares a ring adds a whole side in every
        direction, and ``sqrt(area) / 1.5`` is under it. ``cells`` is
        (a sample of) the cells a table holds, for a grid whose cells
        vary from place to place (`H3IndexSystem`)."""
        return float(np.sqrt(self.cell_area_approx(resolution)) / 1.5)

    def lattice_keys(self, cells):
        """``(keys, margin)`` where the system's cells lie on a lattice a
        ring search can step over with integer adds (`H3IndexSystem`),
        else None: the search then asks `ring_cells`."""
        return None

    def lattice_coords(self, xy: np.ndarray, resolution: int, face=None):
        """Where `lattice_keys` is offered: ``(face, xa, xb, margin)`` —
        each point's CONTINUOUS place on its face's lattice (the cell
        whose key packs ``(face, a, b)`` is the hexagon around ``(a, b)``)
        and the rings around it that stay on that face; else None."""
        return None

    def lattice_pack(self, face, a, b) -> np.ndarray:
        """The `lattice_keys` key of lattice position ``(a, b)`` of
        ``face`` (systems that offer `lattice_coords`)."""
        raise NotImplementedError

    def lattice_unpack(self, keys):
        """``(face, a, b)`` of `lattice_pack`'s keys."""
        raise NotImplementedError

    @abc.abstractmethod
    def grid_distance(self, cells_a: jax.Array, cells_b: jax.Array) -> jax.Array:
        """(N,),(N,) -> (N,) int64 grid distance, consistent with k_loop:
        grid_distance(c, n) == k for every n in k_loop(c, k)."""

    @abc.abstractmethod
    def buffer_radius(self, resolution: int) -> float:
        """Radius (in CRS units) that guarantees a cell containing any point
        of a geometry is reached by buffering the geometry by this much
        (reference: IndexSystem.getBufferRadius)."""

    # ------------------------------------------------------------ polyfill
    @abc.abstractmethod
    def polyfill_candidates(
        self, bounds: np.ndarray, resolution: int
    ) -> np.ndarray:
        """Host: candidate cell ids (K,) covering a bbox [xmin,ymin,xmax,ymax].

        Polyfill = candidates whose *center* falls inside the geometry
        (centroid rule, matching the reference's H3 polyfill semantics and its
        BNG centroid-BFS). The center test runs on device via the PIP kernel.
        """

    def polyfill_candidates_batch(
        self, bounds: np.ndarray, resolution: int
    ) -> list[np.ndarray]:
        """Host: candidates per bbox row of ``bounds`` (G, 4). Default loops;
        systems with batch-friendly math override this to amortize the
        per-call overhead across a whole geometry column."""
        bounds = np.asarray(bounds, dtype=np.float64).reshape(-1, 4)
        return [
            np.asarray(self.polyfill_candidates(bounds[g], resolution))
            for g in range(bounds.shape[0])
        ]

    # ------------------------------------------------------------- strings
    @abc.abstractmethod
    def format(self, cells: np.ndarray) -> list[str]:
        """Host: int64 ids -> canonical string ids."""

    @abc.abstractmethod
    def parse(self, strs: Sequence[str]) -> np.ndarray:
        """Host: string ids -> int64 ids."""

    # ------------------------------------------------------------ validity
    @abc.abstractmethod
    def is_valid(self, cells: jax.Array) -> jax.Array:
        """(N,) -> (N,) bool."""

    # -------------------------------------------------------- conveniences
    def cell_area_approx(self, resolution: int) -> float:
        """Mean cell area in CRS units (used by the resolution analyzer)."""
        raise NotImplementedError

    def resolution_arg(self, res) -> int:
        """Parse user resolution input (int or string like '500m')."""
        if isinstance(res, (int, np.integer)):
            if int(res) not in set(self.resolutions()):
                raise ValueError(f"{self.name}: unsupported resolution {res}")
            return int(res)
        raise ValueError(f"{self.name}: unsupported resolution {res!r}")
