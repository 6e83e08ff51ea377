"""SpatialKNN: distributed approximate/exact K nearest neighbours.

Reference analog: `models/knn/SpatialKNN.scala:28-331` +
`models/knn/GridRingNeighbours.scala:28-206` — iterative grid-ring expansion:
iteration 1 joins each landmark's cell cover k-ring(1) against the
tessellated candidate chips, iteration i>1 joins only the k-loop(i) shell,
so every candidate is inspected once; per-iteration results append to a
checkpoint; early stopping fires when the unmatched count and the total
match count are stable (`earlyStoppingCheck:109-121`); a final exactness
pass widens rings until the grid-guaranteed radius covers each landmark's
current kth-neighbour distance (the reference's buffer-by-kth-distance
final ring, `resultTransform:176-189`).

TPU-native shape: the search itself is `mosaic_tpu.knn.engine.ring_search`,
the one ring engine `KNNFrontend` serves from too — array code over all
landmarks at once. The candidates live in a `knn.KNNIndex`
(`build_knn_index`), which `transform` builds from a geometry column or
takes prebuilt, so a table held resident on the device answers call after
call. Point landmarks against an all-point index run the engine's block
lane (pairs never leave the device), and so do polygon landmarks: their
seed cells are made without clipping (`knn.index.polygon_cover`), their
edges put on the device once a call (`knn.index.pack_landmark_rings`), and
every (landmark, block) chunk's point-to-polygon distances evaluated
there. Any other pairing (`mesh=`, a checkpoint, candidates that are not
points) hands each iteration's fresh pairs to one padded device call over
two DeviceGeometry columns that share the index's f64 recenter shift.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from ..core.geometry import affine as _affine
from ..core.geometry.device import pack_to_device
from ..core.index.base import IndexSystem
from ..core.tessellate import tessellate
from ..core.types import GeometryType
from ..functions._coerce import to_packed
from ..dispatch import core as _dispatch
from ..obs import trace as _trace
from .core import CheckpointManager


def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


@_dispatch.bounded_cache("knn_pair_distance", 1)
def _pair_distance_prog():
    """The process-wide jitted pairwise-distance program: gather both
    DeviceGeometry columns by row, evaluate `_distance_dense` per pair.
    ONE wrapper whose internal executable cache keys on the padded pair
    width — registered in the dispatch cache registry so
    ``cache_stats()``/``clear_caches()`` govern it like every other
    compiled-program cache."""
    import jax

    from ..core.geometry.device import take_rows
    from ..functions.geometry import _distance_dense, _vmap_pair

    def run(dls, dcs, lrows, crows):
        da = take_rows(dls, lrows)
        db = take_rows(dcs, crows)
        return _vmap_pair(_distance_dense, da, db)

    return jax.jit(run)


class GridRingNeighbours:
    """One iteration's distance evaluation over two geometry columns
    (reference: GridRingNeighbours.transform; the ring cells of
    `leftTransform:76-99` are the engine's, `KNNIndex.ring_keys`).

    With ``mesh`` set, each iteration's pair batch shards over the mesh
    devices (`parallel/dist_knn.py`) — the reference's distributed
    join+distance step (`SpatialKNN.scala:202-235`)."""

    def __init__(self, index: IndexSystem, resolution: int, mesh=None):
        self.index = index
        self.resolution = resolution
        self.mesh = mesh

    # --------------------------------------------------------- distances
    def pair_distances(
        self, dl, dc, li: np.ndarray, ci: np.ndarray
    ) -> np.ndarray:
        """Batched geometry distance for (landmark, candidate) row pairs.

        Pads the pair axis to a power of two so iterations share compiled
        kernels, then evaluates `_distance_dense` pairwise on device.
        """
        import jax.numpy as jnp

        P = li.shape[0]
        if P == 0:
            return np.zeros(0)
        if self.mesh is not None:
            from ..parallel.dist_knn import distributed_pair_distances

            return distributed_pair_distances(self.mesh, dl, dc, li, ci)
        Ppad = _pow2(P)
        lip = np.concatenate([li, np.zeros(Ppad - P, dtype=li.dtype)])
        cip = np.concatenate([ci, np.zeros(Ppad - P, dtype=ci.dtype)])

        # the registered program cache (`_pair_distance_prog`) replaces
        # the old per-instance dict: jit's executable cache keys on the
        # padded width, so iterations still share compiles, but the
        # cache is observable and clearable through dispatch.cache_stats
        prog = _pair_distance_prog()
        out = prog(dl, dc, jnp.asarray(lip), jnp.asarray(cip))
        return np.asarray(out, dtype=np.float64)[:P]


@dataclasses.dataclass
class KNNResult:
    """Flat match table (the reference's transformed DataFrame rows)."""

    landmark_id: np.ndarray  # (M,)
    candidate_id: np.ndarray  # (M,)
    distance: np.ndarray  # (M,)
    rank: np.ndarray  # (M,) 1-based neighbour rank per landmark
    metrics: dict


class SpatialKNN:
    """Reference: `SpatialKNN.transform:202-235` params
    (`SpatialKNNParams.scala`): kNeighbours, maxIterations,
    earlyStopIterations, distanceThreshold, approximate, checkpoint dir."""

    def __init__(
        self,
        index: "IndexSystem | None" = None,
        resolution: "int | None" = None,
        k_neighbours: int = 5,
        max_iterations: int = 10,
        early_stop_iterations: int = 3,
        distance_threshold: "float | None" = None,
        approximate: bool = True,
        checkpoint_dir: "str | None" = None,
        mesh=None,
    ):
        if index is None:
            from ..context import current_context

            index = current_context().index_system
        self.index = index
        self.resolution = resolution
        self.k = int(k_neighbours)
        self.max_iterations = int(max_iterations)
        self.early_stop = int(early_stop_iterations)
        self.distance_threshold = distance_threshold
        self.approximate = approximate
        self.checkpoint_dir = checkpoint_dir
        #: optional jax.sharding.Mesh: shards every iteration's pair
        #: batch over its devices (parallel/dist_knn.py)
        self.mesh = mesh
        self.metrics: dict = {}
        #: the frontend of the index last searched — it MUST survive
        #: across transform() calls on a resident index: it holds the
        #: signature set of the warmed programs
        self._frontend: "tuple | None" = None

    # ------------------------------------------------------------ helpers
    def _index(self, candidates):
        """``candidates`` as a `knn.KNNIndex`: itself where the caller
        holds one resident, else built for this call."""
        from ..knn import KNNIndex, build_knn_index

        if isinstance(candidates, KNNIndex):
            return candidates
        return build_knn_index(candidates, self.index, self.resolution)

    def _frontend_of(self, kx):
        from ..knn import KNNFrontend

        if self._frontend is None or self._frontend[0] is not kx:
            self._frontend = (kx, KNNFrontend(kx))
        return self._frontend[1]

    @staticmethod
    def _landmarks(landmarks):
        """``(lxy, land)``: the (N, 2) coordinates where every landmark is
        a point (``land`` is then None unless it came packed), else the
        packed column."""
        from ..knn.index import point_coords

        lxy = point_coords(landmarks)
        land = None if lxy is not None else to_packed(landmarks)
        if land is not None:
            lxy = point_coords(land)
        return lxy, land

    def _lane(self, kx, lxy, land, checkpoint: bool) -> str:
        """How a transform evaluates its pairs: ``"blocks"`` (point
        landmarks) or ``"polygons"`` (polygon landmarks) on the device's
        block lane — an all-point index, no mesh, no checkpoint — else
        ``"pairs"``."""
        if kx.points is None or self.mesh is not None or checkpoint:
            return "pairs"
        if lxy is not None:
            return "blocks"
        areal = (int(GeometryType.POLYGON), int(GeometryType.MULTIPOLYGON))
        return "polygons" if np.isin(land.geom_type, areal).all() else "pairs"

    def warmup(self, candidates, landmarks=None) -> dict:
        """Compile every program a transform against ``candidates`` (a
        resident `knn.KNNIndex`) can launch on landmarks like
        ``landmarks`` — a sample of the column the calls will bring, any
        input `transform` takes; None stands for points:
        `KNNFrontend.warmup` with this model's k, on the lane `transform`
        would take. The programs' shapes hold none of the sample's
        counts, so a column of the same kind compiles nothing more."""
        kx = self._index(candidates)
        polygons = landmarks is not None and self._lane(
            kx, *self._landmarks(landmarks), bool(self.checkpoint_dir)
        ) == "polygons"
        return self._frontend_of(kx).warmup(k=self.k, polygons=polygons)

    # ----------------------------------------------------------- transform
    def transform(self, landmarks, candidates) -> KNNResult:
        """The k nearest ``candidates`` of every landmark. ``landmarks``
        is any geometry input or a float (N, 2) array of points;
        ``candidates`` likewise, or a prebuilt `knn.KNNIndex` held
        resident across calls."""
        from ..knn.frontend import VERTEX_LADDER
        from ..knn.index import (
            pack_landmark_rings,
            polygon_cover,
            points_column,
        )

        kx = self._index(candidates)
        fe = self._frontend_of(kx)
        lxy, land = self._landmarks(landmarks)
        L = lxy.shape[0] if lxy is not None else len(land)
        ring = GridRingNeighbours(kx.index_system, kx.resolution, self.mesh)
        ckpt = (
            CheckpointManager(self.checkpoint_dir, overwrite=True)
            if self.checkpoint_dir
            else None
        )
        #: the shared-shift landmark column: packed when a pair batch
        #: first needs it (the block lane never does)
        packed: dict = {}

        def pair_distances(li, ci):
            if not packed:
                col = land if land is not None else points_column(lxy)
                packed["land"] = col
                packed["dl"] = pack_to_device(
                    _affine.translate(col, -kx.shift[0], -kx.shift[1]),
                    dtype=kx.dtype,
                )
            return _resilient_distances(
                ring, packed["dl"], kx.dc, li, ci, packed["land"],
                kx.candidates,
            )

        def log(it, li, ci, d):
            ckpt.append(
                {"iteration": np.full(li.shape, it), "landmark": li,
                 "candidate": ci, "distance": d}
            )

        search = dict(
            exact=not self.approximate, max_iterations=self.max_iterations,
            early_stop=self.early_stop,
            threshold=self.distance_threshold,
            on_iteration=log if ckpt is not None else None,
        )
        with _trace.span("knn.transform", landmarks=L, k=self.k) as sp:
            lane = self._lane(kx, lxy, land, ckpt is not None)
            if lane == "blocks":
                res = fe.search(self.k, points=lxy, **search)
            elif lane == "polygons":
                with _trace.span("knn.cover", landmarks=L) as cs:
                    seeds = polygon_cover(kx, land, self.max_iterations + 1)
                    cs.set(
                        seeds=int(seeds.cells.shape[0]),
                        tessellated=seeds.tessellated,
                    )
                with _trace.span("knn.landmarks", rows=L) as ls:
                    rings = pack_landmark_rings(kx, land, VERTEX_LADDER)
                    ls.set(
                        nbytes=rings.nbytes, tables=len(rings.tables),
                        vpad=np.unique(rings.pads).tolist(),
                    )
                res = fe.search(
                    self.k, polygons=rings, seeds=seeds, **search
                )
                res.counters.update(
                    seeds=int(seeds.cells.shape[0]),
                    edges=int(rings.edges.sum()),
                    host_landmarks=int(
                        ((rings.table < 0) & (rings.edges > 0)).sum()
                    ),
                )
                sp.set(**res.counters)
            elif lxy is not None:
                res = fe.search(
                    self.k, points=lxy, pair_distances=pair_distances,
                    **search,
                )
            else:
                # a geometry landmark searches from every cell of its cover
                table = tessellate(
                    land, kx.index_system, kx.resolution,
                    keep_core_geoms=False,
                )
                cover = np.unique(
                    np.stack([table.geom_id.astype(np.int64), table.cell_id]),
                    axis=1,
                )
                seed_ptr = np.concatenate(
                    [[0], np.cumsum(np.bincount(cover[0], minlength=L))]
                )
                res = fe.search(
                    self.k, seed_ptr=seed_ptr, seed_cells=cover[1],
                    pair_distances=pair_distances, **search,
                )
            sp.set(
                iterations=res.iterations, pairs=res.pairs,
                pairs_padded=res.pairs_padded, launches=res.launches,
                rows_pulled=res.rows_pulled,
                unrested_landmarks=res.unrested,
                slabs=res.slabs, hidden_s=res.hidden_s,
            )

        # flatten result: one row a filled (landmark, rank) slot
        li_out, slot = np.nonzero(res.cid >= 0)
        filled = res.cid[:, self.k - 1] >= 0
        finite = res.dist[np.isfinite(res.dist)]
        self.metrics = {
            "match_count": int(li_out.size),
            "iterations": res.iterations,
            "landmarks": L,
            "candidates": kx.n,
            "complete_landmarks": int(filled.sum()),
            "max_kth_distance": float(finite.max()) if finite.size else 0.0,
            "resolution": kx.resolution,
            "approximate": self.approximate,
            # landmarks that ``max_iterations`` (or an approximate run's
            # early stop) cut off while they were still owed a ring
            "unrested_landmarks": res.unrested,
            "pairs": res.pairs,
            "pairs_padded": res.pairs_padded,
            "launches": res.launches,
            **res.counters,
            # True when any iteration's distances came from the f64 host
            # oracle after the device path failed past its retry budget
            "degraded": res.degraded is not None,
        }
        if ckpt is not None:
            ckpt.write_meta(self.metrics)
        return KNNResult(
            landmark_id=li_out.astype(np.int64),
            candidate_id=res.cid[li_out, slot],
            distance=res.dist[li_out, slot],
            rank=(slot + 1).astype(np.int64),
            metrics=dict(self.metrics),
        )

    def get_metrics(self) -> dict:
        """Reference: `SpatialKNN.getMetrics:280-318` (MLflow loggables)."""
        return dict(self.metrics)


def _resilient_distances(ring, dl, dc, li, ci, land, cand):
    """Device pair distances with transient-failure retry; past the
    budget the batch degrades to the exact f64 oracle `st_distance`
    (flagged :class:`DegradedResult` — the model records it in metrics
    rather than crashing mid-iteration or dropping pairs)."""
    if not li.size:
        return np.zeros(0)

    def device_eval():
        # the "knn.pair_distances" fault plan trips inside guarded_call
        return ring.pair_distances(dl, dc, li, ci)

    def oracle_eval():
        from ..functions.geometry import st_distance

        return np.asarray(
            st_distance(land.take(li), cand.take(ci), backend="oracle"),
            dtype=np.float64,
        )

    return _dispatch.guarded_call(
        "knn.pair_distances", device_eval, fallback=oracle_eval
    )
