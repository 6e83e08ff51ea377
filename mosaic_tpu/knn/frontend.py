"""Bucketed ring-expansion KNN frontend with a Voronoi convex fast path.

The batch model (`models/knn.SpatialKNN`, reference
`models/knn/SpatialKNN.scala:28-331`) re-tessellates and re-jits per
call; this frontend holds a :class:`~mosaic_tpu.knn.index.KNNIndex`
resident and answers queries with the serving discipline of
`dispatch.DispatchCore`:

- **Shape discipline.** Every device entry runs at a `BucketLadder`
  rung: cell assignment pads query rows to the row ladder, distance
  evaluation pads (query, candidate) pairs to the pair ladder (oversize
  batches CHUNK at the top rung — they never escalate, so the compile
  signature set is closed under any traffic). Candidate caps don't
  exist here at all: a pair batch is exact by construction, the full
  bucket IS the cap.
- **Compile accounting.** Signatures are `("knn", kind, bucket, mesh,
  index fingerprint)`; :meth:`KNNFrontend.warmup` touches every rung and
  freezes the set, after which any new signature counts as a cold
  compile and fires ``on_cold_compile`` (the serve engine turns that
  into a ``serve_compile`` event — the bench asserts zero).
- **AOT persistence.** With a program store bound, each rung's cell and
  pair executables export via `dispatch.programs` (keys
  ``knn_cells``/``knn_pairs`` under the index fingerprint) and reload on
  relaunch, so a store-backed restart replays with zero compiles.
  Meshed executables bind a device topology the store does not model —
  a meshed frontend refuses the store exactly like the core.
- **Failure domains.** ``knn.expand`` (ring/walk candidate generation),
  ``knn.distance`` (the device pair batch), and ``knn.scatter`` (top-k
  merge) run under `dispatch.guarded_call`: watchdog deadline, transient
  retry, fault-plan injection. Past the retry budget the distance batch
  degrades to the exact f64 host oracle (`knn.oracle`) and the answer is
  flagged :class:`~mosaic_tpu.runtime.errors.DegradedResult` — never
  wrong, never dropped. Expand and scatter are pure functions whose
  results commit only after the guarded call returns, so retries are
  idempotent. The block lane's evaluation has two guarded halves
  (:meth:`KNNFrontend._enqueue`): the enqueue of a slab's launches and,
  a slab later, their pull; each degrades to the oracle for its own
  chunks alone.

Lanes
-----
``ring`` is the exact iterative lane: grow k-ring(1) then k-loop(i)
shells per query (the batch model's loop, same stop rule: a query rests
once the grid-guaranteed radius ``(it-1)*cell_width`` covers its current
kth distance). ``voronoi`` collapses the loop: walk the precomputed
Voronoi adjacency of convex chip sites (`sql.join.VoronoiTables`) to a
near-nearest site, read ~k exact host distances to bound the kth
neighbour, and dispatch ONE ring cover of grid radius
``ceil(bound/w)+1`` — same pair program, same rungs, same exact answer,
no iteration. Queries the walk cannot bound (fewer than k reachable
convex geoms) fall back to the ring lane per query. Lane choice is the
``knn_lane`` tune knob, routed by the profiler's convex-share statistic
(`tune/recommend`).
"""

from __future__ import annotations

import dataclasses

import numpy as np

from ..dispatch import (
    BucketLadder,
    ProgramFingerprintMismatch,
    ProgramStoreCorrupt,
    backend_compiles,
    bounded_cache,
    cells_prog,
    guarded_call,
    mesh_key,
    program_key,
    resolve_mesh,
    resolve_program_store,
)
from ..dispatch.programs import deserialize_compiled, serialize_compiled
from ..obs import stages as _stages
from ..obs import trace as _trace
from ..runtime import telemetry as _telemetry
from ..runtime.errors import DegradedResult
from ..utils import get_logger
from . import engine as _engine
from .index import KNNIndex, host_polygon_distances, table_rows
from .oracle import host_pair_distances

logger = get_logger(__name__)

#: default pair ladder: 256 covers a handful of interactive queries'
#: first rings, 16k is one comfortable cover dispatch; bigger pair sets
#: chunk at the top rung (signature set stays closed)
DEFAULT_PAIR_LADDER = BucketLadder(min_bucket=256, max_bucket=16384)
#: default row ladder for query cell assignment
DEFAULT_ROW_LADDER = BucketLadder(min_bucket=64, max_bucket=4096)
#: (query, block) chunks a launch of the block program (point queries on
#: an all-point index): a handful of served queries' rings fit the first
#: rung; a transform's chunks are cut at the top one, which the chip
#: chose (PERF.md section 6, PR 33)
BLOCK_LADDER = BucketLadder(min_bucket=1024, max_bucket=1 << 16, growth=4)
#: edges a polygon landmark's row of the block program's table pads to: a
#: launch holds landmarks of one rung, so the few long outlines of a table
#: (a building layer's 2% of 24-80 vertices) do not pad the rest; past the
#: top rung the host answers
VERTEX_LADDER = BucketLadder(min_bucket=8, max_bucket=128, growth=4)


@dataclasses.dataclass
class _Pending:
    """Block launches enqueued and not yet pulled (`KNNFrontend._enqueue`):
    what `engine.ring_search` holds while it expands the next slab."""

    padded: int  # slots the launches evaluate
    launches: int
    rows: int  # head rows the pull brings, padded to their rungs
    #: the chunks past the last whole top-rung cut, for the next call
    carry: object
    pull: object  # (**span fields) -> (hq, hd, hi) | DegradedResult


@dataclasses.dataclass
class KNNAnswer:
    """Served neighbours (`-1`/`inf` pad unfilled slots when the index
    holds fewer than k candidates). :meth:`KNNFrontend.query` returns
    one per row with (k,) arrays; the serve engine's ``submit_knn``
    future resolves to a single batched answer with (n, k) arrays."""

    ids: np.ndarray  # (..., k) int64 candidate rows, rank order
    distance: np.ndarray  # (..., k) f64
    degraded: bool = False
    reason: "str | None" = None


def decode_knn(out: np.ndarray, k: int):
    """Split the wire encoding ``[distances ‖ ids]`` (rows of width 2k,
    the shape KNN answers travel through the mixed-traffic batcher in)
    back into ``(ids int64, dist f64)``."""
    out = np.asarray(out, dtype=np.float64)
    dist = out[..., :k]
    ids = out[..., k : 2 * k].astype(np.int64)
    return ids, dist


# ------------------------------------------------------- device programs


def _point_column(qxy, shift):
    """Synthesize a POINT DeviceGeometry column from (P, 2) coords
    INSIDE the jit — one vertex per ring, closed form (the vertex
    repeated at index ``ring_len``), so the pair kernel sees the exact
    column `pack_to_device` would build for these points and the compile
    signature depends only on P, never on the query values."""
    import jax.numpy as jnp

    from ..core.geometry.device import DeviceGeometry
    from ..core.types import GeometryType

    n = qxy.shape[0]
    verts = jnp.broadcast_to(qxy[:, None, None, :], (n, 1, 2, 2))
    return DeviceGeometry(
        verts=verts,
        ring_len=jnp.ones((n, 1), dtype=jnp.int32),
        ring_is_hole=jnp.zeros((n, 1), dtype=bool),
        n_rings=jnp.ones((n,), dtype=jnp.int32),
        geom_type=jnp.full((n,), int(GeometryType.POINT), dtype=jnp.int32),
        shift=shift,
    )


@bounded_cache("knn_point_pairs", 1)
def _point_pair_prog():
    """The ONE jitted (query point, candidate row) distance program all
    frontends share — jax's own trace cache keys the pair-bucket shapes,
    the frontend's ladder bounds how many there are. Lives in the
    dispatch cache registry (name ``knn_point_pairs``) so
    `cache_stats`/`clear_caches` cover it."""
    import jax

    from ..core.geometry.device import take_rows
    from ..functions.geometry import _distance_dense, _vmap_pair

    def run(dcs, qxy, crows):
        dq = _point_column(qxy, dcs.shift)
        return _vmap_pair(_distance_dense, dq, take_rows(dcs, crows))

    return jax.jit(run)


@bounded_cache("knn_point_pairs_sharded", 8)
def _sharded_point_pairs(mesh):
    """Meshed variant: candidate column replicated, query coords and
    candidate rows sharded over the pair axis (the `parallel/dist_knn`
    layout — embarrassingly parallel, no collectives)."""
    import jax
    from jax.sharding import PartitionSpec as P

    from ..core.geometry.device import take_rows
    from ..functions.geometry import _distance_dense, _vmap_pair
    from ..parallel.dist_overlay import geom_specs

    row = P(mesh.axis_names)
    rep = geom_specs(P())

    def step(dcs, qxy, crows):
        dq = _point_column(qxy, dcs.shift)
        return _vmap_pair(_distance_dense, dq, take_rows(dcs, crows))

    return jax.jit(
        jax.shard_map(
            step, mesh=mesh, in_specs=(rep, row, row), out_specs=row
        )
    )


class KNNFrontend:
    """Online KNN over a resident :class:`KNNIndex` (see module doc)."""

    def __init__(
        self,
        kx: KNNIndex,
        *,
        lane: str = "ring",
        pair_ladder: "BucketLadder | None" = None,
        row_ladder: "BucketLadder | None" = None,
        max_iterations: int = 64,
        mesh=None,
        program_store=None,
        on_cold_compile=None,
    ):
        if lane not in ("ring", "voronoi"):
            raise ValueError(f"unknown knn lane {lane!r}")
        if kx.n == 0:
            raise ValueError(
                "KNNFrontend needs a non-empty candidate index (warmup "
                "dispatches pair batches against candidate row 0)"
            )
        self.kx = kx
        self.lane = lane
        self.pair_ladder = pair_ladder or DEFAULT_PAIR_LADDER
        self.row_ladder = row_ladder or DEFAULT_ROW_LADDER
        self.max_iterations = int(max_iterations)
        self.mesh = resolve_mesh(mesh)
        if self.mesh is not None:
            for b in self.pair_ladder.buckets:
                if b % self.mesh.size:
                    raise ValueError(
                        f"pair bucket {b} does not divide over the "
                        f"{self.mesh.size}-device mesh"
                    )
        self._dtype = np.dtype(kx.dtype)
        self._signatures: set = set()
        self._warmed: "frozenset | None" = None
        self._cold_compiles = 0
        self._on_cold_compile = on_cold_compile
        # AOT persistence mirrors DispatchCore: explicit arg beats the
        # MOSAIC_PROGRAM_STORE env knob; a meshed frontend refuses the
        # store (sharded executables bind the device topology).
        self._programs = resolve_program_store(program_store)
        if self._programs is not None and self.mesh is not None:
            _telemetry.record(
                "program_store_refused", reason="mesh",
                devices=self.mesh.size,
            )
            self._programs = None
        self._block_fill: "np.ndarray | None" = None
        self._aot: dict = {}  # (kind, bucket) -> compiled | None
        self.aot_stats = {"loaded": 0, "exported": 0, "fallback": 0}
        self.stats = {
            "queries": 0,
            "pairs": 0,
            "pairs_padded": 0,
            "launches": 0,
            "rows_pulled": 0,
            "iterations": 0,
            "degraded": 0,
            "lane_ring": 0,
            "lane_voronoi": 0,
            "voronoi_fallback": 0,
        }

    # ------------------------------------------------------- accounting

    @property
    def cold_compiles(self) -> int:
        """Signatures first seen AFTER :meth:`warmup` froze the set."""
        return self._cold_compiles

    def signature_count(self) -> int:
        return len(self._signatures)

    def freeze(self) -> None:
        self._warmed = frozenset(self._signatures)

    def _note(self, kind: str, bucket: int) -> bool:
        sig = (
            "knn", kind, int(bucket), mesh_key(self.mesh),
            self.kx.fingerprint,
        )
        if sig in self._signatures:
            return False
        self._signatures.add(sig)
        if self._warmed is not None:
            self._cold_compiles += 1
            if self._on_cold_compile is not None:
                self._on_cold_compile(bucket, len(self._signatures))
            else:
                _telemetry.record(
                    "knn_compile", kind=kind, bucket=bucket,
                    signatures=len(self._signatures),
                )
        return True

    # ----------------------------------------------------- AOT programs

    def _aot_program(self, kind: str, bucket: int):
        key = (kind, bucket)
        if key in self._aot:
            return self._aot[key]
        with _trace.span("knn.aot", kind=kind, bucket=bucket):
            try:
                fn = self._load_or_export(kind, bucket)
            except Exception as e:  # lint: broad-except-ok (AOT is an optimization: ANY serialization failure must degrade to plain compilation, not take down the frontend)
                _telemetry.record(
                    "program_store_fallback", bucket=bucket,
                    error=repr(e)[:200],
                )
                self.aot_stats["fallback"] += 1
                fn = None
        self._aot[key] = fn
        return fn

    def _load_or_export(self, kind: str, bucket: int):
        import jax as _jax

        fp = self.kx.fingerprint
        if kind == "cells":
            in_dtype = _jax.dtypes.canonicalize_dtype(np.float64)
            proto = _jax.ShapeDtypeStruct((bucket, 2), in_dtype)
            cfn = cells_prog(
                self.kx.index_system, self.kx.resolution, "cells"
            )
            aval = _jax.eval_shape(cfn, proto)
            return self._one_program(
                program_key(
                    fp, "knn_cells", bucket=bucket,
                    resolution=int(self.kx.resolution),
                ),
                lambda: cfn.lower(proto).compile(),
                (proto,), aval,
                meta={"kind": "knn_cells", "bucket": bucket},
            )
        qproto = _jax.ShapeDtypeStruct((bucket, 2), self._dtype)
        rdtype = _jax.dtypes.canonicalize_dtype(np.int64)
        rproto = _jax.ShapeDtypeStruct((bucket,), rdtype)
        prog = _point_pair_prog()
        aval = _jax.eval_shape(prog, self.kx.dc, qproto, rproto)
        return self._one_program(
            program_key(
                fp, "knn_pairs", bucket=bucket, dtype=str(self._dtype),
            ),
            lambda: prog.lower(self.kx.dc, qproto, rproto).compile(),
            (self.kx.dc, qproto, rproto), aval,
            meta={"kind": "knn_pairs", "bucket": bucket},
        )

    def _one_program(self, key, compile_fn, example_args, out_aval, meta):
        payload = None
        try:
            payload = self._programs.load(key)
        except (ProgramStoreCorrupt, ProgramFingerprintMismatch):
            pass  # typed telemetry already recorded by the store
        if payload is not None:
            # single-device programs (a meshed frontend refuses the
            # store): compiled for the device holding the candidates
            fn = deserialize_compiled(
                payload, example_args, out_aval,
                devices=self.kx.dc.verts.devices(),
            )
            self.aot_stats["loaded"] += 1
            return fn
        compiled = compile_fn()
        self._programs.save(key, serialize_compiled(compiled), meta=meta)
        self.aot_stats["exported"] += 1
        return compiled

    # ---------------------------------------------------- device entries

    def _cells_bucket(self, padded: np.ndarray) -> np.ndarray:
        """One full-bucket cell assignment (the shared `cells_prog`
        executable, AOT-loaded when a store is bound)."""
        import jax.numpy as jnp

        b = padded.shape[0]
        dev = jnp.asarray(padded)
        plain = cells_prog(self.kx.index_system, self.kx.resolution, "cells")
        if self._note("cells", b):
            _stages.register(plain, _stages.shapes_of((dev,)), rows=b)
        fn = None
        if self._programs is not None:
            fn = self._aot_program("cells", b)
        return np.asarray((fn or plain)(dev))

    def _assign_cells(self, pts: np.ndarray) -> np.ndarray:
        """(n, 2) raw query coords -> (n,) int64 seed cells, chunked
        through the row ladder."""
        n = pts.shape[0]
        out = np.empty(n, dtype=np.int64)
        step = self.row_ladder.max_bucket
        for c0 in range(0, n, step):
            chunk = pts[c0 : c0 + step]
            m = chunk.shape[0]
            padded, _ = self.row_ladder.pad(chunk)
            cells = self._cells_bucket(padded)
            out[c0 : c0 + m] = cells[:m].astype(np.int64)
        return out

    def _pair_bucket(self, qxy: np.ndarray, rows: np.ndarray) -> np.ndarray:
        """One padded pair dispatch: (m, 2) shifted device-dtype query
        coords × (m,) candidate rows -> (m,) f64 distances."""
        import jax.numpy as jnp

        m = qxy.shape[0]
        b = self.pair_ladder.bucket_for(m)
        if b > m:
            # pad pairs repeat the first pair (inert, sliced off below)
            qxy = np.concatenate(
                [qxy, np.broadcast_to(qxy[:1], (b - m, 2))]
            )
            rows = np.concatenate(
                [rows, np.broadcast_to(rows[:1], (b - m,))]
            )
        self._note("pairs", b)
        self.stats["pairs"] += m
        self.stats["pairs_padded"] += b
        self.stats["launches"] += 1
        with _trace.span("knn.pairs", bucket=b, pairs=m):
            qdev = jnp.asarray(np.ascontiguousarray(qxy), dtype=self._dtype)
            rdev = jnp.asarray(np.ascontiguousarray(rows, dtype=np.int64))
            if self.mesh is not None:
                vals = _sharded_point_pairs(self.mesh)(
                    self.kx.dc, qdev, rdev
                )
            else:
                fn = None
                if self._programs is not None:
                    fn = self._aot_program("pairs", b)
                if fn is None:
                    fn = _point_pair_prog()
                vals = fn(self.kx.dc, qdev, rdev)
        return np.asarray(vals, dtype=np.float64)[:m]

    def _pair_values(self, qsd, qi, ci) -> np.ndarray:
        """All (query, candidate) pair distances, chunked at the top
        pair rung (chunking keeps the signature set closed — an
        arbitrarily large cover never invents a new shape)."""
        total = qi.shape[0]
        out = np.empty(total, dtype=np.float64)
        step = self.pair_ladder.max_bucket
        for c0 in range(0, total, step):
            c1 = min(total, c0 + step)
            out[c0:c1] = self._pair_bucket(qsd[qi[c0:c1]], ci[c0:c1])
        return out

    def _distances(self, qs64, qsd, qi, ci, default_s):
        """The ``knn.distance`` failure domain: device pair batch with
        watchdog + retry; past the budget the batch degrades to the
        exact f64 host oracle (`DegradedResult`, never dropped)."""
        if not qi.size:
            return np.zeros(0)
        return guarded_call(
            "knn.distance",
            lambda: self._pair_values(qsd, qi, ci),
            default_s=default_s,
            fallback=lambda: host_pair_distances(qs64, self.kx, qi, ci),
        )

    # ------------------------------------------------------- ring lane

    def _launch_chunks(self, prog, kind, k, lead, cols, thr, **fields):
        """Enqueue one chunk list: cut at the block ladder's top rung, each
        cut padded to a rung and handed to ``prog`` (after the blocks and
        ``lead``: the per-chunk columns ``cols``, ``(column, pad value)``
        with the chunks' owner last) and, behind it, to the heads program
        with the slots where a query begins in the cut (`engine.launch_heads`),
        padded to the rung their number takes. A cut folds as many doublings
        as its own longest run of one owner asks. Nothing is pulled. Returns
        ``(outs, head, padded slots, head rows)``, an entry of ``outs``
        being ``(heads, the head rows' (d, id))``."""
        pb = self.kx.points
        heads_prog = _engine.block_heads_prog()
        cap = BLOCK_LADDER.max_bucket
        cq = cols[-1][0]
        head = _engine.launch_heads(cq, cap)
        outs, padded, rows = [], 0, 0
        for c0 in range(0, cq.shape[0], cap):
            m = min(cap, cq.shape[0] - c0)
            b = BLOCK_LADDER.bucket_for(m)
            h0, h1 = np.searchsorted(head, (c0, c0 + m))
            nh = int(h1 - h0)
            h = BLOCK_LADDER.bucket_for(nh)
            at = (head[h0:h1] - c0).astype(np.int32)
            steps = int(np.diff(at, append=m).max() - 1).bit_length()
            args = (
                pb.x, pb.y, pb.rid, *lead,
                *(
                    np.pad(c[c0 : c0 + m], (0, b - m), constant_values=v)
                    for c, v in cols
                ),
                thr, np.int32(steps),
            )
            # (a pad head re-reads slot 0 and is cut off the pull)
            at = np.pad(at, (0, h - nh))
            if self._note(f"{kind}.k{k}", b):
                # how to lower this rung again, for a device
                # trace's stage table (nothing is lowered here)
                _stages.register(
                    prog, _stages.shapes_of(args), {"k": k}, rows=b
                )
            with _trace.span(
                "knn.blocks", bucket=b, chunks=m, heads=nh, head_bucket=h,
                **fields,
            ):
                d, gid = prog(*args, k=k)
                answer = heads_prog(d, gid, at)
                # the rows set out for the host behind their own launch,
                # not behind whatever is enqueued before they are asked for
                for r in answer:
                    r.copy_to_host_async()
                outs.append((nh, answer))
            if self._note(f"heads.k{k}.b{b}", h):
                _stages.register(
                    heads_prog, _stages.shapes_of((d, gid, at)), rows=h,
                )
            padded += b * pb.width
            rows += h
        return outs, head, padded, rows

    @staticmethod
    def _pull_heads(outs, rows: int, chunks: int, **fields):
        """The blocking pull of every enqueued launch's head rows."""
        with _trace.span(
            "knn.pull", launches=len(outs), rows=rows, chunks=chunks, **fields,
        ):
            hd = np.concatenate([np.asarray(o[0])[:n] for n, o in outs])
            hi = np.concatenate([np.asarray(o[1])[:n] for n, o in outs])
        return hd, hi

    def _enqueue(self, launch, oracle, default_s, carry):
        """The two halves of one block evaluation (`engine.ring_search`),
        each under the ``knn.distance`` failure domain. ``launch() ->
        (outs, hq, padded, rows, host rows, chunks)`` enqueues and pulls
        nothing; the handle's ``pull`` waits for those launches (a retry of
        it launches them again) and appends the rows the host answered
        meanwhile. Past the retry budget ``oracle(launched)`` answers in
        f64 on the host: every chunk the enqueue was handed, the carried
        ones too, or (``launched``) those whose launches the pull gave up."""
        got = guarded_call(
            "knn.distance", launch, default_s=default_s,
            fallback=lambda: oracle(False),
        )
        if isinstance(got, DegradedResult):
            return got
        outs, hq, padded, rows, _host, chunks = got
        first = [got]

        def pull(**fields):
            def attempt():
                sent = first.pop() if first else launch()
                o, mine = sent[0], sent[4]
                parts = [self._pull_heads(o, rows, chunks, **fields)] if o else []
                if mine is not None:
                    parts.append(mine)
                if not parts:
                    return hq, None, None
                return (hq, np.concatenate([d for d, _ in parts]),
                        np.concatenate([i for _, i in parts]))

            return guarded_call(
                "knn.distance", attempt, default_s=default_s,
                fallback=lambda: oracle(True),
            )

        return _Pending(padded, len(outs), rows, carry, pull)

    def _block_topk(self, qs64, qsd, k, thr, default_s):
        """The engine's block evaluator (`engine.ring_search`): the
        ``knn.distance`` failure domain over (query, block) chunks, cut at
        the block ladder's top rung and padded to a rung. With each launch
        go the slots where a query begins in it (`engine.launch_heads`),
        padded to the rung their number takes; the heads program gathers
        those rows on the device, and only they are pulled, by the handle
        this returns, when the engine asks. Unless ``last``, what lies past
        the last whole top-rung cut is carried to the next call. Past the
        retry budget the chunks' pairs are made from the CSR and answered
        by the f64 host oracle."""
        import jax.numpy as jnp

        kx, pb = self.kx, self.kx.points
        prog = _engine.block_topk_prog()
        thr = jnp.asarray(thr, dtype=self._dtype)
        cap = BLOCK_LADDER.max_bucket

        def evaluate(active, cq, blk, carry=None, last=True):
            if carry is not None:
                cq = np.concatenate([carry[0], cq])
                blk = np.concatenate([carry[1], blk])
            cut = cq.size if last else cq.size - cq.size % cap

            def launch():
                land = active[cq[:cut]]
                outs, head, padded, rows = self._launch_chunks(
                    prog, "blocks", k, (),
                    ((qsd[land, 0], 0), (qsd[land, 1], 0),
                     (blk[:cut].astype(np.int32), pb.n_blocks),
                     (cq[:cut].astype(np.int32), -1)),
                    thr,
                )
                return outs, cq[head], padded, rows, None, cut

            def oracle(launched):
                sl = slice(0, cut if launched else None)
                qi, ci = _engine.chunk_pairs(kx, active[cq[sl]], blk[sl])
                return np.column_stack(
                    [qi, ci, host_pair_distances(qs64, kx, qi, ci)]
                )

            return self._enqueue(
                launch, oracle, default_s,
                (cq[cut:], blk[cut:]) if cut < cq.size else None,
            )

        return evaluate

    def _poly_block_topk(self, rings, k, thr, default_s, tally):
        """:meth:`_block_topk` for polygon queries (`index.LandmarkRings`):
        a call's chunks are split by their query's table (one edge rung, a
        fixed number of rows), a launch holding one table and each table
        carrying its own remainder, and pulled together. Chunks of a query
        past the top rung are answered here on the host, in f64, behind
        the launches; past the retry budget all of them are
        (`host_polygon_distances`).
        ``tally`` takes what the call's span reports of the edges."""
        import jax.numpy as jnp

        kx, pb = self.kx, self.kx.points
        prog = _engine.poly_block_topk_prog()
        limit = float(thr)
        thr = jnp.asarray(thr, dtype=self._dtype)
        cap = BLOCK_LADDER.max_bucket
        if self._block_fill is None:
            nblk = np.diff(pb.blk_start)
            rank = np.arange(pb.n_blocks) - np.repeat(pb.blk_start[:-1], nblk)
            self._block_fill = np.minimum(
                pb.width, np.repeat(pb.count, nblk) - pb.width * rank
            )
        fill = self._block_fill

        def evaluate(active, cq, blk, carry=None, last=True):
            table = rings.table[active[cq]]
            real, mine = fill[blk], rings.edges[active[cq]]
            tally["edge_rows"] += int(mine.sum())
            tally["edge_pairs"] += int((real * mine).sum())
            # (a table of -1 reads the last pad and is masked: the host
            # evaluates a landmark's own edges)
            tally["edge_pairs_padded"] += int(
                (real * np.where(table < 0, mine, rings.pads[table])).sum()
            )
            # a table's stream: what it carried, then this call's chunks
            streams, rest = {}, {}
            carry = carry or {}
            for t in np.union1d(
                table[table >= 0], np.fromiter(carry, np.int64)
            ).tolist():
                sel = np.flatnonzero(table == t)
                tq, tb = cq[sel], blk[sel]
                if t in carry:
                    tq = np.concatenate([carry[t][0], tq])
                    tb = np.concatenate([carry[t][1], tb])
                cut = tq.size if last else tq.size - tq.size % cap
                if cut:
                    streams[t] = tq[:cut], tb[:cut]
                if cut < tq.size:
                    rest[t] = tq[cut:], tb[cut:]
            sel = np.flatnonzero(table < 0)
            hostq, hostb = cq[sel], blk[sel]

            def host_pairs(own, blk):
                """Every real pair of chunks ``(own, blk)``, ``own`` into
                ``active``, and its f64 distance."""
                oi, ci = _engine.chunk_pairs(kx, own, blk)
                return oi, ci, host_polygon_distances(
                    rings, active[oi], kx.host.xy[ci]
                )

            def launch():
                outs, hq, padded, rows = [], [], 0, 0
                for t, (tq, tb) in streams.items():
                    tab, vpad = rings.tables[t], int(rings.pads[t])
                    o, head, p, w = self._launch_chunks(
                        prog, f"polyblocks.v{vpad}", k, (tab,),
                        ((tb.astype(np.int32), pb.n_blocks),
                         (rings.row[active[tq]].astype(np.int32),
                          tab.shape[0] - 1),
                         (tq.astype(np.int32), -1)),
                        thr, vpad=vpad,
                    )
                    outs += o
                    hq.append(tq[head])
                    padded += p
                    rows += w
                host = None
                if hostq.size:  # the host's landmarks: a row each
                    oi, ci, d = host_pairs(hostq, hostb)
                    keep = d <= limit
                    uq = np.unique(hostq)
                    fd, fi = _engine.merge_topk(
                        np.full((uq.size, k), np.inf),
                        np.full((uq.size, k), -1, dtype=np.int64),
                        np.searchsorted(uq, oi[keep]), ci[keep], d[keep], k,
                    )
                    hq.append(uq)
                    host = fd, np.where(fi < 0, _engine._NO_ID, fi)
                    padded += int(hostq.size) * pb.width
                chunks = sum(tq.size for tq, _ in streams.values())
                return (
                    outs, np.concatenate(hq or [np.zeros(0, np.int64)]),
                    padded, rows, host, chunks,
                )

            def oracle(launched):
                parts = [*streams.values(), (hostq, hostb)]
                if not launched:
                    parts += rest.values()
                oi, ci, d = host_pairs(
                    np.concatenate([q for q, _ in parts]),
                    np.concatenate([b for _, b in parts]),
                )
                return np.column_stack([active[oi], ci, d])

            return self._enqueue(launch, oracle, default_s, rest or None)

        return evaluate

    def search(
        self, k: int, *, points=None, seed_ptr=None, seed_cells=None,
        exact: bool = True, max_iterations=None, early_stop=None,
        threshold=None, pair_distances=None, on_iteration=None,
        default_s=None, polygons=None, seeds=None,
    ) -> "_engine.RingResult":
        """One ring search (`engine.ring_search`) under this frontend's
        discipline: ``points`` (n, 2) raw query coordinates — their cells
        assigned here, their pairs evaluated by the block program where
        the index is all points and there is no mesh, else by the pair
        program (or the caller's ``pair_distances``, where it brings
        one) — or, for geometry queries, their cover cells (``seed_ptr``
        / ``seed_cells``) with the caller's own ``pair_distances``; or
        ``polygons`` (`index.LandmarkRings`) with their ``seeds``
        (`index.LandmarkSeeds`), evaluated by the polygon block program
        against an all-point index without a mesh.
        ``knn.expand`` and ``knn.scatter`` guard the pure stages."""
        kx = self.kx
        block_topk = seed_keys = tally = None
        if polygons is not None:
            tally = dict.fromkeys(
                ("edge_pairs", "edge_pairs_padded", "edge_rows"), 0
            )
            if kx.points is None or self.mesh is not None:
                raise ValueError(
                    "polygon queries run the block lane: an all-point "
                    "index and no mesh"
                )
            seed_ptr, seed_cells = seeds.ptr, seeds.cells
            if seeds.keys is not None:
                seed_keys = (seeds.keys, seeds.margin)
            block_topk = self._poly_block_topk(
                polygons, k, np.inf if threshold is None else threshold,
                default_s, tally,
            )
        if points is not None:
            n = points.shape[0]
            qs64 = points - kx.shift
            qsd = qs64.astype(self._dtype, copy=False)
            seed_ptr = np.arange(n + 1, dtype=np.int64)
            seed_cells = self._assign_cells(points)
            if pair_distances is None:
                def pair_distances(qi, ci):
                    return self._distances(qs64, qsd, qi, ci, default_s)
                if kx.points is not None and self.mesh is None:
                    block_topk = self._block_topk(
                        qs64, qsd, k,
                        np.inf if threshold is None else threshold, default_s,
                    )
        res = _engine.ring_search(
            kx, seed_ptr, seed_cells, k, exact=exact,
            max_iterations=max_iterations or self.max_iterations,
            early_stop=early_stop, threshold=threshold,
            pair_distances=pair_distances, block_topk=block_topk,
            guard=guarded_call,
            on_iteration=on_iteration, seed_keys=seed_keys,
        )
        if tally is not None:
            res.counters.update(tally)
        self.stats["iterations"] += res.iterations
        if block_topk is not None:
            self.stats["pairs"] += res.pairs
            self.stats["pairs_padded"] += res.pairs_padded
            self.stats["launches"] += res.launches
            self.stats["rows_pulled"] += res.rows_pulled
        return res

    def _ring_lane(self, pts, k, default_s):
        """Exact iterative lane — the batch model's search
        (`models/knn.SpatialKNN.transform`) with serve discipline."""
        res = self.search(k, points=pts, default_s=default_s)
        return res.dist, res.cid, res.degraded

    # ---------------------------------------------------- voronoi lane

    def _walk_rows(self, qv: np.ndarray, k: int):
        """Greedy walk on the Voronoi adjacency to a locally nearest
        convex site, then breadth-first neighbour collection until k
        distinct candidate geoms are reachable. Returns (rows, ok)."""
        vt = self.kx.voronoi
        sites, adj = vt.sites, vt.adjacency
        cv = sites.shape[0]
        stride = max(1, cv // 64)
        probe = np.arange(0, cv, stride)
        d2 = np.sum((sites[probe] - qv) ** 2, axis=1)
        cur = int(probe[int(np.argmin(d2))])
        curd = float(np.sum((sites[cur] - qv) ** 2))
        while True:
            nbrs = adj[cur]
            nbrs = nbrs[nbrs >= 0]
            if not nbrs.size:
                break
            nd = np.sum((sites[nbrs] - qv) ** 2, axis=1)
            j = int(np.argmin(nd))
            if nd[j] < curd:
                cur, curd = int(nbrs[j]), float(nd[j])
            else:
                break
        rows = {int(vt.geom[cur])}
        seen_sites = {cur}
        frontier = [cur]
        while frontier and len(rows) < k:
            nxt = []
            for s in frontier:
                for t in adj[s]:
                    t = int(t)
                    if t < 0 or t in seen_sites:
                        continue
                    seen_sites.add(t)
                    nxt.append(t)
                    rows.add(int(vt.geom[t]))
            frontier = nxt
        return np.fromiter(sorted(rows), dtype=np.int64), len(rows) >= k

    def _voronoi_lane(self, pts, k, default_s):
        """One-shot exact lane: the walk's kth-distance bound collapses
        ring iteration into a single guaranteed cover dispatch (grid
        radius r satisfies (r-1)*w >= bound, the same guarantee the
        iterative stop rule relies on — so the answer is the ring
        lane's answer, computed in one device round-trip)."""
        kx = self.kx
        vt = kx.voronoi
        n = pts.shape[0]
        qs64 = pts - kx.shift
        qsd = qs64.astype(self._dtype, copy=False)
        qv = pts - vt.shift
        w = kx.cell_width
        dist = np.full((n, k), np.inf)
        cid = np.full((n, k), -1, dtype=np.int64)
        degraded = None

        def expand():
            # pure: per-query cover pairs + the indices the walk could
            # not bound (they take the iterative lane below)
            seeds = self._assign_cells(pts)
            pairs, fallback = [], []
            for i in range(n):
                rows, ok = self._walk_rows(qv[i], k)
                if not ok:
                    fallback.append(i)
                    continue
                ds = host_pair_distances(
                    qs64, kx, np.full(rows.shape[0], i, np.int64), rows
                )
                bound = float(np.partition(ds, k - 1)[k - 1])
                r = int(np.ceil(bound / w)) + 1 if bound > 0 else 1
                if r > self.max_iterations:
                    fallback.append(i)
                    continue
                cells = np.asarray(
                    kx.index_system.k_ring(seeds[i : i + 1], r)
                )
                cells = np.unique(cells[cells >= 0])
                cover = kx.candidate_rows(cells)
                pairs.append((i, np.sort(cover)))
            return pairs, fallback

        with _telemetry.timed(
            "knn_stage", stage="expand", lane="voronoi", queries=n,
        ):
            pairs, fallback = guarded_call("knn.expand", expand)
        self.stats["voronoi_fallback"] += len(fallback)
        qi = np.concatenate(
            [np.full(r.shape[0], i, np.int64) for i, r in pairs]
        ) if pairs else np.zeros(0, dtype=np.int64)
        ci = np.concatenate([r for _, r in pairs]) if pairs else np.zeros(
            0, dtype=np.int64
        )
        if qi.size:
            with _telemetry.timed(
                "knn_stage", stage="distance", lane="voronoi",
                pairs=int(qi.size),
            ):
                d = self._distances(qs64, qsd, qi, ci, default_s)
            if isinstance(d, DegradedResult):
                degraded = d
                d = np.asarray(d)
            with _telemetry.timed(
                "knn_stage", stage="scatter", lane="voronoi",
                pairs=int(qi.size),
            ):
                dist, cid = guarded_call(
                    "knn.scatter",
                    lambda: _engine.merge_topk(dist, cid, qi, ci, d, k),
                )
        if fallback:
            sub = np.asarray(fallback, dtype=np.int64)
            fdist, fcid, fdeg = self._ring_lane(
                pts[sub], k, default_s
            )
            dist[sub] = fdist
            cid[sub] = fcid
            if degraded is None:  # (an array: it has no truth value)
                degraded = fdeg
        return dist, cid, degraded

    # --------------------------------------------------------- serving

    def dispatch(self, points: np.ndarray, k: int, default_s=None):
        """Answer a batch: (n, 2) raw query coords -> ((n, 2k) f64 wire
        rows ``[distances ‖ ids]``, pair-occupancy). Degraded batches
        come back as :class:`DegradedResult` (values exact — the host
        oracle computed them)."""
        if k < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        pts = np.asarray(points, dtype=np.float64)
        n = pts.shape[0]
        if n == 0:
            return np.zeros((0, 2 * k)), 1.0
        p0, b0 = self.stats["pairs"], self.stats["pairs_padded"]
        lane = (
            "voronoi"
            if self.lane == "voronoi" and self.kx.voronoi is not None
            else "ring"
        )
        with _trace.span("knn.dispatch", rows=n, k=k, lane=lane):
            if lane == "voronoi":
                dist, cid, deg = self._voronoi_lane(pts, k, default_s)
            else:
                dist, cid, deg = self._ring_lane(pts, k, default_s)
        self.stats["queries"] += n
        self.stats[f"lane_{lane}"] += n
        out = np.empty((n, 2 * k))
        out[:, :k] = dist
        out[:, k:] = cid.astype(np.float64)
        padded = self.stats["pairs_padded"] - b0
        occupancy = (self.stats["pairs"] - p0) / padded if padded else 1.0
        if deg is not None:
            self.stats["degraded"] += n
            return (
                DegradedResult.wrap(
                    out, reason=deg.reason, attempts=deg.attempts
                ),
                occupancy,
            )
        return out, occupancy

    def query(self, points: np.ndarray, k: int) -> "list[KNNAnswer]":
        """Direct (engine-less) entry: one :class:`KNNAnswer` per row."""
        out, _ = self.dispatch(points, k)
        degraded = isinstance(out, DegradedResult)
        reason = out.reason if degraded else None
        ids, dist = decode_knn(np.asarray(out), k)
        return [
            KNNAnswer(
                ids=ids[i], distance=dist[i], degraded=degraded,
                reason=reason,
            )
            for i in range(ids.shape[0])
        ]

    def warmup(self, k: "int | None" = None, polygons: bool = False) -> dict:
        """Touch every (kind, rung) pair so serving can only replay:
        compiles (or AOT loads) every cell and pair program, then
        freezes the signature set — any later signature is a cold
        compile and fires ``on_cold_compile``. An all-point index
        answers point queries from its blocks: given ``k`` (the block
        program keeps the k best on the device, so k is part of its
        shape) every block rung is touched with every head rung a launch
        of it can take (the ladder's rungs up to its own), and the pair
        rungs, which only geometry queries reach, are not. ``polygons``
        (the model's lane for the landmarks it was shown) warms the
        polygon block program in the point program's place: every (block
        rung, edge rung) of it — its table's shape is the edge rung's
        alone, so these are the programs any polygon column launches —
        and the same head rungs."""
        c0 = backend_compiles()
        blocks = (
            k is not None and self.kx.points is not None and self.mesh is None
        )
        with _trace.span("knn.warmup"):
            for b in self.row_ladder.buckets:
                with _telemetry.timed(
                    "knn_stage", stage="warmup", kind="cells", bucket=b,
                ):
                    self._cells_bucket(np.zeros((b, 2)))
            if blocks and polygons:
                self._warm_polygon_blocks(int(k))
            elif blocks:
                evaluate = self._block_topk(
                    None, np.zeros((1, 2), self._dtype), int(k), np.inf, None
                )
                for b in BLOCK_LADDER.buckets:
                    for h in (r for r in BLOCK_LADDER.buckets if r <= b):
                        with _telemetry.timed(
                            "knn_stage", stage="warmup", kind="blocks",
                            bucket=b, head_bucket=h,
                        ):
                            # b chunks of h queries: h heads, the rung itself
                            evaluate(
                                np.zeros(h, np.int64),
                                np.minimum(np.arange(b), h - 1),
                                np.zeros(b, np.int64),
                            ).pull()
            for b in () if blocks else self.pair_ladder.buckets:
                with _telemetry.timed(
                    "knn_stage", stage="warmup", kind="pairs", bucket=b,
                ):
                    self._pair_bucket(
                        np.zeros((b, 2), dtype=self._dtype),
                        np.zeros(b, dtype=np.int64),
                    )
        self.freeze()
        c1 = backend_compiles()
        report = {
            "signatures": len(self._signatures),
            "row_buckets": len(self.row_ladder.buckets),
            "pair_buckets": len(self.pair_ladder.buckets),
            "block_buckets": len(BLOCK_LADDER.buckets) if blocks else 0,
            "backend_compiles": (
                c1 - c0 if c0 is not None and c1 is not None else None
            ),
            "aot": dict(self.aot_stats),
        }
        _telemetry.record("knn_warmup", **report)
        return report

    def _warm_polygon_blocks(self, k: int) -> None:
        """One launch of every (block rung, edge rung) of the polygon
        block program, the first edge rung's with every head rung."""
        import jax.numpy as jnp

        prog = _engine.poly_block_topk_prog()
        thr = jnp.asarray(np.inf, dtype=self._dtype)
        for v, vpad in enumerate(VERTEX_LADDER.buckets):
            tab = jnp.zeros((table_rows(vpad), 5, vpad), dtype=self._dtype)
            for b in BLOCK_LADDER.buckets:
                heads = [r for r in BLOCK_LADDER.buckets if r <= b]
                for h in heads if v == 0 else heads[:1]:
                    with _telemetry.timed(
                        "knn_stage", stage="warmup", kind="polyblocks",
                        bucket=b, head_bucket=h, vpad=vpad,
                    ):
                        zeros = np.zeros(b, np.int32)
                        outs, _, _, rows = self._launch_chunks(
                            prog, f"polyblocks.v{vpad}", k, (tab,),
                            ((zeros, 0), (zeros, 0),
                             (np.minimum(np.arange(b), h - 1).astype(np.int32),
                              -1)),
                            thr, vpad=vpad,
                        )
                        self._pull_heads(outs, rows, b)

    def metrics(self) -> dict:
        return {
            "knn_queries": self.stats["queries"],
            "knn_pairs": self.stats["pairs"],
            "knn_pair_occupancy": (
                self.stats["pairs"] / self.stats["pairs_padded"]
                if self.stats["pairs_padded"]
                else None
            ),
            "knn_launches": self.stats["launches"],
            "knn_rows_pulled": self.stats["rows_pulled"],
            "knn_iterations": self.stats["iterations"],
            "knn_degraded": self.stats["degraded"],
            "knn_lane_ring": self.stats["lane_ring"],
            "knn_lane_voronoi": self.stats["lane_voronoi"],
            "knn_voronoi_fallback": self.stats["voronoi_fallback"],
            "knn_signatures": len(self._signatures),
            "knn_cold_compiles": self._cold_compiles,
            "knn_aot": dict(self.aot_stats),
        }
