"""The ring-expansion engine: one KNN search over arrays of queries.

Reference analog: `models/knn/SpatialKNN.scala:136-164`
(`iterationTransform`) and `GridRingNeighbours.scala:76-99`: iteration 1
joins each query's cover k-ring(1) against the cell-sorted candidate
chips, iteration i > 1 only the k-loop(i) shell, so a candidate is
inspected once; a query rests when it holds k matches and the
grid-guaranteed radius ``(i - 1) * cell_width`` reaches its kth distance.

Both `SpatialKNN.transform` and `KNNFrontend` (hence
`ServeEngine.submit_knn`) run :func:`ring_search`. Every step of an
iteration is an array expression over all active queries at once — ring
cells (`KNNIndex.ring_keys`: integer adds on a lattice), the CSR probe, the fresh (query,
candidate) pairs, the top-k merge under the oracle's tie rule (distance,
then candidate id), the rest criterion — so no Python statement runs once
a query or once a pair. Two ways to evaluate an iteration's pairs:

- **pairs** (any geometry on either side): the fresh pairs are made on
  the host from the CSR, deduplicated against the pairs already seen
  (a polygon's chips lie in many cells), handed to the caller's distance
  function, and merged on the host (:func:`merge_topk`).
- **blocks** (point queries against an all-point index,
  `index.PointBlocks`): rings of a point are disjoint and a point has one
  chip, so nothing is deduplicated and no pair is materialised on the
  host. The host lists (query, block) chunks; the device gathers each
  chunk's block row, evaluates the distances, keeps the chunk's k best
  and folds the chunks of one query together (:func:`block_topk_prog`).
  The host names, with each launch, the slots where a query begins in it
  (its *heads*); a second small program gathers those rows on the device
  (:func:`block_heads_prog`), so one row a query a launch comes back and
  the per-chunk answers never leave the chip (:func:`fold_heads`).
  Polygon queries run the same lane from several seed cells each
  (`index.polygon_cover`): their seeds' rings overlap, so an iteration's
  (query, cell) keys are deduplicated and held against the cells the
  query has met (:func:`block_chunks_multi`), and a chunk's candidates
  are evaluated against the query's edges (:func:`poly_block_topk_prog`).
  An iteration of this lane is a software pipeline over **slabs** of its
  active queries (:func:`slab_bounds`): slab ``s`` is expanded and its
  launches enqueued, nothing pulled; only then are the launches enqueued
  before it pulled and folded, so the host expands and merges while the
  device works. What a slab leaves past its last full launch is carried
  into the next slab's first launch, so the launches are the one-slab
  schedule's, cut for cut, and so are the answers, bit for bit.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from ..dispatch import bounded_cache
from ..obs import trace as _trace
from ..runtime import telemetry as _telemetry
from ..runtime.errors import DegradedResult
from .index import KNNIndex, edge_terms, expand_ranges

_NO_ID = np.iinfo(np.int32).max
#: ring keys a slab of the block lane's iteration searches: the host's
#: expand costs what its keys cost (the probe of the occupied cells), so
#: this is the host work that is not yet under device work when an
#: iteration begins, and the grain at which the two alternate after that.
#: An iteration under two slabs' worth is one slab: the schedule of
#: before PR 45, which late iterations, served queries and small tables
#: keep. The chip chose it: a 100,000-landmark call takes 738 ms at
#: 65,536, 699 at 131,072 and 717-729 at 262,144, where one slab an
#: iteration takes 861 (PERF.md section 6, PR 45)
SLAB_KEYS = 1 << 17


@dataclasses.dataclass
class RingResult:
    dist: np.ndarray  # (n, k) f64, inf = unfilled
    cid: np.ndarray  # (n, k) int64, -1 = unfilled
    iterations: int = 0
    #: queries still owed a ring when ``max_iterations`` (or, in an
    #: approximate search, the early stop) ended the loop
    unrested: int = 0
    pairs: int = 0  # (query, candidate) pairs whose distance was evaluated
    pairs_padded: int = 0  # slots the device evaluated for them
    launches: int = 0  # device launches of the distance programs
    rows_pulled: int = 0  # block lane: answer rows pulled, padded to a rung
    slabs: int = 0  # block lane: slabs of queries expanded, all iterations
    #: block lane: host seconds of ``knn.expand`` and ``knn.scatter`` spent
    #: while launches of the same iteration were enqueued and not yet pulled
    hidden_s: float = 0.0
    degraded: "DegradedResult | None" = None
    #: what the evaluator counted besides (polygon queries: `edge_pairs`, ...)
    counters: dict = dataclasses.field(default_factory=dict)


# ------------------------------------------------------------ host merge


def merge_topk(dist, cid, qi, ci, d, k):
    """Pure top-k merge: fold (query, candidate, distance) triples into
    the running (dist, cid) state, ranked by ``(distance, candidate id)``
    — the oracle's tie rule. Pairs are deduplicated upstream, so a
    candidate never appears twice in a row. One lexsort over the touched
    rows' state and the triples; no loop over queries."""
    dist, cid = dist.copy(), cid.copy()
    if not qi.size:
        return dist, cid
    uq = np.unique(qi)
    aq = np.concatenate([np.repeat(uq, k), qi])
    ad = np.concatenate([dist[uq].ravel(), d])
    ac = np.concatenate([cid[uq].ravel(), ci])
    live = ac >= 0  # unfilled slots of the state carry no candidate
    aq, ad, ac = aq[live], ad[live], ac[live]
    order = np.lexsort((ac, ad, aq))
    aq, ad, ac = aq[order], ad[order], ac[order]
    start = np.flatnonzero(np.r_[True, aq[1:] != aq[:-1]])
    rank = np.arange(aq.size) - np.repeat(start, np.diff(np.r_[start, aq.size]))
    keep = rank < k
    dist[uq], cid[uq] = np.inf, -1
    dist[aq[keep], rank[keep]] = ad[keep]
    cid[aq[keep], rank[keep]] = ac[keep]
    return dist, cid


def _merge_rows(dist, cid, fd, fi, k):
    """Row-wise merge of two ranked (m, k) lists (disjoint candidates)."""
    cd = np.concatenate([dist, fd], axis=1)
    cc = np.concatenate([cid, fi], axis=1)
    # unfilled slots (inf, -1) sort after every real pair at inf
    key = np.where(cc < 0, np.iinfo(np.int64).max, cc)
    order = np.lexsort((key, cd), axis=1)[:, :k]
    return (
        np.take_along_axis(cd, order, axis=1),
        np.take_along_axis(cc, order, axis=1),
    )


# ---------------------------------------------------------- device blocks


def _topk_rows(d, ids, k):
    """The k smallest of each row by (distance, id): ``k`` rounds of a
    lane minimum. Empty slots carry (inf, _NO_ID) and come out so."""
    import jax.numpy as jnp

    out_d, out_i = [], []
    for _ in range(k):
        m = jnp.min(d, axis=1, keepdims=True)
        j = jnp.min(jnp.where(d == m, ids, _NO_ID), axis=1, keepdims=True)
        out_d.append(m)
        out_i.append(j)
        taken = (d == m) & (ids == j)
        d = jnp.where(taken, jnp.inf, d)
        ids = jnp.where(taken, _NO_ID, ids)
    return jnp.concatenate(out_d, axis=1), jnp.concatenate(out_i, axis=1)


@bounded_cache("knn_block_topk", 1)
def block_topk_prog():
    """The ONE jitted block program every frontend shares (jax's trace
    cache keys the chunk rung and k). Per (query, block) chunk: gather
    the block's row of candidates, evaluate the distances to the chunk's
    query point, keep the k best by (distance, id); then fold the chunks
    of one query — they are adjacent — by doubling: after step ``s`` a
    chunk holds the best of the ``2s`` chunks from it on, so the first
    chunk of a query ends with the query's k best of this launch. Its
    ``(b, k)`` outputs stay on the device: :func:`block_heads_prog` reads
    the head rows out of them."""
    import jax
    import jax.numpy as jnp

    def knn_blocks(bx, by, rid, px, py, blk, qid, thr, steps, k):
        with jax.named_scope("knn.gather"):
            gx, gy, gid = bx[blk], by[blk], rid[blk]
        with jax.named_scope("knn.distance"):
            dx, dy = px[:, None] - gx, py[:, None] - gy
            d = jnp.sqrt(dx * dx + dy * dy)
            live = (gid >= 0) & (d <= thr)
            d = jnp.where(live, d, jnp.inf)
            gid = jnp.where(live, gid, _NO_ID)
        with jax.named_scope("knn.topk"):
            return _fold_chunks(d, gid, qid, steps, k)

    return jax.jit(knn_blocks, static_argnames=("k",))


def _fold_chunks(d, gid, qid, steps, k):
    """Each chunk's k best of its ``(b, width)`` row, then the chunks of
    one query folded by doubling (see :func:`block_topk_prog`)."""
    import jax
    import jax.numpy as jnp

    d, gid = _topk_rows(d, gid, k)

    def fold(i, state):
        d, gid = state
        s = jnp.left_shift(jnp.int32(1), i)
        same = (jnp.roll(qid, -s) == qid) & (
            jnp.arange(qid.shape[0]) + s < qid.shape[0]
        )
        nd = jnp.where(same[:, None], jnp.roll(d, -s, axis=0), jnp.inf)
        ni = jnp.where(
            same[:, None], jnp.roll(gid, -s, axis=0), _NO_ID
        )
        return _topk_rows(
            jnp.concatenate([d, nd], axis=1),
            jnp.concatenate([gid, ni], axis=1), k,
        )

    return jax.lax.fori_loop(0, steps, fold, (d, gid))


@bounded_cache("knn_poly_block_topk", 1)
def poly_block_topk_prog():
    """The block program of polygon queries (jax's trace cache keys the
    chunk rung, the edge pad and k). Per (query, block) chunk: gather the
    block's row of candidates and the query's row of ``tab`` (see
    `index.LandmarkRings`: ``(rows, 5, vpad)``, the rows fixed by the pad,
    so the landmark column is no part of the shape), then edge after edge
    keep each candidate's least squared distance to a segment and the
    parity of the edges a ray from it crosses: the distance is 0 where the
    parity is odd (inside by the even-odd rule, so not in a courtyard),
    else the root of the least. From there on it is the point program:
    k best a chunk, fold by doubling, ``(b, k)`` outputs that stay on the
    device."""
    import jax
    import jax.numpy as jnp

    def knn_poly_blocks(bx, by, rid, tab, blk, lrow, qid, thr, steps, k):
        with jax.named_scope("knn.gather"):
            gx, gy, gid = bx[blk], by[blk], rid[blk]
            # edge-major, so the loop below indexes the leading axis
            et = jnp.transpose(tab[lrow], (2, 1, 0))[..., None]
        with jax.named_scope("knn.edges"):

            def edge(j, state):
                d2, odd = state
                e = et[j]  # (5, b, 1)
                nd2, cross = edge_terms(
                    gx, gy, e[0], e[1], e[2], e[3], e[4], jnp
                )
                return jnp.minimum(d2, nd2), odd ^ cross

            d2, odd = jax.lax.fori_loop(
                0, et.shape[0], edge,
                (jnp.full(gx.shape, jnp.inf, gx.dtype),
                 jnp.zeros(gx.shape, bool)),
            )
            d = jnp.where(odd, jnp.zeros((), gx.dtype), jnp.sqrt(d2))
            live = (gid >= 0) & (d <= thr)
            d = jnp.where(live, d, jnp.inf)
            gid = jnp.where(live, gid, _NO_ID)
        with jax.named_scope("knn.topk"):
            return _fold_chunks(d, gid, qid, steps, k)

    return jax.jit(knn_poly_blocks, static_argnames=("k",))


@bounded_cache("knn_block_heads", 1)
def block_heads_prog():
    """The gather behind every launch of :func:`block_topk_prog`: the rows
    of its folded ``(d, gid)`` at ``head``, the launch's slots where a
    query begins, padded to a rung of the block ladder. A program (and a
    module name, hence a stage table) of its own: the block program's
    executables stay what they were, whatever the head rung."""
    import jax

    def knn_heads(d, gid, head):
        with jax.named_scope("knn.heads"):
            return d[head], gid[head]

    return jax.jit(knn_heads)


def block_chunks(pb, ring: np.ndarray):
    """The (query, block) chunks of one iteration: ``ring`` is (a, M) ring
    cells of ``a`` active point queries, -1 pads. Returns ``(cq, blk,
    fresh)`` — chunk owner (index into the active set, ascending), chunk
    block, and (a,) candidates met per query."""
    a, m = ring.shape
    flat = ring.ravel()
    pos = np.minimum(np.searchsorted(pb.ucells, flat), pb.ucells.size - 1)
    hit = np.flatnonzero(pb.ucells[pos] == flat)
    return _cell_chunks(pb, hit // m, pos[hit], a)


def _cell_chunks(pb, own, pos, a: int):
    """``(cq, blk, fresh)`` of the occupied cells ``pos`` (indices into
    ``pb.ucells``) that the queries ``own`` (ascending) meet."""
    nblk = pb.blk_start[pos + 1] - pb.blk_start[pos]
    fresh = np.bincount(own, weights=pb.count[pos], minlength=a)
    return (
        np.repeat(own, nblk), expand_ranges(pb.blk_start[pos], nblk),
        fresh.astype(np.int64),
    )


def block_chunks_multi(pb, active, owner, ring, met, many):
    """:func:`block_chunks` for queries of several seeds: ``ring`` is (S,
    M) ring cells of seed ``s`` whose query is ``active[owner[s]]``. The
    seeds' rings overlap, so the occupied (query, cell) keys are made
    unique, and those in ``met`` — sorted keys of the cells the queries
    ``many`` marks (those of more than one seed) met in earlier
    iterations — are dropped: a cell is counted, and its blocks listed,
    once a query. Returns ``(cq, blk, fresh, new)``, ``new`` the keys
    ``met`` grows by (ascending; the caller unites them once an iteration:
    slabs of one iteration hold other queries, so none reads another's)."""
    m = ring.shape[1]
    u = pb.ucells.size
    flat = ring.ravel()
    pos = np.minimum(np.searchsorted(pb.ucells, flat), u - 1)
    hit = np.flatnonzero(pb.ucells[pos] == flat)
    key = np.unique(active[owner[hit // m]] * u + pos[hit])
    if met.size and key.size:
        at = np.minimum(np.searchsorted(met, key), met.size - 1)
        key = key[met[at] != key]
    query, pos = key // u, key % u
    own = np.searchsorted(active, query)
    return (*_cell_chunks(pb, own, pos, active.size), key[many[query]])


def chunk_pairs(kx: KNNIndex, own: np.ndarray, blk: np.ndarray):
    """Every (query, candidate) pair of chunks ``(own, blk)`` — a chunk's
    query and its block — from the CSR alone: a cell's candidates fill its
    blocks in row order (`index.PointBlocks`), so block ``b`` of cell ``u``
    is a run of the cell's CSR rows. What the host oracle answers where
    the device could not."""
    pb = kx.points
    u = np.searchsorted(pb.blk_start, blk, side="right") - 1
    at = (blk - pb.blk_start[u]) * pb.width
    n = np.minimum(pb.width, pb.count[u] - at)
    lo = np.searchsorted(kx.cells, pb.ucells[u]) + at
    return np.repeat(own, n), kx.rows[expand_ranges(lo, n)]


def slab_bounds(keys: np.ndarray) -> np.ndarray:
    """Where an iteration's active queries are cut into slabs: ``keys[i]``
    is the ring keys query ``i`` will search, a slab takes about
    `SLAB_KEYS` of them, and an iteration under two slabs' worth is one
    slab. (s + 1,) ascending bounds into the active set."""
    cum = np.cumsum(keys)
    n = int(cum[-1]) // SLAB_KEYS
    if n < 2:
        return np.array([0, keys.size])
    cuts = np.searchsorted(cum, cum[-1] * np.arange(1, n) // n) + 1
    return np.unique(np.r_[0, cuts, keys.size])


def launch_heads(cq: np.ndarray, cap: int) -> np.ndarray:
    """The head chunks of an iteration's launches (``cap`` chunks each):
    slot 0 of every launch and every chunk whose owner differs from the
    one before it — ``cq`` is ascending, so that is where a query begins.
    Ascending positions into ``cq``."""
    return np.union1d(
        np.arange(0, cq.shape[0], cap), np.flatnonzero(cq[1:] != cq[:-1]) + 1
    )


def fold_heads(hq, hd, hi, a: int, k: int):
    """Per active query the k best of this iteration, from the launches'
    head rows: ``hq`` (ascending) owns row ``(hd, hi)``, a launch's answer
    for that query; a query whose chunks straddle launches has one such
    row a launch (at most one a launch boundary), merged here. Returns
    (a, k) f64 distances and int64 ids (inf / -1 unfilled)."""
    fd = np.full((a, k), np.inf)
    fi = np.full((a, k), -1, dtype=np.int64)
    if not hq.shape[0]:
        return fd, fi
    hd = hd.astype(np.float64, copy=False)
    hi = hi.astype(np.int64)
    hi[hi == _NO_ID] = -1
    first = np.r_[True, hq[1:] != hq[:-1]]
    fd[hq[first]], fi[hq[first]] = hd[first], hi[first]
    rest = np.flatnonzero(~first)
    if rest.size:
        live = hi[rest] >= 0
        fd, fi = merge_topk(
            fd, fi, np.repeat(hq[rest], k)[live.ravel()],
            hi[rest][live], hd[rest][live], k,
        )
    return fd, fi


def ring_pairs(kx: KNNIndex, owner: np.ndarray, ring: np.ndarray):
    """Every (query, candidate) pair of one iteration from the CSR:
    ``ring`` is (s, M) ring cells of seed ``s`` whose query is
    ``owner[s]``. Pairs repeat where a candidate's chips lie in several
    of a query's cells."""
    flat = ring.ravel()
    lo = np.searchsorted(kx.cells, flat, side="left")
    hi = np.searchsorted(kx.cells, flat, side="right")
    hi[flat < 0] = lo[flat < 0]
    qi = np.repeat(np.repeat(owner, ring.shape[1]), hi - lo)
    return qi, kx.rows[expand_ranges(lo, hi - lo)]


# ------------------------------------------------------------- the search


def ring_search(
    kx: KNNIndex, seed_ptr: np.ndarray, seed_cells: np.ndarray, k: int, *,
    exact: bool = True, max_iterations: int, early_stop: "int | None" = None,
    threshold: "float | None" = None, pair_distances=None, block_topk=None,
    guard=None, on_iteration=None, seed_keys=None,
) -> RingResult:
    """Ring-expansion KNN for ``n`` queries given by their cover cells
    (CSR ``seed_ptr`` / ``seed_cells``; a point has one).

    ``pair_distances(qi, ci) -> (P,) f64`` evaluates fresh pairs (it may
    return a `DegradedResult`). ``block_topk(active, cq, blk, carry, last)``
    — given, it is the lane taken — ENQUEUES (query, block) chunks on the
    device and pulls nothing: ``cq`` indexes the iteration's ``active``,
    ``carry`` is what the call before it in this iteration left unlaunched
    (None at first), and unless ``last`` it launches whole top-rung cuts
    only and carries the rest. It returns a pending handle: ``padded``,
    ``launches``, ``rows`` (what it enqueued), ``carry``, and ``pull(**span
    fields) -> (hq, hd, hi)``, the blocking pull of the launches' head rows
    and their owners (see :func:`fold_heads`; rows of one owner adjacent).
    Either half, degraded, returns a `DegradedResult` of (P, 3) rows
    ``(query, candidate, distance)`` instead: the host oracle's answer for
    every chunk that half was handed. ``guard(stage, fn)`` runs the
    pure stages (``knn.expand``, ``knn.scatter``: the frontend's failure
    domains). ``exact=False`` rests a query at k matches, and
    ``early_stop`` rounds without a new match or a newly filled query end
    the loop (the reference's `earlyStoppingCheck`); an exact search ends
    by the rest criterion or ``max_iterations`` alone.
    ``on_iteration(it, qi, ci, d)`` sees each iteration's evaluated pairs
    (the pairs lane only: the checkpoint log). ``seed_keys`` is ``(keys,
    margin)`` of the seeds where the caller made them on the lattice
    (`index.polygon_cover`), else they are looked up (`KNNIndex.probe_keys`)."""
    n = seed_ptr.shape[0] - 1
    guard = guard or (lambda site, fn: fn())
    out = RingResult(
        dist=np.full((n, k), np.inf), cid=np.full((n, k), -1, dtype=np.int64)
    )
    w = kx.cell_width
    thr = np.inf if threshold is None else float(threshold)
    seen_keys = np.zeros(0, dtype=np.int64)  # pairs lane: q * N + c, sorted
    seen_count = np.zeros(n, dtype=np.int64)
    nseed = np.diff(seed_ptr)
    seed_keys, seed_margin = seed_keys or kx.probe_keys(seed_cells)
    # block lane, queries of several seeds: the cells they have met
    many = nseed > 1
    multi = bool(many.any())
    met = np.zeros(0, dtype=np.int64)
    stable, prev = 0, (n, 0)

    def owed(it):
        """Queries that iteration ``it`` still has to serve: candidates
        remain, and the radius the rings before it are sure to cover,
        ``(it - 1) * w``, reaches neither the threshold nor (exact) the
        kth distance / (approximate) a kth match at all."""
        reach = (it - 1) * w
        need = (seen_count < kx.n) & (nseed > 0) & (reach < thr)
        if exact:
            return need & (reach < out.dist[:, k - 1])
        return need & (out.cid[:, k - 1] < 0)

    def ring_of(sub, it):
        """``(owner, ring)``: iteration ``it``'s ring keys of the queries
        ``sub``, a row a seed, and the seed's place in ``sub``."""
        owner = np.repeat(np.arange(sub.size), nseed[sub])
        at = expand_ranges(seed_ptr[sub], nseed[sub])
        return owner, kx.ring_keys(
            seed_cells[at], seed_keys[at],
            None if seed_margin is None else seed_margin[at], it,
        )

    def run_blocks(it, active) -> bool:
        """One iteration of the block lane, slab after slab of ``active``:
        pass ``s`` expands slab ``s`` and enqueues its launches, then pulls
        and folds what pass ``s - 1`` enqueued; the pass after the last
        only pulls and folds. Every step is pure until it commits, slab by
        slab (slabs hold other queries). Returns whether a chunk was met."""
        nonlocal met
        # a seed searches the lattice ring's positions; off a lattice the
        # ring cells are the grid's own device ops, a fixed cost a call
        # whatever the seeds: no keys counted, one slab an iteration
        width = kx.index_system.lattice_ring(it).size if kx.lattice else 0
        bounds = slab_bounds(nseed[active] * width)
        nslab = bounds.size - 1
        out.slabs += nslab
        # rows that may hold something: all after iteration 1; in it, those
        # an earlier pull of this iteration (or the oracle) wrote
        touched = np.full(active.size, it > 1)
        flying = carry = None
        grown, chunks, under = [], 0, 0.0

        def hidden(t0):
            """The seconds since ``t0``, where launches are in flight."""
            if flying is None or not flying.launches:
                return 0.0
            return time.perf_counter() - t0

        def merge_oracle(rows):
            """Fold a degraded half's (query, candidate, distance) triples."""
            if out.degraded is None:  # (an array: it has no truth value)
                out.degraded = rows
            rows = np.asarray(rows)
            qi, ci = rows[:, 0].astype(np.int64), rows[:, 1].astype(np.int64)
            keep = rows[:, 2] <= thr
            with _trace.span("knn.scatter", iteration=it, pairs=int(qi.size)):
                out.dist, out.cid = guard("knn.scatter", lambda: merge_topk(
                    out.dist, out.cid, qi[keep], ci[keep], rows[keep, 2], k))
            touched[np.searchsorted(active, np.unique(qi))] = True

        def fold(hq, hd, hi):
            # (rows of one owner are adjacent: no sort finds the owners)
            first = np.r_[True, hq[1:] != hq[:-1]]
            uq = hq[first]
            fd, fi = fold_heads(np.cumsum(first) - 1, hd, hi, uq.size, k)
            m = np.flatnonzero(touched[uq])
            if m.size:
                at = active[uq[m]]
                fd[m], fi[m] = _merge_rows(
                    out.dist[at], out.cid[at], fd[m], fi[m], k)
            return uq, fd, fi

        for s in range(nslab + 1):
            todo, pairs, cq = None, 0, ()
            if s < nslab:
                lo = int(bounds[s])
                sub = active[lo : bounds[s + 1]]

                def expand():
                    owner, ring = ring_of(sub, it)
                    if multi:
                        return block_chunks_multi(
                            kx.points, sub, owner, ring, met, many)
                    return block_chunks(kx.points, ring)

                t0 = time.perf_counter()
                with _trace.span(
                    "knn.expand", iteration=it, queries=int(sub.size)
                ), _telemetry.timed(
                    "knn_stage", stage="expand", iteration=it,
                    queries=int(sub.size),
                ):
                    cq, blk, fresh, *new = guard("knn.expand", expand)
                under += hidden(t0)
                grown += new
                seen_count[sub] += fresh
                pairs = int(fresh.sum())
                out.pairs += pairs
                chunks += int(cq.size)
                last = s == nslab - 1
                if cq.size or (last and carry is not None):
                    todo = lo + cq, blk, carry, last
            if todo is None and flying is None:
                continue
            got = rows = None
            with _trace.span(
                "knn.distance", iteration=it, pairs=pairs, chunks=len(cq),
            ), _telemetry.timed("knn_stage", stage="distance", pairs=pairs):
                if todo is not None:
                    got = block_topk(active, *todo)
                if flying is not None:
                    rows = flying.pull(
                        iteration=it, slabs=nslab, hidden_s=under)
                    out.hidden_s += under
                    under = 0.0
            flying = None
            if isinstance(got, DegradedResult):
                # the host oracle answered the slab and the carry
                merge_oracle(got)
                carry = None
            elif got is not None:
                out.pairs_padded += got.padded
                out.launches += got.launches
                out.rows_pulled += got.rows
                flying, carry = got, got.carry
            if isinstance(rows, DegradedResult):
                merge_oracle(rows)
            elif rows is not None and rows[0].size:
                t0 = time.perf_counter()
                with _trace.span(
                    "knn.scatter", iteration=it, rows=int(rows[0].size)
                ), _telemetry.timed(
                    "knn_stage", stage="scatter", rows=int(rows[0].size)
                ):
                    uq, fd, fi = guard("knn.scatter", lambda: fold(*rows))
                under += hidden(t0)
                out.dist[active[uq]], out.cid[active[uq]] = fd, fi
                touched[uq] = True
        if grown:
            met = np.union1d(met, np.concatenate(grown))
        return chunks > 0

    for it in range(1, max_iterations + 1):
        active = np.flatnonzero(owed(it))
        if not active.size:
            return out
        out.iterations = it
        if block_topk is not None:
            if not run_blocks(it, active):
                continue
        else:
            def expand():
                # pure: the state commits after the guarded call returns, so
                # a transient-fault retry re-reads identical state
                owner, ring = ring_of(active, it)
                qi, ci = ring_pairs(kx, active[owner], ring)
                keys = np.unique(qi * kx.n + ci)
                return keys[~np.isin(keys, seen_keys, assume_unique=True)]

            with _trace.span(
                "knn.expand", iteration=it, queries=int(active.size)
            ), _telemetry.timed(
                "knn_stage", stage="expand", iteration=it,
                queries=int(active.size),
            ):
                keys = guard("knn.expand", expand)
            seen_keys = np.union1d(seen_keys, keys)
            qi, ci = keys // kx.n, keys % kx.n
            seen_count += np.bincount(qi, minlength=n)
            if not qi.size:
                continue
            pairs = int(qi.size)
            with _trace.span("knn.distance", iteration=it, pairs=pairs), \
                    _telemetry.timed("knn_stage", stage="distance", pairs=pairs):
                d = pair_distances(qi, ci)
            if isinstance(d, DegradedResult):
                if out.degraded is None:
                    out.degraded = d
                d = np.asarray(d)
            out.pairs += pairs
            keep = d <= thr
            qi, ci, d = qi[keep], ci[keep], d[keep]
            with _trace.span("knn.scatter", iteration=it, pairs=pairs), \
                    _telemetry.timed("knn_stage", stage="scatter", pairs=pairs):
                out.dist, out.cid = guard(
                    "knn.scatter",
                    lambda: merge_topk(out.dist, out.cid, qi, ci, d, k),
                )
            if on_iteration is not None:
                on_iteration(it, qi, ci, d)
        if not exact and early_stop is not None:
            now = (int((out.cid[:, k - 1] < 0).sum()), int((out.cid >= 0).sum()))
            stable = stable + 1 if now == prev else 0
            prev = now
            if stable >= early_stop:
                break
    # the loop's end cut these off: they were owed the next ring
    out.unrested = int(owed(out.iterations + 1).sum())
    return out
