"""Device-side overlay join kernels: sorted segment equi-join + clip area.

The overlay candidate generator is the cell-id twin of the segment
machinery `kernels/zonal.py` already uses — both chip tables arrive
sorted by int64 cell id (a one-time host prep, amortized like the chip
index build), and the per-query work runs on device:

- :func:`pair_spans` / :func:`pair_count` — run-length segment spans via
  two ``searchsorted`` probes of the right table per left row: the span
  ``[lo, lo+cnt)`` of right rows sharing the left row's cell. The SEARCHED
  form, over the raw int64 cell ids: what the numpy twin (the oracle)
  runs, and what the form below is tested against.
- :func:`run_offsets` / :func:`rank_spans` — the same spans, READ: the
  prep ranks every row's cell densely (one rank a distinct cell of the
  pair, the two pad sentinels after them), the right column is kept as
  the offsets of its runs over those ranks (an index of it, built once a
  prep), and a left row's span is ``roff[rank], roff[rank + 1]`` — two
  int32 gathers, where a search is some twenty rounds of two-word
  compares on a chip with no 64-bit integers. The device lane's form,
  computed once a call.
- :func:`emit_spans` (and :func:`emit_pairs`, the searched spans in front
  of it) — bounded CSR cross-join emission: pair rank ``k`` maps to its
  left row, the last one whose exclusive span offset is at most ``k``,
  and to its right row by the in-span remainder, against a STATIC pair
  bucket so the compiled program population stays on the dispatch
  ladder. The numpy twin finds the left row by a ``searchsorted`` over
  the offsets (the oracle's job is to be obviously right); the device
  does NOT search — the offsets are sorted and the slots consecutive, so
  it scatters a mark at every row's offset onto the slots, sums the
  marks along the slots and gathers once (:func:`_rows_by_marks`; a
  search is some twenty rounds of a gather of every slot, and the chip
  pays a gather per index). Caps are full-bucket: overflow is structural
  (the caller truncates at an explicit cap and reports OVERFLOW(-2)
  in-band), never an escalation.
- :func:`clip_area_convex` — batched Sutherland–Hodgman clip area
  against a CONVEX window, mirroring
  `core.tessellate.clip_rings_convex_batch` operation for operation
  (same half-plane sign test, same ``denom`` guard, same parametric
  intersection formula) but with a STATIC output width. The subject may
  be any simple ring. Consecutive duplicate vertices are NOT removed —
  they contribute exactly 0.0 to the shoelace sum, and area is the only
  consumer. The shoelace is taken about the clipped ring's first
  vertex, so a ring that collapsed onto an axis-parallel line sums
  exact zeros: a touch along a shared edge reads 0.0 in any dtype.
- :func:`fan_area` — the signed fan for a simple NON-convex window:
  ``area(S ∩ W) = Σ_i sign(T_i) · area(S ∩ |T_i|)`` over the triangles
  ``T_i = (w_0, w_i, w_{i+1})``, each a convex window of three
  half-planes for the clip above.
- :func:`base_areas`, :func:`clip_rows`, :func:`fan_rows`,
  :func:`in_band` — the three streams the fused measure program folds:
  table areas for rows with a core chip, the convex clip in place or
  SWAPPED (the area is symmetric, so the convex ring is the window
  whichever side it is on), the fan; and the epsilon recheck that hands
  a row to the f64 host lane.

The rings arrive in the frame of their own cell's corner
(`sql.overlay._pack_rings`), so a coordinate is at most a cell's extent
and the band ``EDGE_BAND_K · eps(arithmetic) · cell²`` is as narrow as
the dtype allows. The device path of the clip holds no gather — on the
TPU a gather is paid per index, and an edge round has ``P × W`` of them:
a window vertex is a static slice, a ring's next vertex a shift and a
select, the left-pack a masked sum with one live term
(:func:`_pack_rows`).

Every kernel takes ``xp`` (jnp or numpy) and is written against the
array-API subset the two share, so the f64 host twin used by the
overlay oracle IS this code: elementwise IEEE ops agree bitwise between
numpy and XLA CPU, integer searchsorted/cumsum/gather are exact, and
the only float scatter (:func:`_scatter_rows`, the host's left-pack)
writes disjoint targets. (The one place the lanes share no logic is the
emission's left row — searched on the host, marked and summed on the
device, integer adds whose order cannot matter — and the tests hold the
two to each other array for array.) The shoelace accumulation is an
UNROLLED python loop over the static width on both sides — XLA
preserves the float op order of an unrolled chain, which is what makes
the device area bit-identical to the numpy twin under x64 off the TPU.
The fold back to per-geometry-pair totals is
`kernels.zonal.zonal_fold_masked` on device and :func:`host_pair_fold`
(``np.add.at`` — sequential in row order, like XLA's CPU scatter) on
host.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

__all__ = [
    "CLIP_EPS",
    "LEFT_PAD_CELL",
    "RIGHT_PAD_CELL",
    "base_areas",
    "clip_area_convex",
    "clip_rows",
    "emit_pairs",
    "emit_spans",
    "fan_area",
    "fan_rows",
    "host_pair_fold",
    "in_band",
    "pair_count",
    "pair_spans",
    "rank_spans",
    "run_offsets",
    "window_swaps",
]

#: same half-plane epsilon as `core.tessellate._EPS` — device clips and
#: the tessellation clipper must agree on what "on the edge" means
CLIP_EPS = 1e-12

#: pad sentinels for the sorted cell columns. Distinct per side so a pad
#: row can never equi-join another pad row; both sort above every real
#: cell id, so pads stay at the tail of the sorted table. The dense ranks
#: keep the order: the right sentinel's rank follows the last real
#: cell's, the left sentinel's follows that.
LEFT_PAD_CELL = np.int64(2**62 - 1)
RIGHT_PAD_CELL = np.int64(2**62 - 2)


def _scope(name: str, xp):
    """``jax.named_scope(name)`` on the device lane (`obs/stages.py`
    names the device's ops by it), nothing on the numpy twin."""
    if xp is jnp:
        import jax

        return jax.named_scope(name)
    import contextlib

    return contextlib.nullcontext()


# ----------------------------------------------------- segment equi-join


def pair_spans(lcells, rcells, n_left, xp=jnp):
    """Per-left-row right-table span, SEARCHED: ``(lo, cnt)`` with
    ``cnt[i]`` right rows sharing cell ``lcells[i]`` starting at sorted
    right row ``lo[i]``. Both cell columns must be sorted ascending with
    their pad sentinels at the tail; rows at and past ``n_left`` count
    zero. The numpy twin's form (raw ids, no prepared table between the
    oracle and the answer); the device lane reads the same integers
    through :func:`rank_spans`."""
    with _scope("overlay.spans", xp):
        lcells = xp.asarray(lcells)
        rcells = xp.asarray(rcells)
        lo = xp.searchsorted(rcells, lcells, side="left")
        hi = xp.searchsorted(rcells, lcells, side="right")
        valid = xp.arange(lcells.shape[0]) < n_left
        cnt = xp.where(valid, hi - lo, 0)
    return lo, cnt


def pair_count(lcells, rcells, n_left, xp=jnp):
    """Total candidate pair count of the sorted equi-join (exact, the
    number `emit_pairs` would emit uncapped)."""
    _, cnt = pair_spans(lcells, rcells, n_left, xp=xp)
    return cnt.sum()


def run_offsets(rank, length: int) -> np.ndarray:
    """(length,) int32 run offsets of a SORTED dense-rank column (host,
    once a prep): ``roff[r]`` is the first row holding rank ``r`` — the
    count of rows ranked below it, whether or not ``r`` occurs — so
    ``roff[r + 1] - roff[r]`` is rank ``r``'s run length, 0 where the
    column has no such row. ``length`` must exceed every rank by two
    (``roff[r + 1]`` is read for the largest); past the largest rank the
    table holds the column's length."""
    roff = np.zeros(length, np.int32)
    roff[1:] = np.cumsum(np.bincount(rank, minlength=length - 1))
    return roff


def rank_spans(rank, roff, n_left, xp=jnp, after_self: bool = False):
    """:func:`pair_spans`' ``(lo, cnt)``, READ: ``rank`` the left rows'
    dense cell ranks (sorted; the pad rows carry the left sentinel's
    rank, whose run is empty), ``roff`` the right column's
    :func:`run_offsets` over the same ranks. Equal to the searched form
    integer for integer, pad rows included, when the ranks are those of
    the pair's distinct cells followed by ``RIGHT_PAD_CELL`` and
    ``LEFT_PAD_CELL`` (`sql.overlay.prepare_overlay`).

    ``after_self``: ONE table joined with itself (``roff`` its own run
    offsets) — a row's span is the rows of its run that come AFTER it,
    so every unordered pair of rows sharing a rank is emitted once and
    no row meets itself (`sql.proximity`: the self-join's ``a < b``)."""
    with _scope("overlay.spans", xp):
        rank = xp.asarray(rank)
        roff = xp.asarray(roff)
        row = xp.arange(rank.shape[0])
        lo = (row + 1).astype(roff.dtype) if after_self else roff[rank]
        cnt = xp.where(row < n_left, roff[rank + 1] - lo, 0)
    return lo, cnt


def _rows_by_marks(off, start, pair_bucket: int):
    """``searchsorted(off, start + arange(pair_bucket), 'right') - 1``
    with no search — the device lane's form. ``off`` is sorted and the
    slots are consecutive, so the count of rows with ``off[i] <= k`` is a
    running sum over the slots of how many rows' offsets land on each:
    every row adds 1 at ``off[i] - start``, the rows at or before the
    slice's first slot onto slot 0 (what a ``base`` would count), the rows
    at or past its end out of range and dropped. The clamp keeps the
    targets non-decreasing, which the scatter is told; they are NOT
    unique — every zero-count row shares its successor's offset — so the
    marks are added, not set. One scatter of ``nl`` updates and one
    running sum, where the search is some twenty rounds of
    ``pair_bucket`` gathered indices (a gather is paid per index on the
    chip: ``PERF.md`` section 6, PRs 34 and 49)."""
    pos = jnp.clip(off - start, 0, pair_bucket)
    marks = jnp.zeros(pair_bucket, off.dtype).at[pos].add(
        1, mode="drop", indices_are_sorted=True
    )
    return jnp.cumsum(marks) - 1


def emit_spans(lo, cnt, emit_limit, pair_bucket: int, xp=jnp, start=0):
    """CSR cross-join emission of the spans ``(lo, cnt)`` against a
    static ``pair_bucket`` — the pair ranks ``start .. start +
    pair_bucket`` (a traced ``start`` lets one compiled bucket emit a
    long stream a slice at a time).

    Returns ``(li, ri, valid)`` — (Pb,) int32 sorted-table row indices
    and the live-slot mask. Pair rank ``k`` resolves to its left row, the
    last row whose exclusive span offset is at most ``k`` (zero-count
    rows are skipped by construction), and to its right row by ``lo + (k
    - off)``, which lies inside the row's span and so inside the right
    table. The two lanes share no logic for the left row: the numpy twin
    (the oracle) SEARCHES, ``searchsorted(off, k, 'right') - 1``; the
    device scatters the rows' offsets onto the slots and sums
    (:func:`_rows_by_marks`), then reads ``(lo - off)[li] + k`` in one
    gather — the same integers, tested array for array. Emission order
    is left-row-major over the cell-sorted table == cell-major — the
    exact stream order of the host candidate generator, which is what
    makes the downstream fold order reproducible. Slots at and past
    ``min(total, emit_limit)`` are invalid and read row 0 on both sides
    (the caller books ``total - emitted`` as OVERFLOW)."""
    with _scope("overlay.emit", xp):
        off = xp.cumsum(cnt) - cnt
        total = cnt.sum(dtype=off.dtype)  # (jnp would widen an int32 sum)
        nl = cnt.shape[0]
        start = xp.asarray(start).astype(off.dtype)
        k = xp.arange(pair_bucket, dtype=off.dtype) + start
        if xp is jnp:
            li = xp.clip(_rows_by_marks(off, start, pair_bucket), 0, nl - 1)
            ri = (lo - off)[li] + k
        else:
            li = xp.clip(xp.searchsorted(off, k, side="right") - 1, 0, nl - 1)
            ri = lo[li] + (k - off[li])
        valid = k < xp.minimum(total, emit_limit)
        li = xp.where(valid, li, 0)
        ri = xp.where(valid, ri, 0)
    return li.astype(xp.int32), ri.astype(xp.int32), valid


def emit_pairs(lcells, rcells, n_left, emit_limit, pair_bucket: int,
               xp=jnp):
    """:func:`emit_spans` of the SEARCHED spans (:func:`pair_spans`):
    the whole candidate stream from the two raw cell columns, as the
    numpy twin runs it. The device lane has its spans from the count
    program already and emits from those."""
    lo, cnt = pair_spans(lcells, rcells, n_left, xp=xp)
    return emit_spans(lo, cnt, emit_limit, pair_bucket, xp=xp)


# ------------------------------------------------------------- clip area


def _scatter_rows(buf, pos, vals, width: int, xp):
    """Host-side scatter of ``vals`` (P, W, 2) to ``buf[row,
    pos[row, j]]``; slots with ``pos >= width`` are dropped. Targets are
    disjoint by construction (exclusive-cumsum positions), so the
    scatter has no ordering dependence. The device lane packs through
    :func:`_pack_rows` instead."""
    m = pos < width
    rr, jj = np.nonzero(m)
    buf[rr, pos[rr, jj]] = vals[rr, jj]
    return buf


def _pack_rows(cur, inter, emit0, emit1, base, jdx):
    """Device-side twin of the two-scatter pack: left-pack each row's
    emitted vertices (``cur[j]`` where ``emit0``, then ``inter[j]``
    where ``emit1``, in slot order). Every output slot SELECTS the one
    source slot whose exclusive-offset position names it — a
    ``(P, W_out, W_in)`` compare fused into a masked sum with exactly
    one live term, so no gather, no scatter and no arithmetic on the
    payload but ``v + 0.0``: the chip pays a gather per INDEX
    (``PERF.md`` section 6, PR 34), which at ``P x W`` indices an edge
    round would be the whole call. Slots nothing is placed in read 0.0,
    as the host twin's zeroed buffer does."""
    zero = jnp.zeros((), cur.dtype)
    out_slot = jdx[:, :, None]                     # (1, W_out, 1)
    pos0 = jnp.where(emit0, base, -1)[:, None, :]  # (P, 1, W_in)
    pos1 = jnp.where(emit1, base + emit0.astype(jnp.int32), -1)[:, None, :]
    hit0 = (pos0 == out_slot)[..., None]           # (P, W_out, W_in, 1)
    hit1 = (pos1 == out_slot)[..., None]
    return (
        jnp.where(hit0, cur[:, None, :, :], zero).sum(axis=2)
        + jnp.where(hit1, inter[:, None, :, :], zero).sum(axis=2)
    )


def _next_in_ring(arr, clen, jdx, xp):
    """``arr[:, j + 1]`` with the wrap to slot 0 at each row's own
    length — a static shift and a select, not a gather."""
    nxt = xp.concatenate([arr[:, 1:], arr[:, :1]], axis=1)
    wrap = jdx + 1 < clen[:, None]
    first = arr[:, :1]
    if arr.ndim == 3:
        wrap = wrap[:, :, None]
    return xp.where(wrap, nxt, first)


def clip_area_convex(subj, slen, win, wlen, *, eps=CLIP_EPS, xp=jnp,
                     width: int | None = None):
    """Batched Sutherland–Hodgman clip AREA: signed area of
    ``subj ∩ win`` per row.

    ``subj`` (P, Vs, 2) / ``win`` (P, Vw, 2) CCW open rings, left-packed
    to ``slen`` / ``wlen``. The WINDOW must be convex; the subject may
    be any simple ring (a concave subject leaves zero-width bridges in
    the clipped ring, which add nothing to its area). Returns ``(area,
    out_len, spill)`` — the half-shoelace of the clipped ring, its
    vertex count, and a True flag where a round wanted to emit more
    than the static buffer (``width``, default ``Vs + Vw + 2``: enough
    for a convex subject; a wiggly concave one can trip it and is
    re-answered by the f64 host lane in a wider buffer). Rows with
    ``slen == 0`` report area 0.0 exactly.

    Operation order mirrors `core.tessellate.clip_rings_convex_batch`
    half-plane for half-plane. The shoelace is taken RELATIVE TO THE
    CLIPPED RING'S FIRST VERTEX, an unrolled static loop on both
    backends: a ring that collapsed onto one axis-parallel line (a
    subject that only touches the window along a shared edge) sums
    exact zeros, and the f64 device result is bit-identical to the
    numpy twin (``xp=np``) of this very function. The device path
    holds no gather: window vertex ``e`` is a static slice, a ring's
    next vertex a shift and a select, the left-pack a masked sum
    (:func:`_pack_rows`).
    """
    P, Vs, _ = subj.shape
    Vw = win.shape[1]
    W = Vs + Vw + 2 if width is None else int(width)
    dt = subj.dtype
    zero = xp.asarray(0.0, dt)
    one = xp.asarray(1.0, dt)
    if xp is jnp:
        cur = jnp.zeros((P, W, 2), dt).at[:, :Vs].set(subj)
    else:
        cur = np.zeros((P, W, 2), dt)
        cur[:, :Vs] = subj
    clen = xp.asarray(slen).astype(xp.int32)
    wlen = xp.asarray(wlen).astype(xp.int32)
    spill = xp.zeros(P, bool)
    jdx = xp.arange(W, dtype=xp.int32)[None, :]
    for e in range(Vw):
        active = (e < wlen) & (clen > 0)
        a = win[:, e]
        b = xp.where((e + 1 < wlen)[:, None], win[:, min(e + 1, Vw - 1)],
                     win[:, 0])
        ax, ay = a[:, 0][:, None], a[:, 1][:, None]
        dx = (b[:, 0] - a[:, 0])[:, None]
        dy = (b[:, 1] - a[:, 1])[:, None]
        s_cur = dx * (cur[:, :, 1] - ay) - dy * (cur[:, :, 0] - ax)
        nxt_xy = _next_in_ring(cur, clen, jdx, xp)
        s_nxt = _next_in_ring(s_cur, clen, jdx, xp)
        valid = jdx < clen[:, None]
        inside_cur = s_cur >= -eps
        inside_nxt = s_nxt >= -eps
        denom = s_cur - s_nxt
        denom = xp.where(xp.abs(denom) < eps, one, denom)
        t = xp.clip(s_cur / denom, zero, one)[:, :, None]
        inter = cur + t * (nxt_xy - cur)
        emit0 = valid & inside_cur & active[:, None]
        emit1 = valid & (inside_cur != inside_nxt) & active[:, None]
        cnt = emit0.astype(xp.int32) + emit1.astype(xp.int32)
        base = xp.cumsum(cnt, axis=1) - cnt
        new_len = cnt.sum(axis=1)
        spill = spill | (active & (new_len > W))
        if xp is jnp:
            buf = _pack_rows(cur, inter, emit0, emit1, base, jdx)
        else:
            buf = xp.zeros((P, W, 2), dt)
            buf = _scatter_rows(
                buf, xp.where(emit0, base, W), cur, W, xp
            )
            buf = _scatter_rows(
                buf, xp.where(emit1, base + emit0.astype(xp.int32), W),
                inter, W, xp,
            )
        cur = xp.where(active[:, None, None], buf, cur)
        clen = xp.where(active, xp.minimum(new_len, W), clen)
    # unrolled shoelace about the ring's first vertex: a fixed-order add
    # chain on both backends
    ox, oy = cur[:, 0, 0], cur[:, 0, 1]
    acc = xp.zeros(P, dt)
    for j in range(1, W):
        q = xp.where((j + 1 < clen)[:, None], cur[:, min(j + 1, W - 1)],
                     cur[:, 0])
        px, py = cur[:, j, 0] - ox, cur[:, j, 1] - oy
        contrib = px * (q[:, 1] - oy) - (q[:, 0] - ox) * py
        acc = acc + xp.where(j < clen, contrib, zero)
    area = xp.asarray(0.5, dt) * acc
    return area, clen, spill


def fan_area(subj, slen, win, wlen, *, eps=CLIP_EPS, xp=jnp,
             width: int | None = None):
    """Signed-fan clip AREA against a simple NON-convex window ring.

    With ``w_0 … w_{n-1}`` the window and ``T_i = (w_0, w_i, w_{i+1})``,
    ``area(S ∩ W) = Σ_i sign(T_i) · area(S ∩ |T_i|)``: the fan's signed
    triangles cover every point of the plane as often as the window
    winds round it, so the zero-width bridges a Sutherland–Hodgman chip
    carries cost nothing. Each ``|T_i|`` is its triangle turned
    counter-clockwise — a convex window of three half-planes for
    :func:`clip_area_convex`, which is right for any simple subject
    ring; a degenerate triangle adds exactly 0.0. Returns ``(area,
    terms, spill)``: the signed sum, how many triangles gave a non-zero
    piece (the row's band scales with it: each piece carries the
    arithmetic's own absolute error, so a sum that cancels to little is
    only as good as its pieces), and the clip's spill flag. A window
    whose fan from vertex 0 holds no negative triangle (`prepare_overlay`
    turns every ring so that its best apex comes first) is a partition:
    then nothing cancels and a subject outside the window reads exact
    zeros."""
    P, Vw, _ = win.shape
    dt = subj.dtype
    slen = xp.asarray(slen).astype(xp.int32)
    wlen = xp.asarray(wlen).astype(xp.int32)
    total = xp.zeros(P, dt)
    terms = xp.zeros(P, xp.int32)
    spill = xp.zeros(P, bool)
    w0 = win[:, 0]
    three = xp.full(P, 3, xp.int32)
    for i in range(1, Vw - 1):
        a, b = win[:, i], win[:, i + 1]
        cr = (
            (a[:, 0] - w0[:, 0]) * (b[:, 1] - w0[:, 1])
            - (a[:, 1] - w0[:, 1]) * (b[:, 0] - w0[:, 0])
        )
        pos = cr > 0
        live = (i + 1 < wlen) & (cr != 0)
        tri = xp.stack(
            [w0, xp.where(pos[:, None], a, b), xp.where(pos[:, None], b, a)],
            axis=1,
        )
        ar, _, sp = clip_area_convex(
            subj, xp.where(live, slen, 0), tri, three, eps=eps, xp=xp,
            width=width,
        )
        total = total + xp.where(pos, ar, -ar)
        terms = terms + (ar != 0).astype(xp.int32)
        spill = spill | sp
    return total, terms, spill


# ------------------------------------------------------ per-pair measure


def base_areas(lcore, rcore, larea, rarea, lcell_area, xp=jnp):
    """Per-candidate area of the rows that need no clip. Chips are
    clipped to their cell, so within a shared cell ``core ∩ X = X``:
    core × core → the cell's area, core × border → the border ring's
    signed area, both from the precomputed f64 tables; border × border
    rows read 0.0 here and are answered by :func:`clip_rows` /
    :func:`fan_rows` (or the host lane)."""
    zero = xp.asarray(0.0, larea.dtype)
    return xp.where(
        lcore & rcore, lcell_area,
        xp.where(lcore, rarea, xp.where(rcore, larea, zero)),
    )


def in_band(area, terms, band, xp=jnp):
    """The epsilon recheck: a clipped area is the device's to answer
    where it is exactly 0.0 with no non-zero piece (nothing of the
    subject lay inside every half-plane of the window: disjoint, or a
    touch the clip collapsed onto a line) or at least the band; in
    between — a sliver, a near-degenerate contact, a fan whose pieces
    cancel — the f64 host lane answers the WHOLE geometry pair. The
    band is ``EDGE_BAND_K · eps(arithmetic) · cell²`` a piece."""
    width = band * xp.maximum(terms, 1).astype(area.dtype)
    return (terms > 0) & (xp.abs(area) < width)


def window_swaps(lconvex, rconvex, lstar, rstar):
    """Which ring is the window — THE rule, for both lanes: ``(clip
    swap, fan swap)``. The intersection's area is symmetric, so a row
    with a convex ring clips against it: the right ring in place, the
    left one SWAPPED where the right is not convex. A fan runs over the
    right ring unless only the left one's fan is a partition."""
    return ~rconvex, ~rstar & lstar


def _subject_and_window(lverts, lvlen, rverts, rvlen, swap, xp):
    """``(subj, slen, win, wlen)``: left against right, or right against
    left where ``swap``."""
    sw = swap[:, None, None]
    return (
        xp.where(sw, rverts, lverts), xp.where(swap, rvlen, lvlen),
        xp.where(sw, lverts, rverts), xp.where(swap, lvlen, rvlen),
    )


def clip_rows(lverts, lvlen, rverts, rvlen, swap, sign, band, *,
              eps=CLIP_EPS, xp=jnp, width: int | None = None):
    """Border × border rows with a convex window (:func:`window_swaps`
    says which ring it is). ``sign`` is the product of the two rings'
    signs (a hole ring counts negative). Returns ``(area, host_needed,
    spill)``."""
    area, _, spill = clip_area_convex(
        *_subject_and_window(lverts, lvlen, rverts, rvlen, swap, xp),
        eps=eps, xp=xp, width=width,
    )
    host = spill | in_band(area, (area != 0).astype(xp.int32), band, xp)
    zero = xp.asarray(0.0, area.dtype)
    return xp.where(host, zero, sign * area), host, spill


def fan_rows(lverts, lvlen, rverts, rvlen, swap, sign, band, *,
             eps=CLIP_EPS, xp=jnp, width: int | None = None):
    """Border × border rows where neither ring is convex: the signed
    fan over the window :func:`window_swaps` names. Returns ``(area,
    host_needed, spill)``."""
    area, terms, spill = fan_area(
        *_subject_and_window(lverts, lvlen, rverts, rvlen, swap, xp),
        eps=eps, xp=xp, width=width,
    )
    host = spill | in_band(area, terms, band, xp)
    zero = xp.asarray(0.0, area.dtype)
    return xp.where(host, zero, sign * area), host, spill


def host_pair_fold(values, valid, seg, num_segments: int,
                   acc_dtype=np.float64):
    """Sequential-order host fold of per-candidate values into per-pair
    (count, sum) — the ``np.add.at`` twin of
    `kernels.zonal.zonal_fold_masked`'s count/sum lanes: same overflow
    bucket for masked rows, same accumulator dtype, same row-order
    accumulation (XLA's CPU scatter applies updates sequentially, and so
    does ``np.add.at``)."""
    g = int(num_segments)
    dt = np.dtype(acc_dtype)
    seg = np.asarray(seg, np.int64)
    valid = np.asarray(valid, bool) & (seg >= 0)
    segc = np.where(valid, seg, g)
    vals = np.where(valid, np.asarray(values, dt), dt.type(0))
    s = np.zeros(g + 1, dt)
    c = np.zeros(g + 1, np.int64)
    np.add.at(s, segc, vals)
    np.add.at(c, segc, valid.astype(np.int64))
    return c[:g], s[:g]
