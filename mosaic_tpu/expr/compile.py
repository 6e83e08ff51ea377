"""Lower expression trees into ONE jitted device program per dispatch
signature.

Reference analog: Spark's whole-stage codegen collapsing a Catalyst
expression pipeline into one generated function — here the whole tree
(band reads, arithmetic, masking, grid/zone predicates, the terminal
zonal fold) lowers into a single closed jax function, so a 3-op
"NDVI → cloud mask → zonal mean" pipeline is one launch per tile
instead of N staged host→device round trips.

Programs live in the dispatch core's named-cache registry
(:func:`mosaic_tpu.dispatch.core.bounded_cache`, cache name
``expr_programs``) keyed on the tree ITSELF plus the bucket — nodes are
frozen dataclasses with structural equality, so two independently-built
but equal trees share one compiled program. The public execution
signature (:func:`signature_of`) is ``(tree-structure-hash, bucket,
index, mesh)``: :func:`run_zonal` opens a ``dispatch.compile`` span
(site=``expr``) with a ``backend_compiles()`` delta the first time a
signature executes — timeline attribution classifies expr cold-compiles
as *compile*, not *device* — and after :func:`freeze` a novel signature
trips the cold-compile counter plus an ``expr_compile`` telemetry
event, mirroring ``DispatchCore``'s tripwire.

jit purity: the fused body touches only jnp ops and the traceable
:func:`~mosaic_tpu.raster.tiles.assign_tile_cells`; spans, telemetry,
and signature bookkeeping all live OUTSIDE the jitted function.

Warmup is by EXECUTION, not AOT lowering — on this jax version
``jitted.lower(...).compile()`` does not populate the jit dispatch
cache, so :func:`warmup_zonal` runs the program on zero tiles through
the same :func:`run_zonal` wrapper the real path uses.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..dispatch import core as _dispatch
from ..kernels.zonal import zonal_fold_masked
from ..obs import trace as _trace
from ..raster.tiles import assign_tile_cells
from ..runtime import telemetry as _telemetry
from . import ast

__all__ = [
    "PairCtx",
    "cold_compiles",
    "freeze",
    "overlay_program",
    "overlay_signature_of",
    "pixel_program",
    "run_pixels",
    "run_tracked",
    "run_zonal",
    "signature_of",
    "signatures",
    "warmup_zonal",
    "zonal_program",
]


# ------------------------------------------------------------- lowering

_BIN = {
    "add": jnp.add,
    "sub": jnp.subtract,
    "mul": jnp.multiply,
    "div": jnp.divide,
    "min": jnp.minimum,
    "max": jnp.maximum,
}
_CMP = {
    "lt": jnp.less,
    "le": jnp.less_equal,
    "gt": jnp.greater,
    "ge": jnp.greater_equal,
    "eq": jnp.equal,
    "ne": jnp.not_equal,
}


class _Ctx:
    """Per-trace lowering context: band rows of the (B, P) tile stack,
    lazily-computed cell ids, and the zone segment vector."""

    def __init__(self, vals, mask, gt, origin, th, tw, rows,
                 index_system, resolution, seg):
        self.vals = vals
        self.mask = mask
        self.gt = gt
        self.origin = origin
        self.th = th
        self.tw = tw
        self.rows = rows  # band index (1-based) -> stack row
        self.index_system = index_system
        self.resolution = resolution
        self.seg = seg
        self._cells = None

    def cells(self):
        if self._cells is None:
            self._cells = assign_tile_cells(
                self.gt, self.origin, (self.th, self.tw),
                self.index_system, self.resolution,
            ).reshape(-1)
        return self._cells


def _lower(node: ast.Expr, ctx: _Ctx):
    """→ (value, valid) jnp arrays, implementing the mask-propagation
    rule documented in `expr.ast` — the f64 host oracle mirrors this
    function shape for shape."""
    true = jnp.ones((), bool)
    if isinstance(node, ast.Band):
        r = ctx.rows[node.index]
        return ctx.vals[r], ctx.mask[r]
    if isinstance(node, ast.Const):
        return jnp.asarray(node.value, jnp.float64), true
    if isinstance(node, (ast.BinOp, ast.Compare)):
        av, am = _lower(node.a, ctx)
        bv, bm = _lower(node.b, ctx)
        fn = _BIN[node.op] if isinstance(node, ast.BinOp) else _CMP[node.op]
        return fn(av, bv), am & bm
    if isinstance(node, ast.BoolOp):
        av, am = _lower(node.a, ctx)
        bv, bm = _lower(node.b, ctx)
        return (av & bv) if node.op == "and" else (av | bv), am & bm
    if isinstance(node, ast.Not):
        av, am = _lower(node.a, ctx)
        return ~av, am
    if isinstance(node, ast.Where):
        cv, cm = _lower(node.cond, ctx)
        av, am = _lower(node.a, ctx)
        bv, bm = _lower(node.b, ctx)
        return jnp.where(cv, av, bv), cm & jnp.where(cv, am, bm)
    if isinstance(node, ast.MaskWhere):
        vv, vm = _lower(node.value, ctx)
        cv, cm = _lower(node.cond, ctx)
        return vv, vm & cm & cv
    if isinstance(node, ast.CellOf):
        return ctx.cells(), true
    if isinstance(node, ast.InZone):
        return ctx.seg >= 0, true
    if isinstance(node, ast.ZoneData):
        table = jnp.asarray(node.values, jnp.float64)
        inside = ctx.seg >= 0
        idx = jnp.where(inside, ctx.seg, 0)
        return jnp.where(
            inside, table[idx], jnp.asarray(node.fill, jnp.float64)
        ), true
    raise TypeError(
        f"cannot lower {type(node).__name__} — terminals are peeled by "
        "eval before lowering"
    )


def _band_rows(value: ast.Expr) -> dict:
    """Band index (1-based) → row of the (B, P) stack, rows sorted by
    band index — the layout `eval` stacks and both programs consume."""
    return {b: r for r, b in enumerate(ast.bands_of(value))}


class PairCtx:
    """Lowering context for overlay PAIR trees: per-unique-pair (S,)
    tables — the folded intersection area and the two geometry total
    areas. Shared shape with `expr.host_oracle.interpret_pair`."""

    def __init__(self, area, larea, rarea):
        self.area = area
        self.larea = larea
        self.rarea = rarea


def _lower_pair(node: ast.Expr, ctx: PairCtx):
    """→ (value, valid) jnp arrays over the per-pair tables — the pair-
    tree twin of :func:`_lower`, with the same operator maps so the f64
    host oracle (`interpret_pair`) mirrors it op for op."""
    true = jnp.ones((), bool)
    if isinstance(node, ast.Const):
        return jnp.asarray(node.value, jnp.float64), true
    if isinstance(node, ast.OverlapArea):
        return ctx.area, true
    if isinstance(node, ast.LeftArea):
        return ctx.larea, true
    if isinstance(node, ast.RightArea):
        return ctx.rarea, true
    if isinstance(node, (ast.BinOp, ast.Compare)):
        av, am = _lower_pair(node.a, ctx)
        bv, bm = _lower_pair(node.b, ctx)
        fn = _BIN[node.op] if isinstance(node, ast.BinOp) else _CMP[node.op]
        return fn(av, bv), am & bm
    if isinstance(node, ast.BoolOp):
        av, am = _lower_pair(node.a, ctx)
        bv, bm = _lower_pair(node.b, ctx)
        return (av & bv) if node.op == "and" else (av | bv), am & bm
    if isinstance(node, ast.Not):
        av, am = _lower_pair(node.a, ctx)
        return ~av, am
    if isinstance(node, ast.Where):
        cv, cm = _lower_pair(node.cond, ctx)
        av, am = _lower_pair(node.a, ctx)
        bv, bm = _lower_pair(node.b, ctx)
        return jnp.where(cv, av, bv), cm & jnp.where(cv, am, bm)
    if isinstance(node, ast.MaskWhere):
        vv, vm = _lower_pair(node.value, ctx)
        cv, cm = _lower_pair(node.cond, ctx)
        return vv, vm & cm & cv
    raise TypeError(
        f"cannot lower {type(node).__name__} in an overlay pair tree"
    )


# ------------------------------------------------------------- programs


@_dispatch.bounded_cache("expr_programs", 64)
def zonal_program(
    value: ast.Expr, th: int, tw: int, num_segments: int,
    acc_name: str, index_system, resolution: int,
):
    """The fused program: ``(gt, origin, vals (B, P), mask (B, P),
    seg (P,)) → ((S,) count, sum, min, max)``. One launch reads raw
    bands and emits per-segment stats — the per-pixel expression is
    fused INTO the segment-reduced fold. Cached on the tree itself
    (structural equality), so equal trees share one entry."""
    rows = _band_rows(value)
    acc_dt = jnp.dtype(acc_name)
    p = th * tw

    def fused(gt, origin, vals, mask, seg):
        ctx = _Ctx(vals, mask, gt, origin, th, tw, rows,
                   index_system, resolution, seg)
        v, m = _lower(value, ctx)
        v = jnp.broadcast_to(v, (p,)).astype(acc_dt)
        m = jnp.broadcast_to(m, (p,))
        return zonal_fold_masked(
            v, m, seg, num_segments, acc_dtype=acc_dt
        )

    return jax.jit(fused)


@_dispatch.bounded_cache("expr_pixel_programs", 64)
def pixel_program(
    value: ast.Expr, th: int, tw: int, index_system, resolution,
):
    """Per-pixel program for `rst_mapbands`/join values: ``(gt, origin,
    vals, mask, seg) → ((P,) value, (P,) valid)`` — no fold; callers
    without a vector side pass an all ``-1`` segment vector (zone nodes
    are rejected by validation there)."""
    rows = _band_rows(value)
    p = th * tw

    def pixels(gt, origin, vals, mask, seg):
        ctx = _Ctx(vals, mask, gt, origin, th, tw, rows,
                   index_system, resolution, seg)
        v, m = _lower(value, ctx)
        return (
            jnp.broadcast_to(v, (p,)).astype(jnp.float64),
            jnp.broadcast_to(m, (p,)),
        )

    return jax.jit(pixels)


@_dispatch.bounded_cache("overlay_programs", 64)
def overlay_program(
    value: ast.Expr, Lb: int, Rb: int, Pb: int, Cb: int, Fb: int, Sb: int,
    vpad: int, acc_name: str, mesh=None,
):
    """The fused overlay measure program: look the candidate rows' kinds
    and table areas up in the two resident side tables (scope
    ``overlay.gather``), clip the ``Cb`` border × border rows that have
    a convex window (``overlay.clip``: in place, or swapped) and fan the
    ``Fb`` that have none (``overlay.fan``), fold all three streams into
    per-geometry-pair totals (``overlay.fold``) and evaluate the pair
    tree over the folded tables — ONE launch per ``(tree, buckets,
    mesh)`` signature. The host names the clip and fan rows and which
    ring of each is the window (`sql.overlay.pair_routes`), so the clip
    runs over the rows that need one and not over the whole candidate
    bucket. Under ``mesh``
    the two clip stages run data-parallel over their row axes (side
    tables replicated) — pointwise in the row axis, and the fold runs
    on the gathered output, so a sharded run is bit-identical to
    single-device by construction."""
    acc_dt = jnp.dtype(acc_name)
    from ..kernels import overlay as _ko

    def clip_stage(lv, ll, rv, rl, swap, sign, band):
        return _ko.clip_rows(lv, ll, rv, rl, swap, sign, band, xp=jnp)

    def fan_stage(lv, ll, rv, rl, swap, sign, band):
        return _ko.fan_rows(lv, ll, rv, rl, swap, sign, band, xp=jnp)

    regather = None
    if mesh is not None:
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        p, r = P(mesh.axis_names), P()
        specs = dict(
            mesh=mesh, in_specs=(p, p, p, p, p, p, r),
            out_specs=(p, p, p), check_vma=False,
        )
        clip_stage = jax.shard_map(clip_stage, **specs)
        fan_stage = jax.shard_map(fan_stage, **specs)
        # replicate the per-row outputs before the fold: left sharded,
        # GSPMD would split the segment sum into per-shard partials plus
        # a cross-shard combine — a different f64 accumulation order
        # (1-ulp reassociation drift vs single-device)
        regather = NamedSharding(mesh, r)

    def rows_of(idx, n, li, ri, lverts, lvlen, lsign, rverts, rvlen, rsign):
        live = jnp.arange(idx.shape[0]) < n
        cl, cr = li[idx], ri[idx]
        return (
            live, lverts[cl], jnp.where(live, lvlen[cl], 0),
            rverts[cr], jnp.where(live, rvlen[cr], 0),
            lsign[cl] * rsign[cr],
        )

    def fused(li, ri, valid, seg, clip_idx, clip_swap, n_clip,
              fan_idx, fan_swap, n_fan,
              lcore, lsign, lverts, lvlen, larea, lcell,
              rcore, rsign, rverts, rvlen, rarea,
              seg_larea, seg_rarea, band):
        with jax.named_scope("overlay.gather"):
            base = _ko.base_areas(
                lcore[li], rcore[ri], larea[li], rarea[ri], lcell[li],
                xp=jnp,
            )
            c_live, clv, cll, crv, crl, c_sign = rows_of(
                clip_idx, n_clip, li, ri, lverts, lvlen, lsign,
                rverts, rvlen, rsign,
            )
            f_live, flv, fll, frv, frl, f_sign = rows_of(
                fan_idx, n_fan, li, ri, lverts, lvlen, lsign,
                rverts, rvlen, rsign,
            )
        with jax.named_scope("overlay.clip"):
            c_area, c_host, c_spill = clip_stage(
                clv, cll, crv, crl, clip_swap, c_sign, band
            )
        with jax.named_scope("overlay.fan"):
            f_area, f_host, f_spill = fan_stage(
                flv, fll, frv, frl, fan_swap, f_sign, band
            )
        with jax.named_scope("overlay.fold"):
            folded = (
                jnp.concatenate([base, c_area, f_area]),
                jnp.concatenate([valid, c_live, f_live]),
                jnp.concatenate([seg, seg[clip_idx], seg[fan_idx]]),
            )
            if regather is not None:
                folded = tuple(
                    jax.lax.with_sharding_constraint(x, regather)
                    for x in folded
                )
                c_host, c_spill, f_host, f_spill = (
                    jax.lax.with_sharding_constraint(x, regather)
                    for x in (c_host, c_spill, f_host, f_spill)
                )
            cnt, s, _mn, _mx = zonal_fold_masked(
                *folded, Sb, acc_dtype=acc_dt
            )
            val, vok = _lower_pair(
                value, PairCtx(s, seg_larea, seg_rarea)
            )
        return (
            jnp.broadcast_to(val, (Sb,)).astype(jnp.float64),
            jnp.broadcast_to(vok, (Sb,)),
            s, cnt, c_host, f_host, c_spill, f_spill,
        )

    fused.__name__ = "overlay_measure"
    return jax.jit(fused)


def overlay_signature_of(
    value: ast.Expr, Lb: int, Rb: int, Pb: int, Cb: int, Fb: int, Sb: int,
    vpad: int, acc_name: str, index_system, resolution, mesh=None,
) -> tuple:
    """The dispatch signature an overlay measure execution is tracked
    under: ``(tree-hash, buckets, index, mesh)`` — the overlay twin of
    :func:`signature_of`."""
    return (
        "overlay:" + ast.tree_hash(value)[:16],
        (int(Lb), int(Rb), int(Pb), int(Cb), int(Fb), int(Sb), int(vpad),
         str(acc_name)),
        (type(index_system).__name__, int(resolution)),
        _dispatch.mesh_key(mesh),
    )


# ------------------------------------- signature tracking (the tripwire)

_signatures: set = set()
_frozen: "frozenset | None" = None
_cold_compiles = 0


def signature_of(
    value: ast.Expr, th: int, tw: int, num_segments: int,
    acc_name: str, index_system, resolution, mesh=None,
) -> tuple:
    """The dispatch signature a fused execution is tracked under:
    ``(tree-structure-hash, bucket, index, mesh)``."""
    return (
        ast.tree_hash(value)[:16],
        (int(th), int(tw), int(num_segments), str(acc_name)),
        (type(index_system).__name__, int(resolution)),
        _dispatch.mesh_key(mesh),
    )


def signatures() -> "frozenset":
    return frozenset(_signatures)


def freeze() -> "frozenset":
    """Snapshot the signature set after warmup — a NEW signature
    executing later is a cold compile in production, counted and
    telemetered (`DispatchCore.freeze` discipline)."""
    global _frozen
    _frozen = frozenset(_signatures)
    return _frozen


def cold_compiles() -> int:
    return _cold_compiles


def _reset_for_tests():
    global _frozen, _cold_compiles
    _signatures.clear()
    _frozen = None
    _cold_compiles = 0


def _track(sig: tuple):
    """First sight of ``sig`` → open a ``dispatch.compile`` span
    (site=expr) so timeline attribution books the build as *compile*;
    post-freeze novelty additionally trips the cold counter. Returns
    (span, compiles_before) — (None, None) for warm signatures."""
    global _cold_compiles
    if sig in _signatures:
        return None, None
    _signatures.add(sig)
    if _frozen is not None and sig not in _frozen:
        _cold_compiles += 1
        _telemetry.record(
            "expr_compile", signature=repr(sig), after_freeze=True,
            cold_compiles=_cold_compiles,
        )
    c0 = _dispatch.backend_compiles()
    span = _trace.start_span(
        "dispatch.compile", site="expr", signature=repr(sig)
    )
    return span, c0


def _untrack(span, c0):
    if span is None:
        return
    c1 = _dispatch.backend_compiles()
    if c0 is not None and c1 is not None:
        span.set(backend_compiles=c1 - c0)
    span.end()


def run_tracked(sig: tuple, fn, *args):
    """Execute any compiled program under expr signature tracking — the
    public wrapper overlay dispatch uses so its cold compiles land in
    the same `dispatch.compile` span / post-freeze tripwire as the
    raster programs."""
    span, c0 = _track(sig)
    try:
        return fn(*args)
    finally:
        _untrack(span, c0)


def run_zonal_async(prog, sig: tuple, gt, origin, vals, mask, seg):
    """Execute a fused program under signature tracking, returning the
    four partials as DEVICE arrays (async dispatch — the caller owns
    the blocking pull). Tracing/compilation is synchronous inside the
    jit call, so compile counts still land inside the span; only the
    device execution escapes it."""
    span, c0 = _track(sig)
    try:
        return prog(
            jnp.asarray(gt), jnp.asarray(origin),
            jnp.asarray(vals), jnp.asarray(mask), jnp.asarray(seg),
        )
    finally:
        _untrack(span, c0)


def run_zonal(prog, sig: tuple, gt, origin, vals, mask, seg):
    """Execute a fused program under signature tracking; returns the
    four partials as numpy arrays (blocking pulls, so a compile is
    fully inside the span)."""
    cnt, s, mn, mx = run_zonal_async(
        prog, sig, gt, origin, vals, mask, seg
    )
    return (
        np.asarray(cnt), np.asarray(s), np.asarray(mn),
        np.asarray(mx),
    )


def run_pixels(prog, sig: tuple, gt, origin, vals, mask, seg):
    """Execute a per-pixel program under the same signature tracking."""
    span, c0 = _track(sig)
    try:
        v, m = prog(
            jnp.asarray(gt), jnp.asarray(origin),
            jnp.asarray(vals), jnp.asarray(mask), jnp.asarray(seg),
        )
        return np.asarray(v), np.asarray(m)
    finally:
        _untrack(span, c0)


def warmup_zonal(
    value: ast.Expr, th: int, tw: int, num_segments: int,
    acc_name: str, index_system, resolution, mesh=None,
) -> tuple:
    """Precompile one fused signature by EXECUTING it on a zero tile
    (AOT lowering does not populate the jit dispatch cache on this jax
    version). Returns the signature, now registered for `freeze`."""
    prog = zonal_program(
        value, int(th), int(tw), int(num_segments), acc_name,
        index_system, int(resolution),
    )
    sig = signature_of(
        value, th, tw, num_segments, acc_name, index_system,
        resolution, mesh,
    )
    b = len(ast.bands_of(value))
    p = int(th) * int(tw)
    run_zonal(
        prog, sig,
        np.zeros(6, np.float64), np.zeros(2, np.int32),
        np.zeros((b, p), np.float64), np.zeros((b, p), bool),
        np.full(p, -1, np.int32),
    )
    return sig
