"""Pallas TPU kernel: batched ray-crossing point-in-polygon.

This is the north-star kernel (BASELINE.json): the reference evaluates
`ST_Contains` per row through JTS (`core/geometry/MosaicGeometryJTS.scala:101`)
inside Spark codegen; here a block of points is tested against a whole
polygon table resident in VMEM, with the edge and polygon dimensions
streamed through the grid so arbitrarily large polygon tables tile cleanly.

TPU layout (satisfies the (8, 128) f32 tile constraint):

- points ride as ``[tile_n, 1]`` column blocks (sublane axis), polygons
  on the lane axis — so each (point, polygon) pair is one element of a
  ``[tile_n, tile_g]`` vreg tile and every edge step is an elementwise
  sublane-x-lane broadcast, with no layout casts (the previous 3-D
  design needed a lane->leading ``tpu.reshape`` Mosaic cannot infer a
  vector layout for);
- polygon edges are ``[4, E_pad, G_pad]`` coordinate planes whose blocks
  are ``[4, tile_e, tile_g]``: slicing one edge row yields a ``[1,
  tile_g]`` lane vector that broadcasts against the point column;
- the crossing-parity accumulator is a 2-D ``[tile_n, tile_g]`` VMEM
  scratch;
- the grid is (point_blocks, g_blocks, e_blocks) with edges innermost;
  the output block is revisited across g/e and min-accumulated (lane
  reduction at the last edge block), so HBM output stays O(N).

The jnp reference implementation (`core.geometry.predicates.contains_xy`)
is the interpreted oracle; tests assert agreement (SURVEY.md §4(b)).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.geometry.device import DeviceGeometry

_BIG_F = 1e30
_I0 = np.int32(0)  # index-map literal: a python 0 traces as i64 under x64
_SENT = 2**30  # python int: jnp scalars would be captured as kernel consts
_I32_MAX = int(np.iinfo(np.int32).max)


class TilingError(ValueError):
    """A pad/tile size violates the TPU (8, 128) f32 tiling contract.

    Raised at call time, where the bad argument is visible — the
    alternative is a shape miscompile deep inside ``pallas_call`` whose
    message names neither the argument nor the caller.
    """


def _pad_to(x: np.ndarray | jax.Array, size: int, axis: int, value=0):
    pad = size - x.shape[axis]
    if pad < 0:
        raise TilingError(
            f"_pad_to cannot shrink axis {axis}: size {size} < existing "
            f"{x.shape[axis]}"
        )
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def edge_planes(polys: DeviceGeometry, g_pad: int = 128, e_pad: int = 64):
    """Flatten a polygon column to edge coordinate planes ``[4, E, G]``.

    Returns (planes, g_real) where planes[0..3] = ax, ay, bx, by and invalid
    edges are encoded as degenerate (ay == by == BIG) so they never straddle
    any point's scanline. ``e_pad`` must be a multiple of 8 (sublane axis)
    and ``g_pad`` a multiple of 128 (lane axis) — the (8, 128) f32 tile
    contract; violations raise :class:`TilingError` here instead of
    miscompiling inside ``pallas_call``. Align them with pip_zone's
    ``tile_e``/``tile_g`` (defaults do).
    """
    if g_pad < 128 or g_pad % 128:
        raise TilingError(
            f"g_pad must be a positive multiple of 128 (TPU lane width), "
            f"got {g_pad}"
        )
    if e_pad < 8 or e_pad % 8:
        raise TilingError(
            f"e_pad must be a positive multiple of 8 (TPU sublane width), "
            f"got {e_pad}"
        )
    # host-side edge extraction through the shared contract
    # (core.geometry.device.edges with xp=np): one verts-sized
    # device-to-host copy, then pure numpy — no device dispatch during an
    # index build
    from types import SimpleNamespace

    from ..core.geometry.device import edges as _edges

    host = SimpleNamespace(
        verts=np.asarray(polys.verts),
        ring_len=np.asarray(polys.ring_len),
        geom_type=np.asarray(polys.geom_type),
    )
    G, R, V = host.verts.shape[0], host.verts.shape[1], host.verts.shape[2]
    a4, b4, poly_mask, _, _ = _edges(host, xp=np)
    a = a4.reshape(G, R * (V - 1), 2)
    b = b4.reshape(G, R * (V - 1), 2)
    mask = poly_mask.reshape(G, R * (V - 1))
    # compact each zone's real edges to the front and trim E to the max
    # real count: the (R, V) padded flattening interleaves pad slots, and
    # the kernel's cost is linear in E — on the NYC zones this cuts the
    # edge axis (and kernel wall clock) several-fold
    order = np.argsort(~mask, axis=1, kind="stable")
    a = np.take_along_axis(a, order[..., None], axis=1)
    b = np.take_along_axis(b, order[..., None], axis=1)
    mask = np.take_along_axis(mask, order, axis=1)
    # keep at least one (degenerate) edge column: an E=0 plane would give
    # pip_zone a zero-size grid whose output is never initialized
    e_real = max(int(mask.sum(axis=1).max()), 1) if G else 0
    a, b, mask = a[:, :e_real], b[:, :e_real], mask[:, :e_real]
    ax = jnp.asarray(np.where(mask, a[..., 0], 0.0).T)  # (E,G)
    ay = jnp.asarray(np.where(mask, a[..., 1], _BIG_F).T)
    bx = jnp.asarray(np.where(mask, b[..., 0], 0.0).T)
    by = jnp.asarray(np.where(mask, b[..., 1], _BIG_F).T)
    E = ax.shape[0]
    g_sz = ((G + g_pad - 1) // g_pad) * g_pad
    e_sz = ((E + e_pad - 1) // e_pad) * e_pad
    planes = jnp.stack(
        [
            _pad_to(_pad_to(ax, e_sz, 0, 0.0), g_sz, 1, 0.0),
            _pad_to(_pad_to(ay, e_sz, 0, _BIG_F), g_sz, 1, _BIG_F),
            _pad_to(_pad_to(bx, e_sz, 0, 0.0), g_sz, 1, 0.0),
            _pad_to(_pad_to(by, e_sz, 0, _BIG_F), g_sz, 1, _BIG_F),
        ]
    ).astype(polys.verts.dtype)
    return planes, G


def _pip_zone_kernel(
    px_ref, py_ref, planes_ref, out_ref, cnt, *, tile_e, tile_g, n_real_g
):
    """Grid = (point_blocks, g_blocks, e_blocks); edges innermost."""
    g_blk = pl.program_id(1)
    e_blk = pl.program_id(2)
    n_e = pl.num_programs(2)

    @pl.when(jnp.logical_and(g_blk == 0, e_blk == 0))
    def _():
        out_ref[:] = jnp.full_like(out_ref, jnp.int32(_SENT))

    @pl.when(e_blk == 0)
    def _():
        cnt[:] = jnp.zeros_like(cnt)

    px = px_ref[:]  # (tile_n, 1)
    py = py_ref[:]

    def body(t, acc):
        ax = planes_ref[0, t, :][None, :]  # (1, tile_g)
        ay = planes_ref[1, t, :][None, :]
        bx = planes_ref[2, t, :][None, :]
        by = planes_ref[3, t, :][None, :]
        straddle = (ay > py) != (by > py)  # (tile_n, tile_g)
        # ones_like, not the literal 1.0: under x64 a python float lowers
        # as f64 and Mosaic has no f64->f32 cast on TPU.
        # slope is divided on the (1, tile_g) edge vector, not per
        # (point, polygon) element — division is the costliest VPU op.
        denom = jnp.where(by == ay, jnp.ones_like(by), by - ay)
        slope = (bx - ax) / denom
        xcross = ax + (py - ay) * slope
        hit = straddle & (px < xcross)
        return acc + hit.astype(jnp.int32)

    # int32 bounds: under global x64 a python-int bound makes an i64
    # induction variable, which Mosaic cannot legalize on TPU
    cnt[:] = jax.lax.fori_loop(
        jnp.int32(0), jnp.int32(tile_e), body, cnt[:]
    )

    @pl.when(e_blk == n_e - 1)
    def _():
        inside = (cnt[:] & 1) == 1
        gid = (
            jax.lax.broadcasted_iota(jnp.int32, cnt.shape, 1)
            + g_blk * tile_g
        )
        valid = inside & (gid < n_real_g)
        best = jnp.min(
            jnp.where(valid, gid, jnp.int32(_SENT)), axis=1, keepdims=True
        )  # (tile_n, 1)
        out_ref[:] = jnp.minimum(out_ref[:], best)


@functools.partial(
    jax.jit, static_argnames=("n_real_g", "tile_n", "tile_e", "tile_g", "interpret")
)
def pip_zone(
    points: jax.Array,
    planes: jax.Array,
    n_real_g: int | jax.Array = None,
    tile_n: int = 1024,
    tile_e: int = 64,
    tile_g: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """For each point, the id of the first polygon containing it, else -1.

    points: (N, 2); planes: (4, E, G) from :func:`edge_planes`.
    ``tile_n`` must be a multiple of 8 (the point block is a (tile_n, 1)
    sublane column), ``tile_g`` a multiple of 128; E and G are padded
    here if needed.
    """
    if n_real_g is None:
        n_real_g = planes.shape[2]
    if tile_n % 8:
        raise ValueError(f"tile_n must be a multiple of 8, got {tile_n}")
    N = points.shape[0]
    n_pad = ((N + tile_n - 1) // tile_n) * tile_n
    px = _pad_to(points[:, 0], n_pad, 0, _BIG_F).reshape(-1, 1)
    py = _pad_to(points[:, 1], n_pad, 0, _BIG_F).reshape(-1, 1)
    E, G = planes.shape[1], planes.shape[2]
    pad_vals = jnp.array([0.0, _BIG_F, 0.0, _BIG_F], planes.dtype)[:, None, None]
    if E % tile_e:
        e_sz = ((E + tile_e - 1) // tile_e) * tile_e
        planes = jnp.concatenate(
            [planes, jnp.broadcast_to(pad_vals, (4, e_sz - E, G))], axis=1
        )
        E = e_sz
    if G % tile_g:
        g_sz = ((G + tile_g - 1) // tile_g) * tile_g
        planes = jnp.concatenate(
            [planes, jnp.broadcast_to(pad_vals, (4, E, g_sz - G))], axis=2
        )
        G = g_sz
    n_blocks, n_g, n_e = n_pad // tile_n, G // tile_g, E // tile_e

    kernel = functools.partial(
        _pip_zone_kernel, tile_e=tile_e, tile_g=tile_g, n_real_g=int(n_real_g)
    )
    # named scope: the streaming pipeline's per-stage accounting extends
    # into traces — xprof groups this lane's ops under one label so the
    # kernel's share of a fused step is attributable
    with jax.named_scope("pip_zone.pallas"):
        out = pl.pallas_call(
            kernel,
            grid=(n_blocks, n_g, n_e),
            in_specs=[
                pl.BlockSpec(
                    (tile_n, 1), lambda i, g, e: (i, _I0),
                    memory_space=pltpu.VMEM,
                ),
                pl.BlockSpec(
                    (tile_n, 1), lambda i, g, e: (i, _I0),
                    memory_space=pltpu.VMEM,
                ),
                pl.BlockSpec(
                    (4, tile_e, tile_g),
                    lambda i, g, e: (_I0, e, g),
                    memory_space=pltpu.VMEM,
                ),
            ],
            out_specs=pl.BlockSpec(
                (tile_n, 1), lambda i, g, e: (i, _I0),
                memory_space=pltpu.VMEM,
            ),
            out_shape=jax.ShapeDtypeStruct((n_pad, 1), jnp.int32),
            scratch_shapes=[pltpu.VMEM((tile_n, tile_g), jnp.int32)],
            interpret=interpret,
        )(px, py, planes)
    out = out.reshape(-1)[:N]
    return jnp.where(out >= _SENT, -1, out)


def _pip_heavy_kernel(*refs, tile_e, tile_g, m2, banded):
    """Grid = (point_blocks, heavy_row_blocks, edge_blocks); edges innermost.

    Parity is XOR-accumulated per (point, heavy-row) pair with the same
    multiply-then-divide crossing formula as ``sql.join._ray_parity`` so the
    lane is bit-identical to the gather engine it replaces. Zero-padded
    edges are inert: a (0,0)->(0,0) segment never straddles a scanline and
    carries bits == 0, so it contributes to neither parity nor the band.
    """
    if banded:
        (px_ref, py_ref, row_ref, planes_ref, bits_ref, geom_ref, eps_ref,
         out_ref, near_ref, par, nearacc) = refs
    else:
        (px_ref, py_ref, row_ref, planes_ref, bits_ref, geom_ref,
         out_ref, par) = refs
        eps_ref = near_ref = nearacc = None
    g_blk = pl.program_id(1)
    e_blk = pl.program_id(2)
    n_e = pl.num_programs(2)

    @pl.when(jnp.logical_and(g_blk == 0, e_blk == 0))
    def _():
        out_ref[:] = jnp.full_like(out_ref, jnp.int32(_I32_MAX))
        if banded:
            near_ref[:] = jnp.zeros_like(near_ref)

    @pl.when(e_blk == 0)
    def _():
        par[:] = jnp.zeros_like(par)
        if banded:
            nearacc[:] = jnp.zeros_like(nearacc)

    px = px_ref[:]  # (tile_n, 1)
    py = py_ref[:]

    def edge_step(t, carry):
        p = carry[0]
        ax = planes_ref[0, t, :][None, :]  # (1, tile_g)
        ay = planes_ref[1, t, :][None, :]
        bx = planes_ref[2, t, :][None, :]
        by = planes_ref[3, t, :][None, :]
        bits = bits_ref[t, :][None, :]
        straddle = (ay > py) != (by > py)  # (tile_n, tile_g)
        denom = jnp.where(by == ay, jnp.ones_like(by), by - ay)
        # multiply-then-divide, the exact evaluation order of
        # _ray_parity — NOT pip_zone's precomputed slope, whose rounding
        # differs and would break the bit-identity contract
        xcross = ax + (py - ay) * (bx - ax) / denom
        crossed = straddle & (px < xcross)
        p = p ^ jnp.where(crossed, bits, jnp.zeros_like(bits))
        if not banded:
            return (p,)
        eps2v = eps_ref[0, 0]
        ex = bx - ax
        ey = by - ay
        qx = px - ax
        qy = py - ay
        dd = ex * ex + ey * ey
        tt = (qx * ex + qy * ey) / jnp.where(
            dd == jnp.zeros_like(dd), jnp.ones_like(dd), dd
        )
        # clip(x, 0, 1) spelled as min/max of *_like tensors: a python
        # float literal lowers as f64 under x64 and Mosaic cannot cast it
        tt = jnp.minimum(
            jnp.maximum(tt, jnp.zeros_like(tt)), jnp.ones_like(tt)
        )
        rx = qx - tt * ex
        ry = qy - tt * ey
        hit = (rx * rx + ry * ry <= eps2v) & (bits != jnp.zeros_like(bits))
        return (p, carry[1] | hit.astype(jnp.int32))

    if banded:
        pres = jax.lax.fori_loop(
            jnp.int32(0), jnp.int32(tile_e), edge_step,
            (par[:], nearacc[:]),
        )
        par[:] = pres[0]
        nearacc[:] = pres[1]
    else:
        par[:] = jax.lax.fori_loop(
            jnp.int32(0), jnp.int32(tile_e),
            lambda t, p: edge_step(t, (p,))[0], par[:],
        )

    @pl.when(e_blk == n_e - 1)
    def _():
        lane = (
            jax.lax.broadcasted_iota(jnp.int32, par.shape, 1)
            + g_blk * tile_g
        )
        belongs = lane == row_ref[:]  # each point owns exactly one row
        p = par[:]
        best = jnp.full_like(p, jnp.int32(_I32_MAX))
        for m in range(m2):  # static: slot count is a python int
            gm = geom_ref[m, :][None, :]
            inm = ((p >> m) & 1) == 1
            best = jnp.minimum(
                best,
                jnp.where(inm & (gm >= 0), gm, jnp.int32(_I32_MAX)),
            )
        best = jnp.where(belongs, best, jnp.int32(_I32_MAX))
        out_ref[:] = jnp.minimum(
            out_ref[:], jnp.min(best, axis=1, keepdims=True)
        )
        if banded:
            nb = jnp.where(belongs, nearacc[:], jnp.zeros_like(nearacc))
            near_ref[:] = jnp.maximum(
                near_ref[:], jnp.max(nb, axis=1, keepdims=True)
            )


def pip_heavy_tiled(
    px: jax.Array,
    py: jax.Array,
    rows: jax.Array,
    heavy_edges: jax.Array,
    heavy_ebits: jax.Array,
    heavy_slot_geom: jax.Array,
    eps2: jax.Array | float | None = None,
    *,
    tile_n: int = 512,
    tile_e: int = 64,
    tile_g: int = 128,
    interpret: bool = False,
):
    """Tiled heavy-cell probe: per-point slot parity against VMEM tables.

    ``px``/``py``: (K,) f32 compacted heavy-lane points; ``rows``: (K,)
    int32 heavy-table row per point (pad with -1). ``heavy_edges`` (H, E2,
    4) f32, ``heavy_ebits`` (H, E2) uint32 and ``heavy_slot_geom`` (H, M2)
    int32 are the ChipIndex heavy tables, transposed here to lane-major
    planes — heavy rows ride the lane axis, edges the sublane axis, points
    the grid — and zero-padded (zero edges are inert, pad lanes carry
    geom -1 and belong to no point). Returns ``(best, near)`` with
    ``best`` (K,) int32 using int32-max as the no-hit sentinel (the same
    sentinel as sql.join) and ``near`` (K,) bool when ``eps2`` is given,
    else None.
    """
    if heavy_edges.dtype != jnp.float32:
        raise ValueError(
            "pip_heavy_tiled requires float32 heavy tables (Mosaic has no "
            f"f64 path), got {heavy_edges.dtype}"
        )
    if tile_g < 128 or tile_g % 128:
        raise TilingError(
            f"tile_g must be a positive multiple of 128, got {tile_g}"
        )
    if tile_e % 8 or tile_n % 8:
        raise TilingError(
            f"tile_e/tile_n must be multiples of 8, got {tile_e}/{tile_n}"
        )
    K = px.shape[0]
    H, E2 = heavy_ebits.shape
    M2 = heavy_slot_geom.shape[1]
    tile_e = min(tile_e, ((E2 + 7) // 8) * 8)
    tile_n = min(tile_n, ((K + 7) // 8) * 8)
    n_pad = ((K + tile_n - 1) // tile_n) * tile_n
    e_sz = ((E2 + tile_e - 1) // tile_e) * tile_e
    g_sz = ((H + tile_g - 1) // tile_g) * tile_g
    m2_pad = ((M2 + 7) // 8) * 8

    pxp = _pad_to(px.reshape(-1), n_pad, 0, _BIG_F).reshape(-1, 1)
    pyp = _pad_to(py.reshape(-1), n_pad, 0, _BIG_F).reshape(-1, 1)
    rowp = _pad_to(
        rows.reshape(-1).astype(jnp.int32), n_pad, 0, -1
    ).reshape(-1, 1)
    planes = jnp.transpose(heavy_edges, (2, 1, 0))  # (4, E2, H)
    planes = _pad_to(_pad_to(planes, e_sz, 1, 0.0), g_sz, 2, 0.0)
    bits = jax.lax.bitcast_convert_type(heavy_ebits, jnp.int32).T  # (E2, H)
    bits = _pad_to(_pad_to(bits, e_sz, 0, 0), g_sz, 1, 0)
    geom = _pad_to(
        _pad_to(heavy_slot_geom.astype(jnp.int32).T, m2_pad, 0, -1),
        g_sz, 1, -1,
    )

    banded = eps2 is not None
    pt_spec = lambda: pl.BlockSpec(
        (tile_n, 1), lambda i, g, e: (i, _I0), memory_space=pltpu.VMEM
    )
    in_specs = [
        pt_spec(),
        pt_spec(),
        pt_spec(),
        pl.BlockSpec(
            (4, tile_e, tile_g), lambda i, g, e: (_I0, e, g),
            memory_space=pltpu.VMEM,
        ),
        pl.BlockSpec(
            (tile_e, tile_g), lambda i, g, e: (e, g),
            memory_space=pltpu.VMEM,
        ),
        pl.BlockSpec(
            (m2_pad, tile_g), lambda i, g, e: (_I0, g),
            memory_space=pltpu.VMEM,
        ),
    ]
    args = [pxp, pyp, rowp, planes, bits, geom]
    out_shape = [jax.ShapeDtypeStruct((n_pad, 1), jnp.int32)]
    out_specs = [pt_spec()]
    scratch = [pltpu.VMEM((tile_n, tile_g), jnp.int32)]
    if banded:
        in_specs.append(
            pl.BlockSpec(
                (1, 1), lambda i, g, e: (_I0, _I0),
                memory_space=pltpu.SMEM,
            )
        )
        args.append(jnp.asarray(eps2, jnp.float32).reshape(1, 1))
        out_shape.append(jax.ShapeDtypeStruct((n_pad, 1), jnp.int32))
        out_specs.append(pt_spec())
        scratch.append(pltpu.VMEM((tile_n, tile_g), jnp.int32))

    kernel = functools.partial(
        _pip_heavy_kernel, tile_e=tile_e, tile_g=tile_g, m2=int(M2),
        banded=banded,
    )
    with jax.named_scope("pip_heavy.pallas"):
        res = pl.pallas_call(
            kernel,
            grid=(n_pad // tile_n, g_sz // tile_g, e_sz // tile_e),
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=scratch,
            interpret=interpret,
        )(*args)
    best = res[0].reshape(-1)[:K]
    if banded:
        return best, res[1].reshape(-1)[:K] != 0
    return best, None


def pip_zone_reference(points: jax.Array, polys: DeviceGeometry) -> jax.Array:
    """jnp oracle for pip_zone (first containing polygon id per point)."""
    from ..core.geometry.predicates import contains_xy

    inside = contains_xy(points, polys)  # (N,G)
    g_ids = jnp.arange(inside.shape[1], dtype=jnp.int32)[None, :]
    first = jnp.min(jnp.where(inside, g_ids, jnp.int32(2**30)), axis=1)
    return jnp.where(first == 2**30, -1, first)
