"""Segment-reduced zonal statistics kernels.

Two lanes with one contract — fold (count, sum, min, max) of masked
pixel values grouped by a segment id, where segment ``-1`` means "this
pixel folds nowhere" (nodata, tile pad, or no containing zone):

- :func:`zonal_fold` — the jnp segment-reduce twin. Traceable inside
  any outer jit, dtype-polymorphic, and the holder of the f64
  bit-identity contract on CPU (x64): XLA's CPU scatter applies updates
  sequentially in row order, so an f64 fold here is bit-identical to a
  sequential numpy accumulation in the same pixel order — which is
  exactly what the host oracle in `raster/zonal.py` computes. Narrow
  integer values (an int16 scene) fold at their own width instead:
  :func:`fold_lane` names the int32 lane wherever one tile's sum is
  exact there, a dense compare-and-reduce with no scatter and no f64
  (the chip emulates f64: 13.0 ms a 65,536-pixel tile against 0.044);
  exact integers, so the same numbers in any order.
- :func:`zonal_tiled` — the Pallas TPU lane (f32, like every Mosaic
  kernel: no f64 path on the MXU/VPU). Grid is (segment blocks, pixel
  blocks) with pixels innermost, so each (1, TILE_S) accumulator block
  (a lane-aligned column window of one (1, S_pad) output row) stays
  resident in VMEM while every pixel block streams past it; a
  pixel block broadcasts against the segment-lane iota and folds with
  one VPU reduction per statistic. Counts accumulate in f32 — exact up
  to 2**24 pixels per segment, a documented bound enforced at call
  time via ``max_count``.

Pixel values are expected pre-masked (pad/nodata pixels carry value 0
AND segment -1, see `raster/tiles.py`): correctness only needs the
segment to be -1, the zero value just keeps NaN/Inf garbage out of the
``sum`` multiply.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pip import TilingError

__all__ = [
    "DENSE_LANES_MAX", "fold_lane", "zonal_fold", "zonal_fold_masked",
    "zonal_tiled", "TilingError",
]

#: inert fill for min/max lanes — far beyond any geographic or sensor
#: value, well inside f32 range (same constant family as kernels/pip.py).
#: Typed: under the package-wide x64 a python float enters the kernel as
#: an f64 constant, and Mosaic has no f64 -> f32 cast.
_BIG_F = np.float32(1e30)

_I0 = np.int32(0)  # index-map literal: python 0 traces as i64 under x64


# ------------------------------------------------------------ jnp lane


#: the int32 lane is a dense compare-and-reduce over segments x pixels
#: lanes: 0.044 ms for 256 x 65,536 on a v5e (2.6 ps a lane), where four
#: int32 scatters take 2.29 ms and the wide lane's emulated-f64 scatters
#: 13.0 ms at any segment count (chip run, PR 28; PERF.md §6). Its cost
#: grows with the segment count, so past this many lanes (2.8 ms) a fold
#: stays on the wide lane.
DENSE_LANES_MAX = 1 << 30


def fold_lane(values_dtype, pixels: int, num_segments: int) -> str:
    """Which lane one :func:`zonal_fold` sums in, from what the caller
    hands in and nothing else (the storage dtype, the tile's pixel
    count, the segment count), so the host can stage for it and a
    compiled program never reads a knob:

    - ``"int32"`` where the values are integers narrower than 32 bits,
      ``pixels`` times the dtype's largest magnitude cannot leave int32
      (int8, uint8 and int16 at any tile of up to 65,536 pixels; uint16
      up to 32,768) — the sum is exact there — and ``num_segments`` x
      ``pixels`` is at most :data:`DENSE_LANES_MAX`;
    - ``"wide"`` otherwise: the ``acc_dtype`` accumulator.
    """
    dt = np.dtype(values_dtype)
    if dt.kind not in "iu" or dt.itemsize >= 4:
        return "wide"
    info, i32 = np.iinfo(dt), np.iinfo(np.int32)
    n = int(pixels)
    if (
        n * int(info.min) >= i32.min
        and n * int(info.max) <= i32.max
        and n * int(num_segments) <= DENSE_LANES_MAX
    ):
        return "int32"
    return "wide"


def zonal_fold(values, seg, num_segments: int, *, acc_dtype=None):
    """((S,) i32 count, (S,) sum, (S,) min, (S,) max) of ``values``
    grouped by ``seg`` (-1 folds nowhere).

    ``min`` and ``max`` select a value and round nothing, so they
    reduce in the dtype of ``values`` as handed in, whatever
    ``acc_dtype`` says, and come back in it. ``sum`` takes the lane
    :func:`fold_lane` names: int32 for narrow integers (exact: the
    number the wide lane gives, bit for bit once cast), else
    ``acc_dtype`` (default: the value dtype; the zonal frontends stage
    f64 under x64 for the oracle contract). A caller that hands f64
    values gets the f64 scatter program.

    Empty segments report count 0, sum 0 and, in min / max, the inert
    fill of the value dtype: +inf / -inf for floats, the dtype's
    largest / smallest value for integers — callers mask on count.
    """
    values = jnp.asarray(values).reshape(-1)
    seg = jnp.asarray(seg, jnp.int32).reshape(-1)
    vdt = values.dtype
    k = int(num_segments)
    if jnp.issubdtype(vdt, jnp.integer):
        hi, lo = (np.array(v, vdt) for v in
                  (jnp.iinfo(vdt).max, jnp.iinfo(vdt).min))
    else:
        hi, lo = jnp.inf, -jnp.inf
    if fold_lane(vdt, values.shape[0], k) == "int32":
        # every (segment, pixel) lane compared and reduced on the VPU,
        # which has no lanes narrower than 32 bits: widen once. XLA
        # fuses the four reductions; nothing (S, P) is materialised
        hit = seg[None, :] == jnp.arange(k, dtype=jnp.int32)[:, None]
        v = values.astype(jnp.int32)[None, :]
        cnt = jnp.sum(hit, axis=1, dtype=jnp.int32)
        s = jnp.sum(jnp.where(hit, v, np.int32(0)), axis=1, dtype=jnp.int32)
        mn = jnp.min(jnp.where(hit, v, hi.astype(np.int32)), axis=1)
        mx = jnp.max(jnp.where(hit, v, lo.astype(np.int32)), axis=1)
        return cnt, s, mn.astype(vdt), mx.astype(vdt)
    dt = jnp.dtype(acc_dtype) if acc_dtype is not None else vdt
    av = values.astype(dt)
    ns = k + 1  # one overflow bucket for seg == -1
    valid = seg >= 0
    segc = jnp.where(valid, seg, np.int32(k))
    zero = jnp.zeros((), dt)
    cnt = jax.ops.segment_sum(
        valid.astype(jnp.int32), segc, num_segments=ns
    )
    s = jax.ops.segment_sum(
        jnp.where(valid, av, zero), segc, num_segments=ns
    )
    mn = jax.ops.segment_min(
        jnp.where(valid, values, hi), segc, num_segments=ns
    )
    mx = jax.ops.segment_max(
        jnp.where(valid, values, lo), segc, num_segments=ns
    )
    return cnt[:k], s[:k], mn[:k], mx[:k]


def zonal_fold_masked(values, valid, seg, num_segments: int, *,
                      acc_dtype=None):
    """:func:`zonal_fold` with an explicit per-pixel validity lane —
    the pushdown hook of the expression compiler: a fused program
    computes ``values`` and ``valid`` from raw bands (mask propagation
    through the pad∧nodata∧NaN mask AND expression-level masking like
    ``mask_where``) and folds them here inside the SAME jit, so the
    whole pipeline is one launch. Invalid pixels fold nowhere
    (segment forced to -1); NaN/Inf produced on them never reaches the
    accumulators because :func:`zonal_fold` re-masks the value lanes on
    segment validity."""
    seg = jnp.asarray(seg, jnp.int32).reshape(-1)
    valid = jnp.asarray(valid, bool).reshape(-1)
    segm = jnp.where(valid, seg, np.int32(-1))
    return zonal_fold(values, segm, num_segments, acc_dtype=acc_dtype)


# --------------------------------------------------------- Pallas lane


def _zonal_kernel(seg_ref, vals_ref, cnt_ref, sum_ref, min_ref, max_ref,
                  *, tile_n: int, tile_s: int):
    s_blk = pl.program_id(0)
    p_blk = pl.program_id(1)

    @pl.when(p_blk == 0)
    def _init():  # first pixel block of each segment block zeroes
        cnt_ref[:] = jnp.zeros((1, tile_s), jnp.float32)
        sum_ref[:] = jnp.zeros((1, tile_s), jnp.float32)
        min_ref[:] = jnp.full((1, tile_s), _BIG_F, jnp.float32)
        max_ref[:] = jnp.full((1, tile_s), -_BIG_F, jnp.float32)

    with jax.named_scope("zonal_fold_block"):
        lane = (
            jax.lax.broadcasted_iota(jnp.int32, (tile_n, tile_s), 1)
            + s_blk * np.int32(tile_s)
        )
        seg = seg_ref[:]  # (tile_n, 1) int32, -1 = fold nowhere
        vals = vals_ref[:]  # (tile_n, 1) f32, 0 at masked pixels
        belongs = seg == lane  # (tile_n, tile_s) one-hot over lanes
        bf = belongs.astype(jnp.float32)
        cnt_ref[:] = cnt_ref[:] + jnp.sum(bf, axis=0, keepdims=True)
        sum_ref[:] = sum_ref[:] + jnp.sum(
            vals * bf, axis=0, keepdims=True
        )
        min_ref[:] = jnp.minimum(
            min_ref[:],
            jnp.min(jnp.where(belongs, vals, _BIG_F), axis=0,
                    keepdims=True),
        )
        max_ref[:] = jnp.maximum(
            max_ref[:],
            jnp.max(jnp.where(belongs, vals, -_BIG_F), axis=0,
                    keepdims=True),
        )


@functools.partial(
    jax.jit,
    static_argnames=("num_segments", "tile_n", "tile_s", "interpret"),
)
def zonal_tiled(
    values,
    seg,
    num_segments: int,
    *,
    tile_n: int = 2048,
    tile_s: int = 128,
    interpret: bool = False,
):
    """Pallas TPU zonal fold: ((S,) i32 count, (S,) f32 sum, (S,) f32
    min, (S,) f32 max). Same contract as :func:`zonal_fold` at f32.

    Pixels are padded to a ``tile_n`` multiple (pad segment -1),
    segments to a ``tile_s`` multiple; grid (segment blocks, pixel
    blocks) with pixels innermost so each accumulator block is written
    by consecutive grid steps. ``interpret=True`` is the CPU twin the
    tests pin against the jnp lane.
    """
    if tile_n % 8 or tile_s % 128:
        raise TilingError(
            f"tile_n must be a multiple of 8 and tile_s of 128, got "
            f"({tile_n}, {tile_s})"
        )
    values = jnp.asarray(values, jnp.float32).reshape(-1)
    seg = jnp.asarray(seg, jnp.int32).reshape(-1)
    n = values.shape[0]
    if n > (1 << 24):
        raise TilingError(
            f"{n} pixels exceeds the f32-exact count bound 2**24 — "
            "fold per tile and merge, or use zonal_fold"
        )
    n_pad = -(-max(n, 1) // tile_n) * tile_n
    s_pad = -(-max(int(num_segments), 1) // tile_s) * tile_s
    vals_p = jnp.zeros((n_pad, 1), jnp.float32).at[:n, 0].set(values)
    seg_p = jnp.full((n_pad, 1), np.int32(-1)).at[:n, 0].set(seg)
    grid = (s_pad // tile_s, n_pad // tile_n)

    def pix_spec():
        return pl.BlockSpec(
            (tile_n, 1), lambda s, p: (p, _I0),
            memory_space=pltpu.VMEM,
        )

    def acc_spec():
        # one (1, s_pad) row windowed along the lane axis: a block's
        # sublane extent must be a multiple of 8 or the whole axis, which
        # a (1, tile_s) block of an (s_blocks, tile_s) array is not
        return pl.BlockSpec(
            (1, tile_s), lambda s, p: (_I0, s),
            memory_space=pltpu.VMEM,
        )

    out_shape = jax.ShapeDtypeStruct((1, s_pad), jnp.float32)
    cnt, s, mn, mx = pl.pallas_call(
        functools.partial(_zonal_kernel, tile_n=tile_n, tile_s=tile_s),
        grid=grid,
        in_specs=[pix_spec(), pix_spec()],
        out_specs=(acc_spec(), acc_spec(), acc_spec(), acc_spec()),
        out_shape=(out_shape, out_shape, out_shape, out_shape),
        interpret=interpret,
    )(seg_p, vals_p)
    k = int(num_segments)
    return (
        cnt.reshape(-1)[:k].astype(jnp.int32),
        s.reshape(-1)[:k],
        mn.reshape(-1)[:k],
        mx.reshape(-1)[:k],
    )
