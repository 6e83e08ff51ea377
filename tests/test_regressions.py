"""Regression tests for round-2 advisor findings.

(a) `_build_hash` must stay self-consistent even when every multiplier
    retry clusters (the fallback path);
(b) float rasters with NaN nodata must mask NaN pixels (`v != NaN` is
    always True);
(c) a GeoTIFF whose IFD value bytes are truncated must fail the read with
    an error code instead of silently decoding zeros.
(d) the hash probe (`_probe_slot`, one row gather of the u32 table) must
    answer what a plain dict answers, whatever the bucket width, the
    words two ids share, or the bucket a miss lands in.
"""

import types

import jax.numpy as jnp
import numpy as np
import pytest

from mosaic_tpu.raster import Raster, read_raster, write_geotiff
from mosaic_tpu.sql.join import (
    _build_hash,
    _hash_rows,
    _probe_slot,
    build_chip_index,
)


def test_build_hash_exhausted_retries_stay_consistent():
    """max_bucket=0 forces every retry to 'fail': the returned (mult, T)
    must still locate every cell (the round-2 bug desynced keys from T)."""
    cells = np.sort(np.unique(np.random.default_rng(1).integers(
        1, 2**60, 500, dtype=np.int64
    )))
    mult, table_cell, table_slot, _, _ = _build_hash(cells, max_bucket=0)
    T = table_cell.shape[0]
    bits = int(np.log2(T))
    keys = (cells.astype(np.uint64) * mult) >> np.uint64(64 - bits)
    for u, (c, k) in enumerate(zip(cells, keys.astype(np.int64))):
        row = table_cell[k]
        hit = np.nonzero(row == c)[0]
        assert hit.size == 1, f"cell {c} not findable under returned hash"
        assert table_slot[k, hit[0]] == u


_MULT = np.uint64(0x9E3779B97F4A7C15)
_BITS = 4  # a 16-bucket table: buckets fill, and a key is found by search
_LO = np.int64(0xFFFFFFFF)


def _keys(ids, mult=_MULT, bits=_BITS):
    ids = np.asarray(ids, dtype=np.int64)
    return ((ids.astype(np.uint64) * mult) >> np.uint64(64 - bits)).astype(
        np.int64)


def _probe_leaves(table_cell, table_slot, mult):
    """The two leaves of a ChipIndex that `_probe_slot` reads."""
    return types.SimpleNamespace(
        table_rows=jnp.asarray(_hash_rows(table_cell, table_slot)),
        hash_mult=jnp.asarray(np.asarray([mult], dtype=np.uint64)),
    )


def _plain_index(cells, mult=_MULT, bits=_BITS):
    """The probe's two leaves over a plain bucketed table of sorted
    ``cells`` (slot = rank) at a fixed multiplier and size: B is what the
    fullest bucket holds. Returns (index, B, {cell: slot})."""
    cells = np.unique(np.asarray(cells, dtype=np.int64))
    keys = _keys(cells, mult, bits)
    B = int(np.bincount(keys, minlength=1 << bits).max())
    table_cell = np.full((1 << bits, B), -1, dtype=np.int64)
    table_slot = np.full((1 << bits, B), -1, dtype=np.int32)
    fill = np.zeros(1 << bits, dtype=np.int64)
    for u, (c, k) in enumerate(zip(cells, keys)):
        table_cell[k, fill[k]], table_slot[k, fill[k]] = c, u
        fill[k] += 1
    index = _probe_leaves(table_cell, table_slot, mult)
    return index, B, {int(c): u for u, c in enumerate(cells)}


def _ids(n, seed):
    return np.random.default_rng(seed).integers(1, 2**62, n, dtype=np.int64)


def _with_bucket(B):
    """Ids kept while their bucket holds fewer than B: the fullest holds B."""
    pool, kept, fill = _ids(40 * B, 3), [], np.zeros(1 << _BITS, np.int64)
    for c, k in zip(pool, _keys(pool)):
        if fill[k] < B:
            kept.append(c)
            fill[k] += 1
    return np.asarray(kept)


def _in_bucket(k, n, taken, seed=5):
    """n ids that hash to bucket k and are not in ``taken``."""
    pool = _ids(4096, seed)
    pool = pool[(_keys(pool) == k) & ~np.isin(pool, taken)]
    assert pool.size >= n
    return pool[:n]


def _case_grid(res, bbox, n, packs):
    """A real index: every cell of it, each cell with a bit of its low
    word and a bit of its high word flipped, and ids of no grid at all."""
    from mosaic_tpu.core.index.h3 import H3IndexSystem
    from mosaic_tpu.core.tessellate import tessellate
    from mosaic_tpu.datasets import synthetic_zones

    index = build_chip_index(tessellate(
        synthetic_zones(n, n, bbox=bbox), H3IndexSystem(), res,
        keep_core_geoms=False))
    assert (index.table_pack.shape[0] > 0) == packs
    cells = np.asarray(index.cells)
    truth = {int(c): u for u, c in enumerate(cells)}
    return index, truth, np.concatenate(
        [cells, cells ^ np.int64(1 << 20), cells ^ np.int64(1 << 52),
         _ids(64, 9), [-1, 0]])


def _case_bucket(B):
    index, got, truth = _plain_index(_with_bucket(B))
    assert got == B
    cells = np.fromiter(truth, dtype=np.int64)
    return index, truth, np.concatenate([cells, _ids(256, 9), [-1, 0]])


def _case_words(shared):
    """Ids that share their low word and differ in the high one
    (``shared='low'``), or the reverse; the misses share it too."""
    other = np.arange(1, 41, dtype=np.int64) * 7919
    if shared == "low":
        ids = (other << 32) | np.int64(0x1234ABCD)
    else:
        ids = (np.int64(0x08928308) << 32) | other
    index, _, truth = _plain_index(ids[::2])
    return index, truth, ids


def _case_miss(bucket):
    cells = _with_bucket(3)
    cells = cells[_keys(cells) != 0]  # bucket 0 stays empty
    index, _, truth = _plain_index(cells)
    full = int(np.argmax(np.bincount(_keys(cells), minlength=1 << _BITS)))
    if bucket == "empty":
        miss = np.concatenate([_in_bucket(0, 8, cells), [-1]])
    elif bucket == "full":
        miss = _in_bucket(full, 8, cells)
    else:  # the bucket holds the id's low word, under another high word
        c = cells[_keys(cells) == full][0]
        high = np.arange(1, 4096, dtype=np.int64) << 32
        miss = (c & _LO) | high
        miss = miss[(_keys(miss) == full) & ~np.isin(miss, cells)]
        assert miss.size
        # and the reverse: its high word, under another low word
        low = (c & ~_LO) | np.arange(1, 4096, dtype=np.int64)
        low = low[(_keys(low) == full) & ~np.isin(low, cells)]
        assert low.size
        miss = np.concatenate([miss, low])
    return index, truth, np.concatenate([miss, cells])


def _case_sign_bits():
    """Bit 31 of the low word set, bit 63 set, both: an int32 or int64
    compare that sign-extends a word would miss these."""
    ids = np.asarray(
        [0x08928308_80000001, 0x08928308_00000001, 0x08928308_FFFFFFFF,
         -0x7FFFFFFF_7FFFFFFF, 0x7FFFFFFF_80000000, -2], dtype=np.int64)
    index, _, truth = _plain_index(ids[[0, 2, 3, 5]])
    return index, truth, ids


@pytest.mark.parametrize("case", [
    pytest.param(lambda: _case_grid(
        9, (-74.05, 40.60, -73.85, 40.78), 3, True), id="h3-res9-packs"),
    pytest.param(lambda: _case_grid(
        11, (-74.00, 40.70, -73.96, 40.73), 2, False), id="h3-res11-no-pack"),
    pytest.param(lambda: _case_bucket(1), id="bucket-1"),
    pytest.param(lambda: _case_bucket(3), id="bucket-3"),
    pytest.param(lambda: _case_bucket(8), id="bucket-8"),
    pytest.param(lambda: _case_words("low"), id="low-words-collide"),
    pytest.param(lambda: _case_words("high"), id="high-words-collide"),
    pytest.param(lambda: _case_miss("empty"), id="miss-empty-bucket"),
    pytest.param(lambda: _case_miss("full"), id="miss-full-bucket"),
    pytest.param(lambda: _case_miss("word"), id="miss-one-word-only"),
    pytest.param(_case_sign_bits, id="word-sign-bits"),
])
def test_probe_slot_answers_what_a_dict_answers(case):
    index, truth, queries = case()
    queries = np.asarray(queries, dtype=np.int64)
    want = np.asarray([truth.get(int(q), -1) for q in queries], np.int32)
    assert (want >= 0).any() and (want < 0).any()
    got = np.asarray(_probe_slot(jnp.asarray(queries), index))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("n, max_bucket", [(6, 1), (20000, 3), (20000, 8)])
def test_hash_rows_hold_the_table_build_hash_returns(n, max_bucket):
    """Whatever bucket width `_build_hash` settles on (at 20,000 ids its
    hunt for B <= 2 is skipped, so ``max_bucket`` decides), the probed rows
    are its table word for word, and the probe finds every cell by them."""
    cells = np.unique(_ids(n, max_bucket))
    mult, table_cell, table_slot, _, _ = _build_hash(
        cells, max_bucket=max_bucket)
    T, B = table_cell.shape
    assert B <= max(max_bucket, 2)  # the B <= 2 hunt may land first
    rows = _hash_rows(table_cell, table_slot)
    assert rows.dtype == np.uint32 and rows.shape == (T, 3 * B)
    back = (rows[:, B:2 * B].astype(np.int64) << 32) | rows[:, :B]
    np.testing.assert_array_equal(back, table_cell)
    np.testing.assert_array_equal(rows[:, 2 * B:].view(np.int32), table_slot)
    index = _probe_leaves(table_cell, table_slot, mult)
    np.testing.assert_array_equal(index.table_rows, rows)
    got = np.asarray(_probe_slot(jnp.asarray(cells), index))
    np.testing.assert_array_equal(got, np.arange(cells.size))


def test_nan_nodata_masked():
    data = np.full((1, 4, 5), 1.5, dtype=np.float32)
    data[0, 0, 0] = np.nan
    data[0, 1, 2] = np.nan
    r = Raster(
        data=data,
        gt=(0.0, 1.0, 0.0, 0.0, 0.0, -1.0),
        srid=4326,
        nodata=float("nan"),
    )
    m = r.band(1).mask
    assert not m[0, 0] and not m[1, 2]
    assert m.sum() == 18
    assert r.band(1).min() == 1.5  # NaN pixels excluded from stats


def test_truncated_ifd_errors(tmp_path):
    data = (np.arange(200, dtype=np.float64)).reshape(1, 10, 20)
    r = Raster(
        data=data.astype(np.float32),
        gt=(0.0, 1.0, 0.0, 0.0, 0.0, -1.0),
        srid=4326,
        nodata=None,
    )
    p = tmp_path / "full.tif"
    write_geotiff(str(p), r)
    raw = p.read_bytes()
    # truncate into the out-of-line IFD value area: offsets now point past
    # EOF, which must be a hard read error, not a zero-filled success
    for frac in (0.35, 0.6):
        q = tmp_path / f"trunc_{frac}.tif"
        q.write_bytes(raw[: int(len(raw) * frac)])
        with pytest.raises(ValueError):
            read_raster(str(q))
