"""Bytes the join has to move per input row, from shapes.

Counts what the algorithm needs, not what a given lowering moves: the
point in (2 x f64), the cell id written by the assignment and read by the
probe (int64 each way), one hash bucket of the table the probe reads
(``ChipIndex.table_rows``, ``(T, 3B)`` u32: ``B`` entries of three words —
a cell id's low word, its high word, its slot — 12 bytes an entry on every
index, PR 34), for a row whose cell is in the index its tier-1 row (``E1``
edges of 4 coordinates plus one parity word each, ``M1`` slot ids and core
flags), and the int32 answer written and read once by the fold.
``found_share`` is the share of rows whose cell is indexed; the caller
passes a counted lower bound (the match share), so the roofline share built
on this is never counted too high.
"""

from __future__ import annotations

#: bytes of one bucket entry of ``table_rows``: three u32 words
HASH_ENTRY_BYTES = 12


def index_shapes(index) -> dict:
    """The shapes `bytes_per_row` needs, read off a ChipIndex."""
    edges = index.cell_edges
    pack = getattr(index, "table_pack", None)
    return {
        "hash_bucket": int(index.table_rows.shape[1]) // 3,
        # read by no arithmetic here. `tests/test_dist_join.py` (outside
        # the benchmark's paths, so not a benchmark PR's to edit) pins this
        # key; it goes with that pin and the index's dead `table_pack`
        "hash_packed": None if pack is None else int(pack.shape[0]) > 0,
        "tier1_edges": int(edges.shape[1]),
        "tier1_slots": int(index.cell_slot_geom.shape[1]),
        "edge_itemsize": int(edges.dtype.itemsize),
    }


def bytes_per_row(shapes: dict, found_share: float) -> float:
    if not 0.0 <= found_share <= 1.0:
        raise ValueError(f"found_share {found_share} outside [0, 1]")
    point = 2 * 8
    cell = 8 + 8
    bucket = shapes["hash_bucket"] * HASH_ENTRY_BYTES
    tier1 = (
        shapes["tier1_edges"] * (4 * shapes["edge_itemsize"] + 4)
        + shapes["tier1_slots"] * (4 + 1)
    )
    answer = 4 + 4
    return point + cell + bucket + found_share * tier1 + answer
