"""Bytes the join has to move per input row, from shapes.

Counts what the algorithm needs, not what a given lowering moves: the
point in (2 x f64), the cell id written by the assignment and read by the
probe (int64 each way), one hash bucket (``B`` packed int64 entries, or the
cell/slot pair where the index cannot pack), for a row whose cell is in
the index its tier-1 row (``E1`` edges of 4 coordinates plus one parity
word each, ``M1`` slot ids and core flags), and the int32 answer written
and read once by the fold. ``found_share`` is the share of rows whose cell
is indexed; the caller passes a counted lower bound (the match share), so
the roofline share built on this is never counted too high.
"""

from __future__ import annotations


def index_shapes(index) -> dict:
    """The shapes `bytes_per_row` needs, read off a ChipIndex."""
    t_b = int(index.table_cell.shape[1])
    packed = int(index.table_pack.shape[0]) > 0
    edges = index.cell_edges
    return {
        "hash_bucket": t_b,
        "hash_packed": packed,
        "tier1_edges": int(edges.shape[1]),
        "tier1_slots": int(index.cell_slot_geom.shape[1]),
        "edge_itemsize": int(edges.dtype.itemsize),
    }


def bytes_per_row(shapes: dict, found_share: float) -> float:
    if not 0.0 <= found_share <= 1.0:
        raise ValueError(f"found_share {found_share} outside [0, 1]")
    point = 2 * 8
    cell = 8 + 8
    bucket = shapes["hash_bucket"] * (8 if shapes["hash_packed"] else 12)
    tier1 = (
        shapes["tier1_edges"] * (4 * shapes["edge_itemsize"] + 4)
        + shapes["tier1_slots"] * (4 + 1)
    )
    answer = 4 + 4
    return point + cell + bucket + found_share * tier1 + answer
