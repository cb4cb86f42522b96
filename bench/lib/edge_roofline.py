"""Bytes and operations one sparse HAP sweep over an edge list must
move, counted from the graph's stored entries (edges plus one self slot
a node), not from the padded layout: padding shows as a lower share.

As ``roofline.topk_sweep_bytes``: per level the sweep reads s, r, a and
writes r, a (5 arrays of f32 entries), and reads the int32 column map
once, so 5 L + 1 arrays of 4-byte entries, 16 at L=3. The (L, N)
vectors are left out."""
from __future__ import annotations

F32 = 4
I32 = 4


def edge_sweep_bytes(entries: int, levels: int) -> int:
    return levels * 5 * entries * F32 + entries * I32


def edge_sweep_flops(entries: int, levels: int) -> int:
    """About 20 elementwise operations per stored entry per level."""
    return 20 * levels * entries
