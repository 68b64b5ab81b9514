"""Work paged decode attention needs for one engine step.

Each slot reads its own live K and V pages of its own kv heads once, and
its query and output; it scores each of its q heads against its kv head's
keys only (the Hkv-fold scoring of other heads' rows is not counted).
"""
from __future__ import annotations


def decode_step(a, live_lengths, dtype_bytes: int = 4):
    """(flops, bytes) over every layer for slots holding ``live_lengths``
    positions (the new token included)."""
    n = float(sum(live_lengths))
    flops = 4.0 * a.heads * a.head_dim * n * a.layers
    kv = 2.0 * n * a.kv_heads * a.head_dim
    qo = 2.0 * len(live_lengths) * a.heads * a.head_dim
    return flops, (kv + qo) * dtype_bytes * a.layers
