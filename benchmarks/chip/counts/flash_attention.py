"""Work the flash-attention kernels need for one training step.

Forward: S = QK^T and O = PV over the causal pairs.  Backward: dV = P^T dO,
dP = dO V^T, dQ = dS K, dK = dS^T Q — twice the forward's products.  The
forward the backward pass recomputes (and the rematerialised forward of a
remat step) is not work the algorithm needs and is not counted.  Bytes:
q, k, v and dO read and o, dq, dk, dv written once, plus o read once by
the backward (its row statistics are counted as part of o).
"""
from __future__ import annotations


def train_step(a, batch: int, seq: int, dtype_bytes: int = 2):
    """(flops, bytes) of flash forward + backward over every layer of one
    step at ``batch`` x ``seq``, summed over all chips."""
    pairs = seq * (seq + 1) / 2
    fwd = 4.0 * a.heads * a.head_dim * pairs * batch
    q = batch * seq * a.heads * a.head_dim
    kv = batch * seq * a.kv_heads * a.head_dim
    # fwd: read q k v, write o; bwd: read q k v o dO, write dq dk dv
    elems = (q + 2 * kv + q) + (q + 2 * kv + 2 * q) + (q + 2 * kv)
    return 3.0 * fwd * a.layers, float(elems * dtype_bytes * a.layers)
