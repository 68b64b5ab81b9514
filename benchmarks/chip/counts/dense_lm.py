"""Operations of a llama-style decoder that the algorithm needs.

``matmul_params`` counts the weights that take part in a matrix product
(every projection and the output head; not the embedding lookup, which
is a gather).  Attention is causal: position t attends to t+1 keys.
"""
from __future__ import annotations


def matmul_params(a) -> int:
    """a: reference.Arch."""
    hd, kvd = a.heads * a.head_dim, a.kv_heads * a.head_dim
    per_layer = a.hidden * hd * 2 + a.hidden * kvd * 2 + 3 * a.hidden * a.ffn
    return a.layers * per_layer + a.vocab * a.hidden


def weight_params(a) -> int:
    """Every parameter a forward pass reads whole (not the embedding)."""
    return matmul_params(a) + (2 * a.layers + 1) * a.hidden


def attn_flops_fwd(a, q_positions, keys_from: int = 0) -> float:
    """Forward attention FLOPs (QK^T and PV) over all layers for a run of
    queries at positions keys_from .. keys_from+q_positions-1, each
    attending causally to every earlier position and itself."""
    n = q_positions
    pairs = n * keys_from + n * (n + 1) / 2
    return 4.0 * a.heads * a.head_dim * pairs * a.layers


def train_flops_per_token(a, seq: int) -> float:
    """Forward + backward FLOPs per trained token: 6 per matmul parameter
    plus causal attention (backward twice the forward).  Recomputation
    is not counted."""
    return 6.0 * matmul_params(a) + 3.0 * attn_flops_fwd(a, seq) / seq


def forward_flops(a, tokens: int, attn_flops: float) -> float:
    """Forward FLOPs of ``tokens`` through every matmul, plus attention."""
    return 2.0 * matmul_params(a) * tokens + attn_flops
