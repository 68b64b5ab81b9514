"""Work one engine step needs on the chip: its decode and its prefills.

Decode: every matmul weight read once and 2 FLOPs per weight per active
slot, plus the slots' attention over their live KV.  Each prefill: the
weights read once more, its prompt through every layer with causal
attention, the head at its last position only, and its K/V written once.
"""
from __future__ import annotations

from chip.harness import load_count


def engine_step(a, live_lengths, prompt_lengths, dtype_bytes: int = 4):
    """(flops, bytes) of one engine step."""
    lm = load_count("dense_lm")
    paged = load_count("paged_decode")
    flops, nbytes = 0.0, 0.0
    wbytes = lm.weight_params(a) * dtype_bytes
    kv_per_token = 2 * a.kv_heads * a.head_dim * a.layers * dtype_bytes
    if live_lengths:
        af, ab = paged.decode_step(a, live_lengths, dtype_bytes)
        flops += 2.0 * lm.matmul_params(a) * len(live_lengths) + af
        nbytes += wbytes + ab
    head = a.vocab * a.hidden
    for p in prompt_lengths:
        # the head runs at the last prompt position only
        flops += (2.0 * (lm.matmul_params(a) - head) * p + 2.0 * head
                  + lm.attn_flops_fwd(a, p))
        nbytes += wbytes + p * kv_per_token
    return flops, nbytes
