"""jit'd public wrappers for the Pallas kernels.

On this CPU container the kernels run in interpret mode (the TPU is the
TARGET, not the runtime); ``interpret_mode()`` flips automatically
unless a TPU backend is present.  Model code selects the attention data
path via ``attn_impl`` (RunConfig / ParallelContext):

    "jnp"    — the pure-jnp reference paths (blockwise_attention etc.)
    "pallas" — the fused kernels, ALWAYS (interpret mode off-TPU, so the
               kernel data path runs in CPU CI and parity mdchecks)
    "auto"   — resolve per backend: kernels on TPU, jnp elsewhere (the
               attention analogue of matmul_schedule="auto", DESIGN.md §10)
"""
from __future__ import annotations

import jax

from .flash_attention import flash_attention
from .paged_attention import paged_attention
from .ssd import ssd_intra
from .tesseract_mm import tesseract_mm, tesseract_mm_stream


def interpret_mode() -> bool:
    return jax.default_backend() != "tpu"


def effective_attn_impl(impl: str) -> str:
    """Resolve an ``attn_impl`` knob to the executing data path."""
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    if impl not in ("jnp", "pallas"):
        raise ValueError(f"attn_impl must be 'jnp', 'pallas' or 'auto', "
                         f"got {impl!r}")
    return impl


def tesseract_mm_op(a, b, **kw):
    return tesseract_mm(a, b, interpret=interpret_mode(), **kw)


def tesseract_mm_stream_op(a, b, c, **kw):
    """One ring-SUMMA step: c += a @ b with a donated fp32 accumulator.

    Standalone TPU counterpart of matmul_schedule="ring"'s per-step
    contraction (the gathered [T, E, F] operand of the fused kernel never
    materializes).  Not yet wired into core/summa.py — the ring schedule
    currently contracts with jnp.einsum, like the fused path does with
    this module's fused kernel."""
    return tesseract_mm_stream(a, b, c, interpret=interpret_mode(), **kw)


def flash_attention_op(q, k, v, *, causal=True, **kw):
    """Flash fwd + custom-vjp bwd; q/k/v in [B, H, T, D] kernel layout."""
    return flash_attention(q, k, v, causal=causal, interpret=interpret_mode(),
                           **kw)


def paged_attention_op(q, pool_k, pool_v, table, pos, kv_map, **kw):
    """Block-table paged decode attention (no pool gather); see
    kernels/paged_attention.py."""
    return paged_attention(q, pool_k, pool_v, table, pos, kv_map,
                           interpret=interpret_mode(), **kw)


def ssd_intra_op(x, log_a, Bm, Cm, **kw):
    return ssd_intra(x, log_a, Bm, Cm, interpret=interpret_mode(), **kw)
