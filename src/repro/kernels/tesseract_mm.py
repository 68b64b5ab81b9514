"""Pallas TPU kernel: the Tesseract per-device SUMMA accumulation matmul.

After the fused all-gathers (DESIGN.md §2) each device computes

    C[e, g] = sum_t A[t, e, f] B[t, f, g]

— the paper's inner SUMMA loop.  The kernel tiles (E, G) onto the MXU with
128-aligned VMEM blocks and walks the (t, f) reduction in the innermost grid
dimensions, accumulating into the output block in fp32 — so the gathered
operands stream HBM->VMEM exactly once and the accumulator never leaves
VMEM.

Grid: (E/be, G/bg, T, F/bf) — XLA guarantees sequential execution of the
trailing grid dims on TPU, making output-block accumulation safe.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.collectives import unify_vma, vma_of


DEFAULT_BE = 256
DEFAULT_BF = 512
DEFAULT_BG = 256


def check_tiling(name: str, checks) -> None:
    """Raise an actionable ValueError when a dim does not tile into its
    VMEM block (TPU blocks must divide the operand shape).

    ``checks``: iterable of (dim_name, size, block_kwarg, block_size)."""
    bad = [(d, n, kw, b) for (d, n, kw, b) in checks if n % b]
    if bad:
        detail = ", ".join(f"{d}={n} is not a multiple of block {kw}={b}"
                           for d, n, kw, b in bad)
        kwargs = ", ".join(f"{kw}=..." for _, _, kw, _ in bad)
        raise ValueError(
            f"{name}: {detail}. Pad the operands to a multiple of the block "
            f"size (configs.base.round_up) or pass explicit block sizes "
            f"({kwargs}) that divide the shape.")


def _kernel(a_ref, b_ref, o_ref, acc_ref, *, n_inner):
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[0]          # [be, bf]
    b = b_ref[0]          # [bf, bg]
    acc_ref[...] += jnp.dot(a, b, preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_inner - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("be", "bf", "bg", "interpret"))
def tesseract_mm(a, b, *, be=DEFAULT_BE, bf=DEFAULT_BF, bg=DEFAULT_BG,
                 interpret=False):
    """a: [T, E, F]; b: [T, F, G] -> [E, G] fp32."""
    T, E, F = a.shape
    G = b.shape[-1]
    be, bf, bg = min(be, E), min(bf, F), min(bg, G)
    check_tiling("tesseract_mm", [("E", E, "be", be), ("F", F, "bf", bf),
                                  ("G", G, "bg", bg)])
    nf = F // bf
    # fold (t, f) into one inner reduction axis so accumulation order is
    # purely sequential on TPU
    n_inner = T * nf

    def a_index(e, g, i):
        return (i // nf, e, i % nf)

    def b_index(e, g, i):
        return (i // nf, i % nf, g)

    grid = (E // be, G // bg, n_inner)
    a, b = unify_vma(a, b)
    out = pl.pallas_call(
        functools.partial(_kernel, n_inner=n_inner),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, be, bf), a_index),
            pl.BlockSpec((1, bf, bg), b_index),
        ],
        out_specs=pl.BlockSpec((be, bg), lambda e, g, i: (e, g)),
        out_shape=jax.ShapeDtypeStruct((E, G), jnp.float32, vma=vma_of(a)),
        scratch_shapes=[pltpu.VMEM((be, bg), jnp.float32)],
        interpret=interpret,
    )(a, b)
    return out


# --------------------------------------------------------------------------
# Streaming variant: one SUMMA step at a time (matmul_schedule="ring").
#
# The ring schedule never materializes the [T, E, F] gathered operand: each
# ppermute delivers ONE (A_t, W_t) block pair, and this kernel contracts it
# into a persistent fp32 accumulator (C += A_t @ W_t).  The accumulator is
# donated via input_output_aliasing, so across the q ring steps exactly one
# [E, G] fp32 buffer lives in HBM — peak operand memory is O(2 · block)
# instead of the fused kernel's O(q · block).
#
# Standalone for now: core/summa.py's ring schedule contracts with
# jnp.einsum (mirroring the fused path, which likewise does not call the
# fused kernel above); this is the drop-in TPU building block for when the
# per-step contraction is kernelized.
# --------------------------------------------------------------------------

def _stream_kernel(a_ref, b_ref, c_ref, o_ref, acc_ref, *, nf):
    @pl.when(pl.program_id(2) == 0)
    def _load():
        acc_ref[...] = c_ref[...]          # carry in the ring accumulator

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == nf - 1)
    def _done():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("be", "bf", "bg", "interpret"),
                   donate_argnums=(2,))
def tesseract_mm_stream(a, b, c, *, be=DEFAULT_BE, bf=DEFAULT_BF,
                        bg=DEFAULT_BG, interpret=False):
    """One ring step: c + a @ b.  a: [E, F]; b: [F, G]; c: [E, G] fp32."""
    E, F = a.shape
    G = b.shape[-1]
    be, bf, bg = min(be, E), min(bf, F), min(bg, G)
    check_tiling("tesseract_mm_stream",
                 [("E", E, "be", be), ("F", F, "bf", bf), ("G", G, "bg", bg)])
    nf = F // bf
    grid = (E // be, G // bg, nf)
    a, b, c = unify_vma(a, b, c.astype(jnp.float32))
    return pl.pallas_call(
        functools.partial(_stream_kernel, nf=nf),
        grid=grid,
        in_specs=[
            pl.BlockSpec((be, bf), lambda e, g, i: (e, i)),
            pl.BlockSpec((bf, bg), lambda e, g, i: (i, g)),
            pl.BlockSpec((be, bg), lambda e, g, i: (e, g)),
        ],
        out_specs=pl.BlockSpec((be, bg), lambda e, g, i: (e, g)),
        out_shape=jax.ShapeDtypeStruct((E, G), jnp.float32, vma=vma_of(a)),
        scratch_shapes=[pltpu.VMEM((be, bg), jnp.float32)],
        input_output_aliases={2: 0},
        interpret=interpret,
    )(a, b, c)
