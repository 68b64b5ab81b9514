"""Pallas TPU kernel: Mamba2 SSD intra-chunk compute.

Per (batch, chunk) the kernel computes the quadratic-within-chunk form

    Y[i] = sum_{j<=i} (C_i . B_j) * L[i,j] * x_j         (per head)
    S_c  = sum_j decay_to_end[j] * x_j (x) B_j           (chunk-end state)

The decay matrix L is built from a cumulative-sum segment trick; all three
contractions are MXU matmuls.  This is the SSD insight (state-space
duality): the recurrence becomes dense matmuls within chunks — exactly the
TPU-native reformulation called for in the hardware-adaptation brief.

Grid: (B, nc, H/bh) with Q-by-Q score tiles in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core.collectives import unify_vma, vma_of


def _kernel(x_ref, la_ref, b_ref, c_ref, y_ref, s_ref, *, bh):
    # refs: x [1,1,Q,bh,P]; la [1,1,Q,bh]; b/c [1,1,Q,N]
    x = x_ref[0, 0].astype(jnp.float32)          # [Q, bh, P]
    la = la_ref[0, 0].astype(jnp.float32)        # [Q, bh]
    Bm = b_ref[0, 0].astype(jnp.float32)         # [Q, N]
    Cm = c_ref[0, 0].astype(jnp.float32)         # [Q, N]
    Q = x.shape[0]

    cs = jnp.cumsum(la, axis=0)                  # [Q, bh]
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # [Q,Q]
    rows = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    tri = rows >= cols

    def per_head(h, _):
        seg = cs[:, h][:, None] - cs[:, h][None, :]          # [Q, Q]
        L = jnp.where(tri, jnp.exp(seg), 0.0)
        W = scores * L                                       # [Q, Q]
        yh = jax.lax.dot_general(W, x[:, h, :], (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        y_ref[0, 0, :, h, :] = yh.astype(y_ref.dtype)
        tail = cs[-1, h] - cs[:, h]                          # [Q]
        xw = x[:, h, :] * jnp.exp(tail)[:, None]
        sh = jax.lax.dot_general(xw, Bm, (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # [P,N]
        s_ref[0, 0, h] = sh.astype(s_ref.dtype)
        return 0

    jax.lax.fori_loop(0, bh, per_head, 0)


@functools.partial(jax.jit, static_argnames=("bh", "interpret"))
def ssd_intra(x, log_a, Bm, Cm, *, bh=None, interpret=False):
    """x: [B, nc, Q, H, P]; log_a: [B, nc, Q, H]; Bm/Cm: [B, nc, Q, N].
    Returns (Y [B, nc, Q, H, P] f32, S_c [B, nc, H, P, N] f32)."""
    B, nc, Q, H, P = x.shape
    N = Bm.shape[-1]
    bh = bh or H
    assert H % bh == 0
    grid = (B, nc, H // bh)
    x, log_a, Bm, Cm = unify_vma(x, log_a, Bm, Cm)
    vma = vma_of(x)
    y, s = pl.pallas_call(
        functools.partial(_kernel, bh=bh),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, Q, bh, P), lambda b, c, h: (b, c, 0, h, 0)),
            pl.BlockSpec((1, 1, Q, bh), lambda b, c, h: (b, c, 0, h)),
            pl.BlockSpec((1, 1, Q, N), lambda b, c, h: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda b, c, h: (b, c, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Q, bh, P), lambda b, c, h: (b, c, 0, h, 0)),
            pl.BlockSpec((1, 1, bh, P, N), lambda b, c, h: (b, c, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, nc, Q, H, P), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((B, nc, H, P, N), jnp.float32, vma=vma),
        ],
        interpret=interpret,
    )(x, log_a, Bm, Cm)
    return y, s
