"""Pallas TPU kernel: single-step decode attention over a paged KV pool.

The jnp serve path (models/common.py::paged_gather) materializes every
request's ENTIRE block-table view — [B, max_blocks * bs, Hkv, D] per layer
per step — before one softmax over it.  This kernel walks the block table
directly: grid (B, max_blocks) with the physical page resolved by a
scalar-prefetched table lookup in the K/V index maps, so pages stream
HBM -> VMEM one page at a time and nothing is ever gathered.

Block layout: a page is read whole, as the [bs * Hkv, D] view of its
[bs, Hkv, D] slab (row r = token r // Hkv, kv head r % Hkv), and every q
head of the request is scored against every row of it in one matmul.
Rows of another kv head than the q head's (``kv_map``) are masked out, so
the softmax over a page sees exactly that head's bs tokens.  The last two
dims of every block are the array's own, which is what the TPU compiler
asks of a block that is not a multiple of the (8, 128) tile; the Hkv-fold
extra score work is small at decode, where the step is bound by the page
reads, and each page is read once per request instead of once per q head.

Per-request page skipping: pages beyond ``pos[b] // bs`` (and, under a
sliding window, before the window's first page) clamp to the last/first
live page in the index map — the pipeline skips the repeated DMA — and
`pl.when` masks their compute.  Retired slots (whole table pointed at the
group's scratch block, pos = 0) read exactly one page, like the jnp path.

``kv_map`` ([Hq] -> kv head) also covers the non-uniform replicated-KV
maps (smollm head padding) that the flash kernel handles by pre-expansion.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from jax.sharding import PartitionSpec as PS

from ..core.collectives import pvary, unify_vma, vma_of

NEG_INF = -1e30
_M_FLOOR = -1e25


class PagedCfg(NamedTuple):
    bs: int
    nb: int
    window: int
    scale: float
    interpret: bool


def _page_bounds(cfg: PagedCfg, pos_ref, b):
    """[lo, hi) live-page range for request b (jnp scalars)."""
    hi = pos_ref[b] // cfg.bs + 1                  # pos is inclusive
    lo = 0
    if cfg.window > 0:
        lo = jnp.maximum(pos_ref[b] - cfg.window + 1, 0) // cfg.bs
        lo = jnp.minimum(lo, hi - 1)
    return lo, hi


def _kernel(table_ref, pos_ref, q_ref, kvm_ref, cols_ref, k_ref, v_ref,
            o_ref, m_ref, l_ref, acc_ref, *, cfg: PagedCfg):
    b, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    lo, hi = _page_bounds(cfg, pos_ref, b)
    jj = jnp.minimum(lo + j, hi - 1)

    @pl.when(lo + j < hi)
    def _step():
        q = q_ref[0].astype(jnp.float32)                       # [Hq, D]
        k = k_ref[0].astype(jnp.float32)                       # [bs*Hkv, D]
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * cfg.scale
        ppos = jj * cfg.bs + cols_ref[0:1, :]                  # [1, bs*Hkv]
        mask = (cols_ref[1:2, :] == kvm_ref[...]) & (ppos <= pos_ref[b])
        if cfg.window > 0:
            mask &= ppos > pos_ref[b] - cfg.window
        s = jnp.where(mask, s, NEG_INF)                        # [Hq, bs*Hkv]
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - jnp.maximum(m_new, _M_FLOOR))
        corr = jnp.exp(jnp.maximum(m_prev, _M_FLOOR)
                       - jnp.maximum(m_new, _M_FLOOR))
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)                       # [bs*Hkv, Dv]
        acc_ref[...] = acc_ref[...] * corr + lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == cfg.nb - 1)
    def _done():
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(
            o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("local_window", "softmax_scale",
                                             "interpret"))
def paged_attention(q, pool_k, pool_v, table, pos, kv_map, *,
                    local_window: int = 0, softmax_scale=None,
                    interpret=False):
    """One decode step against a paged pool, walking the block table.

    q: [B, Hq, D]; pool_k/pool_v: [P_loc, bs, Hkv, D/Dv]; table: [B, nb]
    LOCAL physical block ids; pos: [B] per-request current position (its
    K/V already written — paged_update-then-attend order); kv_map: [Hq]
    q-head -> kv-head.  Returns [B, Hq, Dv].
    """
    B, Hq, D = q.shape
    P, bs, Hkv = pool_k.shape[:3]
    Dv = pool_v.shape[-1]
    nb = table.shape[1]
    W = bs * Hkv
    scale = (softmax_scale if softmax_scale is not None
             else 1.0 / math.sqrt(D))
    cfg = PagedCfg(bs=bs, nb=nb, window=int(local_window),
                   scale=float(scale), interpret=bool(interpret))
    # row r of a page view: token offset r // Hkv, kv head r % Hkv
    rows = np.arange(W, dtype=np.int32)
    cols = jnp.asarray(np.stack([rows // Hkv, rows % Hkv]))
    table, pos = table.astype(jnp.int32), pos.astype(jnp.int32)
    q, kvm, cols, pk, pv = unify_vma(
        q, kv_map.astype(jnp.int32).reshape(Hq, 1), cols,
        pool_k.reshape(P, W, D), pool_v.reshape(P, W, Dv))
    vma = vma_of(q) | vma_of(table) | vma_of(pos)
    kvpage = lambda b, j, tr, pr: (
        tr[b, jnp.minimum(_page_bounds(cfg, pr, b)[0] + j,
                          _page_bounds(cfg, pr, b)[1] - 1)], 0, 0)
    whole = lambda b, j, tr, pr: (0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nb),
        in_specs=[
            pl.BlockSpec((1, Hq, D), lambda b, j, tr, pr: (b, 0, 0)),
            pl.BlockSpec((Hq, 1), whole),
            pl.BlockSpec((2, W), whole),
            pl.BlockSpec((1, W, D), kvpage),
            pl.BlockSpec((1, W, Dv), kvpage),
        ],
        out_specs=pl.BlockSpec((1, Hq, Dv),
                               lambda b, j, tr, pr: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hq, 1), jnp.float32),
            pltpu.VMEM((Hq, 1), jnp.float32),
            pltpu.VMEM((Hq, Dv), jnp.float32),
        ],
    )
    call = pl.pallas_call(
        functools.partial(_kernel, cfg=cfg),
        name="paged_decode",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, Dv), q.dtype, vma=vma),
        interpret=cfg.interpret,
    )
    args = (table, pos, q, kvm, cols, pk, pv)
    if not (cfg.interpret and vma):
        return call(*args)
    # The Pallas interpreter evaluates the index maps on varying block
    # tables without their vma and trips jax's vma check.  It runs in a
    # nested shard_map that checks none, on copies whose vma that context
    # drops, and its result is marked varying over every axis an operand
    # varies on.
    unchecked = lambda *a: call(*(x + jnp.zeros_like(x) for x in a))
    out = jax.shard_map(unchecked, in_specs=PS(), out_specs=PS(),
                        axis_names=frozenset(), check_vma=False)(*args)
    return pvary(out, tuple(sorted(vma)))
