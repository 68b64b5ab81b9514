"""Shared model components: RoPE, streaming (flash-style) attention in pure
JAX, decode attention against a KV cache, init helpers, activations.

All attention math takes [B, T, H, D] tensors that are already *local* views
(heads sharded over `col`, tokens/seq per the plan) — no mesh axes here except
what the caller passes in explicitly via gathered KV.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

# The train step's layers, as ``jax.named_scope`` names them in every op's
# name stack (HLO ``metadata={op_name=...}``): an op belongs to the
# innermost of these its name stack holds (``roofline.hlo.scope_of``).
LAYER_SCOPES = ("embed", "blocks", "attn", "mlp", "head_loss", "optimizer")
EMBED, BLOCKS, ATTN, MLP, HEAD_LOSS, OPTIMIZER = LAYER_SCOPES
# The SUMMA matmul's collectives (``core/summa.py``), named inside the layer
# that calls the matmul.  Not a layer: an op under it still belongs to the
# layer scope around it (``roofline.hlo.in_scope`` reads it).
TP_COMM = "tp_comm"


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def winit(key, shape, scale=0.02, dtype=jnp.float32):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def winit_padded(key, gen_shape, padded_shape, scale=0.02, dtype=jnp.float32):
    """Generate at the *logical* shape, zero-pad to the sharded shape — keeps
    init values identical across mesh factorizations (padding differs)."""
    w = winit(key, gen_shape, scale, dtype)
    pads = [(0, p - g) for g, p in zip(gen_shape, padded_shape)]
    if any(p != (0, 0) for p in pads):
        w = jnp.pad(w, pads)
    return w


def zinit(_key, shape, dtype=jnp.float32):
    return jnp.zeros(shape, dtype)


# ---------------------------------------------------------------------------
# activations
# ---------------------------------------------------------------------------

def vma_like(x, *refs):
    """Give ``x`` the union of the refs' varying-manifest-axes so it can seed
    a scan carry inside shard_map (numerical no-op; works outside shard_map
    too, unlike an explicit pvary with axis names)."""
    tie = sum((r.reshape(-1)[0] * 0).astype(jnp.float32) for r in refs)
    return x + tie.astype(x.dtype)


def mlp_act(name: str):
    if name == "silu":
        return jax.nn.silu
    if name == "gelu":
        return jax.nn.gelu
    if name == "relu2":  # squared ReLU (nemotron-4)
        return lambda x: jnp.square(jax.nn.relu(x))
    raise ValueError(name)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float = 1e4):
    """x: [B, T, H, D]; positions: [T] or [B, T] global position ids."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                        # [D/2]
    if positions.ndim == 1:
        ang = positions[:, None].astype(jnp.float32) * freqs[None, :]   # [T, D/2]
        ang = ang[None, :, None, :]                     # [1, T, 1, D/2]
    else:
        ang = positions[..., None].astype(jnp.float32) * freqs          # [B, T, D/2]
        ang = ang[:, :, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    y = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention dispatch (DESIGN.md §10): every model-side attention call goes
# through one of the dispatchers below, which route to the fused Pallas
# kernels (kernels/flash_attention.py, kernels/paged_attention.py) or the
# pure-jnp reference paths depending on the resolved ``attn_impl``.
# ---------------------------------------------------------------------------

def attention(q, k, v, *, q_pos, kv_pos, causal: bool = True,
              local_window: int = 0, q_chunk: int = 512,
              kv_chunk: int = 512, softmax_scale=None, impl: str = "jnp",
              q_start=None):
    """Training/prefill attention in the model layout [B, T, H, D].

    Dispatches on ``impl`` (ParallelContext.attn_impl): "pallas" runs the
    fused flash kernel with the causal/window masks driven by ``q_pos``
    (``q_start`` is the static q-row offset enabling block skipping; None
    for traced seq-sharded positions).  The kernel contract assumes KV rows
    sit at positions 0..Tk-1, which every call site satisfies (kv_pos is
    the gathered full-sequence arange; the non-causal cross-attention
    sites pass all-zero positions and no window, where positions are
    inert).  GQA is contiguous Hq = g * Hkv in both paths.
    """
    from ..kernels.ops import effective_attn_impl, flash_attention_op
    if effective_attn_impl(impl) == "pallas":
        out = flash_attention_op(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), causal=causal,
            local_window=local_window,
            q_pos=None if q_start is not None else q_pos,
            q_start=q_start, softmax_scale=softmax_scale)
        return out.transpose(0, 2, 1, 3)
    return blockwise_attention(q, k, v, q_pos=q_pos, kv_pos=kv_pos,
                               causal=causal, local_window=local_window,
                               q_chunk=q_chunk, kv_chunk=kv_chunk,
                               softmax_scale=softmax_scale)


# ---------------------------------------------------------------------------
# Streaming attention (pure-jnp flash): O(block) memory, numerically stable.
# v1 computes every (q-block, kv-block) pair and masks — the causal upper
# triangle is wasted compute; the Pallas kernel removes it (and is wired as
# the default TPU data path via attn_impl, DESIGN.md §10).
# ---------------------------------------------------------------------------

def blockwise_attention(q, k, v, *, q_pos, kv_pos, causal: bool = True,
                        local_window: int = 0, q_chunk: int = 512,
                        kv_chunk: int = 512, softmax_scale=None):
    """q: [B, Tq, Hq, D]; k,v: [B, Tk, Hkv, Dv?]; GQA via Hq = g * Hkv.

    q_pos: [Tq] global positions of queries; kv_pos: [Tk].
    local_window > 0 limits attention to the last `local_window` positions.
    Returns [B, Tq, Hq, Dv].
    """
    B, Tq, Hq, D = q.shape
    Tk, Hkv = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    g = Hq // Hkv
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(D)

    cq = min(q_chunk, Tq)
    while Tq % cq:
        cq -= 1
    ck = min(kv_chunk, Tk)
    while Tk % ck:
        ck -= 1
    nq, nk = Tq // cq, Tk // ck

    qr = q.reshape(B, nq, cq, Hkv, g, D)
    kr = k.reshape(B, nk, ck, Hkv, D)
    vr = v.reshape(B, nk, ck, Hkv, Dv)
    qpr = q_pos.reshape(nq, cq)
    kpr = kv_pos.reshape(nk, ck)

    def q_block(args):
        qb, qp = args                                  # [B, cq, Hkv, g, D], [cq]

        def kv_step(carry, blk):
            m, l, acc = carry
            kb, vb, kp = blk                           # [B, ck, Hkv, D], ...
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qb, kb,
                           preferred_element_type=jnp.float32) * scale
            mask = jnp.ones((cq, ck), bool)
            if causal:
                mask &= qp[:, None] >= kp[None, :]
            if local_window > 0:
                mask &= kp[None, :] > (qp[:, None] - local_window)
            s = jnp.where(mask[None, None, None], s, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            # guard: fully-masked rows keep m=-inf; exp(-inf - -inf) -> nan
            m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
            p = jnp.exp(s - m_safe[..., None])
            corr = jnp.exp(jnp.where(jnp.isneginf(m), -jnp.inf, m - m_safe))
            l = l * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p.astype(vb.dtype), vb,
                preferred_element_type=jnp.float32)
            return (m_new, l, acc), None

        m0 = vma_like(jnp.full((B, Hkv, g, cq), -jnp.inf, jnp.float32), qb, k, v)
        l0 = vma_like(jnp.zeros((B, Hkv, g, cq), jnp.float32), qb, k, v)
        a0 = vma_like(jnp.zeros((B, Hkv, g, cq, Dv), jnp.float32), qb, k, v)
        (m, l, acc), _ = lax.scan(kv_step, (m0, l0, a0), (kr.swapaxes(0, 1),
                                                          vr.swapaxes(0, 1),
                                                          kpr))
        l = jnp.where(l == 0.0, 1.0, l)
        out = acc / l[..., None]                        # [B, Hkv, g, cq, Dv]
        return out.transpose(0, 3, 1, 2, 4)             # [B, cq, Hkv, g, Dv]

    outs = lax.map(q_block, (qr.swapaxes(0, 1), qpr))   # [nq, B, cq, Hkv, g, Dv]
    out = outs.swapaxes(0, 1).reshape(B, Tq, Hq, Dv)
    return out.astype(q.dtype)


def decode_pos_mask(cur_pos, S: int, local_window: int = 0):
    """[B, 1, S] validity mask for single-step decode attention.

    Position-only (layer-independent), so callers hoist it OUT of the layer
    scan and pass it to every block's decode_attention instead of each
    layer recomputing the arange/compare chain (jnp fallback path)."""
    cur_pos = jnp.asarray(cur_pos)
    if cur_pos.ndim == 0:
        cur_pos = cur_pos[None]
    cur = cur_pos[:, None, None]                         # [B, 1, 1]
    pos = jnp.arange(S)
    mask = pos[None, None, :] <= cur
    if local_window > 0:
        mask &= pos[None, None, :] > (cur - local_window)
    return mask


def _decode_bs(S: int) -> int:
    """Page size used to view a dense cache as a pool (pallas decode)."""
    bs = min(128, S)
    while S % bs:
        bs -= 1
    return bs


def _paged_kernel(q, pool_k, pool_v, table, pos, kv_map, *, local_window,
                  softmax_scale):
    """Shared pallas-decode dispatch: default the GQA map to the contiguous
    grouping and run the block-table kernel (used by decode_attention's
    pool view and paged_attention)."""
    from ..kernels.ops import paged_attention_op
    Hq = q.shape[1]
    if kv_map is None:
        kv_map = jnp.arange(Hq, dtype=jnp.int32) // (Hq // pool_k.shape[2])
    return paged_attention_op(q, pool_k, pool_v, table, pos, kv_map,
                              local_window=local_window,
                              softmax_scale=softmax_scale)


def decode_attention(q, k_cache, v_cache, *, cur_pos, kv_map=None,
                     local_window: int = 0, softmax_scale=None,
                     pos_mask=None, impl: str = "jnp"):
    """Single-step attention against a cache.

    q: [B, Hq, D]; k_cache/v_cache: [B, S, Hkv, D]; cur_pos: scalar int —
    number of valid cache entries (new token's position is cur_pos) — or a
    [B] vector of per-request positions (continuous batching mixes lengths).
    kv_map: optional [Hq] map from q-head to kv-head (non-uniform GQA);
    default uses Hq = g*Hkv contiguous grouping.  ``pos_mask`` is the
    hoisted decode_pos_mask(cur_pos, S, local_window) (jnp path only).
    With impl="pallas" the dense cache is viewed as a contiguous page pool
    and the block-table decode kernel runs on it directly.
    """
    from ..kernels.ops import effective_attn_impl
    B, Hq, D = q.shape
    S, Hkv = k_cache.shape[1], k_cache.shape[2]
    Dv = v_cache.shape[-1]
    if effective_attn_impl(impl) == "pallas":
        bs = _decode_bs(S)
        nb = S // bs
        pool_k = k_cache.reshape(B * nb, bs, Hkv, D)
        pool_v = v_cache.reshape(B * nb, bs, Hkv, Dv)
        table = jnp.arange(B * nb, dtype=jnp.int32).reshape(B, nb)
        pos = (jnp.broadcast_to(cur_pos, (B,)) if jnp.ndim(cur_pos) == 0
               else cur_pos)
        return _paged_kernel(q, pool_k, pool_v, table, pos, kv_map,
                             local_window=local_window,
                             softmax_scale=softmax_scale)
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(D)
    if kv_map is not None:
        kc = jnp.take(k_cache, kv_map, axis=2)           # [B, S, Hq, D]
        vc = jnp.take(v_cache, kv_map, axis=2)
        s = jnp.einsum("bhd,bshd->bhs", q, kc,
                       preferred_element_type=jnp.float32) * scale
    else:
        g = Hq // Hkv
        qg = q.reshape(B, Hkv, g, D)
        s = jnp.einsum("bhgd,bshd->bhgs", qg, k_cache,
                       preferred_element_type=jnp.float32) * scale
        s = s.reshape(B, Hq, S)
        vc = None
    if pos_mask is None:
        pos_mask = decode_pos_mask(cur_pos, S, local_window)
    s = jnp.where(pos_mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    if kv_map is not None:
        out = jnp.einsum("bhs,bshd->bhd", p.astype(vc.dtype), vc,
                         preferred_element_type=jnp.float32)
    else:
        g = Hq // Hkv
        pg = p.reshape(B, Hkv, g, S)
        out = jnp.einsum("bhgs,bshd->bhgd", pg.astype(v_cache.dtype), v_cache,
                         preferred_element_type=jnp.float32)
        out = out.reshape(B, Hq, Dv)
    return out.astype(q.dtype)


def cache_update(cache, new_k, new_v, cur_pos):
    """Write one step's K/V into the cache at cur_pos. new_k: [B, 1, Hkv, D]."""
    k = lax.dynamic_update_slice_in_dim(cache["k"], new_k.astype(cache["k"].dtype),
                                        cur_pos, axis=1)
    v = lax.dynamic_update_slice_in_dim(cache["v"], new_v.astype(cache["v"].dtype),
                                        cur_pos, axis=1)
    return dict(cache, k=k, v=v)


# ---------------------------------------------------------------------------
# Paged KV cache primitives (serve/ continuous batching; DESIGN.md §7).
#
# A layer's pool is [P_loc, bs, Hkv, D]: P_loc physical blocks of bs
# positions each.  A block table [B, nb] maps request b's logical block i
# (positions i*bs .. i*bs+bs-1) to a physical block id; ids here are LOCAL
# (the step builder subtracts the device group's offset).  Retired/inactive
# batch slots point every table entry at the group's scratch block and are
# masked by their length, so the math stays fixed-shape across steps.
# ---------------------------------------------------------------------------

def paged_gather(pool_k, pool_v, table, kv_map=None):
    """Gather a request-major contiguous KV view from the block pool.

    pool_k/pool_v: [P_loc, bs, Hkv, D]; table: [B, nb] local block ids.
    Returns k, v: [B, nb*bs, Hkv, D] in logical position order.

    ``kv_map`` ([Hq] q-head -> kv-head) folds the GQA head expansion into
    the SAME gather (one [B, pool, Hq, D] materialization) instead of the
    old gather-then-take chain that built [B, pool, Hkv, D] first and a
    second [B, pool, Hq, D] on top of it.
    """
    B, nb = table.shape
    bs = pool_k.shape[1]
    idx = table.reshape(-1)
    if kv_map is None:
        k = jnp.take(pool_k, idx, axis=0)
        v = jnp.take(pool_v, idx, axis=0)
        sh = (B, nb * bs) + pool_k.shape[2:]
        return k.reshape(sh), v.reshape(sh)
    Hq = kv_map.shape[0]
    # one combined (page, head) gather: [B*nb, Hq, bs, D] -> [B, pool, Hq, D]
    k = pool_k[idx[:, None], :, kv_map[None, :], :]
    v = pool_v[idx[:, None], :, kv_map[None, :], :]
    sh = (B, nb * bs, Hq, pool_k.shape[-1])
    return (k.swapaxes(1, 2).reshape(sh),
            v.swapaxes(1, 2).reshape((sh[:3]) + (pool_v.shape[-1],)))


def paged_step_indices(table, pos, bs: int):
    """(blk, off) scatter coordinates of each request's current position.

    Position-only, so the serve step computes them ONCE and reuses them for
    every layer's paged_update inside the scan instead of re-deriving the
    take_along_axis per layer."""
    blk = jnp.take_along_axis(table, (pos // bs)[:, None], axis=1)[:, 0]
    return blk, pos % bs


def paged_update(pool, table, pos, new_k, new_v, idx=None):
    """Scatter one step's K/V into the pool at each request's position.

    pool: {"k","v": [P_loc, bs, Hkv, D]}; table: [B, nb]; pos: [B] target
    position (count of already-cached tokens); new_k/new_v: [B, 1, Hkv, D].
    ``idx`` is the hoisted paged_step_indices(table, pos, bs).
    """
    bs = pool["k"].shape[1]
    blk, off = idx if idx is not None else paged_step_indices(table, pos, bs)
    k = pool["k"].at[blk, off].set(new_k[:, 0].astype(pool["k"].dtype))
    v = pool["v"].at[blk, off].set(new_v[:, 0].astype(pool["v"].dtype))
    return dict(pool, k=k, v=v)


def paged_attention(q, pool_k, pool_v, table, pos, *, kv_map=None,
                    local_window: int = 0, softmax_scale=None,
                    pos_mask=None, impl: str = "jnp"):
    """Single-step attention against a paged pool.

    q: [B, Hq, D]; pos: [B] per-request current position (the incoming
    token's position; its K/V must already be in the pool — call
    paged_update first, matching the dense cache_update-then-attend order).

    impl="pallas" walks the block table inside the decode kernel — no
    paged_gather materialization at all (kernels/paged_attention.py).  The
    jnp fallback gathers once (kv_map folded in) and reuses the hoisted
    ``pos_mask`` ([B, 1, nb*bs]) across the layer scan.
    """
    from ..kernels.ops import effective_attn_impl
    if effective_attn_impl(impl) == "pallas":
        return _paged_kernel(q, pool_k, pool_v, table, pos, kv_map,
                             local_window=local_window,
                             softmax_scale=softmax_scale)
    k, v = paged_gather(pool_k, pool_v, table, kv_map)
    return decode_attention(q, k, v, cur_pos=pos, kv_map=None,
                            local_window=local_window,
                            softmax_scale=softmax_scale, pos_mask=pos_mask)


# ---------------------------------------------------------------------------
# Chunked-prefill primitives (serve/ prefix cache + chunk interleave;
# DESIGN.md §12).  A chunk step processes C consecutive prompt positions per
# batch slot against the same paged pool the decode step uses; per-slot
# chunk starts differ, so positions/masks carry a [B, C] batch axis that
# the train-path blockwise attention (one shared q_pos vector) cannot
# express.
# ---------------------------------------------------------------------------

def chunk_pos_mask(positions, S: int, local_window: int = 0):
    """[B, C, S] causal validity mask for a prefill chunk.

    positions: [B, C] absolute q positions (garbage in padded rows is fine:
    the attend below keeps every row finite and callers only read rows
    inside their chunk length).  Position-only — hoisted out of the layer
    scan like decode_pos_mask."""
    kv = jnp.arange(S)
    mask = kv[None, None, :] <= positions[:, :, None]
    if local_window > 0:
        mask &= kv[None, None, :] > (positions[:, :, None] - local_window)
    return mask


def paged_chunk_indices(table, positions, bs: int, valid):
    """(blk, off) [B, C] scatter coordinates for a whole prefill chunk.

    Padded entries (``valid`` False) are redirected to the group's scratch
    block (local id 0) at offset 0 — garbage writes land where they are
    masked by construction.  Position-only; hoisted out of the layer scan."""
    nb = table.shape[1]
    blk_i = jnp.clip(positions // bs, 0, nb - 1)
    blk = jnp.take_along_axis(table, blk_i, axis=1)
    blk = jnp.where(valid, blk, 0)
    off = jnp.where(valid, positions % bs, 0)
    return blk, off


def paged_update_chunk(pool, table, positions, new_k, new_v, valid,
                       idx=None):
    """Scatter a C-position chunk of K/V into the pool.

    pool: {"k","v": [P_loc, bs, Hkv, D]}; positions: [B, C] absolute;
    new_k/new_v: [B, C, Hkv, D]; valid: [B, C].  ``idx`` is the hoisted
    paged_chunk_indices.  Invalid entries write garbage into the scratch
    block (masked by contract), exactly like retired decode slots."""
    bs = pool["k"].shape[1]
    blk, off = (idx if idx is not None
                else paged_chunk_indices(table, positions, bs, valid))
    k = pool["k"].at[blk, off].set(new_k.astype(pool["k"].dtype))
    v = pool["v"].at[blk, off].set(new_v.astype(pool["v"].dtype))
    return dict(pool, k=k, v=v)


def chunk_attention(q, k_cache, v_cache, *, mask, softmax_scale=None,
                    kv_map=None):
    """Causal attention of a C-token chunk against a contiguous KV view.

    q: [B, C, Hq, D]; k_cache/v_cache: [B, S, H, D] (H == Hq when the GQA
    map was folded into the gather, else Hkv with contiguous grouping);
    mask: [B, C, S] from chunk_pos_mask.  Full-score fp32 masked softmax —
    no online accumulation — so the result is independent of how the
    prompt was split into chunks (chunked == monolithic prefill
    numerics-for-numerics, which the greedy parity checks lean on).
    Fully-masked rows (padding) come out zero, not NaN."""
    B, C, Hq, D = q.shape
    S, H = k_cache.shape[1], k_cache.shape[2]
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(D)
    if kv_map is not None:
        k_cache = jnp.take(k_cache, kv_map, axis=2)
        v_cache = jnp.take(v_cache, kv_map, axis=2)
        H = Hq
    row_has = jnp.any(mask, axis=-1)[:, None, :, None]   # [B, 1, C, 1]
    if H == Hq:
        s = jnp.einsum("bchd,bshd->bhcs", q, k_cache,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(mask[:, None], s, -jnp.inf)
        s = jnp.where(row_has, s, 0.0)
        p = jax.nn.softmax(s, axis=-1)
        p = jnp.where(row_has, p, 0.0)
        out = jnp.einsum("bhcs,bshd->bhcd", p.astype(v_cache.dtype), v_cache,
                         preferred_element_type=jnp.float32)
        out = out.transpose(0, 2, 1, 3)                  # [B, C, Hq, Dv]
    else:
        g = Hq // H
        qg = q.reshape(B, C, H, g, D)
        s = jnp.einsum("bchgd,bshd->bhgcs", qg, k_cache,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(mask[:, None, None], s, -jnp.inf)
        rh = row_has[:, :, None]                         # [B, 1, 1, C, 1]
        s = jnp.where(rh, s, 0.0)
        p = jax.nn.softmax(s, axis=-1)
        p = jnp.where(rh, p, 0.0)
        out = jnp.einsum("bhgcs,bshd->bhgcd", p.astype(v_cache.dtype),
                         v_cache, preferred_element_type=jnp.float32)
        out = out.transpose(0, 3, 1, 2, 4).reshape(
            B, C, Hq, v_cache.shape[-1])
    return out.astype(q.dtype)
