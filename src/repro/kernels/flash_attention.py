"""Pallas TPU kernels: causal flash attention, forward AND backward.

Forward grid: (B, Hq, Tq/bq, Tk/bk).  The KV walk is the innermost grid
dimension so K/V stream through VMEM one (bk, D) block at a time (TPU
executes trailing grid dims sequentially, so the (m, l, acc) VMEM scratch
carries across the walk) — the classic flash schedule on the Pallas
pipeline, instead of the v1 kernel's whole-[Tk, D] BlockSpec.

Causal / sliding-window block skipping: the K/V index maps clamp the block
index into [lo(i), hi(i)) — out-of-range steps re-request the same block
(the pipeline skips the DMA when the index repeats) and `pl.when` masks
their compute, so the upper triangle costs neither flops nor HBM traffic.
The bounds need the q-row offset statically (``q_start``); seq-sharded
prefill passes traced positions instead and falls back to the full walk
with in-kernel masking.

Backward is the standard two-pass flash bwd (out, logsumexp residuals):

    dQ pass : grid (B, Hq, nq, nk)   — same walk/skipping as forward
    dKV pass: grid (B, Hkv, nk, g, nq) — per KV block, walk the g query
              heads of its GQA group and the (skip-bounded) q blocks,
              accumulating dK/dV in VMEM scratch

GQA: q-head h reads KV head h // g through the K/V index maps — grouped
heads never materialize expanded K/V.  Non-tile-divisible Tq/Tk are
zero-padded and masked (cols >= Tk are dead), so any shape runs.
Fully-masked rows (e.g. a ``local_window`` that excludes every key)
produce EXACT zero output rows, matching models/common.blockwise_attention.

MXU operands (``_mxu_dot``): q, k, v and dO enter every product in the
input dtype, accumulated in f32 — a bf16 x bf16 product is exact in f32,
so S = QK^T and dP = dO V^T lose nothing to the native bf16 MXU path.
P and dS are f32; against bf16 V, K, dO or Q they go as a bf16 hi/lo
pair (two bf16 products, ~16 mantissa bits of P/dS kept) rather than as
an f32 operand the MXU takes in several passes.  f32 inputs keep one f32
product each.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.collectives import unify_vma, vma_of


DEFAULT_BQ = 256
DEFAULT_BK = 256
NEG_INF = -1e30
# floor for the streaming max: exp(NEG_INF - _M_FLOOR) == 0 exactly, so a
# fully-masked block/row contributes nothing (and l stays 0 -> zero output)
_M_FLOOR = -1e25


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _msafe(m):
    return jnp.maximum(m, _M_FLOOR)


class FlashCfg(NamedTuple):
    """Static kernel configuration (hashable: rides custom_vjp nondiff)."""
    causal: bool
    window: int            # 0 = unbounded
    scale: float
    g: int                 # q heads per kv head (contiguous GQA)
    bq: int
    bk: int
    nq: int
    nk: int
    q_start: Optional[int]  # static q-row offset; None -> no block skipping
    tk_real: int           # unpadded Tk (cols >= tk_real are masked dead)
    interpret: bool


# ---------------------------------------------------------------------------
# block-skip bounds (shared by the index maps and the kernel predicates)
# ---------------------------------------------------------------------------

def _kv_bounds(cfg: FlashCfg, i):
    """[lo, hi) KV-block range for q block i (jnp scalars)."""
    lo, hi = 0, cfg.nk
    if cfg.q_start is not None and cfg.causal:
        last_q = cfg.q_start + (i + 1) * cfg.bq - 1
        hi = jnp.minimum(last_q // cfg.bk + 1, cfg.nk)
        hi = jnp.maximum(hi, 1)
    if cfg.q_start is not None and cfg.window > 0:
        first_q = cfg.q_start + i * cfg.bq
        lo = jnp.maximum((first_q - cfg.window + 1) // cfg.bk, 0)
        lo = jnp.minimum(lo, hi - 1)
    return lo, hi


def _kv_index(cfg: FlashCfg, i, j):
    lo, hi = _kv_bounds(cfg, i)
    return jnp.minimum(lo + j, hi - 1)


def _q_bounds(cfg: FlashCfg, kb):
    """[lo, hi) q-block range that touches KV block kb (dKV pass)."""
    lo, hi = 0, cfg.nq
    if cfg.q_start is not None and cfg.causal:
        first_kv = kb * cfg.bk
        lo = jnp.maximum((first_kv - cfg.q_start) // cfg.bq, 0)
        lo = jnp.minimum(lo, cfg.nq - 1)
    if cfg.q_start is not None and cfg.window > 0:
        last_kv = kb * cfg.bk + cfg.bk - 1
        hi = jnp.minimum((last_kv + cfg.window - 1 - cfg.q_start) // cfg.bq
                         + 1, cfg.nq)
        hi = jnp.maximum(hi, lo + 1)
    return lo, hi


def _q_index(cfg: FlashCfg, kb, qi):
    lo, hi = _q_bounds(cfg, kb)
    return jnp.minimum(lo + qi, hi - 1)


def _block_mask(cfg: FlashCfg, rows, jj):
    """(bq, bk) validity mask for KV block jj given the (bq, 1) q-row
    positions."""
    cols = jj * cfg.bk + lax.broadcasted_iota(jnp.int32, (cfg.bq, cfg.bk), 1)
    mask = cols < cfg.tk_real
    if cfg.causal:
        mask &= rows >= cols
    if cfg.window > 0:
        mask &= cols > rows - cfg.window
    return mask


def _mxu_dot(a, b, dims):
    """``dot_general(a, b, dims)`` accumulated in f32, without upcasting
    ``b`` (a block read in the input dtype).  An f32 ``a`` (P, dS) against
    a narrower ``b`` goes as a hi/lo pair in b's dtype."""
    dot = functools.partial(lax.dot_general, dimension_numbers=dims,
                            preferred_element_type=jnp.float32)
    if a.dtype == jnp.float32 and jnp.finfo(b.dtype).bits < 32:
        hi = a.astype(b.dtype)
        lo = (a - hi.astype(jnp.float32)).astype(b.dtype)
        return dot(hi, b) + dot(lo, b)
    return dot(a, b)


_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_NN = (((1,), (0,)), ((), ()))      # a @ b
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


def _rowmap(b, h, i, *_):
    """Index map of a lane-dense [B, H, 1, T] row-statistics block."""
    return (b, h, 0, i)


def _lane_rows(x):
    """[B, H, T] row statistics -> the kernels' lane-dense [B, H, 1, T]."""
    return x.reshape(x.shape[0], x.shape[1], 1, x.shape[2])


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(qpos_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_ref, l_ref, acc_ref, *, cfg: FlashCfg):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    lo, hi = _kv_bounds(cfg, i)
    jj = jnp.minimum(lo + j, hi - 1)

    @pl.when(lo + j < hi)
    def _step():
        s = _mxu_dot(q_ref[0, 0], k_ref[0, 0], _NT) * cfg.scale
        s = jnp.where(_block_mask(cfg, qpos_ref[...].T, jj), s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - _msafe(m_new))                    # masked entries -> 0
        corr = jnp.exp(_msafe(m_prev) - _msafe(m_new))
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + _mxu_dot(p, v_ref[0, 0], _NN)
        m_ref[...] = m_new

    @pl.when(j == cfg.nk - 1)
    def _done():
        l = l_ref[...]
        ls = jnp.where(l == 0.0, 1.0, l)                 # masked row -> 0 out
        o_ref[0, 0] = (acc_ref[...] / ls).astype(o_ref.dtype)
        # row statistics leave the kernel lane-dense: (bq, 1) -> (1, bq)
        lse_ref[0, 0] = (_msafe(m_ref[...]) + jnp.log(ls)).T


def _fwd_call(cfg: FlashCfg, q, k, v, q_pos):
    """-> (out [B, Hq, Tq, Dv], lse [B, Hq, Tq])."""
    B, Hq, Tq, D = q.shape
    Dv = v.shape[-1]
    vma = vma_of(q)
    grid = (B, Hq, cfg.nq, cfg.nk)
    qmap = lambda b, h, i, j: (b, h, i, 0)
    kvmap = lambda b, h, i, j: (b, h // cfg.g, _kv_index(cfg, i, j), 0)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, cfg=cfg),
        name="flash_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, cfg.bq), lambda b, h, i, j: (0, i)),
            pl.BlockSpec((1, 1, cfg.bq, D), qmap),
            pl.BlockSpec((1, 1, cfg.bk, D), kvmap),
            pl.BlockSpec((1, 1, cfg.bk, Dv), kvmap),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, cfg.bq, Dv), qmap),
            pl.BlockSpec((1, 1, 1, cfg.bq), _rowmap),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, Tq, Dv), q.dtype, vma=vma),
            jax.ShapeDtypeStruct((B, Hq, 1, Tq), jnp.float32, vma=vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((cfg.bq, 1), jnp.float32),
            pltpu.VMEM((cfg.bq, 1), jnp.float32),
            pltpu.VMEM((cfg.bq, Dv), jnp.float32),
        ],
        interpret=cfg.interpret,
    )(q_pos, q, k, v)
    return out, lse.reshape(B, Hq, Tq)


# ---------------------------------------------------------------------------
# backward: dQ pass (same walk as forward)
# ---------------------------------------------------------------------------

def _dq_kernel(qpos_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, acc_ref, *, cfg: FlashCfg):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    lo, hi = _kv_bounds(cfg, i)
    jj = jnp.minimum(lo + j, hi - 1)

    @pl.when(lo + j < hi)
    def _step():
        k, do = k_ref[0, 0], do_ref[0, 0]
        s = _mxu_dot(q_ref[0, 0], k, _NT) * cfg.scale
        s = jnp.where(_block_mask(cfg, qpos_ref[...].T, jj), s, NEG_INF)
        p = jnp.exp(s - lse_ref[0, 0].T)                 # normalized probs
        dp = _mxu_dot(do, v_ref[0, 0], _NT)
        ds = p * (dp - delta_ref[0, 0].T)
        acc_ref[...] += _mxu_dot(ds, k, _NN) * cfg.scale

    @pl.when(j == cfg.nk - 1)
    def _done():
        dq_ref[0, 0] = acc_ref[...].astype(dq_ref.dtype)


# ---------------------------------------------------------------------------
# backward: dK/dV pass (grid walks KV blocks; inner dims cover the GQA
# group's q heads and the skip-bounded q blocks)
# ---------------------------------------------------------------------------

def _dkv_kernel(qpos_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, cfg: FlashCfg):
    kb = pl.program_id(2)
    gi, qi = pl.program_id(3), pl.program_id(4)

    @pl.when((gi == 0) & (qi == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    lo, hi = _q_bounds(cfg, kb)
    qq = jnp.minimum(lo + qi, hi - 1)

    @pl.when(lo + qi < hi)
    def _step():
        q, do = q_ref[0, 0], do_ref[0, 0]
        s = _mxu_dot(q, k_ref[0, 0], _NT) * cfg.scale
        s = jnp.where(_block_mask(cfg, qpos_ref[...].T, kb), s, NEG_INF)
        p = jnp.exp(s - lse_ref[0, 0].T)
        dv_acc[...] += _mxu_dot(p, do, _TN)
        dp = _mxu_dot(do, v_ref[0, 0], _NT)
        ds = p * (dp - delta_ref[0, 0].T)
        dk_acc[...] += _mxu_dot(ds, q, _TN) * cfg.scale

    @pl.when((gi == cfg.g - 1) & (qi == cfg.nq - 1))
    def _done():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _dq_call(cfg: FlashCfg, q, k, v, q_pos, lse, delta, dout):
    B, Hq, Tq, D = q.shape
    Dv = v.shape[-1]
    qmap = lambda b, h, i, j: (b, h, i, 0)
    kvmap = lambda b, h, i, j: (b, h // cfg.g, _kv_index(cfg, i, j), 0)
    return pl.pallas_call(
        functools.partial(_dq_kernel, cfg=cfg),
        name="flash_dq",
        grid=(B, Hq, cfg.nq, cfg.nk),
        in_specs=[
            pl.BlockSpec((1, cfg.bq), lambda b, h, i, j: (0, i)),
            pl.BlockSpec((1, 1, cfg.bq, D), qmap),
            pl.BlockSpec((1, 1, cfg.bk, D), kvmap),
            pl.BlockSpec((1, 1, cfg.bk, Dv), kvmap),
            pl.BlockSpec((1, 1, cfg.bq, Dv), qmap),
            pl.BlockSpec((1, 1, 1, cfg.bq), _rowmap),
            pl.BlockSpec((1, 1, 1, cfg.bq), _rowmap),
        ],
        out_specs=pl.BlockSpec((1, 1, cfg.bq, D), qmap),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype, vma=vma_of(q)),
        scratch_shapes=[pltpu.VMEM((cfg.bq, D), jnp.float32)],
        interpret=cfg.interpret,
    )(q_pos, q, k, v, dout, _lane_rows(lse), _lane_rows(delta))


def _dkv_call(cfg: FlashCfg, q, k, v, q_pos, lse, delta, dout):
    B, Hq, Tq, D = q.shape
    Hkv, Tk = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    qmap2 = lambda b, h, kb, gi, qi: (b, h * cfg.g + gi, _q_index(cfg, kb, qi), 0)
    rowmap2 = lambda b, h, kb, gi, qi: (b, h * cfg.g + gi, 0,
                                        _q_index(cfg, kb, qi))
    kvmap2 = lambda b, h, kb, gi, qi: (b, h, kb, 0)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, cfg=cfg),
        name="flash_dkv",
        grid=(B, Hkv, cfg.nk, cfg.g, cfg.nq),
        in_specs=[
            pl.BlockSpec((1, cfg.bq),
                         lambda b, h, kb, gi, qi: (0, _q_index(cfg, kb, qi))),
            pl.BlockSpec((1, 1, cfg.bq, D), qmap2),
            pl.BlockSpec((1, 1, cfg.bk, D), kvmap2),
            pl.BlockSpec((1, 1, cfg.bk, Dv), kvmap2),
            pl.BlockSpec((1, 1, cfg.bq, Dv), qmap2),
            pl.BlockSpec((1, 1, 1, cfg.bq), rowmap2),
            pl.BlockSpec((1, 1, 1, cfg.bq), rowmap2),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, cfg.bk, D), kvmap2),
            pl.BlockSpec((1, 1, cfg.bk, Dv), kvmap2),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype, vma=vma_of(k)),
            jax.ShapeDtypeStruct(v.shape, v.dtype, vma=vma_of(v)),
        ],
        scratch_shapes=[
            pltpu.VMEM((cfg.bk, D), jnp.float32),
            pltpu.VMEM((cfg.bk, Dv), jnp.float32),
        ],
        interpret=cfg.interpret,
    )(q_pos, q, k, v, dout, _lane_rows(lse), _lane_rows(delta))
    return dk, dv


def _bwd_call(cfg: FlashCfg, q, k, v, q_pos, out, lse, dout):
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                              # [B, Hq, Tq]
    dq = _dq_call(cfg, q, k, v, q_pos, lse, delta, dout)
    dk, dv = _dkv_call(cfg, q, k, v, q_pos, lse, delta, dout)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp plumbing (operates on tile-padded operands)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash(cfg: FlashCfg, q, k, v, q_pos):
    out, _ = _fwd_call(cfg, q, k, v, q_pos)
    return out


def _flash_fwd(cfg, q, k, v, q_pos):
    out, lse = _fwd_call(cfg, q, k, v, q_pos)
    return out, (q, k, v, q_pos, out, lse)


def _flash_bwd(cfg, res, dout):
    q, k, v, q_pos, out, lse = res
    dq, dk, dv = _bwd_call(cfg, q, k, v, q_pos, out, lse, dout)
    return dq, dk, dv, np.zeros(q_pos.shape, jax.dtypes.float0)


_flash.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

def flash_attention(q, k, v, *, causal=True, local_window: int = 0,
                    q_pos=None, q_start: Optional[int] = 0,
                    softmax_scale=None, bq=None, bk=None, interpret=False):
    """Fused attention with flash fwd + two-pass bwd.

    q: [B, Hq, Tq, D]; k: [B, Hkv, Tk, D]; v: [B, Hkv, Tk, Dv] with
    Hq = g * Hkv (contiguous GQA groups) -> [B, Hq, Tq, Dv].

    ``q_pos`` ([Tq] int32 global positions, default q_start + arange) drives
    the causal / local_window masks; ``q_start`` is the STATIC row offset
    that enables block skipping — pass None when positions are traced
    (seq-sharded prefill) to fall back to the full masked walk.  KV rows are
    assumed at positions 0..Tk-1.  Non-divisible Tq/Tk are padded+masked.

    The tile lookup runs OUTSIDE the jitted core (which keys on the
    resolved bq/bk), so a later autotune sweep takes effect on the next
    call instead of being pinned by an old trace.
    """
    Tq, Tk, D = q.shape[2], k.shape[2], q.shape[3]
    if bq is None or bk is None:
        from .autotune import flash_tiles
        tq_, tk_ = flash_tiles(Tq, Tk, D, causal=causal)
        bq = bq or tq_
        bk = bk or tk_
    return _flash_jit(q, k, v, q_pos, causal=causal,
                      local_window=local_window, q_start=q_start,
                      softmax_scale=softmax_scale, bq=min(bq, Tq),
                      bk=min(bk, Tk), interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "causal", "local_window", "q_start", "softmax_scale", "bq", "bk",
    "interpret"))
def _flash_jit(q, k, v, q_pos, *, causal, local_window, q_start,
               softmax_scale, bq, bk, interpret):
    B, Hq, Tq, D = q.shape
    Hkv, Tk = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    if Hq % Hkv:
        raise ValueError(f"flash_attention: Hq={Hq} not a multiple of "
                         f"Hkv={Hkv}")
    scale = (softmax_scale if softmax_scale is not None
             else 1.0 / math.sqrt(D))
    Tqp, Tkp = _round_up(Tq, bq), _round_up(Tk, bk)
    if q_pos is None:
        q_pos = (q_start or 0) + jnp.arange(Tqp, dtype=jnp.int32)
    else:
        q_pos = q_pos.astype(jnp.int32)
        if Tqp != Tq:
            # padded rows continue the position sequence (outputs discarded;
            # monotone positions keep the skip bounds consistent)
            q_pos = jnp.concatenate(
                [q_pos, q_pos[-1] + 1 + jnp.arange(Tqp - Tq, dtype=jnp.int32)])
    pad4 = lambda x, t: (x if x.shape[2] == t else
                         jnp.pad(x, ((0, 0), (0, 0), (0, t - x.shape[2]),
                                     (0, 0))))
    qp = pad4(q, Tqp)
    kp, vp = pad4(k, Tkp), pad4(v, Tkp)
    cfg = FlashCfg(causal=bool(causal), window=int(local_window),
                   scale=float(scale), g=Hq // Hkv, bq=bq, bk=bk,
                   nq=Tqp // bq, nk=Tkp // bk,
                   q_start=(None if q_start is None else int(q_start)),
                   tk_real=Tk, interpret=bool(interpret))
    # one vma for every operand, outside the custom_vjp: each cotangent
    # then matches its primal and autodiff reduces what the pvary added
    qp, kp, vp, qpos = unify_vma(qp, kp, vp, q_pos[None])
    out = _flash(cfg, qp, kp, vp, qpos)
    return out[:, :, :Tq] if Tqp != Tq else out


# ---------------------------------------------------------------------------
# per-ring-step entries (core/ring_attention.py)
#
# One ring step is one flash call on the resident Q shard against one K/V
# shard.  The fwd step exposes the (out, logsumexp) pair — the online-softmax
# carry the ring merges across steps — and defines NO vjp: ring_attention is
# itself a custom_vjp that re-streams K/V and drives these bwd entries with
# the GLOBAL (merged) lse/delta, which is exactly the flash bwd math for a
# partitioned softmax.
# ---------------------------------------------------------------------------

def _step_cfg_pad(q, k, v, q_pos, *, causal, local_window, q_start,
                  softmax_scale, bq, bk, interpret):
    B, Hq, Tq, D = q.shape
    Hkv, Tk = k.shape[1], k.shape[2]
    if Hq % Hkv:
        raise ValueError(f"flash step: Hq={Hq} not a multiple of Hkv={Hkv}")
    scale = (softmax_scale if softmax_scale is not None
             else 1.0 / math.sqrt(D))
    Tqp, Tkp = _round_up(Tq, bq), _round_up(Tk, bk)
    if q_pos is None:
        q_pos = (q_start or 0) + jnp.arange(Tqp, dtype=jnp.int32)
    else:
        q_pos = q_pos.astype(jnp.int32)
        if Tqp != Tq:
            q_pos = jnp.concatenate(
                [q_pos, q_pos[-1] + 1 + jnp.arange(Tqp - Tq, dtype=jnp.int32)])
    pad4 = lambda x, t: (x if x.shape[2] == t else
                         jnp.pad(x, ((0, 0), (0, 0), (0, t - x.shape[2]),
                                     (0, 0))))
    cfg = FlashCfg(causal=bool(causal), window=int(local_window),
                   scale=float(scale), g=Hq // Hkv, bq=bq, bk=bk,
                   nq=Tqp // bq, nk=Tkp // bk,
                   q_start=(None if q_start is None else int(q_start)),
                   tk_real=Tk, interpret=bool(interpret))
    return (cfg, *unify_vma(pad4(q, Tqp), pad4(k, Tkp), pad4(v, Tkp),
                            q_pos[None]), Tq, Tqp)


def _pad_rows(x, t):
    """Zero-pad dim 2 of [B, H, T] / [B, H, T, D] to t rows."""
    if x.shape[2] == t:
        return x
    pad = [(0, 0)] * x.ndim
    pad[2] = (0, t - x.shape[2])
    return jnp.pad(x, pad)


def _resolve_step_tiles(Tq, Tk, D, causal, bq, bk):
    if bq is None or bk is None:
        from .autotune import flash_tiles
        tq_, tk_ = flash_tiles(Tq, Tk, D, causal=causal)
        bq = bq or tq_
        bk = bk or tk_
    return min(bq, Tq), min(bk, Tk)


def flash_fwd_step(q, k, v, *, causal=True, local_window: int = 0,
                   q_pos=None, q_start: Optional[int] = None,
                   softmax_scale=None, bq=None, bk=None, interpret=False):
    """Flash forward on one K/V shard -> (out [B,Hq,Tq,Dv], lse [B,Hq,Tq]).

    ``out`` is already normalized by this shard's partial softmax sum;
    fully-masked rows produce exact-zero out and a finite (floored) lse, so
    the caller's pairwise logsumexp merge is NaN-free.  No vjp is attached.
    """
    bq, bk = _resolve_step_tiles(q.shape[2], k.shape[2], q.shape[3],
                                 causal, bq, bk)
    return _fwd_step_jit(q, k, v, q_pos, causal=causal,
                         local_window=local_window, q_start=q_start,
                         softmax_scale=softmax_scale, bq=bq, bk=bk,
                         interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "causal", "local_window", "q_start", "softmax_scale", "bq", "bk",
    "interpret"))
def _fwd_step_jit(q, k, v, q_pos, *, causal, local_window, q_start,
                  softmax_scale, bq, bk, interpret):
    cfg, qp, kp, vp, qpos, Tq, Tqp = _step_cfg_pad(
        q, k, v, q_pos, causal=causal, local_window=local_window,
        q_start=q_start, softmax_scale=softmax_scale, bq=bq, bk=bk,
        interpret=interpret)
    out, lse = _fwd_call(cfg, qp, kp, vp, qpos)
    if Tqp != Tq:
        out, lse = out[:, :, :Tq], lse[:, :, :Tq]
    return out, lse


def flash_dq_step(q, k, v, dout, lse, delta, *, causal=True,
                  local_window: int = 0, q_pos=None,
                  q_start: Optional[int] = None, softmax_scale=None,
                  bq=None, bk=None, interpret=False):
    """dQ contribution of one K/V shard given the GLOBAL lse/delta."""
    bq, bk = _resolve_step_tiles(q.shape[2], k.shape[2], q.shape[3],
                                 causal, bq, bk)
    return _dq_step_jit(q, k, v, dout, lse, delta, q_pos, causal=causal,
                        local_window=local_window, q_start=q_start,
                        softmax_scale=softmax_scale, bq=bq, bk=bk,
                        interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "causal", "local_window", "q_start", "softmax_scale", "bq", "bk",
    "interpret"))
def _dq_step_jit(q, k, v, dout, lse, delta, q_pos, *, causal, local_window,
                 q_start, softmax_scale, bq, bk, interpret):
    cfg, qp, kp, vp, qpos, Tq, Tqp = _step_cfg_pad(
        q, k, v, q_pos, causal=causal, local_window=local_window,
        q_start=q_start, softmax_scale=softmax_scale, bq=bq, bk=bk,
        interpret=interpret)
    # padded q rows carry dout = delta = 0 -> ds = 0, so they contribute
    # nothing and the slice below discards their dq
    dq = _dq_call(cfg, qp, kp, vp, qpos, _pad_rows(lse, Tqp),
                  _pad_rows(delta, Tqp), _pad_rows(dout, Tqp))
    return dq[:, :, :Tq] if Tqp != Tq else dq


def flash_dkv_step(q, k, v, dout, lse, delta, *, causal=True,
                   local_window: int = 0, q_pos=None,
                   q_start: Optional[int] = None, softmax_scale=None,
                   bq=None, bk=None, interpret=False):
    """(dK, dV) contribution of one Q shard against the resident K/V."""
    bq, bk = _resolve_step_tiles(q.shape[2], k.shape[2], q.shape[3],
                                 causal, bq, bk)
    return _dkv_step_jit(q, k, v, dout, lse, delta, q_pos, causal=causal,
                         local_window=local_window, q_start=q_start,
                         softmax_scale=softmax_scale, bq=bq, bk=bk,
                         interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "causal", "local_window", "q_start", "softmax_scale", "bq", "bk",
    "interpret"))
def _dkv_step_jit(q, k, v, dout, lse, delta, q_pos, *, causal, local_window,
                  q_start, softmax_scale, bq, bk, interpret):
    cfg, qp, kp, vp, qpos, Tq, Tqp = _step_cfg_pad(
        q, k, v, q_pos, causal=causal, local_window=local_window,
        q_start=q_start, softmax_scale=softmax_scale, bq=bq, bk=bk,
        interpret=interpret)
    Tk = k.shape[2]
    dk, dv = _dkv_call(cfg, qp, kp, vp, qpos, _pad_rows(lse, Tqp),
                       _pad_rows(delta, Tqp), _pad_rows(dout, Tqp))
    if dk.shape[2] != Tk:
        dk, dv = dk[:, :, :Tk], dv[:, :, :Tk]
    return dk, dv
