"""Tesseract matrix multiplication (the paper's Algorithm 3 + Eq. 3), TPU-native.

Layout (inside ``jax.shard_map`` over the logical mesh, per-device views):

    activations A : [..., E_loc, F_loc]   E sharded over (data, depth, row),
                                          F sharded over col
    weights     W : [F_loc, G_loc]        F over row, G over col,
                                          replicated over (data, depth)
    output      C : [..., E_loc, G_loc]   same layout class as A

Two execution schedules implement the same math (DESIGN.md §2 / §2b,
selected by ``ParallelContext.matmul_schedule``):

``fused`` — the paper's q broadcasts of A along each row of the [q, q] grid
are fused into one ``all_gather`` over ``col``; the q broadcasts of W along
each column fuse into one ``all_gather`` over ``row``; the SUMMA
accumulation loop becomes a single local einsum over the gathered block
index t (identical bytes, one fused collective instead of q serialized
broadcasts).  Peak gathered-operand memory: O(q · block).

``ring`` — Cannon-style skewed double ring: after one skew ppermute per
operand, each of the q SUMMA steps contracts the resident (A, W) block pair
while ``lax.ppermute`` streams the next pair around the ``col`` / ``row``
rings (double buffering; on TPU the async collective-permute overlaps the
MXU).  The C accumulator stays in fp32 and only TWO blocks per operand are
ever resident — O(2 · block) peak.  The backward contractions ride the same
rings: dA and dW partials are accumulated with shift-and-add rings (the ring
form of reduce-scatter), so no q×-gathered operand materializes in bwd
either.

Backward follows the paper exactly:
    A' = C' W^T  : gather W over row, contract, reduce_scatter over col
    W' = A^T C'  : gather A over col, contract, reduce_scatter over row,
                   then all_reduce over depth ("processors with same row and
                   column but different depth") — optionally deferred to the
                   step-level gradient sync (perf lever).
"""
from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
from jax import lax

from ..models.common import TP_COMM
from .api import ParallelContext
from .collectives import all_gather_inv


def _maybe_f32(ctx: ParallelContext):
    return jnp.float32 if ctx.accum_fp32 else None


def effective_schedule(ctx: ParallelContext, e_loc: int) -> str:
    """Resolve ``matmul_schedule`` for one op from its local token-block rows.

    "auto" picks per-op: the ring schedule only pays when each of its q steps
    has enough arithmetic to hide a skew/shift (DESIGN.md §2b: q >= 4 and
    enough local rows); a decode step's token block (E_loc = a handful of
    requests) never does, so serve decode falls back to the fused gathers
    while train/prefill matmuls on the same ParallelContext ride the ring.
    Forward and backward resolve identically because E_loc is a static shape
    shared by A and dC.

    On a seq-sharded mesh (ctx.seq > 1) the local token block is already
    1/seq of the sequence AND the links are busy streaming ring-attention
    K/V, so the rows-per-ring-step threshold scales with seq: blocks that
    look decode-shaped only because the sequence was sharded stay on the
    fused schedule instead of regressing to a ring that can't hide its
    shifts (DESIGN.md §15).
    """
    s = ctx.matmul_schedule
    if s != "auto":
        return s
    return "ring" if ctx.q >= 4 and e_loc >= 2 * ctx.q * ctx.seq else "fused"


def _einsum(subs, *args, ctx: ParallelContext, out_dtype):
    acc = _maybe_f32(ctx)
    out = jnp.einsum(subs, *args, preferred_element_type=acc)
    return out.astype(out_dtype)


# --------------------------------------------------------------------------
# Exact per-op wire-byte model (repro.analysis.shardcheck rule d).
#
# benchmarks/comm_model.py models the *asymptotic* schedules for roofline
# curves; the functions below count the bytes this file's implementations
# actually move, collective by collective, under the same ring cost model as
# roofline/hlo.py (all_gather/psum_scatter over n devices move (n-1)/n of
# the gathered/scattered payload per device; ppermute moves the payload).
# shardcheck traces each schedule and requires byte-exact agreement, so any
# edit to the collective structure above must be mirrored here (that is the
# point: the model IS the reviewed comm contract).
# --------------------------------------------------------------------------

def matmul_comm_bytes(ctx: ParallelContext, e_loc: int, f_loc: int,
                      g_loc: int, *, batch: int = 1, train: bool = True,
                      itemsize: int = 4, schedule: str | None = None) -> dict:
    """Wire bytes per device for ONE ``tesseract_matmul`` call.

    ``e_loc``/``f_loc``/``g_loc`` are the LOCAL block dims of A ([batch,
    E_loc, F_loc]) and W ([F_loc, G_loc]); ``itemsize`` is the compute-dtype
    width.  Returns {"fwd", "bwd", "total"} (bwd = 0 when not train).
    """
    q = ctx.q
    sched = schedule or effective_schedule(ctx, e_loc)
    a = batch * e_loc * f_loc * itemsize          # local A block bytes
    w = f_loc * g_loc * itemsize                  # local W block bytes
    w_rs = f_loc * g_loc * (2 if ctx.dgrad_rs_bf16 else itemsize)
    if sched == "ring":
        fwd = 0 if q == 1 else q * (a + w)        # skew + (q-1) shifts each
        # pass 1: W stream (q shifts) + dA accumulator ring ((q-1) shifts +
        # final shift + unskew); pass 2: A stream + dW accumulator ring.
        bwd = 0 if q == 1 else (q * w + (q + 1) * a + q * a
                                + (q + 1) * w_rs)
    else:
        fwd = (q - 1) * (a + w)                   # fused gathers of A and W
        regather = ((0 if ctx.cache_act_gather else (q - 1) * a)
                    + (0 if ctx.cache_weight_gather else (q - 1) * w))
        # psum_scatter of the [q, ...] dA / dW partial stacks
        bwd = regather + (q - 1) * a + (q - 1) * w_rs
    if train and ctx.reduce_dgrad_in_op:
        ndd = ctx.data * ctx.depth * ctx.seq      # in-op dW all-reduce
        bwd += 2 * w_rs * (ndd - 1) / ndd if ndd > 1 else 0
    if not train:
        bwd = 0
    return {"fwd": float(fwd), "bwd": float(bwd), "total": float(fwd + bwd)}


def ring_vs_fused(ctx: ParallelContext, e_loc: int, f_loc: int, g_loc: int,
                  *, batch: int = 1, train: bool = True,
                  itemsize: int = 4) -> dict:
    """Implementation-exact {schedule: {"fwd","bwd","total"}} byte table for
    one matmul — the tight reference shardcheck diffs traced bytes against
    (benchmarks/comm_model.ring_vs_fused stays the asymptotic roofline)."""
    return {s: matmul_comm_bytes(ctx, e_loc, f_loc, g_loc, batch=batch,
                                 train=train, itemsize=itemsize, schedule=s)
            for s in ("ring", "fused")}


def _dgrad_axes(ctx):
    """Axes the in-op dW reduction must cover: data + depth, plus seq when
    the sequence axis is active (params are replicated over seq as well)."""
    if ctx.seq > 1:
        return (ctx.axis_data, ctx.axis_depth, ctx.axis_seq)
    return (ctx.axis_data, ctx.axis_depth)


# --------------------------------------------------------------------------
# Ring schedule machinery (matmul_schedule="ring", DESIGN.md §2b).
#
# Permutations over the [q, q] (row, col) grid.  ppermute over the axis
# tuple ("row", "col") takes linearized indices i*q + j (first axis major).
# The skews give device (i, j) the SUMMA block with feature index
# t = (i + j) % q so that after s synchronized ring shifts BOTH resident
# operands carry t = (i + j + s) % q — Cannon's initial alignment, which is
# what lets a uniform ppermute replace the paper's per-step broadcasts.
# --------------------------------------------------------------------------

@lru_cache(maxsize=None)
def _perm_shift(q):
    """Ring step: receive from the next device ((j+1) -> j)."""
    return tuple((j, (j - 1) % q) for j in range(q))


@lru_cache(maxsize=None)
def _perm_skew_a(q):
    """dst (i, j) <- src (i, (i+j) % q): row i rotates left by i."""
    return tuple((i * q + (i + j) % q, i * q + j)
                 for i in range(q) for j in range(q))


@lru_cache(maxsize=None)
def _perm_unskew_a(q):
    return tuple((i * q + j, i * q + (i + j) % q)
                 for i in range(q) for j in range(q))


@lru_cache(maxsize=None)
def _perm_skew_w(q):
    """dst (i, j) <- src ((i+j) % q, j): column j rotates up by j."""
    return tuple((((i + j) % q) * q + j, i * q + j)
                 for i in range(q) for j in range(q))


@lru_cache(maxsize=None)
def _perm_unskew_w(q):
    return tuple((i * q + j, ((i + j) % q) * q + j)
                 for i in range(q) for j in range(q))


def _rc(ctx):
    return (ctx.axis_row, ctx.axis_col)


# The collectives of every schedule run under ``jax.named_scope(TP_COMM)``,
# inside the scope of the layer that calls the matmul, so that a trace tells
# the SUMMA matmul's communication from the rest of its layer.
@jax.named_scope(TP_COMM)
def _skew_a(ctx, x):
    return x if ctx.q == 1 else lax.ppermute(x, _rc(ctx), _perm_skew_a(ctx.q))


@jax.named_scope(TP_COMM)
def _unskew_a(ctx, x):
    return x if ctx.q == 1 else lax.ppermute(x, _rc(ctx), _perm_unskew_a(ctx.q))


@jax.named_scope(TP_COMM)
def _skew_w(ctx, x):
    return x if ctx.q == 1 else lax.ppermute(x, _rc(ctx), _perm_skew_w(ctx.q))


@jax.named_scope(TP_COMM)
def _unskew_w(ctx, x):
    return x if ctx.q == 1 else lax.ppermute(x, _rc(ctx), _perm_unskew_w(ctx.q))


@jax.named_scope(TP_COMM)
def _shift_col(ctx, x):
    return x if ctx.q == 1 else lax.ppermute(x, ctx.axis_col, _perm_shift(ctx.q))


@jax.named_scope(TP_COMM)
def _shift_row(ctx, x):
    return x if ctx.q == 1 else lax.ppermute(x, ctx.axis_row, _perm_shift(ctx.q))


def _ring_fwd(ctx, a, w, subs_step):
    """C = sum_t A_t W_t via the skewed double ring; fp32 accumulator.

    Per step: launch the next-block ppermutes, contract the resident pair
    (XLA overlaps the async collective-permute with the einsum on TPU),
    accumulate.  Only two blocks per operand are live at any time."""
    q = ctx.q
    a_cur = _skew_a(ctx, a)
    w_cur = _skew_w(ctx, w)
    acc = None
    for s in range(q):
        a_nxt = _shift_col(ctx, a_cur) if s < q - 1 else None
        w_nxt = _shift_row(ctx, w_cur) if s < q - 1 else None
        part = jnp.einsum(subs_step, a_cur, w_cur,
                          preferred_element_type=_maybe_f32(ctx))
        acc = part if acc is None else acc + part
        a_cur, w_cur = a_nxt, w_nxt
    return acc.astype(a.dtype)


def _ring_bwd(ctx, a, w, dc, da_subs, dw_subs):
    """dA and dW on the same rings (transpose of _ring_fwd), TWO passes.

    The per-step cotangent pieces are pushed around shift-and-add
    accumulator rings — the ring form of the fused schedule's
    psum_scatters — so each device ends holding exactly its own dA / dW
    block and no [q, ...] partial stack is ever resident.  Running the dA
    pass (W stream) and the dW pass (A stream) sequentially keeps the peak
    at two live blocks per operand (stream + accumulator), vs. the fused
    backward's simultaneous re-gathered A and [q, ...] dA stack.  Final
    single-shift + unskew undo the Cannon alignment."""
    q = ctx.q
    rs_dtype = jnp.bfloat16 if ctx.dgrad_rs_bf16 else jnp.float32

    # pass 1 — dA: stream W around the row ring, dA pieces ride a col
    # accumulator ring.
    w_cur = _skew_w(ctx, w)
    b_da = None
    for s in range(q):
        w_nxt = _shift_row(ctx, w_cur) if s < q - 1 else None
        g = _einsum(da_subs, dc, w_cur, ctx=ctx, out_dtype=dc.dtype)
        b_da = g if b_da is None else _shift_col(ctx, b_da) + g
        w_cur = w_nxt
    da = _unskew_a(ctx, _shift_col(ctx, b_da))

    # pass 2 — dW: stream A around the col ring, dW pieces ride a row
    # accumulator ring.
    a_cur = _skew_a(ctx, a)
    b_dw = None
    for s in range(q):
        a_nxt = _shift_col(ctx, a_cur) if s < q - 1 else None
        h = _einsum(dw_subs, a_cur, dc, ctx=ctx, out_dtype=rs_dtype)
        b_dw = h if b_dw is None else _shift_row(ctx, b_dw) + h
        a_cur = a_nxt
    dw = _unskew_w(ctx, _shift_row(ctx, b_dw))
    return da, dw


# --------------------------------------------------------------------------
# Core: C = A @ W
# --------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(0,))
def tesseract_matmul(ctx: ParallelContext, a, w):
    """Distributed C = A @ W per Tesseract Algorithm 3 (local view)."""
    c, _ = _tess_fwd(ctx, a, w)
    return c


@jax.named_scope(TP_COMM)
def _gather_a(ctx, a):
    # A_{h,t} for all t: the q row-broadcasts of Algorithm 3, fused.
    return all_gather_inv(a, ctx.axis_col)          # [q, ..., E_loc, F_loc]


@jax.named_scope(TP_COMM)
def _gather_w(ctx, w):
    # W_{t,j} for all t: the q column-broadcasts of Algorithm 3, fused.
    return all_gather_inv(w, ctx.axis_row)          # [q, F_loc, G_loc]


@jax.named_scope(TP_COMM)
def _scatter(x, axis):
    # sum the [q, ...] partial stack over ``axis``, keep this device's block
    return lax.psum_scatter(x, axis, scatter_dimension=0, tiled=False)


@jax.named_scope(TP_COMM)
def _reduce_dgrad(ctx, dw):
    return lax.psum(dw, _dgrad_axes(ctx))


def _tess_fwd(ctx: ParallelContext, a, w):
    if effective_schedule(ctx, a.shape[-2]) == "ring":
        # Blocks stay resident; nothing gathered, nothing worth caching.
        return _ring_fwd(ctx, a, w, "...ef,fg->...eg"), (a, w)
    ag = _gather_a(ctx, a)
    wg = _gather_w(ctx, w)
    # C_{h,j} = sum_t A_{h,t} W_{t,j}
    c = _einsum("t...ef,tfg->...eg", ag, wg, ctx=ctx, out_dtype=a.dtype)
    res = (ag if ctx.cache_act_gather else a,
           wg if ctx.cache_weight_gather else w)
    return c, res


def _tess_bwd(ctx: ParallelContext, res, dc):
    ar, wr = res
    if effective_schedule(ctx, dc.shape[-2]) == "ring":
        da, dw = _ring_bwd(ctx, ar, wr, dc,
                           "...eg,fg->...ef", "...ef,...eg->fg")
    else:
        ag = ar if ctx.cache_act_gather else _gather_a(ctx, ar)
        wg = wr if ctx.cache_weight_gather else _gather_w(ctx, wr)
        # dA_{h,t} = sum_j dC_{h,j} W_{t,j}^T   (paper's C = A * B^T form)
        da_part = _einsum("...eg,tfg->t...ef", dc, wg, ctx=ctx,
                          out_dtype=dc.dtype)
        da = _scatter(da_part, ctx.axis_col)
        # dW_{t,j} = sum_h A_{h,t}^T dC_{h,j}   (paper's C = A^T * B form)
        rs_dtype = jnp.bfloat16 if ctx.dgrad_rs_bf16 else jnp.float32
        dw_part = _einsum("t...ef,...eg->tfg", ag, dc, ctx=ctx,
                          out_dtype=rs_dtype)
        dw = _scatter(dw_part, ctx.axis_row)
    if ctx.reduce_dgrad_in_op:
        # Paper-faithful per-op reduction: "all_reduce after the computation
        # of B' on processors with same row and column but different depth"
        # (+ the data axis when DP is fused in).  In deferred mode the same
        # reduction happens once per step at the pvary boundary instead.
        # Params are replicated over the seq axis too, so the in-op reduce
        # must cover it (in-op-reduced weights skip the step-level pvary).
        dw = _reduce_dgrad(ctx, dw)
    return da, dw.astype(wr.dtype)  # wr dtype == w dtype in both cache modes


tesseract_matmul.defvjp(_tess_fwd, _tess_bwd)


# --------------------------------------------------------------------------
# Expert-batched variant: C[n] = A[n] @ W[n] for n local experts (MoE).
# A: [N, T, F_loc], W: [N, F_loc, G_loc] — the expert dim N is already local
# (experts sharded over depth); row/col collectives are identical to the
# plain op.  Grad sync over (data,) happens at the grad_sync boundary (EP
# weights are only replicated over data), so no in-op reduction flag here.
# --------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(0,))
def tesseract_matmul_experts(ctx: ParallelContext, a, w):
    c, _ = _tess_exp_fwd(ctx, a, w)
    return c


def _tess_exp_fwd(ctx, a, w):
    if effective_schedule(ctx, a.shape[-2]) == "ring":
        return _ring_fwd(ctx, a, w, "nef,nfg->neg"), (a, w)
    ag = _gather_a(ctx, a)                    # [q, N, T, F_loc]
    wg = _gather_w(ctx, w)                    # [q, N, F_loc, G_loc]
    c = _einsum("tnef,tnfg->neg", ag, wg, ctx=ctx, out_dtype=a.dtype)
    res = (ag if ctx.cache_act_gather else a,
           wg if ctx.cache_weight_gather else w)
    return c, res


def _tess_exp_bwd(ctx, res, dc):
    ar, wr = res
    if effective_schedule(ctx, dc.shape[-2]) == "ring":
        da, dw = _ring_bwd(ctx, ar, wr, dc,
                           "neg,nfg->nef", "nef,neg->nfg")
        return da, dw.astype(wr.dtype)
    ag = ar if ctx.cache_act_gather else _gather_a(ctx, ar)
    wg = wr if ctx.cache_weight_gather else _gather_w(ctx, wr)
    da_part = _einsum("neg,tnfg->tnef", dc, wg, ctx=ctx, out_dtype=dc.dtype)
    da = _scatter(da_part, ctx.axis_col)
    rs_dtype = jnp.bfloat16 if ctx.dgrad_rs_bf16 else jnp.float32
    dw_part = _einsum("tnef,neg->tnfg", ag, dc, ctx=ctx, out_dtype=rs_dtype)
    dw = _scatter(dw_part, ctx.axis_row)
    return da, dw.astype(wr.dtype)


tesseract_matmul_experts.defvjp(_tess_exp_fwd, _tess_exp_bwd)


# --------------------------------------------------------------------------
# Transposed variant: C = A @ W^T (used by tied heads / down-projections that
# store weights in [out, in] layout).  W: [G_loc(row), F_loc(col)].
# --------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(0,))
def tesseract_matmul_wt(ctx: ParallelContext, a, w):
    c, _ = _tess_wt_fwd(ctx, a, w)
    return c


def _ring_wt_fwd(ctx, a, w):
    """C = A @ W^T on the ring: W streams around the row ring while the
    output blocks ride a col accumulator ring (the ring form of the fused
    schedule's psum_scatter).  The final unskew+shift undoes the Cannon
    alignment so each device ends with its own C block."""
    q = ctx.q
    w_cur = _skew_w(ctx, w)
    b = None
    for s in range(q):
        w_nxt = _shift_row(ctx, w_cur) if s < q - 1 else None
        part = _einsum("...ef,gf->...eg", a, w_cur, ctx=ctx, out_dtype=a.dtype)
        b = part if b is None else _shift_col(ctx, b) + part
        w_cur = w_nxt
    return _unskew_a(ctx, _shift_col(ctx, b))


def _ring_wt_bwd(ctx, a, w, dc):
    """dA accumulates locally off the synchronized (dC, W) streams; dW
    partials ride a row accumulator ring (ring reduce-scatter over row)."""
    q = ctx.q
    rs_dtype = jnp.bfloat16 if ctx.dgrad_rs_bf16 else jnp.float32
    # dC blocks live at their own col index (like A blocks): same skew.
    dc_cur = _skew_a(ctx, dc)
    w_cur = _skew_w(ctx, w)
    acc_da = None
    b_dw = None
    for s in range(q):
        dc_nxt = _shift_col(ctx, dc_cur) if s < q - 1 else None
        w_nxt = _shift_row(ctx, w_cur) if s < q - 1 else None
        part = jnp.einsum("...eg,gf->...ef", dc_cur, w_cur,
                          preferred_element_type=_maybe_f32(ctx))
        acc_da = part if acc_da is None else acc_da + part
        h = _einsum("...eg,...ef->gf", dc_cur, a, ctx=ctx, out_dtype=rs_dtype)
        b_dw = h if b_dw is None else _shift_row(ctx, b_dw) + h
        dc_cur, w_cur = dc_nxt, w_nxt
    da = acc_da.astype(dc.dtype)
    dw = _unskew_w(ctx, _shift_row(ctx, b_dw))
    return da, dw


def _tess_wt_fwd(ctx, a, w):
    if effective_schedule(ctx, a.shape[-2]) == "ring":
        return _ring_wt_fwd(ctx, a, w), (a, w)
    # C_{h,t} = sum_j A_{h,j} W_{t,j}^T : broadcast W within its column,
    # compute, then reduce partial C within the row (paper 3.1, C = A*B^T).
    wg = _gather_w(ctx, w)                          # [q(t), G_loc, F_loc]
    part = _einsum("...ef,tgf->t...eg", a, wg, ctx=ctx, out_dtype=a.dtype)
    c = _scatter(part, ctx.axis_col)
    res = (a, wg if ctx.cache_weight_gather else w)
    return c, res


def _tess_wt_bwd(ctx, res, dc):
    a, wr = res
    if effective_schedule(ctx, dc.shape[-2]) == "ring":
        da, dw = _ring_wt_bwd(ctx, a, wr, dc)
    else:
        wg = wr if ctx.cache_weight_gather else _gather_w(ctx, wr)
        dcg = _gather_a(ctx, dc)                    # [q(t), ..., E, G_loc]
        da = _einsum("t...eg,tgf->...ef", dcg, wg, ctx=ctx, out_dtype=dc.dtype)
        rs_dtype = jnp.bfloat16 if ctx.dgrad_rs_bf16 else jnp.float32
        dw_part = _einsum("t...eg,...ef->tgf", dcg, a, ctx=ctx,
                          out_dtype=rs_dtype)
        dw = _scatter(dw_part, ctx.axis_row)
    if ctx.reduce_dgrad_in_op:
        dw = _reduce_dgrad(ctx, dw)
    return da, dw.astype(wr.dtype)


tesseract_matmul_wt.defvjp(_tess_wt_fwd, _tess_wt_bwd)
