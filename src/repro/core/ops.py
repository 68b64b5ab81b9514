"""Operation sets: layout-aware primitives that models are written against.

A model never touches mesh axes directly; it calls methods on an ``OpSet``.
Each parallelization mode (the paper's Tesseract + the baselines it compares
against) implements the same interface:

    TesseractOps   — paper's 2.5-D scheme (covers summa2d via depth=1)
    MegatronOps    — 1-D baseline (column/row split + all-reduce)

Canonical activation layout (per-device local views inside shard_map):

    tesseract : [B_loc, S_loc, h/q]   tokens over (data, depth, row), h over col
    megatron  : [B_loc, S_loc, h]     tokens over (data) [seq over col if SP]

``Plan`` describes how the token dims are laid out for a given shape kind
(train / prefill / decode) — see DESIGN.md §4.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .api import ParallelContext
from . import collectives as col
from .summa import tesseract_matmul


@dataclass(frozen=True)
class Plan:
    kind: str = "train"          # train | prefill | decode
    seq_sharded: bool = False    # shard sequence (not batch) over (depth,row)

    @staticmethod
    def for_shape(kind: str, *, global_batch: int = 0, batch_shards: int = 1,
                  data: int = 1) -> "Plan":
        if kind == "train":
            return Plan("train", seq_sharded=False)
        if kind == "prefill":
            return Plan("prefill", seq_sharded=True)
        if kind in ("decode", "long_decode", "decode_dp"):
            if kind == "decode" and global_batch and global_batch < batch_shards:
                if data > 1 and global_batch >= data and global_batch % data == 0:
                    kind = "decode_dp"      # batch shards over data only
                else:
                    kind = "long_decode"    # batch too small to shard (b=1)
            return Plan(kind, seq_sharded=False)
        raise ValueError(kind)


def kv_group_axes(ctx: ParallelContext, plan: Plan) -> tuple:
    """Mesh axes sharding the decode-layout KV batch/pool dim for ``plan``.

    Devices sharing one coordinate along these axes form a *KV group*: a
    paged pool's block axis is sharded over them, and a batch slot's pages
    live entirely inside its group's shard (serve/kv_cache.py allocates
    from the co-located freelist, so cache reads never cross groups).
    """
    if plan.kind == "decode":
        return ctx.token_axes
    if plan.kind == "decode_dp":
        return (ctx.axis_data,)
    return ()                                 # long_decode: replicated pool


def _f32_einsum(subs, *args, out_dtype):
    return jnp.einsum(subs, *args, preferred_element_type=jnp.float32).astype(out_dtype)


# ===========================================================================
# Tesseract (2.5-D) op set — the paper's scheme
# ===========================================================================

class TesseractOps:
    mode_family = "tesseract"

    def __init__(self, ctx: ParallelContext, plan: Plan):
        self.ctx = ctx
        self.plan = plan

    # ---------------- specs (global param partitioning) ----------------
    def spec_w2d(self, stacked: bool = False):
        s = ("row", "col")
        return P(*((None,) + s if stacked else s))

    def spec_vec(self, stacked: bool = False):
        # bias / norm scale: sharded over col, replicated elsewhere
        return P(None, "col") if stacked else P("col")

    # norm scales / canonical-output biases: canonical features are
    # col-sharded in tesseract
    spec_norm = spec_vec
    spec_bias_up = spec_vec
    spec_bias_down = spec_vec

    def spec_vec_replicated(self, stacked: bool = False):
        return P(None, None) if stacked else P(None)

    def spec_w_down(self, stacked: bool = False):
        return self.spec_w2d(stacked)

    def spec_w_to_replicated(self, stacked: bool = False):
        # [F, G] with F over col (matching x's feature sharding), G full
        return P(None, "col", None) if stacked else P("col", None)

    def spec_replicated(self, stacked: bool = False):
        return P(None, None) if stacked else P(None)

    def spec_embed(self):
        return P("row", "col")

    def spec_head(self):
        return P(("depth", "row", "col"), None)

    def spec_expert(self, stacked: bool = False):
        # [n_experts, F, G]: experts over depth, F over row, G over col
        s = ("depth", "row", "col")
        return P(*((None,) + s if stacked else s))

    def spec_act(self):
        if self.plan.kind == "long_decode":
            return P(None, None, "col")  # batch=1: no token sharding
        if self.plan.kind == "decode_dp":
            return P("data", None, "col")  # batch over data only
        if self.plan.seq_sharded:
            return P("data", ("depth", "row"), "col")
        if self.plan.kind == "train" and self.ctx.seq > 1:
            # long-context train: time over the seq ring (DESIGN.md §15)
            return P(("data", "depth", "row"), "seq", "col")
        return P(("data", "depth", "row"), None, "col")

    def spec_tokens_in(self):
        # ids/labels as fed from the host: sharded over (data, depth) only;
        # the row factor is applied by embed()'s reduce-scatter.
        if self.plan.kind == "long_decode":
            return P(None, None)
        if self.plan.kind == "decode_dp":
            return P("data", None)
        if self.plan.seq_sharded:
            return P("data", "depth")
        if self.plan.kind == "train" and self.ctx.seq > 1:
            return P(("data", "depth"), "seq")
        return P(("data", "depth"), None)

    # ---------------- shape helpers ----------------
    @property
    def feature_shards(self) -> int:
        return self.ctx.cols

    @property
    def token_shards(self) -> int:
        return self.ctx.data * self.ctx.depth * self.ctx.rows

    def vocab_pad_multiple(self) -> int:
        return self.ctx.depth * self.ctx.rows * self.ctx.cols

    # ---------------- core ops (inside shard_map) ----------------
    def seq_gather_in(self, x):
        return x  # canonical tesseract activations stay sharded through blocks

    def linear(self, x, w, b=None):
        # ctx.matmul_schedule picks the SUMMA execution schedule inside the
        # op: "fused" all-gathers, or the overlapped "ring" (DESIGN.md §2b).
        y = tesseract_matmul(self.ctx, x, w)
        if b is not None:
            y = y + b
        return y

    # up/down aliases: in tesseract the canonical activation is already
    # feature-sharded, so both directions are the same op.
    linear_up = linear
    linear_down = linear

    def linear_replicated(self, x, w, b=None):
        """Small matmul with a fully replicated weight [F_glob_over_col, G].

        x has features over col; gather then local matmul. Used for tiny
        projections (routers) where sharding would waste collectives.
        """
        xg = col.all_gather_inv(x, self.ctx.axis_col, tiled=True, axis=x.ndim - 1)
        y = _f32_einsum("...f,fg->...g", xg, w, out_dtype=x.dtype)
        if b is not None:
            y = y + b
        return y

    def linear_to_replicated(self, x, w, b=None):
        """[.., F_loc] x [F_loc, G] -> psum(col) -> [.., G] replicated over col.

        Used for small outputs that must be whole on every device (e.g.
        replicated GQA KV heads when kv_heads % q != 0)."""
        y = _f32_einsum("...f,fg->...g", x, w, out_dtype=x.dtype)
        y = lax.psum(y, self.ctx.axis_col)
        if b is not None:
            y = y + b
        return y

    @property
    def head_shards(self) -> int:
        """How many ways attention heads are sharded (over col)."""
        return self.ctx.cols

    def _scatter_dim(self, has_batch_and_seq: bool = True):
        # which token dim the row-factor is applied to
        return 1 if self.plan.seq_sharded else 0

    def embed(self, ids, table):
        """ids: [B', S'] per (data, depth) group, replicated over (row, col).
        table: local [v_pad/q, h/q] (vocab over row, h over col).
        Returns canonical activation [B_loc, S_loc, h/q]."""
        ctx = self.ctx
        v_loc = table.shape[0]
        v_off = lax.axis_index(ctx.axis_row) * v_loc
        local = ids - v_off
        valid = (local >= 0) & (local < v_loc)
        safe = jnp.clip(local, 0, v_loc - 1)
        emb = jnp.take(table, safe, axis=0)              # [B', S', h/q]
        emb = jnp.where(valid[..., None], emb, jnp.zeros_like(emb))
        if self.plan.kind in ("long_decode", "decode_dp"):
            # tokens not sharded over (depth,row): sum vocab-shard partials.
            return lax.psum(emb, ctx.axis_row)
        # reduce-scatter over row: sums the vocab-shard partials and applies
        # the final row factor of the token sharding (paper Fig. 4 layout).
        dim = self._scatter_dim()
        return col.psum_scatter_dim(emb, ctx.axis_row, dim)

    def shard_tokens(self, t):
        """Slice host-layout ids/labels [B', S'] to this device's token block
        (the non-summing analogue of embed's reduce-scatter)."""
        if self.plan.kind in ("long_decode", "decode_dp"):
            return t
        ctx = self.ctx
        dim = self._scatter_dim()
        n = t.shape[dim] // ctx.rows
        i = lax.axis_index(ctx.axis_row)
        return lax.dynamic_slice_in_dim(t, i * n, n, axis=dim)

    def rmsnorm(self, x, scale, eps=1e-5):
        ctx = self.ctx
        xf = x.astype(jnp.float32)
        ssq = jnp.sum(xf * xf, axis=-1, keepdims=True)
        ssq = lax.psum(ssq, ctx.axis_col)
        h = x.shape[-1] * ctx.cols
        inv = lax.rsqrt(ssq / h + eps)
        return ((xf * inv) * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)

    def layernorm(self, x, scale, bias, eps=1e-5):
        # paper §3.2.2: compute X and X^2 partial sums, all_reduce along the
        # feature-sharding axis, then normalize locally.
        ctx = self.ctx
        xf = x.astype(jnp.float32)
        s1 = lax.psum(jnp.sum(xf, -1, keepdims=True), ctx.axis_col)
        s2 = lax.psum(jnp.sum(xf * xf, -1, keepdims=True), ctx.axis_col)
        h = x.shape[-1] * ctx.cols
        mean = s1 / h
        var = s2 / h - mean * mean
        y = (xf - mean) * lax.rsqrt(var + eps)
        y = y * scale.astype(jnp.float32)
        if bias is not None:
            y = y + bias.astype(jnp.float32)
        return y.astype(x.dtype)

    # ---------------- token/seq info ----------------
    def seq_shard_index(self):
        ctx = self.ctx
        return lax.axis_index(ctx.axis_depth) * ctx.rows + lax.axis_index(ctx.axis_row)

    def positions(self, seq_loc: int):
        """Global position ids [seq_loc] for this device's sequence block."""
        if self.plan.seq_sharded:
            return self.seq_shard_index() * seq_loc + jnp.arange(seq_loc)
        if self.plan.kind == "train" and self.ctx.seq > 1:
            # seq-ring train: contiguous (ring) or round-robin (striped)
            # global rows — must agree with the token permutation applied in
            # runtime/steps.py and the ring mask in core/ring_attention.py
            from .ring_attention import shard_positions
            return shard_positions(seq_loc, self.ctx.seq,
                                   lax.axis_index(self.ctx.axis_seq),
                                   self.ctx.train_attn_schedule())
        return jnp.arange(seq_loc)

    def gather_seq(self, x, axis: int):
        """Gather a seq-sharded tensor to full length (for KV in attention)."""
        if not self.plan.seq_sharded:
            return x
        return col.all_gather_cat(x, (self.ctx.axis_depth, self.ctx.axis_row), axis=axis)

    # --- attention layout contract (differs between 2.5-D and 1-D SP) ---
    def positions_q(self, t_gathered: int):
        """Positions of the q rows coming out of seq_gather_in+linear_up."""
        return self.positions(t_gathered)

    def kv_full(self, k, axis: int = 1):
        """K/V (as produced by the projections) -> full-sequence K/V."""
        return self.gather_seq(k, axis)

    def kv_local_slice(self, k, axis: int = 1):
        """K/V (as produced by the projections) -> this device's seq shard
        (prefill cache layout)."""
        return k

    # ---------------- losses / heads ----------------
    def ce_loss(self, x, w_head, labels, *, vocab_real: int, loss_chunk: int = 512,
                label_mask=None):
        """Chunked cross-entropy with the head weight sharded
        [v_pad/(d·q²), h] over (depth,row,col) — full logits never materialize.

        x: canonical activation [B_loc, S_loc, h/q]
        labels: host layout [B', S'] per (data, depth) group
        Returns (sum_loss, sum_count): replicated over the model group,
        still varying over data (caller psums over data).
        """
        ctx = self.ctx
        dq = ctx.depth * ctx.rows
        E_loc = x.shape[0] * x.shape[1]
        xf = x.reshape(E_loc, x.shape[-1])
        lab = self.shard_tokens(labels).reshape(E_loc)
        if label_mask is not None:
            lm = self.shard_tokens(label_mask).reshape(E_loc)
        else:
            lm = jnp.ones((E_loc,), jnp.float32)

        c_loc = max(1, min(loss_chunk, E_loc))
        while E_loc % c_loc:
            c_loc -= 1
        n_chunks = E_loc // c_loc

        v_loc = w_head.shape[0]
        v_off = col.axis_linear_index((ctx.axis_depth, ctx.axis_row, ctx.axis_col)) * v_loc
        model_axes = (ctx.axis_depth, ctx.axis_row, ctx.axis_col)
        gather_axes = (ctx.axis_depth, ctx.axis_row)

        xc = xf.reshape(n_chunks, c_loc, xf.shape[-1])
        lc = lab.reshape(n_chunks, c_loc)
        mc = lm.reshape(n_chunks, c_loc)
        # made here, not in the checkpointed chunk: remat's partial eval
        # would hoist it out of the chunk loop without the caller's scope
        vmask = (v_off + jnp.arange(v_loc)) < vocab_real

        @jax.checkpoint
        def chunk_loss(xw, chunk):
            x_chunk, l_chunk, m_chunk = chunk
            # gather this chunk's tokens across (depth,row) and features
            # across col -> [C, h] with C = c_loc * dq
            xg = col.all_gather_cat(x_chunk, gather_axes, axis=0)
            xg = col.all_gather_inv(xg, ctx.axis_col, tiled=True, axis=xg.ndim - 1)
            lg = col.all_gather_cat(l_chunk, gather_axes, axis=0)
            logits = _f32_einsum("ch,vh->cv", xg, xw, out_dtype=jnp.float32)
            logits = jnp.where(vmask[None, :], logits, -jnp.inf)
            m_loc = jnp.max(logits, axis=-1)
            m = lax.pmax(lax.stop_gradient(m_loc), model_axes)
            se = lax.psum(jnp.sum(jnp.exp(logits - m[:, None]), axis=-1), model_axes)
            lse = jnp.log(se) + m
            ll_idx = lg - v_off
            lvalid = (ll_idx >= 0) & (ll_idx < v_loc)
            safe = jnp.clip(ll_idx, 0, v_loc - 1)
            ll_part = jnp.take_along_axis(logits, safe[:, None], axis=1)[:, 0]
            ll = lax.psum(jnp.where(lvalid, ll_part, 0.0), model_axes)
            loss_full = lse - ll                         # [C], varying data only
            # apply the loss mask on this device's own token block and reduce
            # once over (depth,row) — keeps the result vma-invariant there.
            i = col.axis_linear_index(gather_axes)
            mine = lax.dynamic_slice_in_dim(loss_full, i * x_chunk.shape[0],
                                            x_chunk.shape[0], axis=0)
            ls = lax.psum(jnp.sum(mine * m_chunk), gather_axes)
            cs = lax.psum(jnp.sum(m_chunk), gather_axes)
            return ls, cs

        def body(carry, chunk):
            s, n = carry
            ls, cs = chunk_loss(w_head, chunk)
            return (s + ls, n + cs), None

        zero_axes = ((ctx.axis_data, ctx.axis_seq) if ctx.seq > 1
                     else (ctx.axis_data,))
        # the sums also vary over whatever else the inputs vary on (the
        # pipe axis of a pipeline stage), but never over the reduced axes
        zero_axes += tuple(a for a in col.vma_of(xf) | col.vma_of(lab)
                           if a not in model_axes)
        zero = col.pvary(jnp.float32(0), zero_axes)
        (loss_sum, count), _ = lax.scan(body, (zero, zero), (xc, lc, mc))
        return loss_sum, count

    def _sharded_logits(self, x, w_head, vocab_real, tokens_sharded):
        """Per-shard decode logits [B(_dd), v_loc] (pad masked -inf) + this
        shard's global vocab offset.  The single head implementation that
        both head_sample's distributed argmax and head_logits' gathered
        full-vocab rows reduce — their bit-parity contract rests on it."""
        ctx = self.ctx
        gather_axes = (ctx.axis_depth, ctx.axis_row)
        model_axes = (ctx.axis_depth, ctx.axis_row, ctx.axis_col)
        xg = col.all_gather_inv(x[:, 0, :], ctx.axis_col, tiled=True, axis=1)
        if tokens_sharded:
            xg = col.all_gather_cat(xg, gather_axes, axis=0)        # [B_dd, h]
        logits = _f32_einsum("bh,vh->bv", xg, w_head, out_dtype=jnp.float32)
        v_loc = w_head.shape[0]
        v_off = col.axis_linear_index(model_axes) * v_loc
        vmask = (v_off + jnp.arange(v_loc)) < vocab_real
        return jnp.where(vmask[None, :], logits, -jnp.inf), v_off

    def head_sample(self, x, w_head, *, vocab_real: int, temperature: float = 0.0,
                    rng=None, tokens_sharded: bool = None):
        """Decode-time next-token selection. x: [B_loc, 1, h/q].
        Returns ids [B_loc] (token-sharded like the canonical layout).

        tokens_sharded: whether x's batch dim is sharded over (depth,row)
        (decode plan) or replicated (prefill last-token / long_decode)."""
        ctx = self.ctx
        if tokens_sharded is None:
            tokens_sharded = self.plan.kind == "decode"
        model_axes = (ctx.axis_depth, ctx.axis_row, ctx.axis_col)
        logits, v_off = self._sharded_logits(x, w_head, vocab_real,
                                             tokens_sharded)
        if temperature > 0.0 and rng is not None:
            g = jax.random.gumbel(rng, logits.shape, jnp.float32)
            logits = logits / temperature + g
        ids = col.distributed_argmax(logits, v_off, model_axes)  # [B_dd]
        if not tokens_sharded:
            return ids
        # keep this device's batch block
        i = self.seq_shard_index()
        b_loc = x.shape[0]
        return lax.dynamic_slice_in_dim(ids, i * b_loc, b_loc, axis=0)

    def head_logits(self, x, w_head, *, vocab_real: int, tokens_sharded=None):
        """Full-vocab decode logits for the serve sampler. x: [B_loc, 1, h/q].

        Returns [B_loc, v_pad] float32, padded vocab masked to -inf; the
        greedy argmax of a row is bit-identical to head_sample's distributed
        argmax (same per-shard values, ties toward the smallest index)."""
        ctx = self.ctx
        if tokens_sharded is None:
            tokens_sharded = self.plan.kind == "decode"
        model_axes = (ctx.axis_depth, ctx.axis_row, ctx.axis_col)
        logits, _ = self._sharded_logits(x, w_head, vocab_real,
                                         tokens_sharded)
        # vocab shards are laid out lexicographically over (depth, row, col),
        # matching all_gather_cat's concatenation order.
        full = col.all_gather_cat(logits, model_axes, axis=1)       # [B_dd, V]
        if not tokens_sharded:
            return full
        i = self.seq_shard_index()
        b_loc = x.shape[0]
        return lax.dynamic_slice_in_dim(full, i * b_loc, b_loc, axis=0)


# ===========================================================================
# Megatron-LM (1-D) op set — the paper's main baseline
# ===========================================================================

class MegatronOps:
    mode_family = "megatron"

    def __init__(self, ctx: ParallelContext, plan: Plan):
        assert ctx.rows == 1 and ctx.depth == 1
        self.ctx = ctx
        self.plan = plan
        # depth/row are size-1 in 1-D mode; including them in every TP
        # reduction is numerically free and keeps vma bookkeeping clean
        # (params are pvary'd over them at the step boundary).
        self.tp_axes = (ctx.axis_depth, ctx.axis_row, ctx.axis_col)

    # ---------------- specs ----------------
    def spec_w2d(self, stacked: bool = False):
        # used for "up" weights [F, G]: G over col.  "down" weights use
        # spec_w2d_down.  Models store both with these two specs.
        return P(None, None, "col") if stacked else P(None, "col")

    def spec_w2d_down(self, stacked: bool = False):
        return P(None, "col", None) if stacked else P("col", None)

    spec_w_down = spec_w2d_down

    def spec_vec(self, stacked: bool = False):
        return P(None, "col") if stacked else P("col")

    spec_bias_up = spec_vec

    def spec_vec_full(self, stacked: bool = False):
        return P(None, None) if stacked else P(None)

    # canonical features are full in megatron: norms/down-biases replicated
    spec_norm = spec_vec_full
    spec_bias_down = spec_vec_full
    spec_vec_replicated = spec_vec_full

    def spec_w_to_replicated(self, stacked: bool = False):
        return P(None, None, None) if stacked else P(None, None)

    def spec_replicated(self, stacked: bool = False):
        return P(None, None) if stacked else P(None)

    def spec_embed(self):
        return P("col", None)

    def spec_head(self):
        return P("col", None)

    def spec_expert(self, stacked: bool = False):
        s = ("col", None, None)
        return P(*((None,) + s if stacked else s))

    def spec_act(self):
        if self.plan.kind == "long_decode":
            return P(None, None, None)
        if self.plan.seq_sharded:
            return P("data", "col", None)
        return P(("data",), None, None)  # decode_dp == decode for 1-D

    def spec_tokens_in(self):
        if self.plan.kind == "long_decode":
            return P(None, None)
        return P("data", None)  # decode_dp == decode for 1-D

    @property
    def feature_shards(self) -> int:
        return 1  # canonical activation carries full features

    @property
    def token_shards(self) -> int:
        return self.ctx.data * (self.ctx.cols if self.plan.seq_sharded else 1)

    def vocab_pad_multiple(self) -> int:
        return self.ctx.cols

    # ---------------- core ops ----------------
    def seq_gather_in(self, x):
        """Megatron-SP entry gather: call once before the up-projections of a
        block (the scatter happens inside linear_down)."""
        if self.plan.seq_sharded:
            return col.all_gather_cat(x, self.ctx.axis_col, axis=1)
        return x

    def _maybe_scatter_seq_out(self, y, reduce: bool):
        if self.plan.seq_sharded:
            return col.psum_scatter_dim(y, self.ctx.axis_col, 1)
        return col.psum_v(y, self.tp_axes) if reduce else y

    def linear_up(self, x, w, b=None):
        """Column-parallel: [.., F] x [F, G/p] -> [.., G/p].

        In SP mode the caller must have applied seq_gather_in() first."""
        y = _f32_einsum("...f,fg->...g", x, w, out_dtype=x.dtype)
        if b is not None:
            y = y + b
        return y

    def linear_down(self, h, w, b=None):
        """Row-parallel: [.., G/p] x [G/p, F] -> psum -> [.., F]."""
        y = _f32_einsum("...g,gf->...f", h, w, out_dtype=h.dtype)
        y = self._maybe_scatter_seq_out(y, reduce=True)
        if b is not None:
            y = y + b
        return y

    def linear(self, x, w, b=None):
        # canonical -> canonical full-feature matmul: column then implicit
        # gather is wasteful; use replicated weight for such (rare) cases.
        return self.linear_replicated(x, w, b)

    def linear_replicated(self, x, w, b=None):
        y = _f32_einsum("...f,fg->...g", x, w, out_dtype=x.dtype)
        if b is not None:
            y = y + b
        return y

    def linear_to_replicated(self, x, w, b=None):
        return self.linear_replicated(x, w, b)

    @property
    def head_shards(self) -> int:
        return self.ctx.cols

    def embed(self, ids, table):
        ctx = self.ctx
        v_loc = table.shape[0]
        v_off = lax.axis_index(ctx.axis_col) * v_loc
        local = ids - v_off
        valid = (local >= 0) & (local < v_loc)
        emb = jnp.take(table, jnp.clip(local, 0, v_loc - 1), axis=0)
        emb = jnp.where(valid[..., None], emb, jnp.zeros_like(emb))
        if self.plan.seq_sharded:
            emb = col.psum_scatter_dim(emb, ctx.axis_col, 1)
            return col.psum_v(emb, (ctx.axis_depth, ctx.axis_row))
        return col.psum_v(emb, self.tp_axes)

    def shard_tokens(self, t):
        if not self.plan.seq_sharded:
            return t
        ctx = self.ctx
        n = t.shape[1] // ctx.cols
        i = lax.axis_index(ctx.axis_col)
        return lax.dynamic_slice_in_dim(t, i * n, n, axis=1)

    def rmsnorm(self, x, scale, eps=1e-5):
        xf = x.astype(jnp.float32)
        inv = lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
        return ((xf * inv) * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)

    def layernorm(self, x, scale, bias, eps=1e-5):
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, -1, keepdims=True)
        var = jnp.mean(xf * xf, -1, keepdims=True) - mean * mean
        y = (xf - mean) * lax.rsqrt(var + eps)
        y = y * scale.astype(jnp.float32)
        if bias is not None:
            y = y + bias.astype(jnp.float32)
        return y.astype(x.dtype)

    def seq_shard_index(self):
        return lax.axis_index(self.ctx.axis_col)

    def positions(self, seq_loc: int):
        if self.plan.seq_sharded:
            return self.seq_shard_index() * seq_loc + jnp.arange(seq_loc)
        return jnp.arange(seq_loc)

    def gather_seq(self, x, axis: int):
        if not self.plan.seq_sharded:
            return x
        return col.all_gather_cat(x, self.ctx.axis_col, axis=axis)

    # --- attention layout contract: megatron-SP projects on the *gathered*
    # sequence, so q/k/v are already full-length per device ---
    def positions_q(self, t_gathered: int):
        return jnp.arange(t_gathered)

    def kv_full(self, k, axis: int = 1):
        return k

    def kv_local_slice(self, k, axis: int = 1):
        if not self.plan.seq_sharded:
            return k
        n = k.shape[axis] // self.ctx.cols
        i = lax.axis_index(self.ctx.axis_col)
        return lax.dynamic_slice_in_dim(k, i * n, n, axis=axis)

    def ce_loss(self, x, w_head, labels, *, vocab_real: int, loss_chunk: int = 512,
                label_mask=None):
        ctx = self.ctx
        E_loc = x.shape[0] * x.shape[1]
        xf = x.reshape(E_loc, x.shape[-1])
        lab = self.shard_tokens(labels).reshape(E_loc)
        lm = (self.shard_tokens(label_mask).reshape(E_loc)
              if label_mask is not None else jnp.ones((E_loc,), jnp.float32))

        c_loc = max(1, min(loss_chunk, E_loc))
        while E_loc % c_loc:
            c_loc -= 1
        n_chunks = E_loc // c_loc
        v_loc = w_head.shape[0]
        v_off = lax.axis_index(ctx.axis_col) * v_loc
        sp = self.plan.seq_sharded  # tokens sharded over col too -> gather

        xc = xf.reshape(n_chunks, c_loc, xf.shape[-1])
        lc = lab.reshape(n_chunks, c_loc)
        mc = lm.reshape(n_chunks, c_loc)
        # made here, not in the checkpointed chunk: remat's partial eval
        # would hoist it out of the chunk loop without the caller's scope
        vmask = (v_off + jnp.arange(v_loc)) < vocab_real

        @jax.checkpoint
        def chunk_loss(xw, chunk):
            x_chunk, l_chunk, m_chunk = chunk
            if sp:
                # SP: col devices hold different tokens; replicate the chunk
                # within the TP group before the vocab-sharded matmul (the
                # loss mask stays local: the final reduction slices back).
                x_chunk = col.all_gather_cat(x_chunk, ctx.axis_col, axis=0)
                l_chunk = col.all_gather_cat(l_chunk, ctx.axis_col, axis=0)
            logits = _f32_einsum("ch,vh->cv", x_chunk, xw, out_dtype=jnp.float32)
            logits = jnp.where(vmask[None, :], logits, -jnp.inf)
            m_l = jnp.max(logits, -1)
            m = col.pmax_v(lax.stop_gradient(m_l), self.tp_axes)
            se = col.psum_v(jnp.sum(jnp.exp(logits - m[:, None]), -1), self.tp_axes)
            lse = jnp.log(se) + m
            idx = l_chunk - v_off
            valid = (idx >= 0) & (idx < v_loc)
            safe = jnp.clip(idx, 0, v_loc - 1)
            ll_p = jnp.take_along_axis(logits, safe[:, None], 1)[:, 0]
            ll = col.psum_v(jnp.where(valid, ll_p, 0.0), self.tp_axes)
            loss_full = lse - ll
            if sp:
                i = lax.axis_index(ctx.axis_col)
                mine = lax.dynamic_slice_in_dim(loss_full, i * c_loc, c_loc, 0)
                return (lax.psum(jnp.sum(mine * m_chunk), ctx.axis_col),
                        lax.psum(jnp.sum(m_chunk), ctx.axis_col))
            return jnp.sum(loss_full * m_chunk), jnp.sum(m_chunk)

        def body(carry, chunk):
            s, n = carry
            ls, cs = chunk_loss(w_head, chunk)
            return (s + ls, n + cs), None

        zero = col.pvary(jnp.float32(0), (ctx.axis_data,))
        (loss_sum, count), _ = lax.scan(body, (zero, zero), (xc, lc, mc))
        return loss_sum, count

    def _sharded_logits(self, x, w_head, vocab_real):
        """Per-shard decode logits + vocab offset (see TesseractOps)."""
        xg = x[:, 0, :]                                   # [B_loc, h]
        logits = _f32_einsum("bh,vh->bv", xg, w_head, out_dtype=jnp.float32)
        v_loc = w_head.shape[0]
        v_off = col.axis_linear_index(self.tp_axes) * v_loc
        vmask = (v_off + jnp.arange(v_loc)) < vocab_real
        return jnp.where(vmask[None, :], logits, -jnp.inf), v_off

    def head_sample(self, x, w_head, *, vocab_real: int, temperature: float = 0.0,
                    rng=None, tokens_sharded: bool = None):
        logits, v_off = self._sharded_logits(x, w_head, vocab_real)
        if temperature > 0.0 and rng is not None:
            g = jax.random.gumbel(rng, logits.shape, jnp.float32)
            logits = logits / temperature + g
        return col.distributed_argmax(logits, v_off, self.tp_axes)

    def head_logits(self, x, w_head, *, vocab_real: int, tokens_sharded=None):
        """Full-vocab decode logits [B_loc, v_pad] (see TesseractOps)."""
        del tokens_sharded  # 1-D decode batch is only ever sharded over data
        logits, _ = self._sharded_logits(x, w_head, vocab_real)
        return col.all_gather_cat(logits, self.tp_axes, axis=1)


def make_ops(ctx: ParallelContext, plan: Plan):
    if ctx.mode in ("tesseract", "summa2d"):
        return TesseractOps(ctx, plan)
    if ctx.mode == "megatron1d":
        return MegatronOps(ctx, plan)
    raise ValueError(f"no OpSet for mode {ctx.mode!r}")
