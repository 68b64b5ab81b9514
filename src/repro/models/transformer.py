"""Dense decoder-only LM (llama/yi/smollm/nemotron family) on Tesseract.

Covers: GQA (sharded or replicated KV heads), GLU / squared-ReLU MLPs,
rmsnorm/layernorm, RoPE, head padding when num_heads % q != 0.

The same class is the backbone base for the VLM (cross-attention) variant.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from ..configs.base import ModelConfig, RunConfig, round_up
from ..core.api import ParallelContext
from ..core.ops import Plan, make_ops
from . import common as cm


def remat_policy(name: str):
    if name == "none":
        return None
    if name == "full":
        return "__full__"
    if name == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    raise ValueError(name)


def maybe_remat(fn, run: RunConfig):
    p = remat_policy(run.remat)
    if p is None:
        return fn
    if p == "__full__":
        return jax.checkpoint(fn)
    return jax.checkpoint(fn, policy=p)


class DenseLM:
    def __init__(self, cfg: ModelConfig, ctx: ParallelContext, run: RunConfig):
        self.cfg, self.ctx, self.run = cfg, ctx, run
        q = ctx.cols
        self.Hp = round_up(cfg.num_heads, q)                 # padded q-heads
        self.kv_shard = cfg.num_kv_heads % q == 0
        self.D = cfg.resolved_head_dim
        probe = make_ops(ctx, Plan.for_shape("train"))
        self.v_pad = round_up(cfg.vocab_size, probe.vocab_pad_multiple())
        self.pdt = jnp.dtype(run.param_dtype)
        self.cdt = jnp.dtype(run.compute_dtype)

    # ------------------------------------------------------------- params
    def _block_init(self, key):
        cfg, D = self.cfg, self.D
        h, ff = cfg.d_model, cfg.d_ff
        ks = jax.random.split(key, 8)
        H = cfg.num_heads
        p = {
            "ln1": jnp.zeros((h,), self.pdt),
            "ln2": jnp.zeros((h,), self.pdt),
            "wq": cm.winit_padded(ks[0], (h, H * D), (h, self.Hp * D), dtype=self.pdt),
            "wk": cm.winit(ks[1], (h, cfg.num_kv_heads * D), dtype=self.pdt),
            "wv": cm.winit(ks[2], (h, cfg.num_kv_heads * D), dtype=self.pdt),
            "wo": cm.winit_padded(ks[3], (H * D, h), (self.Hp * D, h), dtype=self.pdt),
            "w_down": cm.winit(ks[6], (ff, h), dtype=self.pdt),
        }
        if cfg.mlp_glu:
            p["w_gate"] = cm.winit(ks[4], (h, ff), dtype=self.pdt)
            p["w_up"] = cm.winit(ks[5], (h, ff), dtype=self.pdt)
        else:
            p["w_up"] = cm.winit(ks[5], (h, ff), dtype=self.pdt)
        if cfg.use_bias:
            p["bq"] = jnp.zeros((self.Hp * D,), self.pdt)
            p["bv"] = jnp.zeros((cfg.num_kv_heads * D,), self.pdt)
            p["bo"] = jnp.zeros((h,), self.pdt)
            p["b_up"] = jnp.zeros((ff,), self.pdt)
            p["b_down"] = jnp.zeros((h,), self.pdt)
        if cfg.norm == "layernorm":
            p["ln1b"] = jnp.zeros((h,), self.pdt)
            p["ln2b"] = jnp.zeros((h,), self.pdt)
            p["ln1"] = jnp.ones((h,), self.pdt)
            p["ln2"] = jnp.ones((h,), self.pdt)
        return p

    def init(self, key):
        cfg = self.cfg
        k_e, k_h, k_b, k_f = jax.random.split(key, 4)
        blocks = jax.vmap(self._block_init)(jax.random.split(k_b, cfg.num_layers))
        params = {
            "embed": cm.winit_padded(k_e, (cfg.vocab_size, cfg.d_model),
                                     (self.v_pad, cfg.d_model), dtype=self.pdt),
            "head": cm.winit_padded(k_h, (cfg.vocab_size, cfg.d_model),
                                    (self.v_pad, cfg.d_model), dtype=self.pdt),
            "ln_f": (jnp.ones((cfg.d_model,), self.pdt)
                     if cfg.norm == "layernorm" else jnp.zeros((cfg.d_model,), self.pdt)),
            "blocks": blocks,
        }
        if cfg.norm == "layernorm":
            params["ln_fb"] = jnp.zeros((cfg.d_model,), self.pdt)
        return params

    def _block_specs(self, ops):
        cfg = self.cfg
        kv_spec = (ops.spec_w2d(True) if self.kv_shard
                   else ops.spec_w_to_replicated(True))
        s = {
            "ln1": ops.spec_norm(True), "ln2": ops.spec_norm(True),
            "wq": ops.spec_w2d(True), "wk": kv_spec, "wv": kv_spec,
            "wo": ops.spec_w_down(True), "w_down": ops.spec_w_down(True),
            "w_up": ops.spec_w2d(True),
        }
        if cfg.mlp_glu:
            s["w_gate"] = ops.spec_w2d(True)
        if cfg.use_bias:
            s.update(bq=ops.spec_bias_up(True),
                     bv=(ops.spec_bias_up(True) if self.kv_shard
                         else ops.spec_vec_replicated(True)),
                     bo=ops.spec_bias_down(True),
                     b_up=ops.spec_bias_up(True),
                     b_down=ops.spec_bias_down(True))
        if cfg.norm == "layernorm":
            s["ln1b"] = ops.spec_norm(True)
            s["ln2b"] = ops.spec_norm(True)
        return s

    def specs(self, ops):
        s = {
            "embed": ops.spec_embed(),
            "head": ops.spec_head(),
            "ln_f": ops.spec_norm(False),
            "blocks": self._block_specs(ops),
        }
        if self.cfg.norm == "layernorm":
            s["ln_fb"] = ops.spec_norm(False)
        return s

    # ------------------------------------------------------------ helpers
    def _norm(self, ops, x, scale, bias=None):
        if self.cfg.norm == "layernorm":
            return ops.layernorm(x, scale, bias, self.cfg.norm_eps)
        return ops.rmsnorm(x, scale, self.cfg.norm_eps)

    def _heads_loc(self, ops):
        return self.Hp // ops.head_shards

    def _kv_heads_loc(self, ops):
        return (self.cfg.num_kv_heads // ops.head_shards if self.kv_shard
                else self.cfg.num_kv_heads)

    def _head_mask(self, ops):
        """[Hq_loc] 1.0 for real heads, 0.0 for padded (smollm 15->16)."""
        if self.Hp == self.cfg.num_heads:
            return None
        hloc = self._heads_loc(ops)
        gidx = lax.axis_index(self.ctx.axis_col) * hloc + jnp.arange(hloc)
        return (gidx < self.cfg.num_heads).astype(self.cdt)

    def _kv_map(self, ops):
        """[Hq_loc] q-head -> kv-head map for the replicated-KV path."""
        cfg = self.cfg
        hloc = self._heads_loc(ops)
        gidx = lax.axis_index(self.ctx.axis_col) * hloc + jnp.arange(hloc)
        group = max(1, cfg.num_heads // cfg.num_kv_heads)
        return jnp.minimum(gidx // group, cfg.num_kv_heads - 1)

    def _qkv(self, p, xg, ops, positions):
        """Project and rope. Returns q [B,T,HqLoc,D], k/v [B,T,KvLoc,D]."""
        cfg, D = self.cfg, self.D
        B, T = xg.shape[:2]
        q = ops.linear_up(xg, p["wq"], p.get("bq"))
        if self.kv_shard:
            k = ops.linear_up(xg, p["wk"])
            v = ops.linear_up(xg, p["wv"], p.get("bv"))
        else:
            k = ops.linear_to_replicated(xg, p["wk"])
            v = ops.linear_to_replicated(xg, p["wv"], p.get("bv"))
        q = q.reshape(B, T, self._heads_loc(ops), D)
        k = k.reshape(B, T, self._kv_heads_loc(ops), D)
        v = v.reshape(B, T, self._kv_heads_loc(ops), D)
        if cfg.use_rope:
            q = cm.apply_rope(q, positions, cfg.rope_theta)
            k = cm.apply_rope(k, positions, cfg.rope_theta)
        return q, k, v

    def _attn_out(self, p, out, ops, head_mask):
        B, T = out.shape[:2]
        if head_mask is not None:
            out = out * head_mask[None, None, :, None]
        out = out.reshape(B, T, self._heads_loc(ops) * self.D)
        return ops.linear_down(out, p["wo"], p.get("bo"))

    def _mlp(self, p, x, ops):
        cfg = self.cfg
        xg = ops.seq_gather_in(x)
        act = cm.mlp_act("silu" if cfg.mlp_act == "silu" else cfg.mlp_act)
        if cfg.mlp_glu:
            g = ops.linear_up(xg, p["w_gate"])
            u = ops.linear_up(xg, p["w_up"], p.get("b_up"))
            h = act(g) * u
        else:
            h = act(ops.linear_up(xg, p["w_up"], p.get("b_up")))
        return ops.linear_down(h, p["w_down"], p.get("b_down"))

    @jax.named_scope(cm.MLP)
    def _mlp_sublayer(self, p, x, ops):
        """ln2, the MLP and its residual."""
        h2 = self._norm(ops, x, p["ln2"], p.get("ln2b"))
        return x + self._mlp(p, h2, ops)

    # -------------------------------------------------------------- train
    def _ring_axes(self, ops):
        """Mesh axes to stream K/V around, or None for the local schedule.

        Train with ctx.seq > 1 rings over the dedicated "seq" axis;
        seq-sharded prefill with a non-local attn_schedule rings over the
        existing (depth, row) sequence sharding instead of gathering the
        full K/V (DESIGN.md §15)."""
        ctx = self.ctx
        if ops.mode_family != "tesseract" or ctx.attn_schedule == "local":
            return None
        if ops.plan.kind == "train" and ctx.seq > 1:
            return (ctx.axis_seq,)
        if ops.plan.seq_sharded and ctx.dq > 1:
            return ctx.seq_shard_axes
        return None

    def _ring_attn(self, q, k, v, ops, ring_axes):
        """Seq-sharded attention: ring/striped flash over ``ring_axes``."""
        from ..core.ring_attention import ring_attention
        from ..kernels.ops import effective_attn_impl
        ctx = self.ctx
        variant = (ctx.train_attn_schedule() if ops.plan.kind == "train"
                   else "ring")  # prefill prompts are never striped
        if not self.kv_shard:
            kv_map = self._kv_map(ops)
            k = jnp.take(k, kv_map, axis=2)
            v = jnp.take(v, kv_map, axis=2)
        out = ring_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), axes=ring_axes, variant=variant,
            causal=True, local_window=self.cfg.local_window,
            impl=effective_attn_impl(self.ctx.attn_impl))
        return out.transpose(0, 2, 1, 3)

    @jax.named_scope(cm.ATTN)
    def _block_train_attn(self, p, x, ops, full_kv_pos):
        """Attention sublayer (residual included); returns (x, (k, v) local
        seq-slices for prefill caching)."""
        run = self.run
        h = self._norm(ops, x, p["ln1"], p.get("ln1b"))
        hg = ops.seq_gather_in(h)
        T = hg.shape[1]
        qpos = ops.positions_q(T)
        q, k, v = self._qkv(p, hg, ops, qpos)
        ring_axes = self._ring_axes(ops)
        if ring_axes is not None:
            out = self._ring_attn(q, k, v, ops, ring_axes)
            x = x + self._attn_out(p, out, ops, self._head_mask(ops))
            kv = (ops.kv_local_slice(k, axis=1).astype(self.cdt),
                  ops.kv_local_slice(v, axis=1).astype(self.cdt))
            return x, kv
        # seq-sharded plans gather KV to full length (positions 0..S-1)
        kf = ops.kv_full(k, axis=1)
        vf = ops.kv_full(v, axis=1)
        if not self.kv_shard:
            kv_map = self._kv_map(ops)
            kf = jnp.take(kf, kv_map, axis=2)
            vf = jnp.take(vf, kv_map, axis=2)
        # static q-row offset (enables the flash kernel's causal block
        # skipping) except on the seq-sharded tesseract prefill, whose
        # positions carry a traced shard offset
        q_start = (0 if (not ops.plan.seq_sharded
                         or ops.mode_family == "megatron") else None)
        out = cm.attention(
            q, kf, vf, q_pos=qpos, kv_pos=full_kv_pos,
            causal=True, local_window=self.cfg.local_window,
            q_chunk=run.q_chunk, kv_chunk=run.kv_chunk,
            impl=self.ctx.attn_impl, q_start=q_start)
        x = x + self._attn_out(p, out, ops, self._head_mask(ops))
        kv = (ops.kv_local_slice(k, axis=1).astype(self.cdt),
              ops.kv_local_slice(v, axis=1).astype(self.cdt))
        return x, kv

    def _block_train(self, p, x, ops, full_kv_pos):
        x, _ = self._block_train_attn(p, x, ops, full_kv_pos)
        return self._mlp_sublayer(p, x, ops)

    def _run_blocks(self, params, x, ops, block_fn):
        body = maybe_remat(
            lambda xx, bp: (block_fn(bp, xx), None), self.run)
        with jax.named_scope(cm.BLOCKS):
            if self.run.scan_blocks:
                x, _ = lax.scan(body, x, params["blocks"])
            else:
                L = jax.tree.leaves(params["blocks"])[0].shape[0]
                for i in range(L):
                    bp = jax.tree.map(lambda a: a[i], params["blocks"])
                    x, _ = body(x, bp)
        return x

    # --- pipeline stage API (runtime/steps pipelined train path) ---
    # The trunk is decomposed so a pipe stage can run embed / its local block
    # slice / the loss head independently: params["blocks"] leaves arrive
    # stage-sharded over the pipe mesh axis, so pipe_blocks naturally applies
    # only this stage's layers.
    supports_pipeline = True
    # Sequence-axis sharding (ring/striped attention, DESIGN.md §15) needs
    # every time-mixing op to be ring-able: true for pure attention trunks,
    # false for SSM/LRU recurrences (state crosses shard boundaries) and for
    # capacity-factor MoE routing (token grouping is layout dependent).
    supports_seq_shard = True

    @jax.named_scope(cm.EMBED)
    def pipe_embed(self, params, tokens, ops):
        """Host-layout ids -> canonical activation (stage-0 entry)."""
        return ops.embed(tokens, params["embed"]).astype(self.cdt)

    def pipe_blocks(self, params, x, ops):
        """Apply this stage's (local) block slice to a canonical activation."""
        T_loc = x.shape[1]
        n_seq = ops.token_shards // self.ctx.data if ops.plan.seq_sharded else 1
        full_kv_pos = jnp.arange(T_loc * (n_seq if ops.plan.seq_sharded else 1))
        cast = lambda t: jax.tree.map(lambda a: a.astype(self.cdt)
                                      if a.dtype == self.pdt and a.ndim > 1
                                      else a, t)
        return self._run_blocks(
            params, x, ops,
            lambda bp, xx: self._block_train(cast(bp), xx, ops, full_kv_pos))

    @jax.named_scope(cm.HEAD_LOSS)
    def pipe_loss_sums(self, params, x, labels, ops, label_mask=None):
        """Final norm + chunked CE -> local (loss_sum, count) (last stage)."""
        x = self._norm(ops, x, params["ln_f"], params.get("ln_fb"))
        return ops.ce_loss(
            x, params["head"].astype(self.cdt), labels,
            vocab_real=self.cfg.vocab_size, loss_chunk=self.run.loss_chunk,
            label_mask=label_mask)

    def _trunk(self, params, tokens, ops):
        """embed -> blocks -> final norm (shared by loss and prefill)."""
        x = self.pipe_embed(params, tokens, ops)
        x = self.pipe_blocks(params, x, ops)
        return self._norm(ops, x, params["ln_f"], params.get("ln_fb"))

    def loss(self, params, batch, ops):
        x = self.pipe_embed(params, batch["tokens"], ops)
        x = self.pipe_blocks(params, x, ops)
        loss_sum, cnt = self.pipe_loss_sums(params, x, batch["labels"], ops,
                                            batch.get("mask"))
        # each seq shard holds different tokens, so the seq axis joins the
        # data axis in the final loss reduction
        axes = ((self.ctx.axis_data, self.ctx.axis_seq) if self.ctx.seq > 1
                else (self.ctx.axis_data,))
        loss_sum = lax.psum(loss_sum, axes)
        cnt = lax.psum(cnt, axes)
        return loss_sum / jnp.maximum(cnt, 1.0)

    # ------------------------------------------------------------ serving
    def tess_weight_names(self):
        """Param dict keys that flow exclusively through tesseract_matmul
        (their grads are reduced in-op when reduce_dgrad_in_op=True)."""
        if self.ctx.mode not in ("tesseract", "summa2d"):
            return set()
        names = {"wq", "wo", "w_up", "w_down"}
        if self.cfg.mlp_glu:
            names.add("w_gate")
        if self.kv_shard:
            names.update({"wk", "wv"})
        return names

    def cache_abstract(self, batch_global: int, seq_len: int, plan):
        """Global cache ShapeDtypeStructs + specs (decode layout)."""
        from jax import ShapeDtypeStruct as Sds
        from jax.sharding import PartitionSpec as P
        cfg = self.cfg
        if self.ctx.mode == "megatron1d":
            tok = "data" if plan.kind == "decode" else None
            kv_sp = P(None, tok, None, None, None)
        else:
            tok = (("data", "depth", "row") if plan.kind == "decode"
               else "data" if plan.kind == "decode_dp" else None)
            kv_sp = P(None, tok, None, "col" if self.kv_shard else None, None)
        shp = (cfg.num_layers, batch_global, seq_len, cfg.num_kv_heads, self.D)
        return ({"k": Sds(shp, self.cdt), "v": Sds(shp, self.cdt)},
                {"k": kv_sp, "v": kv_sp})

    def paged_cache_abstract(self, num_blocks: int, block_size: int, plan):
        """Global block-pool ShapeDtypeStructs + specs (paged decode layout).

        The pool is [L, P, bs, Hkv, D]: the physical-block axis P is sharded
        over the plan's KV group axes (serve/kv_cache.py keeps each batch
        slot's pages inside its group shard) and KV heads over col exactly
        like the dense decode cache — so reads stay device-local."""
        from jax import ShapeDtypeStruct as Sds
        from jax.sharding import PartitionSpec as P
        from ..core.ops import kv_group_axes
        cfg = self.cfg
        gaxes = kv_group_axes(self.ctx, plan)
        heads = None
        if self.ctx.mode != "megatron1d" and self.kv_shard:
            heads = "col"
        sp = P(None, gaxes if gaxes else None, None, heads, None)
        shp = (cfg.num_layers, num_blocks, block_size, cfg.num_kv_heads,
               self.D)
        return ({"k": Sds(shp, self.cdt), "v": Sds(shp, self.cdt)},
                {"k": sp, "v": sp})

    def _block_decode_paged(self, p, x, pool_l, table, pos, ops, *,
                            idx=None, pos_mask=None, kv_map=None):
        """Paged analogue of _block_decode: walk K/V pages through the
        block table, scatter the new token's K/V at each request's own
        position (mixed lengths in one fixed-shape batch).  ``idx`` /
        ``pos_mask`` / ``kv_map`` are position-only values hoisted out of
        the layer scan by decode_paged."""
        cfg = self.cfg
        with jax.named_scope(cm.ATTN):
            h = self._norm(ops, x, p["ln1"], p.get("ln1b"))
            q, k, v = self._qkv(p, h, ops, pos[:, None])
            pool_l = cm.paged_update(pool_l, table, pos, k, v, idx=idx)
            out = cm.paged_attention(q[:, 0], pool_l["k"], pool_l["v"],
                                     table, pos, kv_map=kv_map,
                                     local_window=cfg.local_window,
                                     pos_mask=pos_mask,
                                     impl=self.ctx.attn_impl)
            x = x + self._attn_out(p, out[:, None], ops,
                                   self._head_mask(ops))
        return self._mlp_sublayer(p, x, ops), pool_l

    def decode_paged(self, params, pool, table, ids, pos, ops):
        """One continuous-batching serve step against the paged block pool.

        ids: [B', 1] host token layout; table: [B_loc, nb] LOCAL block ids;
        pos: [B_loc] per-request positions.  Returns (full-vocab logits
        [B_loc, v_pad] for the serve sampler, updated pool)."""
        x = ops.embed(ids, params["embed"]).astype(self.cdt)
        cast = lambda t: jax.tree.map(lambda a: a.astype(self.cdt)
                                      if a.dtype == self.pdt and a.ndim > 1
                                      else a, t)
        # hoisted position-only work, shared by every layer in the scan
        bs = pool["k"].shape[2]
        idx = cm.paged_step_indices(table, pos, bs)
        pos_mask = cm.decode_pos_mask(pos, table.shape[1] * bs,
                                      self.cfg.local_window)
        kv_map = None if self.kv_shard else self._kv_map(ops)

        def body(xx, xs):
            bp, pl = xs
            y, pl2 = self._block_decode_paged(cast(bp), xx, pl, table, pos,
                                              ops, idx=idx,
                                              pos_mask=pos_mask,
                                              kv_map=kv_map)
            return y, pl2

        x, new_pool = lax.scan(body, x, (params["blocks"], pool))
        x = self._norm(ops, x, params["ln_f"], params.get("ln_fb"))
        logits = ops.head_logits(x, params["head"].astype(self.cdt),
                                 vocab_real=self.cfg.vocab_size)
        return logits, new_pool

    def _block_chunk_paged(self, p, x, pool_l, table, ops, *, positions,
                           valid, idx, mask, kv_map):
        """Chunked-prefill analogue of _block_decode_paged: scatter C new
        positions per slot into the pool, then attend the whole chunk
        against the request's pages (update-then-attend, so a COW donor's
        stale tail is overwritten before it could ever be visible — and the
        causal mask hides whatever this chunk didn't reach)."""
        with jax.named_scope(cm.ATTN):
            h = self._norm(ops, x, p["ln1"], p.get("ln1b"))
            q, k, v = self._qkv(p, h, ops, positions)
            pool_l = cm.paged_update_chunk(pool_l, table, positions, k, v,
                                           valid, idx=idx)
            kg, vg = cm.paged_gather(pool_l["k"], pool_l["v"], table,
                                     kv_map)
            out = cm.chunk_attention(q, kg, vg, mask=mask)
            x = x + self._attn_out(p, out, ops, self._head_mask(ops))
        return self._mlp_sublayer(p, x, ops), pool_l

    def _chunk_trunk(self, params, pool, table, ids, pos, lens, ops):
        """Shared chunk body for prefill_chunk_paged / verify_chunk_paged:
        scatter up to C positions per slot into the pool and run the layer
        scan, returning the final hidden states [B_loc, C, h] plus the
        updated pool.  The chunk attention is the fp32 full-score jnp path
        regardless of attn_impl (per-slot chunk starts are outside the
        flash kernel's static q_start contract)."""
        x = ops.embed(ids, params["embed"]).astype(self.cdt)
        cast = lambda t: jax.tree.map(lambda a: a.astype(self.cdt)
                                      if a.dtype == self.pdt and a.ndim > 1
                                      else a, t)
        # hoisted position-only work, shared by every layer in the scan
        bs = pool["k"].shape[2]
        C = x.shape[1]
        positions = pos[:, None] + jnp.arange(C, dtype=pos.dtype)
        valid = jnp.arange(C, dtype=lens.dtype)[None, :] < lens[:, None]
        idx = cm.paged_chunk_indices(table, positions, bs, valid)
        mask = cm.chunk_pos_mask(positions, table.shape[1] * bs,
                                 self.cfg.local_window) & valid[:, :, None]
        kv_map = None if self.kv_shard else self._kv_map(ops)

        def body(xx, xs):
            bp, pl = xs
            y, pl2 = self._block_chunk_paged(cast(bp), xx, pl, table, ops,
                                             positions=positions,
                                             valid=valid, idx=idx,
                                             mask=mask, kv_map=kv_map)
            return y, pl2

        x, new_pool = lax.scan(body, x, (params["blocks"], pool))
        x = self._norm(ops, x, params["ln_f"], params.get("ln_fb"))
        return x, new_pool

    def prefill_chunk_paged(self, params, pool, table, ids, pos, lens, ops):
        """Prefill C prompt positions per slot straight into the block pool.

        ids: [B', C] host token layout (chunk tokens, 0-padded); table:
        [B_loc, nb] LOCAL block ids; pos: [B_loc] chunk start positions;
        lens: [B_loc] valid positions this chunk (0 = idle slot).  Returns
        (full-vocab logits [B_loc, v_pad] at each slot's LAST valid chunk
        position — only meaningful for slots whose prompt completes this
        chunk — and the updated pool).  Decode steps keep their configured
        kernel; the chunk trunk is the fp32 jnp path (see _chunk_trunk)."""
        x, new_pool = self._chunk_trunk(params, pool, table, ids, pos, lens,
                                        ops)
        C = x.shape[1]
        last = jnp.clip(lens - 1, 0, C - 1)
        xi = jnp.take_along_axis(x, last[:, None, None], axis=1)
        logits = ops.head_logits(xi, params["head"].astype(self.cdt),
                                 vocab_real=self.cfg.vocab_size)
        return logits, new_pool

    def verify_chunk_paged(self, params, pool, table, ids, pos, lens, ops):
        """Speculative-verify forward: same chunk trunk as
        prefill_chunk_paged, but logits at EVERY chunk position.

        Row c of the output is the target distribution for the token at
        absolute position pos+c+1, i.e. the distribution a plain decode
        step would produce after committing ids[:, :c+1].  Accepted
        proposals' K/V are already committed in-place by the trunk's
        update-then-attend scatter; a rejected suffix needs no cleanup —
        the engine simply does not advance ``num_cached`` past the
        rejection point, so the stale pages beyond it are masked by
        position and overwritten by the next verify/prefill write (the
        same argument that makes COW donors' stale tails and
        eviction-replay safe).  Returns ([B_loc, C, v_pad] logits, pool)."""
        x, new_pool = self._chunk_trunk(params, pool, table, ids, pos, lens,
                                        ops)
        B, C, h = x.shape
        # head_logits expects [B', 1, h]; flatten chunk rows into the batch
        # axis (its token gather + local-batch dynamic-slice are layout-
        # compatible with the flattened batch: b_loc scales by C).
        logits = ops.head_logits(x.reshape(B * C, 1, h),
                                 params["head"].astype(self.cdt),
                                 vocab_real=self.cfg.vocab_size)
        return logits.reshape(B, C, -1), new_pool

    def prefill_cache_specs(self, ops):
        """Cache specs in prefill layout: batch over data, seq sharded over
        the sequence-parallel axes (kept local — no gathered-cache output)."""
        from jax.sharding import PartitionSpec as P
        if self.ctx.mode == "megatron1d":
            kv_sp = P(None, "data", "col", None, None)
        else:
            kv_sp = P(None, "data", ("depth", "row"),
                      "col" if self.kv_shard else None, None)
        return {"k": kv_sp, "v": kv_sp}

    def _block_prefill_attnonly(self, p, x, ops, full_kv_pos):
        return self._block_train_attn(p, x, ops, full_kv_pos)

    def _block_prefill(self, p, x, ops, full_kv_pos):
        """Like _block_train but also emits this block's seq-local K/V
        (prefill cache stays sequence-sharded — see prefill_cache_specs)."""
        x, kv = self._block_train_attn(p, x, ops, full_kv_pos)
        return self._mlp_sublayer(p, x, ops), kv

    def batch_extras(self, shape):
        """Extra (modality) inputs: {name: (ShapeDtypeStruct, host_spec)}."""
        return {}

    def prefill(self, params, batch, ops):
        """Process a full prompt; returns (next_ids, cache-in-prefill-layout).

        With an optional ``batch["lengths"]`` ([B'] true prompt lengths for
        right-padded prompts — the serve engine's bucketed prefill) the head
        runs at each request's own last position and the first slot of the
        return is full-vocab LOGITS [B, v_pad] for the sampler instead of
        greedy ids."""
        x = ops.embed(batch["tokens"], params["embed"]).astype(self.cdt)
        S_loc = x.shape[1]
        n_seq = (self.ctx.depth * self.ctx.rows if ops.plan.seq_sharded else 1)
        if self.ctx.mode == "megatron1d" and ops.plan.seq_sharded:
            n_seq = self.ctx.cols
        full_kv_pos = jnp.arange(S_loc * n_seq)
        cast = lambda t: jax.tree.map(lambda a: a.astype(self.cdt)
                                      if a.dtype == self.pdt and a.ndim > 1
                                      else a, t)

        def body(xx, bp):
            y, kv = self._block_prefill(cast(bp), xx, ops, full_kv_pos)
            return y, kv

        body = maybe_remat(body, self.run)
        x, (kc, vc) = lax.scan(body, x, params["blocks"])
        x = self._norm(ops, x, params["ln_f"], params.get("ln_fb"))
        if "lengths" in batch:
            x_last = last_token_at(ops, x, self.ctx, batch["lengths"])
            logits = ops.head_logits(x_last,
                                     params["head"].astype(self.cdt),
                                     vocab_real=self.cfg.vocab_size,
                                     tokens_sharded=False)
            return logits, {"k": kc, "v": vc}
        x_last = ops_last_token(ops, x, self.ctx)
        ids = ops.head_sample(x_last, params["head"].astype(self.cdt),
                              vocab_real=self.cfg.vocab_size)
        return ids, {"k": kc, "v": vc}

    def _block_decode_attnonly(self, p, x, cache_l, pos, ops, *,
                               pos_mask=None, kv_map=None):
        cfg = self.cfg
        with jax.named_scope(cm.ATTN):
            h = self._norm(ops, x, p["ln1"], p.get("ln1b"))
            positions = jnp.full((1,), pos, jnp.int32)
            q, k, v = self._qkv(p, h, ops, positions)
            cache_l = cm.cache_update(cache_l, k, v, pos)
            if kv_map is None and not self.kv_shard:
                kv_map = self._kv_map(ops)
            out = cm.decode_attention(q[:, 0], cache_l["k"], cache_l["v"],
                                      cur_pos=pos, kv_map=kv_map,
                                      local_window=cfg.local_window,
                                      pos_mask=pos_mask,
                                      impl=self.ctx.attn_impl)
            out = out[:, None]                      # [B, 1, H, D]
            x = x + self._attn_out(p, out, ops, self._head_mask(ops))
        return x, cache_l

    def _block_decode(self, p, x, cache_l, pos, ops, **hoisted):
        x, cache_l = self._block_decode_attnonly(p, x, cache_l, pos, ops,
                                                 **hoisted)
        return self._mlp_sublayer(p, x, ops), cache_l

    def decode(self, params, cache, ids, pos, ops):
        """One serve step: ids [B', 1] host-layout; returns (new_ids, cache)."""
        x = ops.embed(ids, params["embed"]).astype(self.cdt)
        cast = lambda t: jax.tree.map(lambda a: a.astype(self.cdt)
                                      if a.dtype == self.pdt and a.ndim > 1
                                      else a, t)
        pos_mask = cm.decode_pos_mask(pos, cache["k"].shape[2],
                                      self.cfg.local_window)
        kv_map = None if self.kv_shard else self._kv_map(ops)

        def body(xx, xs):
            bp, cl = xs
            y, cl2 = self._block_decode(cast(bp), xx, cl, pos, ops,
                                        pos_mask=pos_mask, kv_map=kv_map)
            return y, cl2

        x, new_cache = lax.scan(body, x, (params["blocks"], cache))
        x = self._norm(ops, x, params["ln_f"], params.get("ln_fb"))
        nids = ops.head_sample(x, params["head"].astype(self.cdt),
                               vocab_real=self.cfg.vocab_size)
        return nids, new_cache


def ops_last_token(ops, x, ctx):
    """[B, S_loc, f] -> [B, 1, f]: the true last token, replicated over the
    sequence-sharding axes."""
    if not ops.plan.seq_sharded:
        return x[:, -1:]
    from ..core.collectives import all_gather_inv
    lt = x[:, -1:]
    if ctx.mode == "megatron1d":
        g = all_gather_inv(lt, ctx.axis_col)
    else:
        g = all_gather_inv(lt, (ctx.axis_depth, ctx.axis_row))
    return g[-1]


def last_token_at(ops, x, ctx, lengths):
    """[B, S_loc, f] + per-request true lengths -> [B, 1, f] hidden states at
    position lengths-1, invariant over the sequence-sharding axes.

    The bucketed serve prefill right-pads prompts, so "last token" is a
    per-request position, not column -1.  Each seq shard contributes its own
    slice (zeros elsewhere) and one small psum replicates the result."""
    idx = lengths - 1
    if not ops.plan.seq_sharded:
        return jnp.take_along_axis(x, idx[:, None, None], axis=1)
    S_loc = x.shape[1]
    local = idx - ops.seq_shard_index() * S_loc
    valid = (local >= 0) & (local < S_loc)
    safe = jnp.clip(local, 0, S_loc - 1)
    xl = jnp.take_along_axis(x, safe[:, None, None], axis=1)
    xl = jnp.where(valid[:, None, None], xl, jnp.zeros_like(xl))
    return lax.psum(xl, ctx.seq_shard_axes)
