"""Plain reference of a llama-style decoder, its loss, gradients and AdamW.

Written from the published architecture, in straightforward ``jax.numpy``
at float32 with every matrix product at ``Precision.HIGHEST``, so that on a
TPU it is not silently computed in bfloat16.  It imports nothing of the
system under test and is given only what the benchmark itself made: the
weights of ``weights.make_logical`` and the token ids of the traffic.

Blocks of the architecture (Llama / Yi / SmolLM):
  x = embed[ids]
  per layer:  x += Wo · attn(rope(Wq·n1(x)), rope(Wk·n1(x)), Wv·n1(x))
              x += Wdown · (silu(Wgate·n2(x)) * Wup·n2(x))
  logits = head · nf(x)
with RMSNorm ``n(x) = x / sqrt(mean(x^2) + eps) * (1 + w)``, rotary
embeddings on the two halves of each head (the "rotate half" form), and
grouped-query attention in which q head ``i`` reads kv head
``i // (H / Hkv)``.

One departure from the published form: an RMSNorm gain is stored as its
offset ``w`` from one, because that is the parameter the configuration's
optimizer decays (AdamW decays every leaf).  The forward pass is the same.

``Numerics`` selects how each matrix product is computed: ``highest`` is
the reference; ``bf16`` and ``fp8`` are the lower-precision controls that
the correctness limits are shown to fail on.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


@dataclass(frozen=True)
class Arch:
    """The published sizes the reference needs (from a config file)."""
    layers: int
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int
    rope_theta: float
    eps: float

    @classmethod
    def from_config(cls, cfg: dict) -> "Arch":
        m = cfg["model"]
        heads = m["num_attention_heads"]
        return cls(layers=m["num_hidden_layers"], hidden=m["hidden_size"],
                   heads=heads, kv_heads=m["num_key_value_heads"],
                   head_dim=m.get("head_dim") or m["hidden_size"] // heads,
                   ffn=m["intermediate_size"], vocab=m["vocab_size"],
                   rope_theta=float(m["rope_theta"]),
                   eps=float(m["rms_norm_eps"]))


class Numerics:
    """How a matrix product and the residual stream are computed.

    highest: float32 operands, HIGHEST precision (six bf16 passes on TPU).
    bf16:    operands and the residual stream rounded to bfloat16, products
             accumulated in float32 (a float32 configuration served in bf16).
    fp8:     operands scaled per tensor to float8 e4m3 and accumulated in
             float32 (a bfloat16 configuration computed in fp8).
    """

    def __init__(self, mode: str = "highest"):
        if mode not in ("highest", "bf16", "fp8"):
            raise ValueError(f"unknown numerics {mode!r}")
        self.mode = mode

    def _q(self, a):
        if self.mode == "bf16":
            return a.astype(jnp.bfloat16).astype(jnp.float32)
        if self.mode == "fp8":
            scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / FP8_MAX
            scale = lax.stop_gradient(scale)
            q = (a / scale).astype(FP8).astype(jnp.float32) * scale
            # straight-through: the control's gradient flows as if unrounded
            return a + lax.stop_gradient(q - a)
        return a

    def mm(self, subs, a, b):
        return jnp.einsum(subs, self._q(a), self._q(b), precision=HIGHEST,
                          preferred_element_type=jnp.float32)

    def act(self, x):
        """Rounding of the residual stream between layers."""
        if self.mode == "bf16":
            return x.astype(jnp.bfloat16).astype(jnp.float32)
        return x


def rmsnorm(x, w, eps):
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def rope(x, theta):
    """x [B, T, H, D]: rotate the two halves of each head by position."""
    T, D = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, num: Numerics, q_block: int = 512):
    """Causal softmax attention, exact, computed a block of queries at a
    time so that the [T, T] scores of one block fit.  q [B,T,H,D],
    k/v [B,T,H,D] (kv heads already repeated)."""
    B, T, H, D = q.shape
    qb = min(q_block, T)
    if T % qb:
        qb = T
    scale = 1.0 / math.sqrt(D)
    kpos = jnp.arange(T)

    def one(i):
        qi = lax.dynamic_slice_in_dim(q, i * qb, qb, axis=1)
        s = num.mm("bqhd,bkhd->bhqk", qi, k) * scale
        qpos = i * qb + jnp.arange(qb)
        s = jnp.where(qpos[:, None] >= kpos[None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return num.mm("bhqk,bkhd->bqhd", p, v)

    out = lax.map(one, jnp.arange(T // qb))          # [n, B, qb, H, D]
    return jnp.moveaxis(out, 0, 1).reshape(B, T, H, D)


def layer(arch: Arch, num: Numerics, x, p):
    B, T, _ = x.shape
    H, Hkv, D = arch.heads, arch.kv_heads, arch.head_dim
    h = rmsnorm(x, p["ln1"], arch.eps)
    q = num.mm("bth,hf->btf", h, p["wq"]).reshape(B, T, H, D)
    k = num.mm("bth,hf->btf", h, p["wk"]).reshape(B, T, Hkv, D)
    v = num.mm("bth,hf->btf", h, p["wv"]).reshape(B, T, Hkv, D)
    q, k = rope(q, arch.rope_theta), rope(k, arch.rope_theta)
    g = H // Hkv
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    o = attention(q, k, v, num).reshape(B, T, H * D)
    x = num.act(x + num.mm("btf,fh->bth", o, p["wo"]))
    h2 = rmsnorm(x, p["ln2"], arch.eps)
    a = jax.nn.silu(num.mm("bth,hf->btf", h2, p["w_gate"]))
    u = num.mm("bth,hf->btf", h2, p["w_up"])
    return num.act(x + num.mm("btf,fh->bth", a * u, p["w_down"]))


def hidden(arch: Arch, num: Numerics, params, ids):
    """Final normed hidden states [B, T, h] of token ids [B, T]."""
    x = num.act(params["embed"][ids].astype(jnp.float32))
    body = jax.checkpoint(lambda xx, p: (layer(arch, num, xx, p), None))
    x, _ = lax.scan(body, x, params["layers"])
    return rmsnorm(x, params["ln_f"], arch.eps)


def logits(arch: Arch, num: Numerics, params, ids):
    """[B, T, V] float32 next-token logits."""
    return num.mm("bth,vh->btv", hidden(arch, num, params, ids),
                  params["head"])


def loss_sum(arch: Arch, num: Numerics, params, ids, labels,
             chunk: int = 1024):
    """Summed cross-entropy of ``labels`` under the logits of ``ids``; the
    vocabulary projection runs a chunk of positions at a time."""
    x = hidden(arch, num, params, ids)
    B, T, h = x.shape
    c = min(chunk, T)
    if T % c:
        c = T
    xs = jnp.moveaxis(x.reshape(B, T // c, c, h), 1, 0)
    ls = jnp.moveaxis(labels.reshape(B, T // c, c), 1, 0)

    @jax.checkpoint
    def one(carry, xl):
        xc, lc = xl
        lg = num.mm("bth,vh->btv", xc, params["head"])
        lse = jax.nn.logsumexp(lg, axis=-1)
        ll = jnp.take_along_axis(lg, lc[..., None], axis=-1)[..., 0]
        return carry + jnp.sum(lse - ll), None

    total, _ = lax.scan(one, jnp.float32(0), (xs, ls))
    return total


def loss_and_grads(arch: Arch, num: Numerics, params, ids, labels,
                   rows: int):
    """Mean loss over every position of the batch and its gradients,
    computed ``rows`` sequences at a time and summed."""
    B, T = ids.shape
    n = B // rows
    grad_fn = jax.value_and_grad(
        lambda p, i, l: loss_sum(arch, num, p, i, l))

    def block(carry, il):
        s, g = carry
        ls, gb = grad_fn(params, *il)
        return (s + ls, jax.tree.map(jnp.add, g, gb)), None

    zeros = jax.tree.map(jnp.zeros_like, params)
    blocks = (ids.reshape(n, rows, T), labels.reshape(n, rows, T))
    (s, g), _ = lax.scan(block, (jnp.float32(0), zeros), blocks)
    count = B * T
    return s / count, jax.tree.map(lambda x: x / count, g)


@dataclass(frozen=True)
class Recipe:
    """The configuration's optimizer: AdamW with global-norm clipping and
    a linear warm-up into a cosine decay."""
    lr: float
    b1: float
    b2: float
    eps: float
    weight_decay: float
    grad_clip: float
    warmup: int
    total: int
    min_frac: float

    @classmethod
    def from_config(cls, cfg: dict) -> "Recipe":
        r = cfg["optimizer"]
        return cls(lr=r["lr"], b1=r["b1"], b2=r["b2"], eps=r["eps"],
                   weight_decay=r["weight_decay"], grad_clip=r["grad_clip"],
                   warmup=r["warmup_steps"], total=r["total_steps"],
                   min_frac=r["min_lr_frac"])

    def lr_at(self, step):
        """Learning rate of the update made at optimizer step ``step``
        (0-based: the first update uses step 0)."""
        s = jnp.asarray(step, jnp.float32)
        warm = self.lr * s / max(self.warmup, 1)
        prog = jnp.clip((s - self.warmup) / max(self.total - self.warmup, 1),
                        0.0, 1.0)
        cos = self.lr * (self.min_frac + (1 - self.min_frac) * 0.5
                         * (1 + jnp.cos(jnp.pi * prog)))
        return jnp.where(s < self.warmup, warm, cos)


def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x))
                        for x in jax.tree.leaves(tree)))


def adamw_step(recipe: Recipe, params, grads, m, v, step: int):
    """One clipped AdamW update.  Returns (params, m, v, clipped grads)."""
    gn = global_norm(grads)
    scale = jnp.minimum(1.0, recipe.grad_clip / (gn + 1e-6))
    g = jax.tree.map(lambda x: x * scale, grads)
    t = step + 1
    c1, c2 = 1.0 - recipe.b1 ** t, 1.0 - recipe.b2 ** t
    m = jax.tree.map(lambda a, b: recipe.b1 * a + (1 - recipe.b1) * b, m, g)
    v = jax.tree.map(lambda a, b: recipe.b2 * a + (1 - recipe.b2) * b * b,
                     v, g)
    lr = recipe.lr_at(step)
    params = jax.tree.map(
        lambda p, a, b: p - lr * ((a / c1) / (jnp.sqrt(b / c2) + recipe.eps)
                                  + recipe.weight_decay * p),
        params, m, v)
    return params, m, v, g
