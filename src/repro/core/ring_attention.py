"""Ring / striped flash attention over a mesh-axis ring (DESIGN.md §15).

Sequence-sharded attention: each device keeps its resident Q shard and the
K/V shards stream around the ring via the same Cannon-style double-buffered
``ppermute`` shift the SUMMA matmul schedule uses (core/summa.py).  Every
ring step is one flash call (kernels/flash_attention.py per-step entries)
whose ``(out, logsumexp)`` output is exactly the online-softmax carry the
ring needs: partial outputs merge with a numerically-stable pairwise
logsumexp combine

    lse  = logaddexp(lse_a, lse_b)
    out  = out_a * exp(lse_a - lse) + out_b * exp(lse_b - lse)

(fully-masked steps produce exact-zero out and a floored finite lse, so the
merge is NaN-free).  Backward is a full ``custom_vjp`` single-pass ring:
K/V re-stream exactly as forward while per-shard dK/dV partials ride
shift-and-add accumulator rings that deliver each shard's gradient back to
its home device — dQ accumulates locally, so lse/delta never leave the
device.  Per layer that is 2(n-1) K/V ppermutes per direction plus the
accumulator ring (2(n-1) in-loop shifts + 2 final deliveries).

Two sharding variants (``variant``):

- ``ring``:    contiguous shards; shard r holds global rows r*L..(r+1)*L-1.
  Causal masking is positional (traced relative positions, no static block
  skipping), so late ranks do ~n/2 more mask-visible work than early ones.
- ``striped``: round-robin shards; shard r holds global rows r + n*arange(L)
  (tokens pre-permuted by ``stripe_permutation``).  For q from shard a and
  kv from shard b the causal test  a + n*i >= b + n*k  collapses to the
  LOCAL triangle  i >= k + (1 if b > a else 0), so every (q-shard, kv-shard)
  step does the same (full lower triangle, +-one row) amount of work AND the
  static ``q_start=0`` block-skip bounds of the flash kernel stay valid —
  causal load balance without giving up block skipping.  Train-only
  (causal, no window).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from .collectives import _ppermute_linear, axis_linear_index, axis_size
from .summa import _perm_shift
from ..kernels.ops import interpret_mode
from ..kernels.flash_attention import (
    _M_FLOOR, NEG_INF, flash_dkv_step, flash_dq_step, flash_fwd_step)

_F32 = jnp.float32


# ---------------------------------------------------------------------------
# striped permutation (host-side; applied to tokens/labels before shard_map)
# ---------------------------------------------------------------------------

def stripe_permutation(T: int, n: int) -> np.ndarray:
    """Gather indices such that ``x[..., perm]`` round-robins T rows over n
    contiguous shards: permuted row r*L + i holds original row i*n + r, i.e.
    shard r (the r-th contiguous L-slice) holds global positions
    r + n*arange(L)."""
    if T % n:
        raise ValueError(f"stripe: T={T} not divisible by n={n}")
    return np.arange(T).reshape(T // n, n).T.reshape(-1)


def unstripe_permutation(T: int, n: int) -> np.ndarray:
    """Inverse of ``stripe_permutation``: x[perm][inv] == x."""
    if T % n:
        raise ValueError(f"unstripe: T={T} not divisible by n={n}")
    return np.arange(T).reshape(n, T // n).T.reshape(-1)


def shard_positions(L: int, n: int, rank, variant: str):
    """Global row positions of a shard ([L] int32; ``rank`` may be traced)."""
    ar = jnp.arange(L, dtype=jnp.int32)
    if variant == "striped":
        return rank + n * ar
    return rank * L + ar


# ---------------------------------------------------------------------------
# static spec (hashable: rides custom_vjp nondiff)
# ---------------------------------------------------------------------------

class RingSpec(NamedTuple):
    axes: tuple            # mesh axes forming the ring (lexicographic order)
    n: int                 # ring size == prod(axis sizes)
    variant: str           # "ring" | "striped"
    causal: bool
    window: int            # 0 = unbounded (striped requires 0)
    scale: Optional[float]
    impl: str              # "jnp" | "pallas"
    interpret: bool


def _step_mask_args(spec: RingSpec, L: int, Lk: int, rank, src):
    """(q_pos, q_start) for the step attending q@rank against kv@src."""
    ar = jnp.arange(L, dtype=jnp.int32)
    if spec.variant == "striped":
        # local triangle, strict when the kv shard is a later stripe; static
        # q_start=0 keeps the kernel's causal block-skip bounds valid
        return ar - (src > rank).astype(jnp.int32), 0
    # contiguous: traced relative positions (kv cols live at 0..Lk-1)
    return (rank - src) * Lk + ar, None


# ---------------------------------------------------------------------------
# per-step attention (pallas kernel or jnp reference), matching kernel
# conventions: fp32 scores, exact-zero masked rows, floored finite lse
# ---------------------------------------------------------------------------

def _jnp_sp(spec: RingSpec, q, k, q_pos):
    """Masked fp32 score matrix for one step ([B, Hq, L, Lk])."""
    g = q.shape[1] // k.shape[1]
    ke = jnp.repeat(k, g, axis=1) if g > 1 else k
    scale = spec.scale if spec.scale is not None else 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(_F32), ke.astype(_F32)) * scale
    cols = jnp.arange(k.shape[2], dtype=jnp.int32)
    mask = jnp.ones((q.shape[2], k.shape[2]), dtype=bool)
    if spec.causal:
        mask = mask & (q_pos[:, None] >= cols[None, :])
    if spec.window > 0:
        mask = mask & (cols[None, :] > q_pos[:, None] - spec.window)
    return jnp.where(mask[None, None], s, NEG_INF)


def _step_fwd(spec: RingSpec, q, k, v, rank, src):
    q_pos, q_start = _step_mask_args(spec, q.shape[2], k.shape[2], rank, src)
    if spec.impl == "pallas":
        return flash_fwd_step(
            q, k, v, causal=spec.causal, local_window=spec.window,
            q_pos=q_pos, q_start=q_start, softmax_scale=spec.scale,
            interpret=spec.interpret)
    s = _jnp_sp(spec, q, k, q_pos)
    m = jnp.maximum(jnp.max(s, axis=-1), _M_FLOOR)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    ls = jnp.where(l == 0.0, 1.0, l)
    g = q.shape[1] // v.shape[1]
    ve = jnp.repeat(v, g, axis=1) if g > 1 else v
    o = jnp.einsum("bhqk,bhkd->bhqd", p, ve.astype(_F32)) / ls[..., None]
    return o.astype(q.dtype), m + jnp.log(ls)


def _step_bwd(spec: RingSpec, q, k, v, dout, lse, delta, rank, src):
    """(dq, dk, dv) contributions of one (q@rank, kv@src) step, given the
    GLOBAL merged lse and delta = sum(dout*out) — the standard flash bwd
    identities hold per KV partition with global normalizers."""
    q_pos, q_start = _step_mask_args(spec, q.shape[2], k.shape[2], rank, src)
    if spec.impl == "pallas":
        kw = dict(causal=spec.causal, local_window=spec.window, q_pos=q_pos,
                  q_start=q_start, softmax_scale=spec.scale,
                  interpret=spec.interpret)
        dq = flash_dq_step(q, k, v, dout, lse, delta, **kw)
        dk, dv = flash_dkv_step(q, k, v, dout, lse, delta, **kw)
        return dq, dk, dv
    scale = spec.scale if spec.scale is not None else 1.0 / np.sqrt(q.shape[-1])
    g = q.shape[1] // k.shape[1]
    ke = jnp.repeat(k, g, axis=1) if g > 1 else k
    ve = jnp.repeat(v, g, axis=1) if g > 1 else v
    s = _jnp_sp(spec, q, k, q_pos)
    p = jnp.exp(s - lse[..., None])            # globally-normalized probs
    do = dout.astype(_F32)
    dv = jnp.einsum("bhqk,bhqd->bhkd", p, do)
    dp = jnp.einsum("bhqd,bhkd->bhqk", do, ve.astype(_F32))
    ds = p * (dp - delta[..., None])
    dq = jnp.einsum("bhqk,bhkd->bhqd", ds, ke.astype(_F32)) * scale
    dk = jnp.einsum("bhqk,bhqd->bhkd", ds, q.astype(_F32)) * scale
    if g > 1:
        B, Hq, Lk, D = dk.shape[0], dk.shape[1], dk.shape[2], dk.shape[3]
        dk = dk.reshape(B, Hq // g, g, Lk, D).sum(axis=2)
        dv = dv.reshape(B, Hq // g, g, Lk, dv.shape[-1]).sum(axis=2)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _merge(o, lse, ot, lt):
    """Pairwise logsumexp combine of two normalized partials (fp32 o)."""
    lnew = jnp.logaddexp(lse, lt)
    o = (o * jnp.exp(lse - lnew)[..., None]
         + ot.astype(_F32) * jnp.exp(lt - lnew)[..., None])
    return o, lnew


# ---------------------------------------------------------------------------
# the ring custom_vjp
# ---------------------------------------------------------------------------

def _shift(spec: RingSpec, x):
    return _ppermute_linear(x, spec.axes, _perm_shift(spec.n))


def _ring_fwd_impl(spec: RingSpec, q, k, v):
    rank = axis_linear_index(spec.axes)
    n = spec.n
    o = lse = None
    kc, vc = k, v
    for t in range(n):
        if t < n - 1:               # issue the shift before the compute so
            kn = _shift(spec, kc)   # the next shard is in flight while this
            vn = _shift(spec, vc)   # step's flash call runs (summa idiom)
        ot, lt = _step_fwd(spec, q, kc, vc, rank, (rank + t) % n)
        if t == 0:
            o, lse = ot.astype(_F32), lt
        else:
            o, lse = _merge(o, lse, ot, lt)
        if t < n - 1:
            kc, vc = kn, vn
    return o.astype(q.dtype), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ring(spec: RingSpec, q, k, v):
    out, _ = _ring_fwd_impl(spec, q, k, v)
    return out


def _ring_vjp_fwd(spec, q, k, v):
    out, lse = _ring_fwd_impl(spec, q, k, v)
    return out, (q, k, v, out, lse)


def _ring_vjp_bwd(spec, res, dout):
    q, k, v, out, lse = res
    n, rank = spec.n, axis_linear_index(spec.axes)
    delta = jnp.sum(dout.astype(_F32) * out.astype(_F32), axis=-1)
    dq = jnp.zeros(q.shape, _F32)
    kc, vc = k, v
    dka = dva = None
    for t in range(n):
        if t < n - 1:
            kn, vn = _shift(spec, kc), _shift(spec, vc)
        src = (rank + t) % n
        dqt, dkt, dvt = _step_bwd(spec, q, kc, vc, dout, lse, delta,
                                  rank, src)
        dq = dq + dqt.astype(_F32)
        if t == 0:
            dka, dva = dkt.astype(_F32), dvt.astype(_F32)
        else:
            # the accumulator ring travels WITH the K/V shards: after this
            # shift the partial for shard s sits wherever shard s's K/V just
            # left, so each device adds its own contribution to s in turn
            dka = _shift(spec, dka) + dkt.astype(_F32)
            dva = _shift(spec, dva) + dvt.astype(_F32)
        if t < n - 1:
            kc, vc = kn, vn
    if n > 1:                       # one last hop delivers shard r's dK/dV
        dka, dva = _shift(spec, dka), _shift(spec, dva)
    return dq.astype(q.dtype), dka.astype(k.dtype), dva.astype(v.dtype)


_ring.defvjp(_ring_vjp_fwd, _ring_vjp_bwd)


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

def ring_attention(q, k, v, *, axes, variant: str = "ring", causal: bool = True,
                   local_window: int = 0, softmax_scale=None,
                   impl: str = "jnp", interpret: bool | None = None):
    """Seq-sharded attention over the ring formed by mesh ``axes``.

    q: [B, Hq, L, D], k: [B, Hkv, Lk, D], v: [B, Hkv, Lk, Dv] — the LOCAL
    shards, kernel layout, inside shard_map.  Shard r holds global rows
    r*L..(r+1)*L-1 (``variant="ring"``) or r + n*arange(L) (``"striped"``,
    tokens pre-permuted with ``stripe_permutation``).  Returns the local
    [B, Hq, L, Dv] output shard; differentiable (full custom_vjp).
    """
    if variant not in ("ring", "striped"):
        raise ValueError(f"ring_attention variant must be 'ring' or "
                         f"'striped', got {variant!r}")
    if variant == "striped" and (not causal or local_window > 0):
        raise ValueError("striped ring attention requires causal=True and "
                         "local_window=0 (window breaks the stripe balance)")
    if q.shape[2] != k.shape[2]:
        raise ValueError(f"ring_attention needs equal q/kv shard lengths, "
                         f"got {q.shape[2]} vs {k.shape[2]}")
    n = axis_size(axes)
    spec = RingSpec(axes=tuple(axes), n=int(n), variant=variant,
                    causal=bool(causal), window=int(local_window),
                    scale=(None if softmax_scale is None
                           else float(softmax_scale)),
                    impl=impl,
                    interpret=interpret_mode() if interpret is None
                    else bool(interpret))
    return _ring(spec, q, k, v)


# ---------------------------------------------------------------------------
# comm model hooks (roofline/analysis.py and analysis/shardcheck.py gate
# against these EXACT counts/bytes)
# ---------------------------------------------------------------------------

def ring_ppermute_counts(n: int, *, train: bool = True,
                         remat_replay: bool = True) -> dict:
    """ppermute issue counts per attention call (per layer, per device).

    fwd: (n-1) shifts of {K, V}.  bwd: the same K/V re-stream, plus the
    dK/dV accumulator rings — (n-1) in-loop shifts and 1 final delivery
    each — plus (with remat) the fwd replay."""
    fwd = 2 * (n - 1)
    if not train:
        return dict(fwd=fwd, bwd=0, total=fwd)
    bwd = 2 * (n - 1) + 2 * (n - 1) + (2 if n > 1 else 0)
    if remat_replay:
        bwd += fwd
    return dict(fwd=fwd, bwd=bwd, total=fwd + bwd)


def ring_ppermute_bytes(n: int, *, kv_block_bytes: int, acc_block_bytes: int,
                        train: bool = True, remat_replay: bool = True) -> dict:
    """Wire bytes per attention call (per layer, per device), matching the
    collective-IR convention that a ppermute moves its full operand.

    ``kv_block_bytes``: bytes of ONE K (== one V) local shard in the compute
    dtype; ``acc_block_bytes``: bytes of one fp32 dK (== dV) accumulator."""
    fwd = 2 * (n - 1) * kv_block_bytes
    if not train:
        return dict(fwd=fwd, bwd=0, total=fwd)
    bwd = 2 * (n - 1) * kv_block_bytes
    bwd += (2 * (n - 1) + (2 if n > 1 else 0)) * acc_block_bytes
    if remat_replay:
        bwd += fwd
    return dict(fwd=fwd, bwd=bwd, total=fwd + bwd)
