"""Property-based tests (hypothesis) on system invariants."""
import math

import pytest

hypothesis = pytest.importorskip("hypothesis")
import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings

from repro.configs.base import round_up
from repro.models import common as cm

hypothesis.settings.register_profile(
    "ci", settings(deadline=None, max_examples=20))
hypothesis.settings.load_profile("ci")


@given(st.integers(1, 10_000_000), st.integers(1, 4096))
def test_round_up(x, m):
    r = round_up(x, m)
    assert r >= x and r % m == 0 and r - x < m


@given(st.integers(1, 4), st.integers(1, 3), st.integers(1, 4),
       st.sampled_from([16, 24, 32]), st.booleans(), st.integers(0, 3))
def test_blockwise_attention_matches_naive(b, hkv, g, d, causal, seed):
    """Streaming (flash-style) attention == naive softmax attention for
    arbitrary chunkings, GQA groupings, and causal flags."""
    tq, tk = 16, 16
    key = jax.random.PRNGKey(seed)
    q = jax.random.normal(key, (b, tq, hkv * g, d), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, tk, hkv, d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, tk, hkv, d))
    qp = jnp.arange(tq)
    kp = jnp.arange(tk)
    got = cm.blockwise_attention(q, k, v, q_pos=qp, kv_pos=kp, causal=causal,
                                 q_chunk=8, kv_chunk=4)
    # naive
    qg = np.asarray(q).reshape(b, tq, hkv, g, d)
    s = np.einsum("bqhgd,bkhd->bhgqk", qg, np.asarray(k)) / math.sqrt(d)
    if causal:
        mask = np.arange(tq)[:, None] >= np.arange(tk)[None, :]
        s = np.where(mask[None, None, None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.einsum("bhgqk,bkhd->bqhgd", p, np.asarray(v)).reshape(
        b, tq, hkv * g, d)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-4)


@given(st.integers(0, 3), st.sampled_from([4, 8, 16]),
       st.sampled_from([8, 16, 32]))
def test_ssd_chunk_size_invariance(seed, chunk, t):
    """SSD output must not depend on the chunk size."""
    from repro.models.ssm import ssd_chunked
    key = jax.random.PRNGKey(seed)
    B, H, P, N = 1, 2, 8, 4
    x = jax.random.normal(key, (B, t, H, P), jnp.float32)
    la = -jnp.abs(jax.random.normal(jax.random.fold_in(key, 1), (B, t, H))) * 0.2
    Bm = jax.random.normal(jax.random.fold_in(key, 2), (B, t, N))
    Cm = jax.random.normal(jax.random.fold_in(key, 3), (B, t, N))
    y1, h1, _ = ssd_chunked(x, la, Bm, Cm, chunk)
    y2, h2, _ = ssd_chunked(x, la, Bm, Cm, t)   # single chunk
    np.testing.assert_allclose(y1, y2, rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(h1, h2, rtol=2e-3, atol=2e-3)


@given(st.integers(0, 5))
def test_rope_preserves_norm_and_relativity(seed):
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (1, 8, 2, 16), jnp.float32)
    pos = jnp.arange(8)
    y = cm.apply_rope(x, pos)
    # rotation preserves per-pair norms
    np.testing.assert_allclose(np.linalg.norm(np.asarray(y), axis=-1),
                               np.linalg.norm(np.asarray(x), axis=-1),
                               rtol=1e-5, atol=1e-5)
    # relative property: <R(p)q, R(k)x> depends only on p-k
    q = jax.random.normal(jax.random.fold_in(key, 1), (1, 1, 1, 16))
    k = jax.random.normal(jax.random.fold_in(key, 2), (1, 1, 1, 16))
    def dot_at(p, kk):
        qr = cm.apply_rope(q, jnp.array([p]))
        kr = cm.apply_rope(k, jnp.array([kk]))
        return float(jnp.sum(qr * kr))
    np.testing.assert_allclose(dot_at(5, 3), dot_at(7, 5), rtol=1e-4, atol=1e-4)


@given(st.sampled_from([4, 8, 16, 64]), st.integers(0, 2))
def test_ce_loss_chunk_invariance(chunk, seed):
    """Chunked CE must not depend on the chunk size (1-device ctx)."""
    from repro.configs.base import RunConfig, ShapeSpec
    from repro.core.api import ParallelContext
    from repro.core.mesh import logical_mesh
    from repro.core.ops import Plan, make_ops
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    ctx = ParallelContext(mode="tesseract", data=1, depth=1, rows=1, cols=1)
    mesh = logical_mesh(ctx)
    ops = make_ops(ctx, Plan.for_shape("train"))
    key = jax.random.PRNGKey(seed)
    E, h, v = 64, 16, 40
    x = jax.random.normal(key, (4, 16, h), jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (v, h), jnp.float32)
    labels = jax.random.randint(jax.random.fold_in(key, 2), (4, 16), 0, 37)

    def make(c):
        def f(x_, w_, l_):
            ls, cnt = ops.ce_loss(x_, w_, l_, vocab_real=37, loss_chunk=c)
            # ce_loss leaves the sums varying over data; reduce like the
            # models do
            return jax.lax.psum(ls, "data") / jax.lax.psum(cnt, "data")
        from jax import shard_map
        return shard_map(f, mesh=mesh,
                             in_specs=(P(None, None, None), P(None, None),
                                       P(None, None)),
                             out_specs=P())

    loss = float(make(chunk)(x, w, labels))
    ref = float(make(1024)(x, w, labels))
    np.testing.assert_allclose(loss, ref, rtol=1e-5, atol=1e-6)
    # cross-check against plain softmax CE (pad vocab masked to -inf)
    logits = np.asarray(x).reshape(64, h) @ np.asarray(w).T
    logits = np.where(np.arange(v)[None, :] < 37, logits, -np.inf)
    lse = np.log(np.exp(logits - logits.max(-1, keepdims=True)).sum(-1)) + \
        logits.max(-1)
    ll = logits[np.arange(64), np.asarray(labels).ravel()]
    np.testing.assert_allclose(loss, float((lse - ll).mean()), rtol=1e-4)
