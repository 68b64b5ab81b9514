"""Pallas kernels vs pure-jnp oracles, interpret mode, shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.ops import (effective_attn_impl, flash_attention_op,
                               paged_attention_op, ssd_intra_op,
                               tesseract_mm_op, tesseract_mm_stream_op)
from repro.models.common import blockwise_attention, paged_attention

KEY = jax.random.PRNGKey(0)


def _bwise(q, k, v, *, causal, window, q_pos=None, scale=None):
    """blockwise_attention oracle lifted to the kernel layout [B, H, T, D]."""
    Tq, Tk = q.shape[2], k.shape[2]
    qp = q_pos if q_pos is not None else jnp.arange(Tq)
    out = blockwise_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), q_pos=qp, kv_pos=jnp.arange(Tk),
        causal=causal, local_window=window, q_chunk=32, kv_chunk=32,
        softmax_scale=scale)
    return out.transpose(0, 2, 1, 3)


@pytest.mark.parametrize("T,E,F,G", [(2, 256, 512, 256), (4, 512, 512, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_tesseract_mm_stream_matches_fused(T, E, F, G, dtype):
    """Accumulating the per-t blocks one ring step at a time must equal the
    fused kernel over the full [T, E, F] gathered operand."""
    a = jax.random.normal(KEY, (T, E, F), jnp.float32).astype(dtype)
    b = jax.random.normal(jax.random.fold_in(KEY, 1), (T, F, G),
                          jnp.float32).astype(dtype)
    acc = jnp.zeros((E, G), jnp.float32)
    for t in range(T):
        acc = tesseract_mm_stream_op(a[t], b[t], acc)
    want = tesseract_mm_op(a, b)
    tol = 2e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(acc), np.asarray(want),
                               rtol=tol, atol=tol)


def test_tesseract_mm_rejects_non_aligned():
    a = jax.random.normal(KEY, (2, 300, 512), jnp.float32)
    b = jax.random.normal(jax.random.fold_in(KEY, 1), (2, 512, 256),
                          jnp.float32)
    with pytest.raises(ValueError, match="tesseract_mm.*Pad"):
        tesseract_mm_op(a, b)
    with pytest.raises(ValueError, match="tesseract_mm_stream.*Pad"):
        tesseract_mm_stream_op(a[0], b[0], jnp.zeros((300, 256), jnp.float32))


# ---------------------------------------------------------------------------
# flash attention: fwd + custom-vjp bwd vs blockwise_attention / jax.vjp
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("Hq,Hkv", [(2, 2), (4, 2), (3, 1)])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 7), (False, 0)])
@pytest.mark.parametrize("Tq,Tk", [(64, 64), (37, 37), (24, 56)])
def test_flash_fwd_bwd_grid(Hq, Hkv, causal, window, Tq, Tk):
    """Interpret-mode grid: causal x GQA x local_window x odd lengths,
    forward AND gradients vs blockwise_attention under jax.vjp."""
    if causal and Tq != Tk:
        pytest.skip("causal cells use square shapes (train contract)")
    B, D = 2, 16
    q = jax.random.normal(KEY, (B, Hq, Tq, D), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, Hkv, Tk, D),
                          jnp.float32)
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, Hkv, Tk, D),
                          jnp.float32)
    ct = jax.random.normal(jax.random.fold_in(KEY, 3), (B, Hq, Tq, D),
                           jnp.float32)

    got, vjp = jax.vjp(lambda a, b, c: flash_attention_op(
        a, b, c, causal=causal, local_window=window, bq=16, bk=16), q, k, v)
    want, vjp_ref = jax.vjp(lambda a, b, c: _bwise(
        a, b, c, causal=causal, window=window), q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    for name, a, b in zip(("dq", "dk", "dv"), vjp(ct), vjp_ref(ct)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-5, atol=5e-5, err_msg=name)


def test_flash_pads_non_aligned():
    """Non-tile-divisible Tq/Tk pad-and-mask instead of raising (the v1
    kernel's check_tiling ValueError is gone)."""
    q = jax.random.normal(KEY, (1, 2, 300, 32), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (1, 2, 300, 32),
                          jnp.float32)
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (1, 2, 300, 32),
                          jnp.float32)
    got = flash_attention_op(q, k, v, causal=True, bq=256, bk=256)
    want = _bwise(q, k, v, causal=True, window=0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_traced_qpos_matches_oracle():
    """Seq-sharded prefill shape: traced q positions (q_start=None, no block
    skipping) against full-length KV."""
    Tloc, S, D = 24, 72, 16
    q = jax.random.normal(KEY, (1, 2, Tloc, D), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (1, 2, S, D),
                          jnp.float32)
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (1, 2, S, D),
                          jnp.float32)
    qpos = 48 + jnp.arange(Tloc)
    got = flash_attention_op(q, k, v, causal=True, q_pos=qpos, q_start=None,
                             bq=16, bk=24)
    want = _bwise(q, k, v, causal=True, window=0, q_pos=qpos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_fully_masked_rows_exact_zero():
    """Regression: a row masked out entirely by local_window must produce
    EXACT zeros (the l == 0 guard), not exp-of--inf garbage."""
    D = 8
    q = jax.random.normal(KEY, (1, 1, 4, D), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (1, 1, 16, D),
                          jnp.float32)
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (1, 1, 16, D),
                          jnp.float32)
    qpos = 100 + jnp.arange(4)          # window (95, 100] misses kv 0..15
    got = np.asarray(flash_attention_op(q, k, v, causal=True, local_window=5,
                                        q_pos=qpos, q_start=None))
    assert (got == 0.0).all()
    want = np.asarray(_bwise(q, k, v, causal=True, window=5, q_pos=qpos))
    np.testing.assert_array_equal(got, want)


def test_flash_bwd_through_fully_masked_rows():
    ct = jnp.ones((1, 1, 4, 8))
    q = jax.random.normal(KEY, (1, 1, 4, 8), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (1, 1, 16, 8),
                          jnp.float32)
    qpos = 100 + jnp.arange(4)
    _, vjp = jax.vjp(lambda a, b, c: flash_attention_op(
        a, b, c, causal=True, local_window=5, q_pos=qpos, q_start=None),
        q, k, k)
    for g in vjp(ct):
        assert np.isfinite(np.asarray(g)).all()
        np.testing.assert_array_equal(np.asarray(g), 0.0)


@pytest.mark.parametrize("dtype,counts", [
    ("bfloat16", {"flash_fwd": 3, "flash_dq": 4, "flash_dkv": 6}),
    ("float32", {"flash_fwd": 2, "flash_dq": 3, "flash_dkv": 4}),
])
def test_flash_mxu_operands_stay_in_input_dtype(dtype, counts):
    """Every product in the three kernels takes its operands in the input
    dtype: bf16 inputs reach the MXU as bf16 (P and dS as a hi/lo pair,
    one extra product each), f32 inputs as one f32 product.  Traced only."""
    from repro.analysis.pallas_lint import find_eqns
    sds = jax.ShapeDtypeStruct
    q = sds((1, 2, 256, 64), dtype)
    kv = sds((1, 1, 256, 64), dtype)

    def loss(q, k, v):
        out = flash_attention_op(q, k, v, causal=True, bq=128, bk=128)
        return jnp.sum(out.astype(jnp.float32))

    tr = jax.jit(jax.value_and_grad(loss, (0, 1, 2))).trace(q, kv, kv)
    dots = {e.params["name"]: [tuple(str(v.aval.dtype) for v in d.invars)
                               for d in find_eqns(e.params["jaxpr"],
                                                  "dot_general")]
            for e in find_eqns(tr.jaxpr)}
    assert {n: len(d) for n, d in dots.items()} == counts
    for name, pairs in dots.items():
        assert set(pairs) == {(dtype, dtype)}, (name, pairs)


def test_flash_bf16_precision_pinned():
    """bf16 GQA fwd+bwd against an f32 HIGHEST-precision oracle: relative
    errors within 5% of those of the kernels with f32 MXU operands.  P and
    dS rounded once to bf16 (no lo half) read 17-23% higher."""
    B, Hq, Hkv, T, D = 2, 3, 1, 512, 64
    ks = jax.random.split(KEY, 4)
    q, k, v = (jax.random.normal(kk, (B, h, T, D), jnp.float32)
               .astype(jnp.bfloat16)
               for kk, h in zip(ks, (Hq, Hkv, Hkv)))
    ct = jax.random.normal(ks[3], (B, Hq, T, D), jnp.float32)

    def oracle(q, k, v):
        k, v = jnp.repeat(k, Hq // Hkv, 1), jnp.repeat(v, Hq // Hkv, 1)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                       precision="highest") / np.sqrt(D)
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v,
                          precision="highest")

    got, vjp = jax.vjp(lambda a, b, c: flash_attention_op(
        a, b, c, causal=True, bq=128, bk=128), q, k, v)
    want, vjp_ref = jax.vjp(
        oracle, *(x.astype(jnp.float32) for x in (q, k, v)))

    def rel(a, b):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        return np.linalg.norm(a - b) / np.linalg.norm(b)

    errs = [rel(got, want)] + [
        rel(a, b) for a, b in zip(vjp(ct.astype(jnp.bfloat16)), vjp_ref(ct))]
    pinned = [1.5916e-3, 2.433e-3, 2.441e-3, 2.381e-3]    # out, dq, dk, dv
    np.testing.assert_allclose(errs, pinned, rtol=0.05)


# ---------------------------------------------------------------------------
# paged decode kernel vs the jnp gather path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window", [0, 6])
@pytest.mark.parametrize("kv_map", [None, (0, 0, 0, 1)])
def test_paged_kernel_matches_gather_path(window, kv_map):
    P, bs, Hkv, D, B, nb, Hq = 17, 4, 2, 16, 3, 5, 4
    pool_k = jax.random.normal(KEY, (P, bs, Hkv, D), jnp.float32)
    pool_v = jax.random.normal(jax.random.fold_in(KEY, 1), (P, bs, Hkv, D),
                               jnp.float32)
    q = jax.random.normal(jax.random.fold_in(KEY, 2), (B, Hq, D),
                          jnp.float32)
    rng = np.random.RandomState(0)
    table = jnp.asarray(rng.permutation(P)[:B * nb].reshape(B, nb)
                        .astype(np.int32))
    pos = jnp.array([0, 7, 18], jnp.int32)     # mixed lengths + retired-ish
    kvm = (jnp.array(kv_map, jnp.int32) if kv_map is not None
           else jnp.arange(Hq, dtype=jnp.int32) // (Hq // Hkv))
    got = paged_attention_op(q, pool_k, pool_v, table, pos, kvm,
                             local_window=window)
    want = paged_attention(q, pool_k, pool_v, table, pos,
                           kv_map=(None if kv_map is None
                                   else jnp.array(kv_map, jnp.int32)),
                           local_window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-6, atol=2e-6)


def test_paged_gather_folds_kv_map():
    """paged_gather(kv_map=...) == gather-then-take (one materialization)."""
    P, bs, Hkv, D, B, nb = 9, 4, 2, 8, 2, 3
    from repro.models.common import paged_gather
    pool_k = jax.random.normal(KEY, (P, bs, Hkv, D), jnp.float32)
    pool_v = jax.random.normal(jax.random.fold_in(KEY, 1), (P, bs, Hkv, D),
                               jnp.float32)
    table = jnp.array([[3, 1, 6], [2, 8, 4]], jnp.int32)
    kvm = jnp.array([0, 0, 1, 1, 1], jnp.int32)
    k, v = paged_gather(pool_k, pool_v, table, kvm)
    k0, v0 = paged_gather(pool_k, pool_v, table)
    np.testing.assert_array_equal(np.asarray(k),
                                  np.asarray(jnp.take(k0, kvm, axis=2)))
    np.testing.assert_array_equal(np.asarray(v),
                                  np.asarray(jnp.take(v0, kvm, axis=2)))


def test_dense_decode_pallas_path_matches_jnp():
    from repro.models.common import decode_attention
    B, S, Hkv, Hq, D = 3, 24, 2, 4, 16
    q = jax.random.normal(KEY, (B, Hq, D), jnp.float32)
    kc = jax.random.normal(jax.random.fold_in(KEY, 1), (B, S, Hkv, D),
                           jnp.float32)
    vc = jax.random.normal(jax.random.fold_in(KEY, 2), (B, S, Hkv, D),
                           jnp.float32)
    for cur in (jnp.int32(5), jnp.array([3, 0, 20], jnp.int32)):
        got = decode_attention(q, kc, vc, cur_pos=cur, impl="pallas")
        want = decode_attention(q, kc, vc, cur_pos=cur, impl="jnp")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-6, atol=2e-6)


# ---------------------------------------------------------------------------
# attn_impl resolution + tile autotuner
# ---------------------------------------------------------------------------

def test_effective_attn_impl():
    assert effective_attn_impl("jnp") == "jnp"
    assert effective_attn_impl("pallas") == "pallas"
    # this container is CPU: auto resolves to the jnp path
    expect = "pallas" if jax.default_backend() == "tpu" else "jnp"
    assert effective_attn_impl("auto") == expect
    with pytest.raises(ValueError, match="attn_impl"):
        effective_attn_impl("bogus")
    from repro.core.api import ParallelContext
    with pytest.raises(ValueError, match="attn_impl"):
        ParallelContext(attn_impl="bogus")
    from repro.configs.base import RunConfig
    with pytest.raises(ValueError, match="attn_impl"):
        RunConfig(attn_impl="bogus")


def test_autotune_cache_and_sweep():
    from repro.kernels import autotune
    assert autotune.flash_tiles(10_000, 10_000, 64) == autotune.DEFAULT_TILES
    res = autotune.autotune_flash(1, 1, 64, 64, 16, causal=True, iters=1,
                                  candidates=((32, 32), (64, 64)))
    assert tuple(res["best"]) in ((32, 32), (64, 64))
    assert autotune.flash_tiles(64, 64, 16, causal=True) == tuple(res["best"])
    # best tiles feed flash_attention when bq/bk are not given
    q = jax.random.normal(KEY, (1, 1, 64, 16), jnp.float32)
    got = flash_attention_op(q, q, q, causal=True)
    want = _bwise(q, q, q, causal=True, window=0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # tiles tuned AFTER a shape's first call must take effect on the next
    # call (regression: the lookup used to sit inside the jitted body, so
    # the first trace pinned the tiles forever)
    from repro.kernels import flash_attention as fa
    n0 = fa._flash_jit._cache_size()
    autotune.set_tiles(64, 64, 16, True, (16, 16))
    got = flash_attention_op(q, q, q, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    assert fa._flash_jit._cache_size() > n0, \
        "post-tuning call did not recompile with the new tiles"
    # round-trip through the on-disk cache
    import tempfile, pathlib
    with tempfile.TemporaryDirectory() as d:
        p = pathlib.Path(d) / "tiles.json"
        autotune.save_cache(p)
        autotune._CACHE.clear()
        assert autotune.load_cache(p) >= 1
        assert autotune.flash_tiles(64, 64, 16, causal=True) == (16, 16)


def test_attention_traffic_model():
    from repro.roofline.analysis import (flash_attention_traffic,
                                         paged_decode_traffic)
    t = flash_attention_traffic(1, 8, 4096, 4096, 128, bq=256, bk=256)
    assert t["flash_bytes"] < t["materialized_bytes"]
    d = paged_decode_traffic(8, 8, 128, pool_positions=4096,
                             live_positions=256, block_size=64)
    assert d["kernel_wins"] and d["kernel_tok_s"] > d["gather_tok_s"]


@pytest.mark.parametrize("T,E,F,G", [
    (1, 256, 512, 256), (2, 256, 512, 256), (4, 512, 1024, 512),
    (2, 512, 512, 512),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_tesseract_mm(T, E, F, G, dtype):
    a = jax.random.normal(KEY, (T, E, F), jnp.float32).astype(dtype)
    b = jax.random.normal(jax.random.fold_in(KEY, 1), (T, F, G),
                          jnp.float32).astype(dtype)
    got = tesseract_mm_op(a, b)
    want = ref.tesseract_mm_ref(a, b)
    tol = 2e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("B,H,Tq,Tk,D,causal", [
    (1, 2, 256, 256, 64, True),
    (2, 1, 512, 512, 128, True),
    (1, 2, 256, 512, 64, False),
    (1, 1, 512, 256, 64, False),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(B, H, Tq, Tk, D, causal, dtype):
    q = jax.random.normal(KEY, (B, H, Tq, D), jnp.float32).astype(dtype)
    k = jax.random.normal(jax.random.fold_in(KEY, 2), (B, H, Tk, D),
                          jnp.float32).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(KEY, 3), (B, H, Tk, D),
                          jnp.float32).astype(dtype)
    got = flash_attention_op(q, k, v, causal=causal)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    tol = 3e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("B,nc,Q,H,P,N", [
    (1, 2, 64, 4, 32, 16), (2, 1, 128, 2, 64, 32), (1, 1, 256, 2, 64, 128),
])
def test_ssd_intra(B, nc, Q, H, P, N):
    x = jax.random.normal(KEY, (B, nc, Q, H, P), jnp.float32)
    la = -jnp.abs(jax.random.normal(jax.random.fold_in(KEY, 4),
                                    (B, nc, Q, H))) * 0.1
    Bm = jax.random.normal(jax.random.fold_in(KEY, 5), (B, nc, Q, N))
    Cm = jax.random.normal(jax.random.fold_in(KEY, 6), (B, nc, Q, N))
    gy, gs = ssd_intra_op(x, la, Bm, Cm)
    wy, ws = ref.ssd_intra_ref(x, la, Bm, Cm)
    np.testing.assert_allclose(gy, wy, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(gs, ws, rtol=2e-4, atol=2e-4)


def test_ssd_chunked_pallas_matches_jnp():
    """ssd_chunked(use_pallas=True) must equal the pure-jnp path."""
    from repro.models.ssm import ssd_chunked
    B, T, H, P, N = 2, 128, 4, 32, 16
    x = jax.random.normal(KEY, (B, T, H, P), jnp.float32)
    la = -jnp.abs(jax.random.normal(jax.random.fold_in(KEY, 7), (B, T, H))) * 0.1
    Bm = jax.random.normal(jax.random.fold_in(KEY, 8), (B, T, N))
    Cm = jax.random.normal(jax.random.fold_in(KEY, 9), (B, T, N))
    y0, h0, a0 = ssd_chunked(x, la, Bm, Cm, 32, use_pallas=False)
    y1, h1, a1 = ssd_chunked(x, la, Bm, Cm, 32, use_pallas=True)
    np.testing.assert_allclose(y1, y0, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(h1, h0, rtol=2e-4, atol=2e-4)


def test_ssd_chunked_matches_naive_recurrence():
    """Chunked SSD == step-by-step linear recurrence."""
    from repro.models.ssm import ssd_chunked
    B, T, H, P, N = 1, 64, 2, 16, 8
    x = jax.random.normal(KEY, (B, T, H, P), jnp.float32)
    la = -jnp.abs(jax.random.normal(jax.random.fold_in(KEY, 10), (B, T, H))) * 0.2
    Bm = jax.random.normal(jax.random.fold_in(KEY, 11), (B, T, N))
    Cm = jax.random.normal(jax.random.fold_in(KEY, 12), (B, T, N))
    y, h_last, _ = ssd_chunked(x, la, Bm, Cm, 16)
    # naive
    h = np.zeros((B, H, P, N), np.float32)
    ys = []
    a = np.exp(np.asarray(la))
    for t in range(T):
        h = a[:, t][:, :, None, None] * h + np.einsum(
            "bhp,bn->bhpn", np.asarray(x[:, t]), np.asarray(Bm[:, t]))
        ys.append(np.einsum("bn,bhpn->bhp", np.asarray(Cm[:, t]), h))
    y_naive = np.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y, np.float32), y_naive,
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(h_last), h, rtol=2e-3, atol=2e-3)
