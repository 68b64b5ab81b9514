"""Multi-device correctness checks, run in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=N (so the main pytest
process keeps a single device).

Usage:  python -m repro.testing.mdchecks <check-name>

Each check asserts and prints "PASS <name>"; nonzero exit on failure.
"""
from __future__ import annotations

import os
import sys

if __name__ == "__main__" and "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import numpy as np  # noqa: E402


def _ref_mesh_ctx():
    """1-device reference context (uses the first of the fake devices)."""
    import jax
    from repro.core.api import ParallelContext
    from repro.core.mesh import logical_mesh
    ctx = ParallelContext(mode="tesseract", data=1, depth=1, rows=1, cols=1)
    return ctx, logical_mesh(ctx, jax.devices()[:1])


PARALLEL_VARIANTS = {
    "tesseract_222": dict(mode="tesseract", data=1, depth=2, rows=2, cols=2),
    "tesseract_221_dp2": dict(mode="tesseract", data=2, depth=2, rows=1, cols=1),
    "summa2d_22_dp2": dict(mode="summa2d", data=2, depth=1, rows=2, cols=2),
    "megatron_dp2": dict(mode="megatron1d", data=2, depth=1, rows=1, cols=4),
}


def check_summa_exact(schedules=("fused", "ring", "auto")):
    """Distributed matmul == dense reference, loss AND grads.

    Grads are computed INSIDE shard_map (the production pattern: the step
    functions run value_and_grad in the local view), with the deferred
    (data, depth) weight reduction supplied by grad_sync."""
    import jax, jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P
    from repro.core.api import ParallelContext
    from repro.core.mesh import logical_mesh
    from repro.core.summa import tesseract_matmul, tesseract_matmul_wt
    from repro.core.collectives import grad_sync
    from jax import shard_map

    E, F, G = 24, 8, 12
    A = jax.random.normal(jax.random.PRNGKey(0), (2, E, F), jnp.float32)
    W = jax.random.normal(jax.random.PRNGKey(1), (F, G), jnp.float32)
    Wt = jax.random.normal(jax.random.PRNGKey(3), (G, F), jnp.float32)
    S = jax.random.normal(jax.random.PRNGKey(2), (2, E, G), jnp.float32)

    variants = [("d2q2", dict(depth=2, rows=2, cols=2)),
                ("d1q2dp2", dict(mode="summa2d", data=2, depth=1, rows=2, cols=2))]
    for sched in schedules:
        for name, kw in variants:
            for inop in (True, False):
                for cache_w in (True, False):
                    ctx = ParallelContext(mode=kw.get("mode", "tesseract"),
                                          data=kw.get("data", 1), depth=kw["depth"],
                                          rows=kw["rows"], cols=kw["cols"],
                                          reduce_dgrad_in_op=inop,
                                          cache_weight_gather=cache_w,
                                          matmul_schedule=sched)
                    mesh = logical_mesh(ctx)
                    tok = P(None, ("data", "depth", "row"), "col")

                    def make(op):
                        def local(a, w, s):
                            def loss(a_, w_):
                                if not inop:
                                    w_ = grad_sync(w_, (ctx.axis_data,
                                                        ctx.axis_depth))
                                c = op(ctx, a_, w_)
                                # differentiate the LOCAL contribution: the
                                # cross-device reductions live in the ops'
                                # custom bwds (grad_sync / in-op psum), the
                                # same discipline the train step uses.
                                return jnp.sum(c * s)
                            l, (ga_, gw_) = jax.value_and_grad(
                                loss, argnums=(0, 1))(a, w)
                            l = lax.psum(l, ("data", "depth", "row", "col"))
                            return l, ga_, gw_
                        return shard_map(
                            local, mesh=mesh,
                            in_specs=(tok, P("row", "col"), tok),
                            out_specs=(P(), tok, P("row", "col")))

                    tag = f"{sched}/{name}/inop={inop}/cache_w={cache_w}"
                    l, ga, gw = make(tesseract_matmul)(A, W, S)
                    np.testing.assert_allclose(np.asarray(l),
                                               float(jnp.sum((A @ W) * S)),
                                               rtol=1e-5, err_msg=tag)
                    np.testing.assert_allclose(ga, np.einsum("beg,fg->bef", S, W),
                                               rtol=1e-4, atol=1e-5, err_msg=tag)
                    np.testing.assert_allclose(gw, np.einsum("bef,beg->fg", A, S),
                                               rtol=1e-4, atol=1e-5, err_msg=tag)

                    # A @ Wt^T : Wt [G(row), F(col)]
                    Swt = jax.random.normal(jax.random.PRNGKey(4), (2, E, G),
                                            jnp.float32)
                    l2, ga2, gw2 = make(tesseract_matmul_wt)(A, Wt, Swt)
                    np.testing.assert_allclose(
                        np.asarray(l2),
                        float(jnp.sum((A @ Wt.T) * Swt)), rtol=1e-5, err_msg=tag)
                    np.testing.assert_allclose(ga2, np.einsum("beg,gf->bef", Swt, Wt),
                                               rtol=1e-4, atol=1e-5, err_msg=tag)
                    np.testing.assert_allclose(gw2, np.einsum("beg,bef->gf", Swt, A),
                                               rtol=1e-4, atol=1e-5, err_msg=tag)
    print("PASS summa_exact")


def check_ring_schedule():
    """matmul_schedule="ring" == "fused" == dense reference for q in
    {1, 2, 4} (q=4 needs 16 fake devices), all three op variants, forward
    AND both backward contractions."""
    import jax, jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P
    from repro.core.api import ParallelContext
    from repro.core.mesh import logical_mesh
    from repro.core.summa import (tesseract_matmul, tesseract_matmul_experts,
                                  tesseract_matmul_wt)
    from jax import shard_map
    from repro.core.collectives import grad_sync, psum_v

    ndev = jax.device_count()
    grids = [dict(data=1, depth=1, rows=1, cols=1),
             dict(data=1, depth=2, rows=2, cols=2),
             dict(mode="summa2d", data=2, depth=1, rows=2, cols=2)]
    if ndev >= 16:
        grids.append(dict(data=1, depth=1, rows=4, cols=4))
    else:
        print("  (16 devices unavailable: q=4 grid skipped)")

    E, F, G = 24, 16, 24
    A = jax.random.normal(jax.random.PRNGKey(0), (2, E, F), jnp.float32)
    W = jax.random.normal(jax.random.PRNGKey(1), (F, G), jnp.float32)
    Wt = jax.random.normal(jax.random.PRNGKey(2), (G, F), jnp.float32)
    S = jax.random.normal(jax.random.PRNGKey(3), (2, E, G), jnp.float32)
    N, T = 4, 12
    Ae = jax.random.normal(jax.random.PRNGKey(4), (N, T, F), jnp.float32)
    We = jax.random.normal(jax.random.PRNGKey(5), (N, F, G), jnp.float32)
    Se = jax.random.normal(jax.random.PRNGKey(6), (N, T, G), jnp.float32)

    refs_plain = (float(jnp.sum((A @ W) * S)),
                  np.einsum("beg,fg->bef", S, W),
                  np.einsum("bef,beg->fg", A, S))
    Swt = jax.random.normal(jax.random.PRNGKey(7), (2, E, G), jnp.float32)
    refs_wt = (float(jnp.sum((A @ Wt.T) * Swt)),
               np.einsum("beg,gf->bef", Swt, Wt),
               np.einsum("beg,bef->gf", Swt, A))
    refs_exp = (float(jnp.sum(jnp.einsum("ntf,nfg->ntg", Ae, We) * Se)),
                np.einsum("neg,nfg->nef", Se, We),
                np.einsum("nef,neg->nfg", Ae, Se))

    for g in grids:
        for sched in ("fused", "ring"):
            # deferred dW sync (grad_sync below); in-op mode is covered by
            # check_summa_exact for both schedules.
            ctx = ParallelContext(mode=g.get("mode", "tesseract"),
                                  data=g["data"], depth=g["depth"],
                                  rows=g["rows"], cols=g["cols"],
                                  reduce_dgrad_in_op=False,
                                  matmul_schedule=sched)
            mesh = logical_mesh(ctx, jax.devices()[:ctx.data * ctx.tp])
            tok = P(None, ("data", "depth", "row"), "col")
            wspec = P("row", "col")
            tag = f"ring_schedule q={ctx.q} d={ctx.depth} dp={ctx.data} {sched}"

            def run(op, a, w, s):
                def local(a_l, w_l, s_l):
                    def loss(a_, w_):
                        w_ = grad_sync(w_, (ctx.axis_data, ctx.axis_depth))
                        return jnp.sum(op(ctx, a_, w_) * s_l)
                    l, (ga, gw) = jax.value_and_grad(loss, argnums=(0, 1))(
                        a_l, w_l)
                    return (lax.psum(l, ("data", "depth", "row", "col")),
                            ga, gw)
                sm = shard_map(local, mesh=mesh, in_specs=(tok, wspec, tok),
                               out_specs=(P(), tok, wspec))
                return sm(a, w, s)

            for op, w_in, s_in, refs, nm in (
                    (tesseract_matmul, W, S, refs_plain, "plain"),
                    (tesseract_matmul_wt, Wt, Swt, refs_wt, "wt")):
                l, ga, gw = run(op, A, w_in, s_in)
                np.testing.assert_allclose(np.asarray(l), refs[0], rtol=1e-5,
                                           err_msg=f"{tag}/{nm}/loss")
                np.testing.assert_allclose(ga, refs[1], rtol=1e-4, atol=1e-5,
                                           err_msg=f"{tag}/{nm}/dA")
                np.testing.assert_allclose(gw, refs[2], rtol=1e-4, atol=1e-5,
                                           err_msg=f"{tag}/{nm}/dW")

            if ctx.data == 1:  # experts: EP over depth, no data factor
                espec = P("depth", "row", "col")

                def local_e(a_l, w_l, s_l):
                    def loss(a_, w_):
                        return jnp.sum(
                            tesseract_matmul_experts(ctx, a_, w_) * s_l)
                    l, (ga, gw) = jax.value_and_grad(loss, argnums=(0, 1))(
                        a_l, w_l)
                    # the expert loss does not vary over the size-1 data axis
                    return (psum_v(l, ("data", "depth", "row", "col")),
                            ga, gw)
                sm = shard_map(local_e, mesh=mesh,
                               in_specs=(espec, espec, espec),
                               out_specs=(P(), espec, espec))
                l, ga, gw = sm(Ae, We, Se)
                np.testing.assert_allclose(np.asarray(l), refs_exp[0],
                                           rtol=1e-5,
                                           err_msg=f"{tag}/experts/loss")
                np.testing.assert_allclose(ga, refs_exp[1], rtol=1e-4,
                                           atol=1e-5,
                                           err_msg=f"{tag}/experts/dA")
                np.testing.assert_allclose(gw, refs_exp[2], rtol=1e-4,
                                           atol=1e-5,
                                           err_msg=f"{tag}/experts/dW")
            print(f"  {tag}: plain+wt" +
                  ("+experts ok" if ctx.data == 1 else " ok"))
    print("PASS ring_schedule")


def _build(arch_name, variant, run_kw=None, family_kw=None):
    import jax
    from repro.configs.base import RunConfig
    from repro.core.api import ParallelContext
    from repro.core.mesh import logical_mesh
    from repro.models.registry import get_reduced, build_model
    arch = get_reduced(arch_name)
    kw = dict(param_dtype="float32", compute_dtype="float32",
              loss_chunk=8, q_chunk=8, kv_chunk=8, lr=1e-3)
    kw.update(run_kw or {})
    run = RunConfig(**kw)
    ctx = ParallelContext(**variant)
    mesh = logical_mesh(ctx, jax.devices()[:ctx.data * ctx.seq * ctx.tp])
    model = build_model(arch.model, ctx, run)
    return arch, run, ctx, mesh, model


def _make_batch(model, shape, key, train=True):
    import jax, jax.numpy as jnp
    B, S = shape.global_batch, shape.seq_len
    tok = jax.random.randint(key, (B, S), 0, min(250, model.cfg.vocab_size))
    batch = {"tokens": tok}
    if train:
        batch["labels"] = jnp.roll(tok, -1, 1)
    for name, (sd, _sp) in model.batch_extras(shape).items():
        batch[name] = jax.random.normal(jax.random.fold_in(key, 1),
                                        sd.shape, sd.dtype)
    return batch


def _train_losses(arch_name, variant, batch, n_steps=3, run_kw=None):
    import jax, jax.numpy as jnp
    from repro.configs.base import ShapeSpec
    from repro.runtime.steps import build_train_step
    from repro.optim.adamw import adamw_init
    arch, run, ctx, mesh, model = _build(arch_name, variant, run_kw)
    B, S = batch["tokens"].shape
    shape = ShapeSpec("t", seq_len=S, global_batch=B, kind="train")
    if model.batch_extras(shape):
        batch = dict(batch)
        batch.update({k: v for k, v in
                      _make_batch(model, shape, jax.random.PRNGKey(42)).items()
                      if k not in ("tokens", "labels")})
    bundle = build_train_step(model, mesh, shape)
    params = model.init(jax.random.PRNGKey(0))
    if run.zero_enabled:
        from repro.optim.zero import zero_opt_init
        opt = zero_opt_init(bundle)
    else:
        opt = adamw_init(params, master=run.master_weights)
    losses, gnorms = [], []
    p, o = params, opt
    for _ in range(n_steps):
        p, o, m = bundle.fn(p, o, batch)
        losses.append(float(m["loss"]))
        gnorms.append(float(m["grad_norm"]))
    return np.array(losses), (p, o, model, mesh, ctx, run, np.array(gnorms),
                              bundle)


def check_dense_parity(arch_name="yi-6b"):
    import jax, jax.numpy as jnp
    B, S = 8, 16
    tok = jax.random.randint(jax.random.PRNGKey(7), (B, S), 0, 250)
    batch = {"tokens": tok, "labels": jnp.roll(tok, -1, 1)}

    ref_losses, _ = _train_losses(
        arch_name, dict(mode="tesseract", data=1, depth=1, rows=1, cols=1),
        batch)
    assert np.all(np.isfinite(ref_losses))
    for name, variant in PARALLEL_VARIANTS.items():
        losses, _ = _train_losses(arch_name, variant, batch)
        np.testing.assert_allclose(losses, ref_losses, rtol=2e-4, atol=2e-4,
                                   err_msg=f"{arch_name}/{name}")
        print(f"  {arch_name}/{name}: losses match ref {losses}")
    print(f"PASS dense_parity[{arch_name}]")


def check_inop_matches_deferred():
    import jax, jax.numpy as jnp
    B, S = 8, 16
    tok = jax.random.randint(jax.random.PRNGKey(9), (B, S), 0, 250)
    batch = {"tokens": tok, "labels": jnp.roll(tok, -1, 1)}
    base = dict(mode="tesseract", data=1, depth=2, rows=2, cols=2)
    l_inop, _ = _train_losses("yi-6b", dict(base, reduce_dgrad_in_op=True), batch)
    l_def, _ = _train_losses("yi-6b", dict(base, reduce_dgrad_in_op=False), batch)
    np.testing.assert_allclose(l_inop, l_def, rtol=1e-5, atol=1e-6)
    print("PASS inop_matches_deferred")


def check_decode_parity(arch_name="yi-6b"):
    import jax, jax.numpy as jnp
    from repro.configs.base import ShapeSpec
    from repro.runtime.steps import build_decode_step
    B, S = 8, 32

    def run_variant(variant):
        arch, run, ctx, mesh, model = _build(arch_name, variant)
        shape = ShapeSpec("d", seq_len=S, global_batch=B, kind="decode")
        bundle = build_decode_step(model, mesh, shape)
        params = model.init(jax.random.PRNGKey(0))
        cache_sds, _ = model.cache_abstract(B, S, bundle.plan)
        cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), cache_sds)
        ids = jnp.arange(B, dtype=jnp.int32)[:, None] % 100
        out = [np.asarray(ids).ravel()]
        for t in range(3):
            ids, cache = bundle.fn(params, cache, ids, jnp.int32(t))
            out.append(np.asarray(ids).ravel())
        return np.stack(out)

    ref = run_variant(dict(mode="tesseract", data=1, depth=1, rows=1, cols=1))
    for name, variant in PARALLEL_VARIANTS.items():
        got = run_variant(variant)
        np.testing.assert_array_equal(got, ref, err_msg=f"{arch_name}/{name}")
        print(f"  decode {arch_name}/{name}: ids match")
    print(f"PASS decode_parity[{arch_name}]")


def check_prefill_parity(arch_name="yi-6b"):
    import jax, jax.numpy as jnp
    from repro.configs.base import ShapeSpec
    from repro.runtime.steps import build_prefill_step
    B, S = 4, 16

    def run_variant(variant):
        arch, run, ctx, mesh, model = _build(arch_name, variant)
        shape = ShapeSpec("p", seq_len=S, global_batch=B, kind="prefill")
        bundle = build_prefill_step(model, mesh, shape)
        params = model.init(jax.random.PRNGKey(0))
        tok = jax.random.randint(jax.random.PRNGKey(5), (B, S), 0, 250)
        ids, cache = bundle.fn(params, {"tokens": tok})
        return np.asarray(ids), np.asarray(cache["k"]), np.asarray(cache["v"])

    ref = run_variant(dict(mode="tesseract", data=1, depth=1, rows=1, cols=1))
    for name, variant in PARALLEL_VARIANTS.items():
        got = run_variant(variant)
        np.testing.assert_array_equal(got[0], ref[0], err_msg=f"ids {name}")
        np.testing.assert_allclose(got[1], ref[1], rtol=1e-4, atol=1e-5,
                                   err_msg=f"cache-k {name}")
        np.testing.assert_allclose(got[2], ref[2], rtol=1e-4, atol=1e-5,
                                   err_msg=f"cache-v {name}")
        print(f"  prefill {arch_name}/{name}: ids+cache match")
    print(f"PASS prefill_parity[{arch_name}]")


def check_moe_parity():
    """MoE (EP over depth) + MLA parity vs single device.

    capacity_factor is set high enough that no tokens are dropped — with
    drops, routing depends on the per-group token count and parity cannot
    hold bitwise (documented behaviour)."""
    import jax, jax.numpy as jnp
    run_kw = dict(capacity_factor=16.0)
    B, S = 8, 16
    tok = jax.random.randint(jax.random.PRNGKey(11), (B, S), 0, 250)
    batch = {"tokens": tok, "labels": jnp.roll(tok, -1, 1)}
    variants = {
        "tesseract_222": dict(mode="tesseract", data=1, depth=2, rows=2, cols=2),
        "summa2d_22_dp2": dict(mode="summa2d", data=2, depth=1, rows=2, cols=2),
    }
    for arch in ("llama4-scout-17b-a16e", "deepseek-v2-236b"):
        ref, _ = _train_losses(
            arch, dict(mode="tesseract", data=1, depth=1, rows=1, cols=1),
            batch, run_kw=run_kw)
        assert np.all(np.isfinite(ref))
        for name, v in variants.items():
            losses, _ = _train_losses(arch, v, batch, run_kw=run_kw)
            np.testing.assert_allclose(losses, ref, rtol=3e-4, atol=3e-4,
                                       err_msg=f"{arch}/{name}")
            print(f"  {arch}/{name}: losses match ref {losses}")
    print("PASS moe_parity")


def check_moe_decode():
    import jax, jax.numpy as jnp
    from repro.configs.base import ShapeSpec
    from repro.runtime.steps import build_decode_step
    B, S = 8, 32

    def run_variant(arch, variant):
        _, run, ctx, mesh, model = _build(arch, variant,
                                          dict(capacity_factor=16.0))
        shape = ShapeSpec("d", seq_len=S, global_batch=B, kind="decode")
        bundle = build_decode_step(model, mesh, shape)
        params = model.init(jax.random.PRNGKey(0))
        cache_sds, _ = model.cache_abstract(B, S, bundle.plan)
        cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), cache_sds)
        ids = jnp.arange(B, dtype=jnp.int32)[:, None] % 100
        out = []
        for t in range(3):
            ids, cache = bundle.fn(params, cache, ids, jnp.int32(t))
            out.append(np.asarray(ids).ravel())
        return np.stack(out)

    for arch in ("llama4-scout-17b-a16e", "deepseek-v2-236b"):
        ref = run_variant(arch, dict(mode="tesseract", data=1, depth=1,
                                     rows=1, cols=1))
        got = run_variant(arch, dict(mode="tesseract", data=1, depth=2,
                                     rows=2, cols=2))
        np.testing.assert_array_equal(got, ref, err_msg=arch)
        print(f"  moe decode {arch}: ids match")
    print("PASS moe_decode")


def check_smollm_padding():
    """Head padding (15->16) + replicated KV (5) parity."""
    check_dense_parity("smollm-360m")
    print("PASS smollm_padding")


def check_families_parity():
    """vision / whisper / ssm / hybrid: train-loss parity vs 1 device."""
    import jax, jax.numpy as jnp
    B, S = 8, 16
    tok = jax.random.randint(jax.random.PRNGKey(13), (B, S), 0, 250)
    batch = {"tokens": tok, "labels": jnp.roll(tok, -1, 1)}
    variants = {
        "tesseract_222": dict(mode="tesseract", data=1, depth=2, rows=2, cols=2),
        "summa2d_22_dp2": dict(mode="summa2d", data=2, depth=1, rows=2, cols=2),
    }
    for arch in ("llama-3.2-vision-11b", "whisper-base", "mamba2-1.3b",
                 "recurrentgemma-9b"):
        ref, _ = _train_losses(
            arch, dict(mode="tesseract", data=1, depth=1, rows=1, cols=1),
            batch)
        assert np.all(np.isfinite(ref)), (arch, ref)
        for name, v in variants.items():
            losses, _ = _train_losses(arch, v, batch)
            np.testing.assert_allclose(losses, ref, rtol=5e-4, atol=5e-4,
                                       err_msg=f"{arch}/{name}")
            print(f"  {arch}/{name}: losses match ref {losses}")
    print("PASS families_parity")


def check_families_serve():
    """prefill (distributed scans!) + decode parity for the new families."""
    import jax, jax.numpy as jnp
    from repro.configs.base import ShapeSpec
    from repro.runtime.steps import build_decode_step, build_prefill_step
    B, S = 4, 16
    archs = ("llama-3.2-vision-11b", "whisper-base", "mamba2-1.3b",
             "recurrentgemma-9b")

    def run_prefill(arch, variant):
        _, run, ctx, mesh, model = _build(arch, variant)
        shape = ShapeSpec("p", seq_len=S, global_batch=B, kind="prefill")
        bundle = build_prefill_step(model, mesh, shape)
        params = model.init(jax.random.PRNGKey(0))
        batch = _make_batch(model, shape, jax.random.PRNGKey(5), train=False)
        ids, cache = bundle.fn(params, batch)
        flat = [np.asarray(x) for x in jax.tree.leaves(cache)]
        return np.asarray(ids), flat

    def run_decode(arch, variant):
        _, run, ctx, mesh, model = _build(arch, variant)
        shape = ShapeSpec("d", seq_len=24, global_batch=8, kind="decode")
        bundle = build_decode_step(model, mesh, shape)
        params = model.init(jax.random.PRNGKey(0))
        cache_sds, _ = model.cache_abstract(8, 24, bundle.plan)
        cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), cache_sds)
        ids = jnp.arange(8, dtype=jnp.int32)[:, None] % 100
        out = []
        for t in range(3):
            ids, cache = bundle.fn(params, cache, ids, jnp.int32(t))
            out.append(np.asarray(ids).ravel())
        return np.stack(out)

    one = dict(mode="tesseract", data=1, depth=1, rows=1, cols=1)
    multi = dict(mode="tesseract", data=1, depth=2, rows=2, cols=2)
    for arch in archs:
        ids0, c0 = run_prefill(arch, one)
        ids1, c1 = run_prefill(arch, multi)
        np.testing.assert_array_equal(ids1, ids0, err_msg=f"prefill ids {arch}")
        for a, b in zip(c0, c1):
            np.testing.assert_allclose(b, a, rtol=2e-3, atol=2e-3,
                                       err_msg=f"prefill cache {arch}")
        d0 = run_decode(arch, one)
        d1 = run_decode(arch, multi)
        np.testing.assert_array_equal(d1, d0, err_msg=f"decode {arch}")
        print(f"  serve parity {arch}: ok")
    print("PASS families_serve")


def check_ring_train_parity():
    """Full train steps with matmul_schedule="ring" == "fused" (yi-6b
    reduced, tesseract [2,2,2]) — the schedule swaps transparently under
    jit + remat + custom-vjp + grad clip."""
    import jax, jax.numpy as jnp
    B, S = 8, 16
    tok = jax.random.randint(jax.random.PRNGKey(21), (B, S), 0, 250)
    batch = {"tokens": tok, "labels": jnp.roll(tok, -1, 1)}
    base = dict(mode="tesseract", data=1, depth=2, rows=2, cols=2)
    l_fused, _ = _train_losses("yi-6b", dict(base, matmul_schedule="fused"),
                               batch)
    l_ring, _ = _train_losses("yi-6b", dict(base, matmul_schedule="ring"),
                              batch)
    np.testing.assert_allclose(l_ring, l_fused, rtol=2e-5, atol=2e-5)
    print("PASS ring_train_parity", l_ring)


def _opt_bytes_per_device(bundle):
    """Per-device optimizer-state bytes from the bundle's real shardings."""
    import jax
    abs_opt = bundle.abstract_inputs[1]
    sh_opt = bundle.in_shardings[1]
    total = 0
    for ab, sh in zip(jax.tree.leaves(abs_opt), jax.tree.leaves(sh_opt)):
        loc = sh.shard_shape(tuple(ab.shape))
        n = 1
        for d in loc:
            n *= d
        total += n * ab.dtype.itemsize
    return total


def check_zero1_parity():
    """ZeRO-1 step == replicated-optimizer baseline over 5 steps (params,
    loss, grad norm), per cell: q in {1, 2} x dp in {2, 4} x master off/on
    (param_dtype fp32 / bf16+fp32-master), a depth-sharded-leaf grid
    (head/experts keep state depth-local), deferred grad sync, and the
    [pipe x data x ...] 1F1B mesh.  fp32 cells match to fp32 exactness;
    bf16 cells to bf16-wire accumulation noise.  Per-device opt-state
    bytes must shrink ~dp x on the dp=4 cell."""
    import jax, jax.numpy as jnp
    ndev = jax.device_count()
    B, S = 8, 16
    tok = jax.random.randint(jax.random.PRNGKey(7), (B, S), 0, 250)
    batch = {"tokens": tok, "labels": jnp.roll(tok, -1, 1)}

    grids = [
        ("q1_dp2", dict(mode="tesseract", data=2, depth=1, rows=1, cols=1)),
        ("q1_dp4", dict(mode="tesseract", data=4, depth=1, rows=1, cols=1)),
        ("q2_dp2", dict(mode="tesseract", data=2, depth=1, rows=2, cols=2)),
        ("q1_d2_dp2", dict(mode="tesseract", data=2, depth=2, rows=1,
                           cols=1)),
        ("q2_dp2_deferred", dict(mode="tesseract", data=2, depth=1, rows=2,
                                 cols=2, reduce_dgrad_in_op=False)),
        # fused Pallas attention under ZeRO-1 (both sides run the kernel
        # data path; fp32 exactness must hold like every other cell)
        ("q1_dp2_pallas", dict(mode="tesseract", data=2, depth=1, rows=1,
                               cols=1, attn_impl="pallas")),
        # 16 fake devices (tests/test_zero.py spawns with that count)
        ("q2_dp4", dict(mode="tesseract", data=4, depth=1, rows=2, cols=2)),
    ]
    # tests/test_zero.py runs single cells on bigger fake-device counts
    only = os.environ.get("ZERO1_CELLS")
    if only:
        cells = set(only.split(","))
        grids = [g for g in grids if g[0] in cells]
        assert grids or "pipe" in cells, f"no such cells: {only}"
    masters = [("fp32", dict()),
               ("bf16_master", dict(param_dtype="bfloat16",
                                    compute_dtype="bfloat16"))]

    def compare(tag, ref_pack, got_pack, tol):
        (ref, (pr, *_r)), (got, (pz, *_z)) = ref_pack, got_pack
        gr, gz = _r[5], _z[5]
        np.testing.assert_allclose(got, ref, rtol=tol, atol=tol,
                                   err_msg=f"{tag}: loss")
        np.testing.assert_allclose(gz, gr, rtol=tol, atol=tol,
                                   err_msg=f"{tag}: grad_norm")
        for (ka, a), (kb, b) in zip(
                jax.tree_util.tree_flatten_with_path(pr)[0],
                jax.tree_util.tree_flatten_with_path(pz)[0]):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                rtol=10 * tol, atol=10 * tol,
                err_msg=f"{tag}: param {jax.tree_util.keystr(ka)}")

    for name, variant in grids:
        need = (variant["data"] * variant["depth"] * variant["rows"]
                * variant["cols"])
        if need > ndev:
            print(f"  zero1 {name}: skipped ({need} devices > {ndev})")
            continue
        for mname, mkw in masters:
            tol = 2e-6 if mname == "fp32" else 3e-5
            ref = _train_losses("yi-6b", variant, batch, n_steps=5,
                                run_kw=mkw)
            got = _train_losses("yi-6b", variant, batch, n_steps=5,
                                run_kw=dict(mkw, zero1=True))
            compare(f"{name}/{mname}", ref, got, tol)
            print(f"  zero1 {name}/{mname}: losses/gnorm/params match "
                  f"{got[0][-2:]}")
        if name == "q1_dp4":
            b_ref = ref[1][7]
            b_got = got[1][7]
            ratio = _opt_bytes_per_device(b_ref) / _opt_bytes_per_device(
                b_got)
            assert ratio > 3.2, \
                f"dp=4 opt-state bytes shrank only {ratio:.2f}x"
            print(f"  zero1 q1_dp4: per-device opt state {ratio:.2f}x "
                  f"smaller")

    # ---- 1F1B pipeline mesh: blocks stage-sharded, embed/head shard their
    # state over (data, pipe) ----
    if ndev >= 4 and (not only or "pipe" in only):
        from repro.configs.base import RunConfig, ShapeSpec
        from repro.core.api import ParallelContext
        from repro.models.registry import build_model, get_reduced
        from repro.optim.adamw import adamw_init
        from repro.runtime.steps import build_train_step
        shape = ShapeSpec("t", seq_len=S, global_batch=B, kind="train")
        ctx = ParallelContext(mode="tesseract", data=2, depth=1, rows=1,
                              cols=1)

        def run_pipe(zero):
            run = RunConfig(param_dtype="float32", compute_dtype="float32",
                            loss_chunk=8, q_chunk=8, kv_chunk=8, lr=1e-3,
                            pipeline_microbatches=4, zero1=zero)
            mesh = _mesh5(ctx, 2)
            model = build_model(get_reduced("yi-6b").model, ctx, run)
            bundle = build_train_step(model, mesh, shape)
            p = jax.device_put(model.init(jax.random.PRNGKey(0)),
                               bundle.in_shardings[0])
            if zero:
                from repro.optim.zero import zero_opt_init
                o = jax.device_put(zero_opt_init(bundle),
                                   bundle.in_shardings[1])
            else:
                o = jax.device_put(adamw_init(p), bundle.in_shardings[1])
            out = []
            for _ in range(5):
                p, o, m = bundle.fn(p, o, batch)
                out.append((float(m["loss"]), float(m["grad_norm"])))
            return np.array(out), p

        ref, pr = run_pipe(False)
        got, pz = run_pipe(True)
        np.testing.assert_allclose(got, ref, rtol=2e-6, atol=2e-6,
                                   err_msg="pipe mesh: loss/gnorm")
        for a, b in zip(jax.tree.leaves(pr), jax.tree.leaves(pz)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=2e-5,
                                       err_msg="pipe mesh: params")
        print(f"  zero1 pipe2_dp2: 1F1B ZeRO-1 matches replicated "
              f"{got[-1]}")
    print("PASS zero1_parity")


def check_zero1_elastic():
    """ZeRO-1 state survives dp-degree and layout changes:

    (a) checkpoint round-trip — save under dp=4/ZeRO-1, restore onto
        dp=2/ZeRO-1 AND onto a dp=1 replicated-optimizer run (and from the
        replicated run back onto dp=4/ZeRO-1); every resumed trajectory
        matches the uninterrupted dp=4 run (uneven-leaf padding path
        covered by the reduced model's odd-sized norm/ vocab leaves);
    (b) elastic replan — fault at step 5 of a dp=8 ZeRO-1 run, replan onto
        4 devices (accum_steps=2 consumed), trajectory preserved while the
        opt-state shards re-partition 8 -> 4 via the manifest layout.
    """
    import tempfile

    import jax, jax.numpy as jnp
    from repro.checkpoint.ckpt import CheckpointManager
    from repro.configs.base import RunConfig, ShapeSpec
    from repro.core.api import ParallelContext
    from repro.core.mesh import logical_mesh
    from repro.models.registry import build_model, get_reduced
    from repro.optim.adamw import adamw_init
    from repro.optim.zero import make_ckpt_converter
    from repro.runtime.steps import build_train_step

    B, S = 8, 16
    tok = jax.random.randint(jax.random.PRNGKey(7), (B, S), 0, 250)
    batch = {"tokens": tok, "labels": jnp.roll(tok, -1, 1)}
    shape = ShapeSpec("t", seq_len=S, global_batch=B, kind="train")
    arch = get_reduced("yi-6b")

    def build(dp, zero):
        run = RunConfig(param_dtype="float32", compute_dtype="float32",
                        loss_chunk=8, q_chunk=8, kv_chunk=8, lr=1e-3,
                        zero1=zero)
        ctx = ParallelContext(mode="tesseract", data=dp, depth=1, rows=1,
                              cols=1)
        mesh = logical_mesh(ctx, jax.devices()[:dp])
        model = build_model(arch.model, ctx, run)
        bundle = build_train_step(model, mesh, shape)
        p = jax.device_put(model.init(jax.random.PRNGKey(0)),
                           bundle.in_shardings[0])
        if zero:
            from repro.optim.zero import zero_opt_init
            o = jax.device_put(zero_opt_init(bundle),
                               bundle.in_shardings[1])
        else:
            o = jax.device_put(adamw_init(p), bundle.in_shardings[1])
        return bundle, p, o

    def steps_n(bundle, p, o, n):
        out = []
        for _ in range(n):
            p, o, m = bundle.fn(p, o, batch)
            out.append(float(m["loss"]))
        return out, p, o

    def restore_into(mgr, step, bundle):
        abs_p, abs_o, _ = bundle.abstract_inputs
        conv = make_ckpt_converter(bundle.opt_layouts_json())
        return mgr.restore(step, {"params": abs_p, "opt": abs_o},
                           {"params": bundle.in_shardings[0],
                            "opt": bundle.in_shardings[1]}, convert=conv)

    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d)
        b4, p, o = build(4, zero=True)
        _, p, o = steps_n(b4, p, o, 2)
        mgr.save(1, {"params": p, "opt": o}, blocking=True,
                 meta={"opt_layout": b4.opt_layouts_json()})
        ref, _, _ = steps_n(b4, p, o, 3)

        # dp=4 ZeRO -> dp=2 ZeRO (zn 4 -> 2 re-partition)
        b2, _, _ = build(2, zero=True)
        st = restore_into(mgr, 1, b2)
        got2, _, _ = steps_n(b2, st["params"], st["opt"], 3)
        np.testing.assert_allclose(got2, ref, rtol=1e-5, atol=1e-6,
                                   err_msg="dp4 ZeRO ckpt -> dp2 ZeRO")
        print(f"  zero1 ckpt dp4 -> dp2: losses continue {got2}")

        # dp=4 ZeRO -> dp=1 replicated optimizer (unshard path)
        b1, _, _ = build(1, zero=False)
        st1 = restore_into(mgr, 1, b1)
        got1, p1, o1 = steps_n(b1, st1["params"], st1["opt"], 1)
        np.testing.assert_allclose(got1, ref[:1], rtol=1e-5, atol=1e-6,
                                   err_msg="dp4 ZeRO ckpt -> dp1 replicated")

        # ... and BACK: replicated dp=1 ckpt -> dp=4 ZeRO (shard path)
        mgr.save(2, {"params": p1, "opt": o1}, blocking=True,
                 meta={"opt_layout": b1.opt_layouts_json()})
        stb = restore_into(mgr, 2, b4)
        gotb, _, _ = steps_n(b4, stb["params"], stb["opt"], 2)
        np.testing.assert_allclose(gotb, ref[1:], rtol=1e-5, atol=1e-6,
                                   err_msg="replicated ckpt -> dp4 ZeRO")
        print(f"  zero1 ckpt dp4 -> dp1(replicated) -> dp4: losses "
              f"continue {got1 + gotb}")

    # ---- (b) elastic 8 -> 4 replan under ZeRO-1 ----
    from repro.runtime.elastic import replan
    from repro.runtime.train_loop import train
    run = RunConfig(param_dtype="float32", compute_dtype="float32",
                    loss_chunk=8, q_chunk=8, kv_chunk=8, lr=1e-3, zero1=True)
    eshape = ShapeSpec("t", seq_len=16, global_batch=16, kind="train")
    ctx8 = ParallelContext(mode="tesseract", data=8, depth=1, rows=1, cols=1)
    mesh8 = logical_mesh(ctx8, jax.devices()[:8])
    model8 = build_model(arch.model, ctx8, run)

    with tempfile.TemporaryDirectory() as dref, \
            tempfile.TemporaryDirectory() as dft:
        ref = train(model8, mesh8, eshape, steps=8, ckpt_dir=dref,
                    ckpt_every=100, log_every=0)

        def fault(step):
            if step == 5:
                raise RuntimeError("injected: half the fleet lost")

        try:
            train(model8, mesh8, eshape, steps=8, ckpt_dir=dft,
                  ckpt_every=2, log_every=0, fault_hook=fault,
                  max_restarts=0)
            raise AssertionError("fault did not surface")
        except RuntimeError:
            pass

        rp = replan(4, ctx8, global_batch=eshape.global_batch)
        assert rp.ctx.data == 4 and rp.accum_steps == 2, rp
        model4 = build_model(arch.model, rp.ctx, run)
        mesh4 = logical_mesh(rp.ctx, jax.devices()[:rp.n_used])
        res = train(model4, mesh4, eshape, steps=8, ckpt_dir=dft,
                    ckpt_every=100, log_every=0,
                    accum_steps=rp.accum_steps)
        np.testing.assert_allclose(res.losses, ref.losses[4:],
                                   rtol=1e-5, atol=1e-6,
                                   err_msg="post-replan ZeRO trajectory")
    print(f"  zero1 elastic: 8 -> 4 devices, opt shards re-partitioned, "
          f"trajectory preserved {res.losses}")
    print("PASS zero1_elastic")


def check_moe_local_layout():
    """Expert-local (beyond-paper) MoE layout == 2d layout numerics."""
    import jax, jax.numpy as jnp
    B, S = 8, 16
    tok = jax.random.randint(jax.random.PRNGKey(11), (B, S), 0, 250)
    batch = {"tokens": tok, "labels": jnp.roll(tok, -1, 1)}
    for arch in ("llama4-scout-17b-a16e", "deepseek-v2-236b"):
        ref, _ = _train_losses(
            arch, dict(mode="tesseract", data=1, depth=1, rows=1, cols=1),
            batch, run_kw=dict(capacity_factor=16.0))
        got, _ = _train_losses(
            arch, dict(mode="tesseract", data=1, depth=2, rows=2, cols=2),
            batch,
            run_kw=dict(capacity_factor=16.0, moe_expert_layout="local"))
        np.testing.assert_allclose(got, ref, rtol=3e-4, atol=3e-4)
        print(f"  moe local layout {arch}: match")
    print("PASS moe_local_layout")


def _engine_reference(model, mesh, params, prompts, n_new, S=64):
    """The pre-engine static-batch decode loop (prompt replay, fixed batch):
    the bit-parity oracle for the continuous-batching engine."""
    import jax, jax.numpy as jnp
    from repro.configs.base import ShapeSpec
    from repro.runtime.steps import build_decode_step
    B, lens = len(prompts), [len(p) for p in prompts]
    dec = build_decode_step(model, mesh,
                            ShapeSpec("d", S, B, "decode"))
    cache_sds, _ = model.cache_abstract(B, S, dec.plan)
    cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), cache_sds)
    ids = np.array([[p[0]] for p in prompts], np.int32)
    out = [[] for _ in range(B)]
    for t in range(max(l + n for l, n in zip(lens, n_new)) - 1):
        nxt, cache = dec.fn(params, cache, jnp.asarray(ids), jnp.int32(t))
        nxt = np.asarray(nxt)
        for b in range(B):
            if t + 1 < lens[b]:
                ids[b, 0] = prompts[b][t + 1]
            else:
                if t + 1 - lens[b] < n_new[b]:
                    out[b].append(int(nxt[b, 0]))
                ids[b, 0] = nxt[b, 0]
    return out


def full_forward_argmax(model, mesh, params, seq, n_new):
    """Greedy oracle with no KV cache at all: full forward over the growing
    sequence each step, argmax at its true last position.  Shared by the
    serve_engine check and tests/test_serve.py."""
    import jax.numpy as jnp
    from repro.configs.base import ShapeSpec
    from repro.runtime.steps import build_prefill_step
    bundles, out, seq = {}, [], list(seq)
    for _ in range(n_new):
        bucket = 8
        while bucket < len(seq):
            bucket *= 2
        if bucket not in bundles:
            bundles[bucket] = build_prefill_step(
                model, mesh, ShapeSpec("p", bucket, 1, "prefill"),
                with_lengths=True)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :len(seq)] = seq
        logits, _ = bundles[bucket].fn(
            params, {"tokens": jnp.asarray(toks),
                     "lengths": jnp.asarray([len(seq)], jnp.int32)})
        tok = int(np.argmax(np.asarray(logits)[0]))
        out.append(tok)
        seq.append(tok)
    return out


def check_serve_engine():
    """Continuous-batching engine == static-batch decode loop, bit-identical
    greedy tokens, for q in {1, 2} (tesseract + 1-D serve layout), mixed
    prompt lengths in one batch, including a pool-pressure (eviction +
    re-prefill) run."""
    import jax
    from repro.serve import EngineConfig, InferenceEngine, SamplingParams

    rng = np.random.RandomState(3)
    lens = [5, 9, 16, 12, 7, 3, 21, 10]
    n_new = [6, 10, 4, 8, 5, 12, 3, 7]
    prompts = [rng.randint(0, 250, (l,)).tolist() for l in lens]

    grids = [
        ("q1", dict(mode="tesseract", data=1, depth=1, rows=1, cols=1)),
        ("q2_d2", dict(mode="tesseract", data=1, depth=2, rows=2, cols=2)),
        ("q2_dp2", dict(mode="summa2d", data=2, depth=1, rows=2, cols=2)),
        ("megatron_dp2", dict(mode="megatron1d", data=2, depth=1, rows=1,
                              cols=4)),
        # attn_impl="pallas" cells (DESIGN.md §10): flash prefill +
        # block-table paged decode kernel on BOTH the engine and the static
        # reference loop; greedy tokens must stay bit-identical for
        # q in {1, 2}
        ("q1_pallas", dict(mode="tesseract", data=1, depth=1, rows=1,
                           cols=1, attn_impl="pallas")),
        ("q2_d2_pallas", dict(mode="tesseract", data=1, depth=2, rows=2,
                              cols=2, attn_impl="pallas")),
    ]
    for name, variant in grids:
        _, run, ctx, mesh, model = _build("yi-6b", variant)
        params = model.init(jax.random.PRNGKey(0))
        ref = _engine_reference(model, mesh, params, prompts, n_new)

        eng = InferenceEngine(model, mesh, params, EngineConfig(
            n_slots=8, block_size=4, num_blocks=128, max_seq_len=64))
        reqs = [eng.add_request(p, SamplingParams(max_new_tokens=n))
                for p, n in zip(prompts, n_new)]
        res = eng.run()
        got = [res[r.rid] for r in reqs]
        assert got == ref, f"{name}: engine != static loop\n{got}\n{ref}"
        if name in ("q1", "q2_d2", "q1_pallas", "q2_d2_pallas"):
            # the issue's q in {1, 2} criterion, per attn_impl
            for b in (0, 3):
                ffwd = full_forward_argmax(model, mesh, params, prompts[b],
                                           n_new[b])
                assert got[b] == ffwd, \
                    f"{name} req{b}: engine != full-forward argmax" \
                    f"\n{got[b]}\n{ffwd}"
        print(f"  serve engine {name}: bit-identical to static loop "
              f"({eng.stats.tokens} tokens, {eng.stats.steps} steps)")

    # pool pressure: two slots per KV group and per-group freelists too
    # small for both residents at full length -> preemption-by-eviction +
    # re-prefill (slots_per_group must be > 1 for cross-request eviction)
    _, run, ctx, mesh, model = _build(
        "yi-6b", dict(mode="tesseract", data=1, depth=2, rows=2, cols=2))
    params = model.init(jax.random.PRNGKey(0))
    ref = _engine_reference(model, mesh, params, prompts, n_new)
    eng = InferenceEngine(model, mesh, params, EngineConfig(
        n_slots=8, block_size=4, num_blocks=32, max_seq_len=64))
    reqs = [eng.add_request(p, SamplingParams(max_new_tokens=n))
            for p, n in zip(prompts, n_new)]
    res = eng.run()
    got = [res[r.rid] for r in reqs]
    assert got == ref, f"evicted run != static loop\n{got}\n{ref}"
    assert eng.stats.preemptions > 0, "pool pressure never triggered"
    print(f"  serve engine eviction: parity held through "
          f"{eng.stats.preemptions} preemptions")
    print("PASS serve_engine")


def check_engine_elastic():
    """runtime.elastic.replan driven from the engine: drop 8 -> 4 devices
    mid-generation, reshard live KV blocks, finish — tokens must match an
    uninterrupted run."""
    import jax
    from repro.serve import EngineConfig, InferenceEngine, SamplingParams

    rng = np.random.RandomState(5)
    lens = [5, 9, 16, 12, 7, 3, 21, 10]
    n_new = [6, 10, 4, 8, 5, 12, 3, 7]
    prompts = [rng.randint(0, 250, (l,)).tolist() for l in lens]

    _, run, ctx, mesh, model = _build(
        "yi-6b", dict(mode="tesseract", data=2, depth=1, rows=2, cols=2))
    params = model.init(jax.random.PRNGKey(0))
    ref = _engine_reference(model, mesh, params, prompts, n_new)

    eng = InferenceEngine(model, mesh, params, EngineConfig(
        n_slots=8, block_size=4, num_blocks=128, max_seq_len=64))
    reqs = [eng.add_request(p, SamplingParams(max_new_tokens=n))
            for p, n in zip(prompts, n_new)]
    for _ in range(3):
        eng.step()
    rp = eng.replan_to(4)
    assert rp.ctx.data == 1 and rp.n_used == 4, rp
    res = eng.run()
    got = [res[r.rid] for r in reqs]
    assert got == ref, f"post-replan tokens diverged\n{got}\n{ref}"
    print(f"  elastic: 8 -> {rp.n_used} devices mid-run, tokens identical")
    print("PASS engine_elastic")


def check_spec_decode():
    """Speculative decoding (DESIGN.md §14) commits bit-identical greedy
    tokens to plain paged decode for q in {1, 2}, with both the n-gram
    prompt-lookup proposer and a smollm-360m draft model, under pool
    pressure (eviction + re-prefill mid-speculation) and through an
    8 -> 4 elastic replan."""
    import dataclasses

    import jax
    from repro.models.registry import build_model, get_reduced
    from repro.serve import EngineConfig, InferenceEngine, SamplingParams

    rng = np.random.RandomState(9)
    # repetitive prompts give the n-gram proposer something to accept;
    # parity must hold regardless of acceptance
    prompts, n_new = [], []
    for i in range(8):
        base = rng.randint(0, 250, (rng.randint(3, 6),)).tolist()
        prompts.append((base * 6)[:rng.randint(6, 18)])
        n_new.append(int(rng.randint(4, 10)))

    def run_spec(model, mesh, params, cfg_kw, draft=None, dparams=None):
        eng = InferenceEngine(model, mesh, params,
                              EngineConfig(n_slots=8, block_size=4,
                                           max_seq_len=64, **cfg_kw),
                              draft_model=draft, draft_params=dparams)
        reqs = [eng.add_request(p, SamplingParams(max_new_tokens=n))
                for p, n in zip(prompts, n_new)]
        eng.run()
        return [list(r.generated) for r in reqs], eng

    grids = [
        ("q1", dict(mode="tesseract", data=1, depth=1, rows=1, cols=1)),
        ("q2_dp2", dict(mode="summa2d", data=2, depth=1, rows=2, cols=2)),
    ]
    for name, variant in grids:
        _, run, ctx, mesh, model = _build("yi-6b", variant)
        params = model.init(jax.random.PRNGKey(0))
        plain, _ = run_spec(model, mesh, params, dict(num_blocks=128))

        darch = get_reduced("smollm-360m")
        dcfg = dataclasses.replace(darch.model,
                                   vocab_size=model.cfg.vocab_size)
        draft = build_model(dcfg, ctx, run)
        dparams = draft.init(jax.random.PRNGKey(7))

        for mode, dm, dp in (("ngram", None, None),
                             ("draft", draft, dparams)):
            got, eng = run_spec(model, mesh, params,
                                dict(num_blocks=128, spec_k=3,
                                     spec_mode=mode), dm, dp)
            s = eng.stats
            assert got == plain, \
                f"{name}/{mode}: spec != plain\n{got}\n{plain}"
            assert s.spec_rounds > 0 and s.spec_committed > 0
            print(f"  spec {name}/{mode}: bit-identical "
                  f"(acceptance={s.acceptance_rate():.2f}, "
                  f"tokens/slot-round={s.tokens_per_round():.2f})")

        # pool pressure: evictions interleave with speculative rounds;
        # position-keyed replay must keep parity (rollback correctness)
        got, eng = run_spec(model, mesh, params,
                            dict(num_blocks=32, spec_k=3,
                                 spec_mode="ngram"))
        assert eng.stats.preemptions > 0, f"{name}: no eviction triggered"
        assert got == plain, f"{name}: evicted spec run != plain"
        print(f"  spec {name}/evict: parity held through "
              f"{eng.stats.preemptions} preemptions")

    # elastic: speculate, drop 8 -> 4 devices (verify bundle + draft pool
    # rebuilt, draft watermarks reset), finish — tokens identical
    _, run, ctx, mesh, model = _build(
        "yi-6b", dict(mode="tesseract", data=2, depth=1, rows=2, cols=2))
    params = model.init(jax.random.PRNGKey(0))
    plain, _ = run_spec(model, mesh, params, dict(num_blocks=128))
    darch = get_reduced("smollm-360m")
    dcfg = dataclasses.replace(darch.model, vocab_size=model.cfg.vocab_size)
    draft = build_model(dcfg, ctx, run)
    dparams = draft.init(jax.random.PRNGKey(7))
    eng = InferenceEngine(model, mesh, params, EngineConfig(
        n_slots=8, block_size=4, num_blocks=128, max_seq_len=64,
        spec_k=3, spec_mode="draft"), draft_model=draft,
        draft_params=dparams)
    reqs = [eng.add_request(p, SamplingParams(max_new_tokens=n))
            for p, n in zip(prompts, n_new)]
    for _ in range(3):
        eng.step()
    rp = eng.replan_to(4)
    assert rp.ctx.data == 1 and rp.n_used == 4, rp
    eng.run()
    got = [list(r.generated) for r in reqs]
    assert got == plain, f"post-replan spec tokens diverged\n{got}\n{plain}"
    print(f"  spec elastic: 8 -> {rp.n_used} devices mid-speculation, "
          f"tokens identical")
    print("PASS spec_decode")


def _mesh5(ctx, pipe):
    """[pipe x data x depth x row x col] mesh (pipe=1 kept as a real axis so
    the 1-stage baseline runs the same 1F1B code path)."""
    import jax
    from repro.core.mesh import pipeline_mesh
    n = pipe * ctx.data * ctx.tp
    return pipeline_mesh(ctx, pipe, jax.devices()[:n], keep_pipe_axis=True)


def check_pipeline_parity():
    """1F1B pipelined training on a [2-stage pipe x tesseract] mesh matches
    the 1-stage baseline (same code path, pipe=1) to bit precision on the
    loss for q in {1, 2} (grad-norm bitwise at q=2, <= 2 ulp at q=1), and
    the flat non-pipe step within fp-association noise; the measured
    schedule bubble equals the analytic (S-1)/(M+S-1); a checkpoint taken
    at pipe=2 restores onto the pipe=1 mesh (stage re-shard) and continues
    the run."""
    import jax, jax.numpy as jnp
    from repro.configs.base import RunConfig, ShapeSpec
    from repro.core.api import ParallelContext
    from repro.core.mesh import logical_mesh
    from repro.models.registry import build_model, get_reduced
    from repro.optim.adamw import adamw_init
    from repro.runtime.steps import build_train_step

    B, S = 8, 16
    tok = jax.random.randint(jax.random.PRNGKey(7), (B, S), 0, 250)
    batch = {"tokens": tok, "labels": jnp.roll(tok, -1, 1)}
    shape = ShapeSpec("t", seq_len=S, global_batch=B, kind="train")
    run = RunConfig(param_dtype="float32", compute_dtype="float32",
                    loss_chunk=8, q_chunk=8, kv_chunk=8, lr=1e-3,
                    pipeline_microbatches=4)

    def build(ctx, mesh):
        model = build_model(get_reduced("yi-6b").model, ctx, run)
        bundle = build_train_step(model, mesh, shape)
        params = jax.device_put(model.init(jax.random.PRNGKey(0)),
                                bundle.in_shardings[0])
        opt = jax.device_put(adamw_init(params), bundle.in_shardings[1])
        return model, bundle, params, opt

    def run_steps(ctx, mesh, n_steps=5):
        _, bundle, p, o = build(ctx, mesh)
        out = []
        for _ in range(n_steps):
            p, o, m = bundle.fn(p, o, batch)
            out.append((float(m["loss"]), float(m["grad_norm"])))
        return np.array(out), bundle

    grids = [("q1", dict(mode="tesseract", data=1, depth=1, rows=1, cols=1)),
             ("q2", dict(mode="tesseract", data=1, depth=1, rows=2, cols=2)),
             # 1F1B with the fused Pallas attention kernels: the microbatch
             # composition replays the identical kernel op sequence, so the
             # bitwise-loss contract must survive attn_impl="pallas"
             ("q1_pallas", dict(mode="tesseract", data=1, depth=1, rows=1,
                                cols=1, attn_impl="pallas"))]
    for name, kw in grids:
        ctx = ParallelContext(**kw)
        r2, b2 = run_steps(ctx, _mesh5(ctx, 2))
        r1, _ = run_steps(ctx, _mesh5(ctx, 1))
        info = b2.pipe_info
        assert info["n_stages"] == 2 and info["n_micro"] == 4, info
        assert abs(info["measured_bubble"] - info["predicted_bubble"]) \
            < 1e-9, info
        np.testing.assert_array_equal(
            r2[:, 0], r1[:, 0],
            err_msg=f"{name}: pipelined loss != 1-stage baseline (bitwise)")
        np.testing.assert_allclose(
            r2[:, 1], r1[:, 1], rtol=0, atol=3e-7,
            err_msg=f"{name}: grad_norm drifted past ulp noise")
        rf, _ = run_steps(ctx, logical_mesh(ctx, jax.devices()[:ctx.tp]))
        np.testing.assert_allclose(r2, rf, rtol=1e-5, atol=1e-6,
                                   err_msg=f"{name}: vs flat step")
        print(f"  pipeline {name}: 5-step loss bitwise == 1-stage "
              f"(bubble {info['measured_bubble']:.3f})")

    # ---- checkpoint across a pipe-degree change (2 -> 1 stages) ----
    import tempfile
    from repro.checkpoint.ckpt import CheckpointManager
    ctx = ParallelContext(mode="tesseract", data=1, depth=1, rows=2, cols=2)
    _, b2, p, o = build(ctx, _mesh5(ctx, 2))
    ref = []
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d)
        for i in range(4):
            p, o, m = b2.fn(p, o, batch)
            ref.append(float(m["loss"]))
            if i == 1:   # snapshot before donation reuses the buffers
                mgr.save(1, {"params": p, "opt": o}, blocking=True)
        _, b1, _, _ = build(ctx, _mesh5(ctx, 1))
        abs_p, abs_o, _ = b1.abstract_inputs
        st = mgr.restore(1, {"params": abs_p, "opt": abs_o},
                         {"params": b1.in_shardings[0],
                          "opt": b1.in_shardings[1]})
    p1, o1 = st["params"], st["opt"]
    got = []
    for _ in range(2):
        p1, o1, m = b1.fn(p1, o1, batch)
        got.append(float(m["loss"]))
    np.testing.assert_allclose(got, ref[2:], rtol=0, atol=1e-6,
                               err_msg="pipe=2 ckpt -> pipe=1 restore")
    print("  pipeline ckpt: pipe=2 checkpoint restored onto pipe=1, "
          "losses continue")
    print("PASS pipeline_parity")


def check_attn_impl_parity():
    """attn_impl="pallas" (fused flash fwd+bwd, paged decode kernel —
    interpret mode on CPU) == the jnp reference path, end to end:

    - training-loss + grad-norm trajectories for q in {1, 2} over 5 steps
      to fp32 exactness (the issue's trajectory-parity criterion);
    - GQA head padding (smollm 15->16, replicated KV with a non-uniform
      kv_map) on the q=2 grid;
    - greedy decode ids bit-identical through the dense decode step (the
      dense cache viewed as a page pool by the decode kernel).
    """
    import jax, jax.numpy as jnp
    B, S = 8, 16
    tok = jax.random.randint(jax.random.PRNGKey(23), (B, S), 0, 250)
    batch = {"tokens": tok, "labels": jnp.roll(tok, -1, 1)}

    grids = [
        ("q1", dict(mode="tesseract", data=1, depth=1, rows=1, cols=1)),
        ("q2_d2", dict(mode="tesseract", data=1, depth=2, rows=2, cols=2)),
    ]
    for name, variant in grids:
        for arch in (("yi-6b", "smollm-360m") if name == "q2_d2"
                     else ("yi-6b",)):
            ref, (_, _, _, _, _, _, gn_ref, _) = _train_losses(
                arch, variant, batch, n_steps=5)
            got, (_, _, _, _, _, _, gn_got, _) = _train_losses(
                arch, dict(variant, attn_impl="pallas"), batch, n_steps=5)
            np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5,
                                       err_msg=f"{arch}/{name}: loss")
            np.testing.assert_allclose(gn_got, gn_ref, rtol=2e-5, atol=2e-5,
                                       err_msg=f"{arch}/{name}: grad_norm")
            print(f"  attn_impl {arch}/{name}: pallas trajectory == jnp "
                  f"{got[-2:]}")

    # dense decode ids through the paged-view kernel
    from repro.configs.base import ShapeSpec
    from repro.runtime.steps import build_decode_step

    def decode_ids(variant):
        _, run, ctx, mesh, model = _build("yi-6b", variant)
        shape = ShapeSpec("d", seq_len=32, global_batch=8, kind="decode")
        bundle = build_decode_step(model, mesh, shape)
        params = model.init(jax.random.PRNGKey(0))
        cache_sds, _ = model.cache_abstract(8, 32, bundle.plan)
        cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), cache_sds)
        ids = jnp.arange(8, dtype=jnp.int32)[:, None] % 100
        out = []
        for t in range(3):
            ids, cache = bundle.fn(params, cache, ids, jnp.int32(t))
            out.append(np.asarray(ids).ravel())
        return np.stack(out)

    for name, variant in grids:
        ref = decode_ids(variant)
        got = decode_ids(dict(variant, attn_impl="pallas"))
        np.testing.assert_array_equal(got, ref, err_msg=f"decode {name}")
        print(f"  attn_impl decode {name}: ids bit-identical")
    print("PASS attn_impl_parity")


def check_ring_attention():
    """Ring/striped flash attention over the seq mesh axis (DESIGN.md §15)
    == the unsharded flash baseline, end to end:

    - training-loss + grad-norm trajectories for q in {1, 2} x seq in
      {2, 4} over 5 steps to fp32 exactness, striped (causal
      load-balanced) AND contiguous-ring schedules, jnp and pallas data
      paths (cells needing more fake devices than available are skipped);
    - seq-sharded PREFILL with attn_schedule="ring": K/V ring over the
      (depth, row) sharding produces bit-identical greedy ids vs the
      gather-full-KV local schedule.
    """
    import jax, jax.numpy as jnp
    B, S = 8, 16
    tok = jax.random.randint(jax.random.PRNGKey(11), (B, S), 0, 250)
    batch = {"tokens": tok, "labels": jnp.roll(tok, -1, 1)}
    ndev = len(jax.devices())

    ref, (_, _, _, _, _, _, gn_ref, _) = _train_losses(
        "yi-6b", dict(mode="tesseract", data=1, depth=1, rows=1, cols=1),
        batch, n_steps=5)
    assert np.all(np.isfinite(ref))

    cells = [
        ("q1_seq2_striped", dict(rows=1, cols=1, seq=2,
                                 attn_schedule="striped")),
        ("q1_seq2_ring", dict(rows=1, cols=1, seq=2, attn_schedule="ring")),
        ("q1_seq4_striped", dict(rows=1, cols=1, seq=4,
                                 attn_schedule="striped")),
        ("q1_seq4_ring", dict(rows=1, cols=1, seq=4, attn_schedule="ring")),
        ("q1_seq2_striped_pallas", dict(rows=1, cols=1, seq=2,
                                        attn_schedule="striped",
                                        attn_impl="pallas")),
        ("q2_seq2_striped", dict(rows=2, cols=2, seq=2,
                                 attn_schedule="striped")),
        ("q2_seq2_ring", dict(rows=2, cols=2, seq=2, attn_schedule="ring")),
        ("q2_seq4_striped", dict(rows=2, cols=2, seq=4,
                                 attn_schedule="striped")),
        ("q2_seq4_ring", dict(rows=2, cols=2, seq=4, attn_schedule="ring")),
    ]
    for name, kw in cells:
        variant = dict(mode="tesseract", data=1, depth=1)
        variant.update(kw)
        need = (variant["rows"] * variant["cols"] * variant["seq"])
        if need > ndev:
            print(f"  ring_attention {name}: ({need} devices unavailable: "
                  f"skipped)")
            continue
        got, (_, _, _, _, _, _, gn_got, _) = _train_losses(
            "yi-6b", variant, batch, n_steps=5)
        np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5,
                                   err_msg=f"{name}: loss")
        np.testing.assert_allclose(gn_got, gn_ref, rtol=2e-5, atol=2e-5,
                                   err_msg=f"{name}: grad_norm")
        print(f"  ring_attention {name}: trajectory == unsharded flash "
              f"{got[-2:]}")

    # ---- op-level fwd+bwd parity incl. sliding window + GQA (no windowed
    # model can seq-shard, so the window path is pinned here) ----
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
    from repro.core.ring_attention import ring_attention, stripe_permutation
    n = 4
    if n <= ndev:
        Bq, Hq, Hkv, L, D, W = 2, 4, 2, 8, 16, 8
        T = n * L
        ks = jax.random.split(jax.random.PRNGKey(3), 4)
        q = jax.random.normal(ks[0], (Bq, Hq, T, D), jnp.float32)
        k = jax.random.normal(ks[1], (Bq, Hkv, T, D), jnp.float32)
        v = jax.random.normal(ks[2], (Bq, Hkv, T, D), jnp.float32)
        cot = jax.random.normal(ks[3], (Bq, Hq, T, D), jnp.float32)
        mesh = Mesh(np.array(jax.devices()[:n]), ("s",))
        sp = P(None, None, "s", None)

        def dense_ref(qg, kg, vg, window):
            kk = jnp.repeat(kg, Hq // Hkv, axis=1)
            vv = jnp.repeat(vg, Hq // Hkv, axis=1)
            s = jnp.einsum("bhtd,bhsd->bhts", qg, kk) / np.sqrt(D)
            i = jnp.arange(T)[:, None]
            j = jnp.arange(T)[None, :]
            ok = j <= i
            if window:
                ok &= j > i - window
            s = jnp.where(ok, s, -jnp.inf)
            return jnp.einsum("bhts,bhsd->bhtd", jax.nn.softmax(s, -1), vv)

        for variant, window, impl in (("ring", 0, "jnp"),
                                      ("ring", W, "jnp"),
                                      ("ring", W, "pallas"),
                                      ("striped", 0, "jnp"),
                                      ("striped", 0, "pallas")):
            perm = (stripe_permutation(T, n) if variant == "striped"
                    else np.arange(T))

            def fwd(qa, ka, va):
                f = shard_map(
                    lambda q_, k_, v_: ring_attention(
                        q_, k_, v_, axes=("s",), variant=variant,
                        causal=True, local_window=window, impl=impl,
                        interpret=True),
                    mesh=mesh, in_specs=(sp, sp, sp), out_specs=sp)
                return f(qa[:, :, perm], ka[:, :, perm], va[:, :, perm])

            def obj(args):
                return jnp.sum(fwd(*args) * cot[:, :, perm])

            out = fwd(q, k, v)
            grads = jax.grad(obj)((q, k, v))
            ref_out = dense_ref(q, k, v, window)[:, :, perm]

            def ref_obj(args):
                return jnp.sum(dense_ref(*args, window)[:, :, perm]
                               * cot[:, :, perm])
            ref_grads = jax.grad(ref_obj)((q, k, v))
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(ref_out), rtol=2e-5, atol=2e-5,
                err_msg=f"op {variant}/w{window}/{impl}: out")
            for g, rg, nm in zip(grads, ref_grads, ("dq", "dk", "dv")):
                np.testing.assert_allclose(
                    np.asarray(g), np.asarray(rg), rtol=2e-5, atol=2e-5,
                    err_msg=f"op {variant}/w{window}/{impl}: {nm}")
            print(f"  ring_attention op {variant}/w{window}/{impl}: "
                  f"fwd+grads == dense ref")
    else:
        print("  ring_attention op-level: (4 devices unavailable: skipped)")

    # ---- seq-sharded prefill: (depth, row) K/V ring vs gather-full-KV ----
    from repro.configs.base import ShapeSpec
    from repro.runtime.steps import build_prefill_step

    def prefill_ids(variant):
        _, run, ctx, mesh, model = _build("yi-6b", variant)
        shape = ShapeSpec("p", seq_len=32, global_batch=2, kind="prefill")
        bundle = build_prefill_step(model, mesh, shape)
        params = model.init(jax.random.PRNGKey(0))
        ptok = jax.random.randint(jax.random.PRNGKey(29), (2, 32), 0, 250)
        ids, _cache = bundle.fn(params, {"tokens": ptok})
        return np.asarray(ids)

    grid = dict(mode="tesseract", data=1, depth=2, rows=2, cols=2)
    if 8 <= ndev:
        ref_ids = prefill_ids(grid)
        got_ids = prefill_ids(dict(grid, attn_schedule="ring"))
        np.testing.assert_array_equal(got_ids, ref_ids,
                                      err_msg="prefill ring ids")
        print("  ring_attention prefill d2q2: ring ids == gather-full-KV")
    else:
        print("  ring_attention prefill: (8 devices unavailable: skipped)")
    print("PASS ring_attention")


def check_train_elastic_accum():
    """Fault -> restore -> elastic 8 -> 4 device shrink mid-run: the train
    loop consumes Replan.accum_steps, so the global batch per optimizer
    step is preserved and the loss trajectory continues the uninterrupted
    8-device run under the step-keyed data stream."""
    import tempfile

    import jax
    from repro.configs.base import RunConfig, ShapeSpec
    from repro.core.api import ParallelContext
    from repro.core.mesh import logical_mesh
    from repro.models.registry import build_model, get_reduced
    from repro.runtime.elastic import replan
    from repro.runtime.train_loop import train

    run = RunConfig(param_dtype="float32", compute_dtype="float32",
                    loss_chunk=8, q_chunk=8, kv_chunk=8, lr=1e-3)
    shape = ShapeSpec("t", seq_len=16, global_batch=16, kind="train")
    arch = get_reduced("yi-6b")
    ctx8 = ParallelContext(mode="tesseract", data=8, depth=1, rows=1, cols=1)
    mesh8 = logical_mesh(ctx8, jax.devices()[:8])
    model8 = build_model(arch.model, ctx8, run)

    with tempfile.TemporaryDirectory() as dref, \
            tempfile.TemporaryDirectory() as dft:
        ref = train(model8, mesh8, shape, steps=8, ckpt_dir=dref,
                    ckpt_every=100, log_every=0)

        fired = set()

        def fault(step):
            if step == 5 and step not in fired:
                fired.add(step)
                raise RuntimeError("injected: half the fleet lost")

        try:
            train(model8, mesh8, shape, steps=8, ckpt_dir=dft, ckpt_every=2,
                  log_every=0, fault_hook=fault, max_restarts=0)
            raise AssertionError("fault did not surface")
        except RuntimeError:
            pass

        # driver-level elastic re-plan onto the surviving 4 devices
        rp = replan(4, ctx8, global_batch=shape.global_batch)
        assert rp.ctx.data == 4 and rp.accum_steps == 2 and rp.n_idle == 0, rp
        model4 = build_model(arch.model, rp.ctx, run)
        mesh4 = logical_mesh(rp.ctx, jax.devices()[:rp.n_used])
        res = train(model4, mesh4, shape, steps=8, ckpt_dir=dft,
                    ckpt_every=100, log_every=0,
                    accum_steps=rp.accum_steps)
        # restored from the step-3 checkpoint -> steps 4..7 remain
        assert res.last_step == 7 and len(res.losses) == 4, \
            (res.last_step, len(res.losses))
        np.testing.assert_allclose(res.losses, ref.losses[4:],
                                   rtol=1e-5, atol=1e-6,
                                   err_msg="post-replan trajectory diverged")
    print(f"  elastic train: 8 -> {rp.n_used} devices, accum_steps="
          f"{rp.accum_steps} consumed, trajectory preserved {res.losses}")
    print("PASS train_elastic_accum")


def check_chaos_train():
    """The ISSUE-6 acceptance schedule on the train side: one NaN step, one
    corrupted checkpoint (the newest at crash time), then device loss with
    an 8 -> 4 elastic replan — all from one seeded FaultPlan.  The run must
    recover, rejoin the fault-free 8-device loss trajectory, and the whole
    schedule must replay identically from the same seed."""
    import tempfile

    import jax
    from repro.configs.base import RunConfig, ShapeSpec
    from repro.core.api import ParallelContext
    from repro.core.mesh import logical_mesh
    from repro.models.registry import build_model, get_reduced
    from repro.runtime.elastic import replan
    from repro.runtime.faults import (DeviceLostError, FaultInjector,
                                      FaultPlan)
    from repro.runtime.train_loop import train

    run = RunConfig(param_dtype="float32", compute_dtype="float32",
                    loss_chunk=8, q_chunk=8, kv_chunk=8, lr=1e-3)
    shape = ShapeSpec("t", seq_len=16, global_batch=16, kind="train")
    arch = get_reduced("yi-6b")
    ctx8 = ParallelContext(mode="tesseract", data=8, depth=1, rows=1, cols=1)
    mesh8 = logical_mesh(ctx8, jax.devices()[:8])
    model8 = build_model(arch.model, ctx8, run)

    ref = train(model8, mesh8, shape, steps=10, log_every=0)

    # NaN at 2; corrupt the step-5 checkpoint (newest when the device dies
    # at 6, so recovery MUST fall back to step 3); lose half the fleet at 6
    plan = FaultPlan.parse(
        "train.grads@2:nan;ckpt.write@5:corrupt(0,bit_flip);"
        "train.step@6:device_loss(4)", seed=13)

    def chaos_run():
        inj = FaultInjector(plan)
        with tempfile.TemporaryDirectory() as d:
            try:
                train(model8, mesh8, shape, steps=10, ckpt_dir=d,
                      ckpt_every=2, log_every=0, injector=inj)
                raise AssertionError("device loss did not surface")
            except DeviceLostError as e:
                partial = e.partial_result
                rp = replan(e.n_surviving, ctx8,
                            global_batch=shape.global_batch)
            assert rp.ctx.data == 4 and rp.accum_steps == 2, rp
            model4 = build_model(arch.model, rp.ctx, run)
            mesh4 = logical_mesh(rp.ctx, jax.devices()[:rp.n_used])
            # same injector: spent faults stay spent across the replan
            res = train(model4, mesh4, shape, steps=10, ckpt_dir=d,
                        ckpt_every=100, log_every=0,
                        accum_steps=rp.accum_steps, injector=inj)
            return partial, res, list(inj.fired)

    partial, res, fired = chaos_run()
    assert partial.nan_skips == 1, partial.nan_skips
    assert res.ckpt_fallbacks == 1, res.ckpt_fallbacks   # corrupt step-5
    # restored from step 3 -> the 4-device run covers steps 4..9
    assert res.last_step == 9 and len(res.losses) == 6, \
        (res.last_step, len(res.losses))
    np.testing.assert_allclose(res.losses, ref.losses[4:],
                               rtol=1e-5, atol=1e-6,
                               err_msg="post-recovery trajectory diverged")
    assert fired == [("train.grads", 2, "nan"), ("ckpt.write", 5, "corrupt"),
                     ("train.step", 6, "device_loss")], fired

    partial2, res2, fired2 = chaos_run()
    assert fired2 == fired, "fault schedule did not replay identically"
    np.testing.assert_array_equal(
        np.array(res2.losses), np.array(res.losses),
        err_msg="replay from the same seed diverged")
    print(f"  chaos train: NaN skip + corrupt-ckpt fallback + 8->4 replan, "
          f"trajectory rejoined {res.losses}")
    print("PASS chaos_train")


def check_chaos_serve():
    """ISSUE-6 acceptance, serve side: NaN logits in one slot, a dropped
    engine step, KV pool exhaustion and a device loss (8 -> 4 replan) from
    one seeded plan — every surviving request keeps bit-exact greedy parity
    with the fault-free run, and the schedule replays identically."""
    import jax
    from repro.runtime.faults import FaultInjector, FaultPlan
    from repro.serve import EngineConfig, InferenceEngine, SamplingParams

    rng = np.random.RandomState(7)
    lens = [5, 9, 16, 12, 7, 3, 21, 10]
    n_new = [6, 10, 4, 8, 5, 12, 3, 7]
    prompts = [rng.randint(0, 250, (l,)).tolist() for l in lens]

    _, run, ctx, mesh, model = _build(
        "yi-6b", dict(mode="tesseract", data=2, depth=1, rows=2, cols=2))
    params = model.init(jax.random.PRNGKey(0))
    cfg = EngineConfig(n_slots=8, block_size=4, num_blocks=128,
                       max_seq_len=64)

    eng = InferenceEngine(model, mesh, params, cfg)
    reqs = [eng.add_request(p, SamplingParams(max_new_tokens=n))
            for p, n in zip(prompts, n_new)]
    ref_out = eng.run()
    ref = [ref_out[r.rid] for r in reqs]

    plan = FaultPlan.parse(
        "serve.logits@2:nan(3);serve.step@4:drop_step;"
        "serve.step@5:pool_exhaust(2);serve.step@8:device_loss(4)", seed=17)

    def chaos_run():
        e = InferenceEngine(model, mesh, params, cfg,
                            injector=FaultInjector(plan))
        rs = [e.add_request(p, SamplingParams(max_new_tokens=n))
              for p, n in zip(prompts, n_new)]
        out = e.run()
        return [out[r.rid] for r in rs], e.stats, list(e.injector.fired)

    got, stats, fired = chaos_run()
    assert stats.nan_quarantines >= 1, "NaN guard never fired"
    assert stats.dropped_steps == 1, stats.dropped_steps
    assert stats.pool_exhaust_events == 1, stats.pool_exhaust_events
    assert stats.failed == 0, f"{stats.failed} requests failed (expected " \
                              f"quarantine-and-replay, not shedding)"
    assert got == ref, f"survivor parity broke under chaos\n{got}\n{ref}"

    got2, stats2, fired2 = chaos_run()
    assert fired2 == fired, "fault schedule did not replay identically"
    assert got2 == got, "replay from the same seed diverged"
    print(f"  chaos serve: {stats.nan_quarantines} quarantines, "
          f"{stats.preemptions} preemptions, 8->4 replan — "
          f"bit-exact parity + identical replay")
    print("PASS chaos_serve")


def check_prefix_cache():
    """ISSUE-7 acceptance: overlapping-prefix workloads keep bit-identical
    greedy tokens cache-on vs cache-off — on q=1 and q=2 grids, under
    cache-eviction pressure with forced ``serve.prefix`` faults (eviction
    must respect refcounts: shared pages survive), and across an elastic
    8 -> 4 replan — while measuring a hit rate > 0 and COW splits."""
    import jax
    from repro.runtime.faults import FaultInjector, FaultPlan
    from repro.serve import EngineConfig, InferenceEngine, SamplingParams

    rng = np.random.RandomState(21)
    sys_prompt = rng.randint(0, 250, (10,)).tolist()
    # shared system prompt + per-request suffixes: block_size 4 puts the
    # shared/unique boundary 2 tokens into block 2 (a COW donor on every
    # later hit); an identical twin exercises the whole-prompt-hit clamp
    prompts = [sys_prompt + rng.randint(0, 250, (sl,)).tolist()
               for sl in (5, 9, 2, 13, 5, 7)]
    prompts.append(list(prompts[0]))                        # identical twin
    prompts.append(prompts[1][:12] + rng.randint(0, 250, (6,)).tolist())
    n_new = [6, 4, 8, 5, 7, 3, 6, 5]

    def run_eng(ctx_kw, *, cache_on, num_blocks, n_slots, plan=None,
                replan_to=0):
        _, run, ctx, mesh, model = _build("yi-6b", ctx_kw)
        params = model.init(jax.random.PRNGKey(0))
        cfg = EngineConfig(n_slots=n_slots, block_size=4,
                           num_blocks=num_blocks, max_seq_len=64,
                           prefix_cache=cache_on)
        inj = FaultInjector(plan) if plan is not None else None
        e = InferenceEngine(model, mesh, params, cfg, injector=inj)
        rs = [e.add_request(p, SamplingParams(max_new_tokens=n))
              for p, n in zip(prompts, n_new)]
        if replan_to:
            e.step()
            e.step()
            e.replan_to(replan_to)
        out = e.run()
        return [out[r.rid] for r in rs], e.stats

    grids = ((1, dict(mode="tesseract", data=1, depth=1, rows=1, cols=1),
              2, 64),
             (2, dict(mode="tesseract", data=2, depth=1, rows=2, cols=2),
              4, 128))
    for q, ctx_kw, n_slots, nb in grids:
        ref, _ = run_eng(ctx_kw, cache_on=False, num_blocks=nb,
                         n_slots=n_slots)
        got, st = run_eng(ctx_kw, cache_on=True, num_blocks=nb,
                          n_slots=n_slots)
        assert got == ref, f"q={q}: cache-on diverged\n{got}\n{ref}"
        assert st.cache_hit_rate() > 0 and st.prefix_tokens_reused > 0, \
            "shared-prefix workload never hit the cache"
        assert st.cow_splits >= 1, "mid-block divergence never COW-split"
        print(f"  q={q}: parity ok, hit_rate={st.cache_hit_rate():.3f} "
              f"({st.prefix_hits}/{st.prefix_lookups} admissions, "
              f"{st.prefix_tokens_reused} tokens), cow={st.cow_splits}")

    # tiny pool -> capacity evictions, plus forced serve.prefix faults;
    # only refcount-1 leaves may be reclaimed, so parity must survive
    q1 = grids[0][1]
    ref, _ = run_eng(q1, cache_on=False, num_blocks=16, n_slots=2)
    plan = FaultPlan.parse("serve.prefix@3:evict(2);serve.prefix@6:flush",
                           seed=5)
    got, st = run_eng(q1, cache_on=True, num_blocks=16, n_slots=2,
                      plan=plan)
    assert got == ref, f"eviction/fault parity broke\n{got}\n{ref}"
    assert st.cache_evictions >= 1, "tiny pool never evicted a cache leaf"
    print(f"  eviction: parity ok under {st.cache_evictions} evictions "
          f"+ forced evict/flush faults")

    # elastic 8 -> 4 replan with the cache on (index dies with the old
    # pool; carried residents un-share into private pages)
    q2 = grids[1][1]
    ref, _ = run_eng(q2, cache_on=False, num_blocks=128, n_slots=4)
    got, st = run_eng(q2, cache_on=True, num_blocks=128, n_slots=4,
                      replan_to=4)
    assert got == ref, f"post-replan parity broke\n{got}\n{ref}"
    print("  replan: 8 -> 4 devices, cache flushed, bit-exact parity")
    print("PASS prefix_cache")


def check_shardcheck():
    """Static analyzer end-to-end on real traces (DESIGN.md §13): IR facts
    (mesh capture, scan/while multiplicity), the replication sanitizer
    catching a seeded divergence, a clean verdict on a real train step, and
    byte-exact matmul comm-model conformance."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.analysis import rules
    from repro.analysis import shardcheck as sc
    from repro.analysis.collective_ir import extract_ir, replication_taints
    from jax import shard_map

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))
    sds = jax.ShapeDtypeStruct((4, 4), jnp.float32)

    # IR facts: scan multiplies by length, while by its cond bound, and the
    # shard_map records the mesh axis sizes
    def local(x):
        def body(c, _):
            return c + jax.lax.psum(x, "tp"), jax.lax.ppermute(
                c, "dp", [(0, 1), (1, 0)])
        c, ys = jax.lax.scan(body, x, None, length=3)

        def wbody(carry):
            i, v = carry
            return i + 1, v + jax.lax.psum(v, "dp")
        _, v = jax.lax.while_loop(lambda carry: carry[0] < 5, wbody,
                                  (jnp.int32(0), c))
        return v + jnp.sum(ys, axis=0)

    f = shard_map(local, mesh=mesh, in_specs=P("dp", "tp"),
                  out_specs=P("dp", "tp"))
    prog = extract_ir(jax.jit(f).trace(sds).jaxpr)
    by = prog.by_key()
    assert prog.axis_sizes == {"dp": 2, "tp": 2}, prog.axis_sizes
    assert by["psum@tp"]["count"] == 3, by          # scan length
    assert by["ppermute@dp"]["count"] == 3, by
    assert by["psum@dp"]["count"] == 5, by          # while cond bound
    print(f"  ir: mesh {prog.axis_sizes}, scan x3 + while x5 multiplicity ok")

    # replication sanitizer: axis_index flowing to a declared-replicated
    # output is a violation; an intervening psum clears it
    def leaky(x):
        return x + jax.lax.axis_index("dp").astype(x.dtype)

    def synced(x):
        leak = x + jax.lax.axis_index("dp").astype(x.dtype)
        return jax.lax.psum(leak, "dp") / 2.0

    rep = P(None, None)
    # check_vma=False: with it on, shard_map itself refuses the leak
    bad = jax.jit(shard_map(leaky, mesh=mesh, in_specs=rep, out_specs=rep,
                            check_vma=False)).trace(sds).jaxpr
    good = jax.jit(shard_map(synced, mesh=mesh, in_specs=rep, out_specs=rep,
                             check_vma=False)).trace(sds).jaxpr
    viols = replication_taints(bad, seed_inputs=False)
    assert any("dp" in v["axes"] for v in viols), viols
    assert replication_taints(good, seed_inputs=False) == [], \
        "psum-synced output flagged as divergent"
    print(f"  replication: leak caught ({len(viols)} violation), sync clean")

    # a real train step traces clean under the full rule catalog, and the
    # builder's meta promises real reductions
    jaxpr, meta, bundle, _ = sc._train_entry(data=2, rows=2, cols=2)
    prog = extract_ir(jaxpr)
    findings = rules.run_all(prog, meta, jaxpr, entry="q2_dp2")
    assert findings == [], "\n".join(map(str, findings))
    assert meta["grad_psum_axes"], meta.keys()
    assert len(meta["leaves"]) > 10, len(meta["leaves"])
    assert bundle.shardcheck_meta is meta
    got = prog.psum_axis_counts()
    for axes, want in meta["grad_psum_axes"].items():
        assert got.get(tuple(sorted(axes)), 0) >= want, (axes, want, got)
    print(f"  train q2_dp2: 0 findings over {len(prog.collectives)} "
          f"collectives, {len(meta['leaves'])} leaves")

    # comm-model conformance: traced wire bytes == summa.matmul_comm_bytes
    # exactly for every schedule x in-op variant
    findings, results = sc.matmul_conformance()
    assert findings == [], "\n".join(map(str, findings))
    for name, r in results.items():
        assert r["traced_bytes"] == r["predicted_bytes"], (name, r)
    print(f"  matmul: {len(results)} variants byte-exact vs comm model")
    print("PASS shardcheck")


CHECKS = {
    "summa_exact": check_summa_exact,
    "ring_schedule": check_ring_schedule,
    "ring_train_parity": check_ring_train_parity,
    "dense_parity": check_dense_parity,
    "inop_matches_deferred": check_inop_matches_deferred,
    "decode_parity": check_decode_parity,
    "prefill_parity": check_prefill_parity,
    "smollm_padding": check_smollm_padding,
    "moe_parity": check_moe_parity,
    "moe_decode": check_moe_decode,
    "families_parity": check_families_parity,
    "families_serve": check_families_serve,
    "zero1_parity": check_zero1_parity,
    "zero1_elastic": check_zero1_elastic,
    "moe_local_layout": check_moe_local_layout,
    "serve_engine": check_serve_engine,
    "engine_elastic": check_engine_elastic,
    "attn_impl_parity": check_attn_impl_parity,
    "ring_attention": check_ring_attention,
    "pipeline_parity": check_pipeline_parity,
    "train_elastic_accum": check_train_elastic_accum,
    "chaos_train": check_chaos_train,
    "chaos_serve": check_chaos_serve,
    "prefix_cache": check_prefix_cache,
    "spec_decode": check_spec_decode,
    "shardcheck": check_shardcheck,
}


def main():
    name = sys.argv[1]
    if name == "all":
        for n, fn in CHECKS.items():
            fn()
    else:
        CHECKS[name]()


if __name__ == "__main__":
    main()
