"""ZeRO-1 optimizer-state sharding + bf16 mixed precision (DESIGN.md §9).

Single-device tests run in-process: host-side layout/reshard properties
(flat-index partitioning with padding, uneven leaves), the replicated <->
ZeRO checkpoint conversions, bf16-vs-fp32 numerics, fp32-master
bit-stability, and the adamw m/v downcast guard.

Multi-device parity (q x dp x master grid, pipeline mesh, elastic
re-partitioning) runs through repro.testing.mdchecks subprocesses —
``zero1_parity`` / ``zero1_elastic`` in tests/test_multidevice*.py on 8
fake devices; here only the q=2 x dp=4 cell that needs 16 fake devices.
"""
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

SRC = str(pathlib.Path(__file__).resolve().parents[1] / "src")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.optim import adamw, zero  # noqa: E402


# ---------------------------------------------------------------------------
# host-side layout properties (flat-index partitioning + padding)
# ---------------------------------------------------------------------------

CASES = [
    # (shape, spec, axis_sizes)
    ((7,), P(None), dict(data=2, depth=1, row=1, col=1)),
    ((7,), P("col"), dict(data=4, depth=2, row=1, col=1)),
    ((8, 6), P("row", "col"), dict(data=2, depth=2, row=2, col=2)),
    ((12, 5), P(("depth", "row"), None), dict(data=4, depth=2, row=2,
                                              col=1)),
    ((12, 4), P(("depth", "row", "col"), None), dict(data=2, depth=3,
                                                     row=2, col=2)),
    ((3, 8, 6), P(None, "row", "col"), dict(data=8, depth=1, row=2, col=2)),
    ((10, 10), P(None, None), dict(data=3, depth=2, row=1, col=1)),
    ((4, 6, 2), P("pipe", None, "col"), dict(data=2, depth=2, row=1, col=2,
                                             pipe=2)),
]


def _candidates(axis_sizes):
    return zero.ZERO_CANDIDATE_AXES + (("pipe",) if "pipe" in axis_sizes
                                       else ())


@pytest.mark.parametrize("case", CASES, ids=[str(c[0]) for c in CASES])
def test_host_shard_roundtrip(case):
    shape, spec, sizes = case
    lay = zero.layout_for(spec, shape, sizes, _candidates(sizes))
    rng = np.random.RandomState(0)
    full = rng.randn(*shape).astype(np.float32)
    z = zero.host_shard(full, lay)
    assert z.shape == (lay.n_slices, lay.k)
    assert lay.k * lay.zn >= int(np.prod(lay.local_shape))  # padding holds
    back = zero.host_unshard(z, lay)
    np.testing.assert_array_equal(back, full)


def test_layout_partitions_only_replicated_axes():
    """A leaf SHARDED over depth must not partition its state over depth
    (the head/expert case: chunks would be orphaned)."""
    sizes = dict(data=4, depth=2, row=2, col=2)
    lay = zero.layout_for(P(("depth", "row", "col"), None), (24, 4), sizes)
    assert lay.zaxes == ("data",)
    lay2 = zero.layout_for(P("row", "col"), (8, 4), sizes)
    assert lay2.zaxes == ("data", "depth")
    # pipe joins the candidates on pipeline meshes; pipe-sharded blocks
    # keep state stage-local
    sizes_p = dict(sizes, pipe=2)
    lay3 = zero.layout_for(P("pipe", None, "col"), (4, 6, 8), sizes_p,
                           zero.ZERO_CANDIDATE_AXES + ("pipe",))
    assert lay3.zaxes == ("data", "depth")
    lay4 = zero.layout_for(P("row", "col"), (8, 4), sizes_p,
                           zero.ZERO_CANDIDATE_AXES + ("pipe",))
    assert lay4.zaxes == ("data", "depth", "pipe")


def test_property_random_layout_roundtrip():
    """Property sweep: random shapes/shardings, shard->unshard == id and
    every element lands in exactly one slice row."""
    rng = np.random.RandomState(3)
    axes_pool = ["data", "depth", "row", "col"]
    for trial in range(50):
        nd = rng.randint(1, 4)
        sizes = {a: int(rng.choice([1, 2, 3, 4])) for a in axes_pool}
        shape, entries, used = [], [], set()
        for d in range(nd):
            ax = tuple(a for a in rng.permutation(axes_pool)
                       [:rng.randint(0, 3)] if a not in used)
            used.update(ax)
            base = int(rng.randint(1, 7))
            f = int(np.prod([sizes[a] for a in ax])) if ax else 1
            shape.append(base * f)
            entries.append(ax)
        spec = P(*[None if not e else e[0] if len(e) == 1 else e
                   for e in entries])
        lay = zero.layout_for(spec, tuple(shape), sizes)
        full = rng.randn(*shape).astype(np.float32)
        z = zero.host_shard(full, lay)
        np.testing.assert_array_equal(zero.host_unshard(z, lay), full,
                                      err_msg=f"trial {trial}: {shape} "
                                              f"{spec} {sizes}")
        # conservation: sum of slices == sum of elements (padding is zero)
        np.testing.assert_allclose(z.sum(), full.sum(), rtol=1e-5)


def test_convert_leaf_across_dp_and_layouts():
    """dp=8 ZeRO -> dp=4 ZeRO -> replicated -> dp=2 ZeRO round-trips."""
    shape, spec = (10, 6), P(None, "col")
    full = np.random.RandomState(1).randn(*shape).astype(np.float32)
    lays = {dp: zero.layout_for(spec, shape,
                                dict(data=dp, depth=1, row=1, col=2))
            for dp in (8, 4, 2)}
    z8 = zero.convert_leaf(full, None, lays[8])
    z4 = zero.convert_leaf(z8, lays[8], lays[4])
    np.testing.assert_array_equal(zero.host_unshard(z4, lays[4]), full)
    rep = zero.convert_leaf(z4, lays[4], None)
    np.testing.assert_array_equal(rep, full)
    z2 = zero.convert_leaf(rep, None, lays[2])
    np.testing.assert_array_equal(zero.host_unshard(z2, lays[2]), full)
    # JSON round-trip (the checkpoint-manifest form)
    j = lays[8].to_json()
    assert zero.LeafLayout.from_json(j) == lays[8]


def test_ckpt_converter_paths():
    conv = zero.make_ckpt_converter(None)
    arr = np.ones((3, 2), np.float32)
    # params and step pass through untouched
    assert conv("params/blocks/wq", arr, {}) is arr
    assert conv("opt/step", arr, {}) is arr
    # zero ckpt leaf -> replicated target unshards
    lay = zero.layout_for(P(None, None), (3, 2),
                          dict(data=2, depth=1, row=1, col=1))
    z = zero.host_shard(arr, lay)
    meta = {"opt_layout": {"blocks/wq": lay.to_json()}}
    out = conv("opt/m/blocks/wq", z, meta)
    np.testing.assert_array_equal(out, arr)
    # replicated ckpt leaf -> zero target shards
    conv2 = zero.make_ckpt_converter({"blocks/wq": lay.to_json()})
    np.testing.assert_array_equal(conv2("opt/m/blocks/wq", arr, {}), z)


# ---------------------------------------------------------------------------
# adamw m/v dtype guard (regression: nothing used to stop a silent downcast)
# ---------------------------------------------------------------------------

def test_adamw_never_downcasts_moments():
    w = {"w": jnp.ones((4,), jnp.bfloat16)}
    st = adamw.adamw_init(w, master=True)
    g = {"w": jnp.full((4,), 0.1, jnp.bfloat16)}   # low-precision grads
    _, st2 = adamw.adamw_update(w, g, st, lr=1e-2)
    assert st2["m"]["w"].dtype == jnp.float32
    assert st2["v"]["w"].dtype == jnp.float32
    assert st2["master"]["w"].dtype == jnp.float32


@pytest.mark.parametrize("leaf", ["m", "v", "master"])
def test_adamw_rejects_low_precision_state(leaf):
    w = {"w": jnp.ones((4,), jnp.float32)}
    st = adamw.adamw_init(w, master=True)
    st[leaf] = jax.tree.map(lambda x: x.astype(jnp.bfloat16), st[leaf])
    with pytest.raises(TypeError, match="must be float32"):
        adamw.adamw_update(w, {"w": w["w"]}, st, lr=1e-2)
    with pytest.raises(TypeError, match="must be float32"):
        adamw.lamb_update(w, {"w": w["w"]}, st, lr=1e-2)


# ---------------------------------------------------------------------------
# bf16 mixed-precision numerics (single device, full train step)
# ---------------------------------------------------------------------------

def _build_step(run_kw, n_steps=5):
    from repro.configs.base import RunConfig, ShapeSpec
    from repro.core.api import ParallelContext
    from repro.core.mesh import logical_mesh
    from repro.models.registry import build_model, get_reduced
    from repro.runtime.steps import build_train_step

    run = RunConfig(loss_chunk=8, q_chunk=8, kv_chunk=8, lr=1e-3, **run_kw)
    ctx = ParallelContext(mode="tesseract", data=1, depth=1, rows=1, cols=1)
    mesh = logical_mesh(ctx, jax.devices()[:1])
    model = build_model(get_reduced("yi-6b").model, ctx, run)
    shape = ShapeSpec("t", 16, 8, "train")
    bundle = build_train_step(model, mesh, shape)
    p = model.init(jax.random.PRNGKey(0))
    if run.zero_enabled:
        o = zero.zero_opt_init(bundle)
    else:
        o = adamw.adamw_init(p, master=run.master_weights)
    tok = jax.random.randint(jax.random.PRNGKey(7), (8, 16), 0, 250)
    batch = {"tokens": tok, "labels": jnp.roll(tok, -1, 1)}
    traj = []
    for _ in range(n_steps):
        p, o, m = bundle.fn(p, o, batch)
        traj.append((float(m["loss"]), float(m["grad_norm"])))
    return np.array(traj), p, o


FP32 = dict(param_dtype="float32", compute_dtype="float32")
BF16 = dict(param_dtype="bfloat16", compute_dtype="bfloat16")


def test_bf16_trajectory_tracks_fp32():
    """param_dtype/compute_dtype are live config: the bf16 step must run
    AND stay within mixed-precision noise of the fp32 trajectory."""
    ref, _, _ = _build_step(FP32)
    got, p, _ = _build_step(BF16)
    assert jax.tree.leaves(p)[0].dtype == jnp.bfloat16
    np.testing.assert_allclose(got[:, 0], ref[:, 0], rtol=0, atol=2e-2)
    np.testing.assert_allclose(got[:, 1], ref[:, 1], rtol=5e-2, atol=0)


@pytest.mark.parametrize("zero1", [False, True])
def test_fp32_master_bit_stable(zero1):
    """Under mixed precision the bf16 params must be EXACTLY the bf16 cast
    of the fp32 master at every step (the master is the single source of
    truth; no drift through the update/gather path)."""
    _, p, o = _build_step(dict(BF16, zero1=zero1))
    assert "master" in o
    for m, pp in zip(jax.tree.leaves(o["master"]), jax.tree.leaves(p)):
        assert m.dtype == jnp.float32
        if zero1:   # [1, k] padded slice on 1 device: trim + reshape
            m = np.asarray(m).reshape(-1)[:pp.size].reshape(pp.shape)
        np.testing.assert_array_equal(
            np.asarray(m, jnp.bfloat16.dtype), np.asarray(pp))


def test_loss_scale_neutral_in_fp32():
    ref, _, _ = _build_step(FP32)
    got, _, _ = _build_step(dict(FP32, loss_scale=4096.0))
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# the q=2 x dp=4 parity cell (needs 16 fake devices -> subprocess)
# ---------------------------------------------------------------------------

def test_zero1_parity_q2_dp4_16dev():
    env = dict(os.environ, PYTHONPATH=SRC, ZERO1_CELLS="q2_dp4",
               XLA_FLAGS="--xla_force_host_platform_device_count=16")
    r = subprocess.run(
        [sys.executable, "-m", "repro.testing.mdchecks", "zero1_parity"],
        env=env, capture_output=True, text=True, timeout=1800)
    assert r.returncode == 0, \
        f"zero1_parity[q2_dp4] failed:\n{r.stdout[-3000:]}\n{r.stderr[-3000:]}"
    assert "q2_dp4/fp32: losses/gnorm/params match" in r.stdout, r.stdout
