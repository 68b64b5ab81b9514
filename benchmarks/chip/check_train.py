"""``correct`` for a training cell: the program's first three optimizer
steps against the reference's.

The numbers compared (each against the limit in the cell's traffic file):
  loss_gap    worst relative gap of the three steps' losses;
  grad_gap    worst leaf of the first step's gradient norms, as the
              optimizer got them (after clipping), read back from the
              program's first moment: m1 = (1 - b1) * g;
  update_gap  worst leaf of the norm of the parameters' change over the
              three steps (the parameters step 4 receives, less the
              initial ones).
A leaf is one parameter of one layer.  ``leaf_gap`` measures each gap
against the larger of the leaf's reference norm and the median leaf's.
Leaves whose reference gradient norm is under a thousandth of the median
leaf's move under AdamW by rounding alone and are left out of update_gap.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import reference as R
from . import weights as W
from .harness import leaf_gap

UPDATE_FLOOR = 1e-3
STEPS = 3


def leaf_norms(tree: dict, layered: str) -> dict:
    """{name: norm} per parameter and per layer; ``layered`` names the
    sub-tree whose leaves stack layers on axis 0."""
    out = {}
    for k, v in tree.items():
        if k == layered:
            for kk, vv in v.items():
                n = jnp.sqrt(jnp.sum(jnp.square(vv.astype(jnp.float32)),
                                     axis=tuple(range(1, vv.ndim))))
                out.update({f"{kk}.{i}": n[i] for i in range(vv.shape[0])})
        else:
            out[k] = jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
    return out


def host(d: dict) -> dict:
    return {k: float(v) for k, v in jax.device_get(d).items()}


def program_leaf_norms(tree: dict) -> dict:
    """Program-tree leaf norms under the reference's leaf names."""
    return {k.replace("blocks.", ""): v
            for k, v in host(leaf_norms(tree, "blocks")).items()}


def program_delta_norms(arch: R.Arch, seed: int, params, abstract,
                        shardings) -> dict:
    """Norm of (params - initial params) per leaf; the initial parameters
    are made again from the seed inside the same call."""
    def f(p, key):
        p0 = W.to_program(W.make_logical(arch, key), abstract)
        p0 = jax.lax.with_sharding_constraint(p0, shardings)
        return leaf_norms(jax.tree.map(lambda a, b: a.astype(jnp.float32)
                                       - b.astype(jnp.float32), p, p0),
                          "blocks")
    out = jax.jit(f)(params, W.seed_key(seed))
    return {k.replace("blocks.", ""): v for k, v in host(out).items()}


def reference_shardings(arch: R.Arch, devices):
    """Each reference leaf split over every device along its largest axis
    that divides (replicated where none does)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(devices), ("x",))
    n = len(devices)

    def spec(shape):
        dims = sorted(range(len(shape)), key=lambda i: -shape[i])
        for i in dims:
            if shape[i] % n == 0 and shape[i] >= n:
                s = [None] * len(shape)
                s[i] = "x"
                return NamedSharding(mesh, P(*s))
        return NamedSharding(mesh, P())
    return jax.tree.map(spec, W.logical_shapes(arch),
                        is_leaf=lambda x: isinstance(x, tuple)), mesh


@functools.lru_cache(maxsize=None)
def _ref_fns(arch: R.Arch, recipe: R.Recipe, mode: str, rows: int,
             devices: tuple):
    shard, mesh = reference_shardings(arch, list(devices))
    num = R.Numerics(mode)
    from jax.sharding import NamedSharding, PartitionSpec as P
    rep = NamedSharding(mesh, P())
    make = jax.jit(lambda k: W.make_logical(arch, k), out_shardings=shard)
    grads = jax.jit(
        lambda p, i, l: R.loss_and_grads(arch, num, p, i, l, rows),
        in_shardings=(shard, rep, rep), out_shardings=(rep, shard))

    def upd(p, g, m, v, step):
        return R.adamw_step(recipe, p, g, m, v, step)
    update = jax.jit(upd, static_argnums=(4,), donate_argnums=(0, 1, 2, 3),
                     out_shardings=(shard, shard, shard, shard))
    zeros = jax.jit(lambda: jax.tree.map(
        lambda s: jnp.zeros(s, jnp.float32), W.logical_shapes(arch),
        is_leaf=lambda x: isinstance(x, tuple)), out_shardings=shard)
    norms = jax.jit(lambda t: leaf_norms(t, "layers"))
    delta = jax.jit(lambda p, k: leaf_norms(jax.tree.map(
        jnp.subtract, p, jax.lax.with_sharding_constraint(
            W.make_logical(arch, k), shard)), "layers"))
    return make, grads, update, zeros, norms, delta


def reference_readings(arch: R.Arch, recipe: R.Recipe, seed: int, batches,
                       devices, *, mode: str = "highest", rows: int = 1,
                       drop_half: bool = False) -> dict:
    """The reference's three steps on ``batches`` [(ids, labels)] (host
    arrays).  ``mode`` picks the numerics (a control runs in a lower one);
    ``drop_half`` plants the half-batch fault (the mean over the first
    half of the rows only)."""
    make, grads, update, zeros, norms, delta = _ref_fns(
        arch, recipe, mode, rows, tuple(devices))
    key = W.seed_key(seed)
    p, m, v = make(key), zeros(), zeros()
    losses, gnorm = [], None
    for step, (ids, labels) in enumerate(batches[:STEPS]):
        if drop_half:
            ids, labels = ids[: len(ids) // 2], labels[: len(ids) // 2]
        loss, g = grads(p, ids, labels)
        p, m, v, gc = update(p, g, m, v, step)
        losses.append(float(loss))
        if step == 0:
            gnorm = host(norms(gc))
        del g, gc
    dnorm = host(delta(p, key))
    del p, m, v
    return {"losses": losses, "grad": gnorm, "delta": dnorm}


def compare(prog: dict, ref: dict) -> dict:
    """The three numbers of a run, from program and reference readings."""
    lp, lr = np.array(prog["losses"]), np.array(ref["losses"])
    loss_gap = float(np.max(np.abs(lp - lr) / np.abs(lr)))
    grad_gap, grad_leaf, _ = leaf_gap(prog["grad"], ref["grad"])
    keep = {k for k, r in ref["grad"].items()
            if r >= UPDATE_FLOOR * float(np.median(list(ref["grad"].values())))}
    upd_gap, upd_leaf, n = leaf_gap({k: prog["delta"][k] for k in keep},
                                    {k: ref["delta"][k] for k in keep})
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "grad_gap_leaf": grad_leaf, "update_gap": upd_gap,
            "update_gap_leaf": upd_leaf, "update_leaves": n,
            "update_leaves_out": len(ref["grad"]) - n}
