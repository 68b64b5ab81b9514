"""Seeded random weights, made by the benchmark on the device.

``make_logical`` draws the weights of the published architecture at their
published shapes, float32, from ``--seed``: the reference takes exactly
these.  ``program_params`` lays the same numbers out as the system under
test holds them (its leaf names, padding and dtype) in one jitted call with
the step's output shardings, so nothing is built whole on one device.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .reference import Arch

STD = 0.02          # every matrix and norm offset ~ N(0, STD^2)
LAYER_LEAVES = ("ln1", "ln2", "wq", "wk", "wv", "wo", "w_gate", "w_up",
                "w_down")


def seed_key(seed: int):
    """A PRNG key from any whole number up to 64 bits."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    k = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, seed >> 32)


def logical_shapes(a: Arch) -> dict:
    L, h, D = a.layers, a.hidden, a.head_dim
    return {
        "embed": (a.vocab, h), "head": (a.vocab, h), "ln_f": (h,),
        "layers": {
            "ln1": (L, h), "ln2": (L, h),
            "wq": (L, h, a.heads * D), "wk": (L, h, a.kv_heads * D),
            "wv": (L, h, a.kv_heads * D), "wo": (L, a.heads * D, h),
            "w_gate": (L, h, a.ffn), "w_up": (L, h, a.ffn),
            "w_down": (L, a.ffn, h),
        },
    }


def make_logical(a: Arch, key) -> dict:
    """All weights, float32, at the published shapes (traced; jit it)."""
    shapes = logical_shapes(a)
    flat, tree = jax.tree.flatten(shapes, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(key, len(flat))
    leaves = [STD * jax.random.normal(k, s, jnp.float32)
              for k, s in zip(keys, flat)]
    return jax.tree.unflatten(tree, leaves)


def _pad_to(x, shape, dtype):
    pads = [(0, t - s) for s, t in zip(x.shape, shape)]
    if any(p[1] < 0 for p in pads):
        raise ValueError(f"cannot pad {x.shape} to {shape}")
    return jnp.pad(x, pads).astype(dtype)


def to_program(logical: dict, abstract: dict) -> dict:
    """The logical weights in the program's parameter tree: ``blocks``
    stacked per layer, tail-padded (padded heads and vocabulary rows are
    zero) and cast to the program's parameter dtype."""
    src = {"embed": logical["embed"], "head": logical["head"],
           "ln_f": logical["ln_f"], "blocks": logical["layers"]}
    if (set(abstract) != set(src)
            or set(abstract["blocks"]) != set(LAYER_LEAVES)):
        raise ValueError(f"program parameter tree differs from the "
                         f"reference's: {sorted(abstract)} / "
                         f"{sorted(abstract.get('blocks', {}))}")
    return jax.tree.map(lambda x, s: _pad_to(x, s.shape, s.dtype), src,
                        abstract)


def program_params(a: Arch, seed: int, abstract: dict, shardings):
    """The program's parameters from ``seed``, made in one jitted call
    straight into ``shardings``."""
    fn = jax.jit(lambda k: to_program(make_logical(a, k), abstract),
                 out_shardings=shardings)
    return fn(seed_key(seed))
