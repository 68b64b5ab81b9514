"""The main path's Pallas kernels compile for a TPU v5e (interpret=False).

Interpret mode accepts block shapes that the chip's compiler refuses, so
these tests compile the kernels for a described ``v5e:2x2`` topology at
real model widths.  Nothing runs: a compile needs only shapes.  The
topology, shardings and shapes are built in fixtures (never at import), so
every pytest worker collects the same tests and only the one that runs
them loads the TPU compiler.
"""
import os

import pytest

import jax
import jax.numpy as jnp

from repro.kernels.autotune import DEFAULT_TILES
from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_attention import paged_attention

# (Hq, Hkv, head_dim) of the two dense configs the launchers serve
WIDTHS = {"smollm-360m": (15, 5, 64), "yi-6b": (32, 4, 128)}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    # a compile for a described chip cannot be read back from the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


# train seq 2048 at both widths; a prefill bucket of 320 rows pads to two
# 256-row tiles (the masked tail of the padded path)
@pytest.mark.parametrize("arch,T", [("smollm-360m", 2048), ("yi-6b", 2048),
                                    ("smollm-360m", 320)])
def test_flash_fwd_bwd_compiles(arch, T, one_chip, no_compile_cache):
    Hq, Hkv, D = WIDTHS[arch]
    bq, bk = DEFAULT_TILES          # what flash_tiles resolves to on TPU

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, bq=bq, bk=bk,
                              interpret=False)
        return jnp.sum(out.astype(jnp.float32))

    step = jax.value_and_grad(loss, argnums=(0, 1, 2))
    c = _compile(step, [((1, Hq, T, D), jnp.bfloat16),
                        ((1, Hkv, T, D), jnp.bfloat16),
                        ((1, Hkv, T, D), jnp.bfloat16)], one_chip)
    # forward, dQ and dK/dV passes are three kernels
    assert c.as_text().count("tpu_custom_call") >= 3


@pytest.mark.parametrize("arch", sorted(WIDTHS))
def test_paged_decode_compiles(arch, one_chip, no_compile_cache):
    Hq, Hkv, D = WIDTHS[arch]
    B, bs, nb, P = 8, 16, 40, 8 * 40 + 1

    def step(q, pk, pv, table, pos):
        kv_map = jnp.arange(Hq, dtype=jnp.int32) * Hkv // Hq
        return paged_attention(q, pk, pv, table, pos, kv_map,
                               interpret=False)

    c = _compile(step, [((B, Hq, D), jnp.bfloat16),
                        ((P, bs, Hkv, D), jnp.bfloat16),
                        ((P, bs, Hkv, D), jnp.bfloat16),
                        ((B, nb), jnp.int32), ((B,), jnp.int32)], one_chip)
    assert "tpu_custom_call" in c.as_text()
