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
    # forward, dQ and dK/dV passes are three kernels, each by its name
    text = c.as_text()
    assert text.count("tpu_custom_call") >= 3
    for name in ("flash_fwd", "flash_dq", "flash_dkv"):
        assert f"%{name}" in text


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
    assert "%paged_decode" in c.as_text()


# a tiny DenseLM train step as the one-chip cell runs it (fp32 params,
# bf16 compute, full remat, the Pallas kernels), compiled on one chip and
# on a [2,2,1] grid; the ops the device trace shows carry the layer scopes
WORK_OPS = ("fusion", "dot", "convolution", "custom-call", "reduce",
            "scatter")


def _train_step_hlo(devices, rows, cols, monkeypatch, schedule="fused"):
    import repro.kernels.ops as kops
    from repro.configs.base import RunConfig, ShapeSpec
    from repro.core.api import ParallelContext
    from repro.core.mesh import logical_mesh
    from repro.models.registry import build_model, get_reduced
    from repro.runtime.steps import build_train_step
    monkeypatch.setattr(kops, "interpret_mode", lambda: False)
    run = RunConfig(param_dtype="float32", compute_dtype="bfloat16",
                    remat="full", attn_impl="pallas")
    ctx = ParallelContext(mode="tesseract", data=1, depth=1, rows=rows,
                          cols=cols, attn_impl="pallas",
                          matmul_schedule=schedule)
    model = build_model(get_reduced("smollm-360m").model, ctx, run)
    b = build_train_step(model, logical_mesh(ctx, devices),
                         ShapeSpec("t", seq_len=256, global_batch=4,
                                   kind="train"))
    return b.fn.lower(*b.abstract_inputs).compile().as_text()


@pytest.mark.parametrize("rows,cols", [(1, 1), (2, 2)],
                         ids=["one_chip", "grid_2x2x1"])
def test_train_step_ops_carry_layer_scopes(rows, cols, topo, monkeypatch,
                                           no_compile_cache):
    import re
    from repro.models.common import LAYER_SCOPES
    from repro.roofline.hlo import instructions, scope_of
    text = _train_step_hlo(topo.devices[:rows * cols], rows, cols,
                           monkeypatch)
    ops = [i for i in instructions(text) if i.runs]
    # buffers the compiler allocates for itself: no work, no op_name
    alloc = set(re.findall(r"%([\w.\-]+) = \S+ custom-call\(\), "
                           r'custom_call_target="AllocateBuffer"', text))
    work = [i for i in ops if i.opcode in WORK_OPS and i.name not in alloc]
    scoped = {i.name: scope_of(i.op_name, i.fused) for i in work}
    assert [n for n, s in scoped.items() if s[0] is None] == []
    assert {s for s, _ in scoped.values()} == set(LAYER_SCOPES)
    assert any(r for _, r in scoped.values())        # remat recomputes
    # the kernels by name, in attention; full remat runs the forward twice
    kern = {n: scoped[n] for n in scoped if re.match(r"flash_", n)}
    assert {n.split(".")[0] for n in kern} == {"flash_fwd", "flash_dq",
                                                 "flash_dkv"}
    assert {s for s, _ in kern.values()} == {"attn"}
    fwd = sorted(r for n, (_, r) in kern.items() if n.startswith("flash_fwd"))
    assert fwd == [False, True]


# the SUMMA matmul's collectives, fused (all-gathers, reduce-scatters) and
# ring (collective-permutes), forward and backward, carry ``tp_comm`` inside
# their layer scope.  Which ops core/summa.py issues is marked here, apart
# from the program's own scope: each collective it calls runs under a
# marker scope of this test's.
MARK = "issued_by_summa"


@pytest.mark.parametrize("schedule,kinds", [
    ("fused", {"all-gather", "reduce-scatter"}),
    ("ring", {"collective-permute"})])
def test_summa_collectives_carry_tp_comm(schedule, kinds, topo, monkeypatch,
                                         no_compile_cache):
    import re
    from types import SimpleNamespace
    from jax import lax
    import repro.core.summa as summa
    from repro.models.common import LAYER_SCOPES, TP_COMM
    from repro.roofline.hlo import in_scope, instructions, scope_of
    mark = jax.named_scope(MARK)
    marked_lax = {n: getattr(lax, n) for n in dir(lax) if not n.startswith("_")}
    marked_lax.update({n: mark(getattr(lax, n))
                       for n in ("ppermute", "psum_scatter", "psum")})
    monkeypatch.setattr(summa, "lax", SimpleNamespace(**marked_lax))
    monkeypatch.setattr(summa, "all_gather_inv", mark(summa.all_gather_inv))
    text = _train_step_hlo(topo.devices[:4], 2, 2, monkeypatch, schedule)
    ops = [i for i in instructions(text) if i.runs]
    issued = [i for i in ops if in_scope(i.op_name, i.fused, MARK)]
    # the collectives by their own opcode (an async pair by its start)
    own = {re.sub(r"-start$", "", i.opcode) for i in issued}
    assert kinds <= own
    for i in issued:
        assert in_scope(i.op_name, i.fused, TP_COMM), i.name
        assert scope_of(i.op_name, i.fused)[0] in LAYER_SCOPES, i.name
    # nothing else carries it
    assert [i.name for i in ops if in_scope(i.op_name, i.fused, TP_COMM)
            and i not in issued] == []
    # forward and backward
    names = [n for i in issued for n in (i.op_name, *i.fused)
             if f"/{MARK}/" in n]
    assert any("transpose(" in n for n in names)
    assert any("transpose(" not in n for n in names)
