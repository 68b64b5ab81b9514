"""Device seconds by layer scope, on a small recorded trace and on the
tiny train step compiled here."""
from types import SimpleNamespace as NS

import pytest

from chip import scopes as S
from chip import trace_reduce as T
from repro.models.common import LAYER_SCOPES
from repro.roofline.hlo import scope_of

from test_trace_reduce import ev, line, plane

STEP = "jit(local_step)/transpose(jvp(blocks))/while/body/closed_call"
# instruction -> op_name as the compiled step's HLO text gives them
OP_NAMES = {
    "while.9": "jit(local_step)/while",
    "flash_fwd.3": "jit(local_step)/jvp(blocks)/while/body/closed_call/"
                   "checkpoint/attn/jit(_flash_jit)/flash_fwd/pallas_call",
    "flash_fwd.1": f"{STEP}/checkpoint/rematted_computation/attn/"
                   "jit(_flash_jit)/flash_fwd/pallas_call",
    "flash_dq.2": f"{STEP}/checkpoint/transpose(jvp(attn))/"
                  "jit(_flash_jit)/flash_dq/pallas_call",
    "fusion.4": f"{STEP}/checkpoint/rematted_computation/mlp/dot_general",
    "fusion.5": f"{STEP}/dynamic_update_slice",
    "fusion.6": "jit(local_step)/optimizer/mul",
    "copy.7": "",
    "copy-start.10": "",
}


def _ops(names=OP_NAMES):
    """{instruction: (scope, remat, opcode)}, the opcode from the name."""
    return {k: scope_of(v) + (k.split(".")[0],) for k, v in names.items()}

# window 0..100 ns; chip 0: the remat twin 0-20, the forward 20-30, dQ
# 30-45, an MLP fusion 45-60 (recomputed), the blocks' plumbing 60-65, the
# optimizer 65-80, an op without a scope 80-90 and one the text does not
# name 90-95; a loop 0-95 around them all, and a copy's start 40-41 inside
# dQ (which a rule of intervals alone would take dQ for a container of)
PLANES = [
    plane("/host:CPU", [line("python", [
        ev("bench.window", 0, 100), ev("repro.data.wait", 90, 5)])]),
    plane("/device:TPU:0", [line("XLA Ops", [
        ev("%while.9 = (f32[2]) while(...)", 0, 95),
        ev("%flash_fwd.1 = bf16[8] custom-call()", 0, 20),
        ev("%flash_fwd.3 = bf16[8] custom-call()", 20, 10),
        ev("%flash_dq.2 = bf16[8] custom-call()", 30, 15),
        ev("%copy-start.10 = (f32[8], f32[8]) copy-start(%x)", 40, 1),
        ev("%fusion.4 = f32[8] fusion(%x)", 45, 15),
        ev("%fusion.5 = f32[8] fusion(%x)", 60, 5),
        ev("%fusion.6 = f32[8] fusion(%x)", 65, 15),
        ev("%copy.7 = f32[8] copy(%x)", 80, 10),
        ev("%fusion.8 = f32[8] fusion(%x)", 90, 5)])]),
]


@pytest.fixture()
def scoped():
    return S.Scoped(T.reduce_planes(PLANES), _ops())


def test_scope_seconds(scoped):
    # the innermost scope, through transpose(jvp(...)) and inside blocks
    assert scoped.scope_seconds("attn") == pytest.approx(45e-9)
    assert scoped.scope_seconds("mlp") == pytest.approx(15e-9)
    assert scoped.scope_seconds("blocks") == pytest.approx(5e-9)
    assert scoped.scope_seconds("optimizer") == pytest.approx(15e-9)
    assert scoped.scope_seconds("embed") == 0.0
    # no layer in its name stack, or not named by the text at all
    assert scoped.scope_seconds(None) == pytest.approx(16e-9)
    assert scoped.missing() == 1


def test_kernel_with_a_nested_op_counts_whole(scoped):
    # dQ 30-45 holds the copy's start: both count, the kernel whole
    assert scoped._seconds(lambda s, r: s == "attn" and not r) == \
        pytest.approx(25e-9)


def test_buckets_sum_to_op_time(scoped):
    total = sum(scoped.scope_seconds(k) for k in LAYER_SCOPES + (None,))
    assert total == pytest.approx(scoped.leaf_seconds())
    # every op but the loop; the nested copy start overlaps dQ for 1
    assert scoped.leaf_seconds() == pytest.approx(96e-9)


def test_opcode_of_an_op_the_text_does_not_name(scoped):
    assert scoped._of(scoped.trace.devices[0].all_ops[-1]) == \
        (None, False, "fusion")
    ops = _ops({k: v for k, v in OP_NAMES.items() if k != "while.9"})
    # the loop's opcode from its event's name: still not counted
    assert S.Scoped(scoped.trace, ops).leaf_seconds() == \
        pytest.approx(96e-9)


def test_remat_and_unscoped_shares(scoped):
    # the forward's remat twin and the recomputed MLP fusion
    assert scoped.remat_seconds() == pytest.approx(35e-9)
    busy = scoped.trace.busy_s()
    assert busy == pytest.approx(95e-9)
    assert 100 * scoped.remat_seconds() / busy == pytest.approx(
        100 * 35 / 95)
    assert 100 * scoped.scope_seconds(None) / busy == pytest.approx(
        100 * 16 / 95)


def _run(trace):
    import tiny
    return NS(trace=trace, devices=__import__("jax").devices()[:1],
              cell=NS(config=tiny.config(), traffic=dict(tiny.TRAIN)))


def test_for_run_without_a_trace():
    assert S.for_run(_run(None)) is None


def test_step_ops_of_the_tiny_step():
    """The step built again as the train loop builds it: its ops carry
    every layer scope, and remat marks some."""
    ops = S.step_ops(_run(None))
    assert {s for s, _, _ in ops.values()} >= set(LAYER_SCOPES)
    assert any(r for _, r, _ in ops.values())
    assert {op for _, _, op in ops.values()} >= {"while", "fusion"}


def test_for_run_reads_the_step_once(monkeypatch):
    calls = []

    def fake_ops(run):
        calls.append(run)
        return _ops()
    monkeypatch.setattr(S, "step_ops", fake_ops)
    run = _run(T.reduce_planes(PLANES))
    run.cell.traffic["trace_steps"] = 1
    s = S.for_run(run)
    assert S.for_run(run) is s and len(calls) == 1
    assert s.scope_seconds("attn") == pytest.approx(45e-9)


def test_metric_readers(monkeypatch):
    from chip.harness import load_reader
    monkeypatch.setattr(S, "step_ops", lambda run: _ops())
    run = _run(T.reduce_planes(PLANES))
    run.cell.traffic["trace_steps"] = 1
    got = {m: load_reader(m).read(run) for m in (
        "attn_ms.train", "mlp_ms.train", "head_loss_ms.train",
        "optimizer_ms.train", "remat_share.train", "unscoped_share.train")}
    assert got == pytest.approx({
        "attn_ms.train": 45e-6, "mlp_ms.train": 15e-6,
        "head_loss_ms.train": 0.0, "optimizer_ms.train": 15e-6,
        "remat_share.train": 100 * 35 / 95,
        "unscoped_share.train": 100 * 16 / 95})
    assert all(load_reader(m).read(_run(None)) is None for m in got)

