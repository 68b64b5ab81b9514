"""The SUMMA matmul's communication in a traced run (``chip/comm.py``): on a
small recorded trace, and on the tiny Yi-shaped step compiled for a
[2,2,1] grid of four virtual CPU devices."""
import json
import os
import pathlib
import subprocess
import sys
from types import SimpleNamespace as NS

import pytest

from chip import comm
from chip import trace_reduce as T
from repro.roofline.hlo import Instr

from test_trace_reduce import ev, line, plane

HERE = pathlib.Path(__file__).resolve().parent
STEP = "jit(local_step)/shard_map/jvp(blocks)/while/body/closed_call"
GATHER = f"{STEP}/attn/tp_comm/all_gather_invariant"
INSTRS = [
    # XLA's async pair around a SUMMA gather: the start names no op itself
    Instr("async-collective-start", "fusion", "", (GATHER,), True),
    Instr("async-collective-done", "fusion", GATHER, (GATHER,), True),
    # a matmul fusion that carries a step of the same gather
    Instr("fusion.2", "fusion", f"{STEP}/attn/dot_general",
          (f"{STEP}/attn/dot_general", GATHER), True),
    Instr("reduce-scatter.4", "reduce-scatter",
          f"{STEP}/mlp/tp_comm/reduce_scatter", (), True),
    # the loss's own gather, outside the matmul
    Instr("all-gather.5", "all-gather",
          f"{STEP}/head_loss/all_gather_invariant", (), True),
    # inside a fusion: not an op of the trace
    Instr("all-gather.6", "all-gather", GATHER, (), False),
]
# window 0..100 ns on two chips: start 0-5, the matmul 5-40, done 40-45,
# the scatter 45-60 (chip 1: 45-80), the loss's gather 60-70
PLANES = [
    plane("/host:CPU", [line("python", [ev("bench.window", 0, 100)])]),
] + [plane(f"/device:TPU:{d}", [line("XLA Ops", [
    ev("%async-collective-start = (f32[8]) fusion(%x)", 0, 5),
    ev("%fusion.2 = f32[8] fusion(%x)", 5, 35),
    ev("%async-collective-done = f32[8] fusion(%x)", 40, 5),
    ev("%reduce-scatter.4 = f32[4] reduce-scatter(%x)", 45, 15 + 20 * d),
    ev("%all-gather.5 = f32[8] all-gather(%x)", 60 + 20 * d, 10)])])
    for d in (0, 1)]


def _run(trace):
    return NS(trace=trace, devices=[None, None],
              cell=NS(traffic={"trace_steps": 1}))


def test_comm_ops_are_the_collectives_of_the_scope():
    ops = comm.comm_ops(INSTRS, "tp_comm")
    assert {k for k, v in ops.items() if v[0]} == {
        "async-collective-start", "async-collective-done",
        "reduce-scatter.4"}
    assert "all-gather.6" not in ops


def test_reader_on_a_recorded_trace(monkeypatch):
    from chip.harness import load_reader
    calls = []

    def fake(run, tp_comm):
        calls.append(run)
        return comm.comm_ops(INSTRS, tp_comm)
    monkeypatch.setattr(comm, "step_ops", fake)
    run = _run(T.reduce_planes(PLANES))
    got = load_reader("tp_comm_ms.train").read(run)
    # chip 0: 5 + 5 + 15 ns, chip 1: 5 + 5 + 35 ns; averaged, in ms
    assert got == pytest.approx(35e-6)
    assert load_reader("tp_comm_ms.train").read(run) == got
    assert len(calls) == 1


def test_reader_without_a_trace_or_the_scope(monkeypatch):
    from chip.harness import load_reader
    assert load_reader("tp_comm_ms.train").read(_run(None)) is None
    import repro.models.common as common
    monkeypatch.delattr(common, "TP_COMM")
    assert comm.seconds(_run(T.reduce_planes(PLANES))) is None


SCRIPT = r'''
import json, pathlib, sys
here = pathlib.Path(sys.argv[1])
sys.path[:0] = [str(here.parents[1]), str(here.parents[2] / "src"), str(here)]
from types import SimpleNamespace as NS
import jax
import tiny
from chip import comm
cfg = tiny.config({"mode": "tesseract", "depth": 1, "rows": 2, "cols": 2})
run = NS(cell=NS(config=cfg, traffic=tiny.TRAIN), devices=jax.devices()[:4])
ops = comm.step_ops(run, "tp_comm")
print(json.dumps(sorted({op for s, _, op in ops.values() if s})))
'''


def test_step_ops_of_the_tiny_grid_step():
    """The tiny step compiled for [2,2,1]: the SUMMA matmul's gathers and
    reduce-scatters are its communication ops."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", SCRIPT, str(HERE)], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    opcodes = set(json.loads(p.stdout.strip().splitlines()[-1]))
    assert {"all-gather", "reduce-scatter"} <= opcodes
