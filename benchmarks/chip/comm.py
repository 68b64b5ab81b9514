"""The SUMMA matmul's communication in a traced run: the ops of the train
step that run its collectives.

The program issues every collective of its Tesseract matmul
(``repro.core.summa``) under ``jax.named_scope(TP_COMM)``, inside the
layer scope that calls the matmul, and XLA keeps each op's name stack in
its HLO metadata.  As in ``scopes.py``, the name stacks come from the
compiled step's HLO text (the step built again as the train loop built
it: a hit in the run's persistent compilation cache), once, after the
window and only in a traced run.

An op counts when its name stack, or that of an op fused into it, holds
``TP_COMM`` (``repro.roofline.hlo.in_scope``) and no matrix product is
fused into it: the collectives, the async starts and dones that XLA wraps
them in, and their layout copies; not the matmul fusions into which the
compiler folds a step of a gather, whose time is the layer's compute.

A program without the scope gives ``None``, and the metric that reads it
is left out of the run's line.
"""
from __future__ import annotations

from chip import scopes
from chip.harness import note

MATMULS = ("dot_general", "conv_general_dilated")


def _primitive(op_name: str) -> str:
    return op_name.split(";", 1)[0].rsplit("/", 1)[-1]


def comm_ops(instrs, tp_comm: str) -> dict:
    """{instruction: (``tp_comm`` or None, False, opcode)} of the ops that
    run (``hlo.instructions``): the shape ``scopes.Scoped`` reads."""
    from repro.roofline.hlo import in_scope
    out = {}
    for i in instrs:
        if i.runs:
            comm = (in_scope(i.op_name, i.fused, tp_comm)
                    and not any(_primitive(o) in MATMULS
                                for o in (i.op_name, *i.fused)))
            out[i.name] = (tp_comm if comm else None, False, i.opcode)
    return out


def step_ops(run, tp_comm: str) -> dict:
    """``comm_ops`` of the train step the cell runs."""
    from chip import system
    from repro.configs.base import ShapeSpec
    from repro.roofline.hlo import instructions
    from repro.runtime.steps import build_train_step
    tr = run.cell.traffic
    model, mesh = system.build(run.cell.config, "train", run.devices)
    b = build_train_step(model, mesh, ShapeSpec(
        "bench", seq_len=tr["seq_len"], global_batch=tr["global_batch"],
        kind="train"))
    text = b.fn.lower(*b.abstract_inputs).compile().as_text()
    return comm_ops(instructions(text), tp_comm)


def seconds(run):
    """Device seconds of the communication ops inside the window,
    averaged over chips; None without a trace or where the program names
    no ``TP_COMM``.  The step's ops are read once and kept on the run."""
    if run.trace is None:
        return None
    if not hasattr(run, "tp_comm_s"):
        run.tp_comm_s = _seconds(run)
    return run.tp_comm_s


def _seconds(run):
    try:               # a program from before the scope
        from repro.models.common import TP_COMM
    except ImportError:
        return None
    ops = step_ops(run, TP_COMM)
    t = scopes.Scoped(run.trace, ops).scope_seconds(TP_COMM)
    n = sum(v[0] is not None for v in ops.values())
    note(f"{TP_COMM}: {1e3 * t / run.cell.traffic['trace_steps']:.3f} ms a "
         f"step, {n} ops of the step's HLO")
    return t
