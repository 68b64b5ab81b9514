"""The train step's layers in a traced run: each traced op's layer scope and
remat mark, and device seconds by layer.

The program names its layers with ``jax.named_scope``
(``repro.models.common.LAYER_SCOPES``) and XLA keeps each op's name stack
in its HLO metadata (``op_name``).  The op events of a v5e trace carry no
name stack, and the reduced trace (``trace_reduce``) names an op by its
instruction alone, so the op_names come from the compiled step's HLO text:
the step is built again as the train loop built it, lowered and compiled
(a hit in the run's persistent compilation cache: the same program), once,
after the window and only in a traced run.  An op belongs to the innermost
layer scope of its name stack; an op whose name stack holds
``rematted_computation`` was recomputed by remat
(``repro.roofline.hlo.scope_of``).  An op of the trace that the text does
not name, or whose name stack holds no layer, is unscoped.

Every op event counts, but the loops and calls that run other ops
(``CONTAINERS``): the trace nests those around their ops, and sometimes
nests a short op inside a kernel's interval, which would make the kernel
look like a container to a rule of intervals alone.

A program without the scopes gives ``None``, and the metrics that read
them are left out of the run's line.
"""
from __future__ import annotations

import re

from chip.harness import note

# opcodes of the ops that run other ops: their time is their ops' time
CONTAINERS = ("while", "call", "conditional")
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")


class Scoped:
    """Per-op layers of one run's reduced trace (``Reduced``)."""

    def __init__(self, trace, ops: dict):
        self.trace = trace
        self.ops = ops           # instruction -> (scope or None, remat, opcode)

    def _of(self, e) -> tuple:
        if e.op in self.ops:
            return self.ops[e.op]
        m = _OPCODE.search(e.name)
        return None, False, m.group(1) if m else ""

    def _seconds(self, match) -> float:
        """Device seconds of the ops (not containers) whose (scope, remat)
        match, inside the window, averaged over chips."""
        tr = self.trace
        if not tr.devices:
            return 0.0
        s0, s1 = tr.window
        tot = 0
        for d in tr.devices:
            for e in d.all_ops:
                scope, remat, opcode = self._of(e)
                if opcode not in CONTAINERS and match(scope, remat):
                    tot += max(0, min(e.end, s1) - max(e.start, s0))
        return tot / len(tr.devices) / 1e9

    def scope_seconds(self, scope) -> float:
        """Device seconds of the ops in ``scope`` (None: unscoped)."""
        return self._seconds(lambda s, r: s == scope)

    def remat_seconds(self) -> float:
        """Device seconds of the ops that remat recomputes."""
        return self._seconds(lambda s, r: r)

    def leaf_seconds(self) -> float:
        """Device seconds of every op: the sum of the buckets."""
        return self._seconds(lambda s, r: True)

    def missing(self) -> int:
        """Op events of chip 0 in the window that the HLO text does not
        name."""
        s0, s1 = self.trace.window
        return sum(e.op not in self.ops for e in self.trace.devices[0].all_ops
                   if s0 <= e.start < s1)


def step_ops(run) -> dict:
    """{instruction: (scope, remat, opcode)} of the train step the cell
    runs."""
    from chip import system
    from repro.configs.base import ShapeSpec
    from repro.roofline.hlo import instructions, scope_of
    from repro.runtime.steps import build_train_step
    tr = run.cell.traffic
    model, mesh = system.build(run.cell.config, "train", run.devices)
    b = build_train_step(model, mesh, ShapeSpec(
        "bench", seq_len=tr["seq_len"], global_batch=tr["global_batch"],
        kind="train"))
    text = b.fn.lower(*b.abstract_inputs).compile().as_text()
    return {i.name: scope_of(i.op_name, i.fused) + (i.opcode,)
            for i in instructions(text) if i.runs}


def for_run(run):
    """The run's ``Scoped``, made once and kept on the run; None without a
    trace or where the program names no layers."""
    if run.trace is None:
        return None
    if not hasattr(run, "scoped"):
        run.scoped = _scoped(run)
    return run.scoped


def _scoped(run):
    try:               # a program from before the scopes has none of these
        from repro.models.common import LAYER_SCOPES
        from repro.roofline.hlo import scope_of  # noqa: F401
    except ImportError:
        return None
    s = Scoped(run.trace, step_ops(run))
    steps = run.cell.traffic["trace_steps"]
    per = {k: 1e3 * s.scope_seconds(k) / steps
           for k in LAYER_SCOPES + (None,)}
    note("layer scopes, ms a step: " + ", ".join(
        f"{k or 'unscoped'} {v:.3f}" for k, v in per.items())
        + f"; remat {1e3 * s.remat_seconds() / steps:.3f}; ops "
        f"{1e3 * s.leaf_seconds() / steps:.3f}, busy "
        f"{1e3 * run.trace.busy_s() / steps:.3f}; op events the step's HLO "
        f"does not name: {s.missing()}")
    return s
