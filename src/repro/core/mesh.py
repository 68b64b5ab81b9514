"""Logical mesh construction.

The production mesh (launch/mesh.py) is fixed by the cluster:
(16, 16) = ("data", "model") per pod, or (2, 16, 16) = ("pod", "data", "model").

The framework reshapes that device array into the logical mesh

    ("data", "depth", "row", "col")

where the contiguous "model" axis is factorized into (depth, row, col) —
Tesseract's [q, q, d] — and "pod" (if present) folds into "data".  Keeping the
model group contiguous maps (row, col) onto the innermost ICI links and
"depth" onto the outer ones, matching the paper's placement of the
least-communicating axis on the slowest links.
"""
from __future__ import annotations

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .api import LOGICAL_AXES, LOGICAL_AXES_SEQ, ParallelContext


def _axis_types(n):
    return (jax.sharding.AxisType.Auto,) * n


def make_mesh(shape, axes):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=_axis_types(len(axes)))


def logical_mesh(ctx: ParallelContext, devices=None) -> Mesh:
    """Build the ("data","depth","row","col") mesh from a flat device list.

    With ctx.seq > 1 the mesh gains a "seq" axis between "data" and the TP
    group — ("data","seq","depth","row","col") — so each sequence shard owns
    a contiguous [depth x row x col] sub-mesh and ring neighbors along "seq"
    are adjacent device blocks (DESIGN.md §15)."""
    if devices is None:
        devices = jax.devices()
    flat = np.asarray(devices).reshape(-1)
    need = ctx.data * ctx.seq * ctx.depth * ctx.rows * ctx.cols
    if flat.size != need:
        raise ValueError(
            f"need {need} devices for data={ctx.data} x seq={ctx.seq} x "
            f"[q={ctx.rows},{ctx.cols},d={ctx.depth}], got {flat.size}")
    if ctx.seq > 1:
        arr = flat.reshape(ctx.data, ctx.seq, ctx.depth, ctx.rows, ctx.cols)
        axes = LOGICAL_AXES_SEQ
    else:
        arr = flat.reshape(ctx.data, ctx.depth, ctx.rows, ctx.cols)
        axes = LOGICAL_AXES
    return Mesh(arr, axes, axis_types=_axis_types(len(axes)))


def pipeline_mesh(ctx: ParallelContext, pipe: int, devices=None, *,
                  keep_pipe_axis: bool = False) -> Mesh:
    """Build the ("pipe","data","depth","row","col") mesh: pipeline stages
    OUTERMOST (paper §3.4 composes PP outside the Tesseract TP group), each
    stage owning a full [data x q x q x d] sub-mesh on contiguous devices.

    pipe == 1 returns the plain 4-axis mesh (flat train step) unless
    ``keep_pipe_axis`` is set, which keeps the size-1 pipe axis so
    ``build_train_step`` runs the same 1F1B code path as a 1-stage
    baseline (the bit-parity oracle of the pipeline tests)."""
    if pipe < 1:
        raise ValueError(f"pipe must be >= 1, got {pipe}")
    if ctx.seq > 1 and (pipe > 1 or keep_pipe_axis):
        raise ValueError(
            "seq-axis sharding (ctx.seq > 1) does not compose with the "
            "pipeline mesh; use pipe=1 without keep_pipe_axis")
    if pipe == 1 and not keep_pipe_axis:
        return logical_mesh(ctx, devices)
    if devices is None:
        devices = jax.devices()
    flat = np.asarray(devices).reshape(-1)
    need = pipe * ctx.data * ctx.depth * ctx.rows * ctx.cols
    if flat.size != need:
        raise ValueError(
            f"need {need} devices for pipe={pipe} x data={ctx.data} x "
            f"[q={ctx.rows},{ctx.cols},d={ctx.depth}], got {flat.size}")
    arr = flat.reshape(pipe, ctx.data, ctx.depth, ctx.rows, ctx.cols)
    axes = ("pipe",) + LOGICAL_AXES
    return Mesh(arr, axes, axis_types=_axis_types(len(axes)))


def logical_from_production(prod_mesh: Mesh, ctx: ParallelContext) -> Mesh:
    """Reshape the harness-defined production mesh into the logical mesh.

    The trailing mesh axis of the production mesh is "model" (size 16); it must
    equal depth*rows*cols.  Leading axes ("pod", "data") fold into "data".
    """
    devs = prod_mesh.devices
    model = devs.shape[-1]
    if model != ctx.tp:
        raise ValueError(f"model axis {model} != depth*rows*cols {ctx.tp}")
    data_total = int(np.prod(devs.shape[:-1]))
    if data_total != ctx.data:
        raise ValueError(f"data axes {devs.shape[:-1]} != ctx.data {ctx.data}")
    return logical_mesh(ctx, devs.reshape(-1))


def named_sharding(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec(*spec))
