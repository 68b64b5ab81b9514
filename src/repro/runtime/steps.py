"""Step builders: wire a model + ParallelContext + shape into jit-able
train / prefill / decode step functions (shard_map inside jit).

Gradient synchronization design (see DESIGN.md §2 and core/summa.py):

- Replication axes of every param leaf except ``data`` are handled by
  ``pvary`` at the loss boundary — its transpose inserts one fused psum per
  (stacked) leaf per step.
- The ``data`` (DP) axis is synced explicitly after grad computation so it
  can be compressed (bf16 wire format) — a distributed-optimization lever.
- ``ctx.reduce_dgrad_in_op=True`` switches the Tesseract matmul weights to
  the paper's literal per-op all-reduce schedule (baseline measurements).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs.base import RunConfig, ShapeSpec
from ..core.api import LOGICAL_AXES, ParallelContext
from ..core.collectives import pvary, grad_sync, axis_size, psum_v
from ..core.ops import Plan, make_ops
from ..models.common import OPTIMIZER
from ..optim import adamw


# ---------------------------------------------------------------------------
# spec utilities
# ---------------------------------------------------------------------------

def spec_axes(spec: P) -> tuple:
    out = []
    for entry in spec:
        if entry is None:
            continue
        if isinstance(entry, (tuple, list)):
            out.extend(entry)
        else:
            out.append(entry)
    return tuple(out)


def replicated_axes(spec: P, axes: tuple = LOGICAL_AXES) -> tuple:
    """Mesh axes (from ``axes``) a leaf with partition ``spec`` is
    replicated over.  Pass ``ctx.mesh_axes`` so the seq axis (when active)
    counts as a replication axis for every param leaf."""
    used = set(spec_axes(spec))
    return tuple(a for a in axes if a not in used)


def rep_factor(ctx: ParallelContext, spec: P) -> int:
    sizes = dict(data=ctx.data, seq=ctx.seq, depth=ctx.depth, row=ctx.rows,
                 col=ctx.cols)
    f = 1
    for a in replicated_axes(spec, ctx.mesh_axes):
        f *= sizes[a]
    return f


def mark_by_name(tree, names: set, default=False):
    """Bool tree: True where any dict key on the leaf's path is in ``names``."""
    def f(path, _leaf):
        for p in path:
            key = getattr(p, "key", None)
            if key in names:
                return True
        return default
    return jax.tree_util.tree_map_with_path(f, tree)


def make_plan(ctx: ParallelContext, shape: ShapeSpec) -> Plan:
    return Plan.for_shape(shape.kind, global_batch=shape.global_batch,
                          batch_shards=ctx.batch_shards, data=ctx.data)


# ---------------------------------------------------------------------------
# bundles
# ---------------------------------------------------------------------------

@dataclass
class StepBundle:
    fn: Callable                 # jitted
    abstract_inputs: tuple       # trees of ShapeDtypeStruct (global shapes)
    in_shardings: tuple
    out_shardings: Any
    mesh: Any
    plan: Plan
    pipe_info: Any = None        # 1F1B schedule stats (pipelined steps only)
    # ZeRO-1 per-param-leaf optimizer-state layouts (optim/zero.LeafLayout
    # tree; None when the optimizer state is replicated).  Checkpoints store
    # layouts_to_json(opt_layouts) in their manifest so restore can re-shard
    # across dp-degree changes (checkpoint/ckpt.py + optim/zero.py).
    opt_layouts: Any = None
    # Ground truth for repro.analysis.shardcheck (train steps only): the
    # fused grad reductions the traced jaxpr must contain per axis set,
    # plus per-leaf layout facts for the zaxes-overlap rule.  See
    # _shardcheck_meta below for the schema.
    shardcheck_meta: Any = None

    def opt_layouts_json(self):
        from ..optim import zero as zopt
        return (zopt.layouts_to_json(self.opt_layouts)
                if self.opt_layouts is not None else None)


def _shardings(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def _shardcheck_meta(mesh, specs, red_tree, is_tess, layouts):
    """StepBundle.shardcheck_meta for a train step: what the deferred
    grad-sync machinery promises the traced jaxpr will contain, derived
    from the same trees the step builder wires into grad_sync /
    zreduce_scatter (so the analyzer checks the implementation against the
    builder's intent, not against a re-derivation of it).

    Schema:
      mesh_axes / axis_sizes  — the declared mesh
      grad_psum_axes          — {sorted axis tuple: n leaves} fused grad
                                psums (grad_sync bwd / pipeline red())
      grad_rs_axes            — {sorted axis tuple: n leaves} ZeRO-1
                                zreduce_scatter calls (zn > 1 leaves only)
      leaves                  — per-leaf {name, spec_axes, reduce_axes,
                                zaxes, tess} for the layout rules
    """
    is_p = lambda x: isinstance(x, P)
    kps = jax.tree_util.tree_flatten_with_path(specs, is_leaf=is_p)[0]
    red_l = jax.tree_util.tree_leaves(
        red_tree, is_leaf=lambda x: isinstance(x, tuple))
    tess_l = jax.tree_util.tree_leaves(is_tess)
    lay_l = (jax.tree_util.tree_leaves(layouts)
             if layouts is not None else [None] * len(kps))
    psums: dict = {}
    rs: dict = {}
    leaves = []
    for (kp, spec), red, tess, lay in zip(kps, red_l, tess_l, lay_l):
        red = tuple(sorted(red))
        if red:
            psums[red] = psums.get(red, 0) + 1
        zaxes = tuple(sorted(lay.zaxes)) if lay is not None else ()
        if lay is not None and not tess and lay.zn > 1:
            rs[zaxes] = rs.get(zaxes, 0) + 1
        leaves.append({
            "name": jax.tree_util.keystr(kp),
            "spec_axes": tuple(spec_axes(spec)),
            "reduce_axes": red,
            "zaxes": zaxes,
            "tess": bool(tess),
        })
    return {
        "mesh_axes": tuple(str(a) for a in mesh.axis_names),
        "axis_sizes": dict(zip([str(a) for a in mesh.axis_names],
                               mesh.devices.shape)),
        "grad_psum_axes": psums,
        "grad_rs_axes": rs,
        "leaves": leaves,
    }


def batch_abstract(ops, shape: ShapeSpec, ctx: ParallelContext, model=None):
    """Global ShapeDtypeStructs + specs for the host-layout token batch."""
    B, S = shape.global_batch, shape.seq_len
    sds = jax.ShapeDtypeStruct
    if shape.kind == "train":
        t = sds((B, S), jnp.int32)
        shapes = {"tokens": t, "labels": t}
        specs = {"tokens": ops.spec_tokens_in(), "labels": ops.spec_tokens_in()}
    elif shape.kind == "prefill":
        t = sds((B, S), jnp.int32)
        shapes, specs = {"tokens": t}, {"tokens": ops.spec_tokens_in()}
    else:
        raise ValueError(shape.kind)
    if model is not None:
        for name, (sd, sp) in model.batch_extras(shape).items():
            shapes[name] = sd
            specs[name] = sp
    return shapes, specs


# ---------------------------------------------------------------------------
# ZeRO-1 optimizer section (shared by the flat and pipelined train steps)
# ---------------------------------------------------------------------------

def zero_optimizer_step(params, opt_state, grads, *, layouts, is_tess,
                        specs, axis_sizes, run, update_fn, lr, gnorm_axes,
                        mesh_axes=LOGICAL_AXES):
    """ZeRO-1 update inside shard_map (DESIGN.md §9): reduce_scatter the
    zaxes-partial grads into each device's [k] state slice (in-op tesseract
    weights arrive reduced: plain slice), clip on the slices, run the
    optimizer on the fp32 m/v/master slices (master lazily adopted from the
    params at step 0), and all_gather the new param slices back — cast to
    param dtype FIRST so bf16 params ride the wire in bf16.

    Returns (new_params, new_opt_state, grad_norm)."""
    from ..optim import zero as zopt

    g_sl = jax.tree.map(
        lambda g, lay, t: (zopt.zslice(g, lay) if t else
                           zopt.zreduce_scatter(g, lay,
                                                run.grad_compression)),
        grads, layouts, is_tess)

    # --- global grad-norm clip on the slices (every element counted once
    # across the zaxes groups; the leaf's remaining replication divided out
    # as in the dense path) ---
    def slice_sq(sl, lay, s):
        rem = tuple(a for a in replicated_axes(s, mesh_axes)
                    if a not in lay.zaxes)
        rep = 1
        for a in rem:
            rep *= axis_sizes[a]
        val = jnp.sum(sl.astype(jnp.float32) ** 2) / rep
        return pvary(val, rem)
    sq = sum(jax.tree.leaves(jax.tree.map(slice_sq, g_sl, layouts, specs)))
    gnorm = jnp.sqrt(psum_v(sq, gnorm_axes))
    scale = jnp.minimum(1.0, run.grad_clip / (gnorm + 1e-6))
    g_sl = jax.tree.map(lambda g: g * scale, g_sl)

    p_sl = jax.tree.map(zopt.zslice, params, layouts)
    sq_ = lambda t: jax.tree.map(lambda x: x[0], t)  # [1, k] -> [k]
    st = {"step": opt_state["step"], "m": sq_(opt_state["m"]),
          "v": sq_(opt_state["v"])}
    if "master" in opt_state:
        # lazy master init: step 0 adopts the param slice
        is0 = (opt_state["step"] == 0)
        st["master"] = jax.tree.map(
            lambda m, pp: jnp.where(is0, pp.astype(jnp.float32), m),
            sq_(opt_state["master"]), p_sl)
    new_psl, new_state = update_fn(p_sl, g_sl, st, lr=lr,
                                   weight_decay=run.weight_decay)
    un = lambda t: jax.tree.map(lambda x: x[None], t)  # [k] -> [1, k]
    new_state = {"step": new_state["step"], "m": un(new_state["m"]),
                 "v": un(new_state["v"]),
                 **({"master": un(new_state["master"])}
                    if "master" in new_state else {})}
    new_params = jax.tree.map(
        lambda sl, p0, lay: zopt.zgather(sl, lay, p0.dtype),
        new_psl, params, layouts)
    return new_params, new_state, gnorm


# ---------------------------------------------------------------------------
# train step
# ---------------------------------------------------------------------------

def build_train_step(model, mesh, shape: ShapeSpec, *, accum_steps: int = 1,
                     fault_port: bool = False):
    """Build the jitted train step.

    accum_steps > 1 accumulates gradients over that many microbatches split
    from the (step-keyed) global batch before the single optimizer update —
    the knob ``runtime/elastic.Replan.accum_steps`` feeds so an elastic
    shrink keeps the global batch (and per-device activation memory)
    constant.  On a mesh with a ``pipe`` axis of size > 1 the pipelined
    1F1B builder is used instead (accum_steps folds into its microbatch
    count).

    Every step carries the non-finite update guard (DESIGN.md §11): when
    the loss or any gradient is NaN/Inf, the optimizer update is
    where-selected away — params and opt state come back bit-identical and
    ``metrics["skipped"]`` reads 1 — so one poisoned step can never corrupt
    the training state; the train loop retries/backs off the loss scale.

    fault_port=True adds a reserved scalar batch leaf ``fault_scale``
    multiplied into the gradients, the deterministic injection point
    ``runtime/faults.py`` uses to exercise that guard end-to-end (NaN/Inf
    grads by (seed, step), replayable).  Off by default: the compiled step
    and its batch schema are unchanged for normal runs.
    """
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
    if "pipe" in mesh.axis_names:
        # any mesh carrying a pipe axis trains through the 1F1B schedule —
        # a pipe=1 mesh is the exact 1-stage baseline of the same code path
        return _build_pipeline_train_step(model, mesh, shape, accum_steps,
                                          fault_port=fault_port)
    ctx: ParallelContext = model.ctx
    run: RunConfig = model.run
    if ctx.seq > 1:
        if not getattr(model, "supports_seq_shard", False):
            raise NotImplementedError(
                f"{type(model).__name__} does not support sequence-axis "
                f"sharding (supports_seq_shard=False): every time-mixing "
                f"op must be ring-able")
        if shape.seq_len % ctx.seq:
            raise ValueError(
                f"seq_len={shape.seq_len} not divisible by seq shards "
                f"{ctx.seq}")
        if model.batch_extras(shape):
            raise NotImplementedError(
                "seq-sharded training with modality extras is not "
                "supported (extra batch leaves would need seq striping)")
    maxes = ctx.mesh_axes
    plan = make_plan(ctx, shape)
    ops = make_ops(ctx, plan)

    specs = model.specs(ops)
    tess_names = getattr(model, "tess_weight_names", lambda: set())()
    inop = ctx.reduce_dgrad_in_op and ctx.mode in ("tesseract", "summa2d")
    is_tess = (mark_by_name(specs, tess_names) if inop
               else jax.tree.map(lambda _: False, specs))

    rep_tree = jax.tree.map(lambda s: rep_factor(ctx, s), specs)
    from ..optim import zero as zopt

    use_zero = run.zero_enabled
    opt_master = run.master_weights
    if run.optimizer == "lamb":
        if use_zero:
            raise NotImplementedError(
                "optimizer='lamb' with ZeRO-1 is not wired: the trust "
                "ratios need unsharded per-leaf norms")
        def _leaf_norm(x):
            # global L2 of a sharded leaf: psum_v reduces only the axes
            # the leaf is split over
            return jnp.sqrt(psum_v(jnp.sum(x.astype(jnp.float32) ** 2),
                                   maxes))
        update_fn = partial(adamw.lamb_update, norm_fn=_leaf_norm)
    else:
        update_fn = adamw.adamw_update

    # ---- ZeRO-1 (DESIGN.md §9): per-leaf optimizer-state partitioning ----
    # Each leaf's state is partitioned over the DP-like axes the leaf is
    # REPLICATED on (zaxes = (data, depth) minus the leaf's own sharding
    # axes — head/experts are depth-sharded and keep their state
    # depth-local).  The data/depth grad psum is replaced by a
    # reduce_scatter onto the flat-index slice; the update runs on the
    # slice and one all_gather per leaf (in param dtype — bf16 wire under
    # mixed precision) rebuilds the params.
    axis_sizes = dict(data=ctx.data, depth=ctx.depth, row=ctx.rows,
                      col=ctx.cols, **(dict(seq=ctx.seq) if ctx.seq > 1
                                       else {}))
    abs_params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    layouts = (zopt.build_layouts(specs, abs_params, axis_sizes)
               if use_zero else None)

    def pvary_axes(s, t):
        if t:  # in-op tesseract weight: custom bwd reduces (data, depth
            # [, seq]) — summa._dgrad_axes covers the seq axis in-op
            return ()
        ax = replicated_axes(s, maxes)
        if use_zero:
            # the leaf's zaxes stay UNREDUCED here: zreduce_scatter below
            # reduces them into the device-local state slice instead
            ax = tuple(a for a in ax if a not in zopt.ZERO_CANDIDATE_AXES)
        return ax

    ls = run.loss_scale

    def local_step(params, opt_state, batch):
        fscale = None
        if fault_port:
            batch = dict(batch)
            fscale = batch.pop("fault_scale")

        def loss_fn(p, mb):
            # grad_sync: fwd pvary / bwd fused (optionally bf16-compressed)
            # psum over each leaf's replication axes — the deferred form of
            # the paper's depth all-reduce, plus the DP reduction (under
            # ZeRO-1 the DP reduction moves to the reduce_scatter below).
            pv = jax.tree.map(
                lambda x, s, t: grad_sync(x, pvary_axes(s, t),
                                          run.grad_compression),
                p, specs, is_tess)
            out = model.loss(pv, mb, ops)
            return out * ls if ls != 1.0 else out

        # ZeRO-1 differentiates w.r.t. params made varying over their zaxes
        # OUTSIDE the vjp: the grads then arrive as unreduced partial sums
        # there (no autodiff psum) and zreduce_scatter reduces them.
        dparams = (jax.tree.map(
            lambda x, lay, t: pvary(x, () if t else lay.zaxes),
            params, layouts, is_tess) if use_zero else params)
        if accum_steps == 1:
            loss, grads = jax.value_and_grad(loss_fn)(dparams, batch)
        else:
            # microbatch gradient accumulation: split every batch leaf's
            # local batch dim into accum_steps slices and scan, so only one
            # microbatch's activations are ever live.  Equal-sized
            # microbatches -> mean-of-means == full-batch mean CE.
            mbs = jax.tree.map(
                lambda x: x.reshape((accum_steps, x.shape[0] // accum_steps)
                                    + x.shape[1:]), batch)

            def micro(carry, mb):
                c_loss, c_grads = carry
                l, g = jax.value_and_grad(loss_fn)(dparams, mb)
                return (c_loss + l, jax.tree.map(jnp.add, c_grads, g)), None

            init = (jnp.float32(0),
                    jax.tree.map(lambda p: p * 0, dparams))
            (loss, grads), _ = lax.scan(micro, init, mbs)
            loss = loss / accum_steps
            grads = jax.tree.map(lambda g: g / accum_steps, grads)

        if ls != 1.0:  # static loss scaling: unscale before clip/optimizer
            loss = loss / ls
            grads = jax.tree.map(lambda g: g / ls, grads)
        if fscale is not None:
            grads = jax.tree.map(lambda g: g * fscale, grads)

        with jax.named_scope(OPTIMIZER):
            lr = adamw.cosine_lr(opt_state["step"], base_lr=run.lr,
                                 warmup=100, total=10000)
            if use_zero:
                new_params, new_state, gnorm = zero_optimizer_step(
                    params, opt_state, grads, layouts=layouts,
                    is_tess=is_tess, specs=specs, axis_sizes=axis_sizes,
                    run=run, update_fn=update_fn, lr=lr, gnorm_axes=maxes,
                    mesh_axes=maxes)
            else:
                # --- global grad-norm clip (layout aware) ---
                def leaf_sq(g, rep, s):
                    val = jnp.sum(g.astype(jnp.float32) ** 2) / rep
                    return pvary(val, replicated_axes(s, maxes))
                sq = sum(jax.tree.leaves(jax.tree.map(leaf_sq, grads,
                                                      rep_tree, specs)))
                gnorm = jnp.sqrt(psum_v(sq, maxes))
                scale = jnp.minimum(1.0, run.grad_clip / (gnorm + 1e-6))
                grads = jax.tree.map(lambda g: g * scale, grads)
                new_params, new_state = update_fn(
                    params, grads, opt_state, lr=lr,
                    weight_decay=run.weight_decay)
            # non-finite update guard: any NaN/Inf grad poisons gnorm (sum
            # of squares), so one scalar predicate covers every leaf; the
            # select keeps params/opt bit-identical on a poisoned step
            finite = jnp.isfinite(loss) & jnp.isfinite(gnorm)
            new_params = jax.tree.map(lambda n, o: jnp.where(finite, n, o),
                                      new_params, params)
            new_state = jax.tree.map(lambda n, o: jnp.where(finite, n, o),
                                     new_state, opt_state)
        metrics = {"loss": loss, "grad_norm": gnorm, "lr": lr,
                   "skipped": 1.0 - finite.astype(jnp.float32)}
        return new_params, new_state, metrics

    if use_zero:
        # opt leaves: [n_slices, k] with dim0 mapped over the leaf's zaxes
        # PLUS its own sharded axes (row-replicated leaves must stay
        # row-replicated in their opt slices or the reconstructed param's
        # vma would spuriously vary over row).
        zspec_tree = jax.tree.map(lambda lay: lay.state_spec(), layouts)
        opt_specs = {"m": zspec_tree, "v": zspec_tree, "step": P(),
                     **({"master": zspec_tree} if opt_master else {})}
    else:
        opt_specs = {
            "m": specs, "v": specs, "step": P(),
            **({"master": specs} if opt_master else {}),
        }
    batch_sds, batch_specs_ = batch_abstract(ops, shape, ctx, model)
    if accum_steps > 1:
        # tokens/labels are additionally split over row by embed's
        # reduce-scatter, so each microbatch must keep that divisible too
        row_factor = ctx.rows if ctx.mode != "megatron1d" else 1
        for name, sd in batch_sds.items():
            loc0 = NamedSharding(mesh, batch_specs_[name]).shard_shape(
                tuple(sd.shape))[0]
            rf = row_factor if name in ("tokens", "labels", "mask") else 1
            if loc0 % accum_steps or (loc0 // accum_steps) % rf:
                raise ValueError(
                    f"accum_steps={accum_steps} does not evenly split batch "
                    f"leaf {name!r}: local batch {loc0} (global "
                    f"{sd.shape[0]}) must divide into accum_steps "
                    f"microbatches of a multiple of the row factor {rf}; "
                    f"pick accum_steps dividing global_batch/"
                    f"(data*depth*row) or re-plan")
    if fault_port:
        batch_sds = dict(batch_sds,
                         fault_scale=jax.ShapeDtypeStruct((), jnp.float32))
        batch_specs_ = dict(batch_specs_, fault_scale=P())
    metric_specs = {"loss": P(), "grad_norm": P(), "lr": P(),
                    "skipped": P()}

    smapped = shard_map(
        local_step, mesh=mesh,
        in_specs=(specs, opt_specs, batch_specs_),
        out_specs=(specs, opt_specs, metric_specs))
    if ctx.seq > 1 and ctx.train_attn_schedule() == "striped":
        # Striped ring attention (DESIGN.md §15): permute the TIME dim of
        # the host-layout batch inside jit, before shard_map, so seq shard
        # r receives global positions r, r+seq, r+2*seq, ... .  Labels ride
        # the same permutation (they are per-position), ops.positions()
        # emits the matching striped RoPE positions, and the ring mask in
        # core/ring_attention.py assumes exactly this placement.
        from ..core.ring_attention import stripe_permutation
        perm = jnp.asarray(stripe_permutation(shape.seq_len, ctx.seq))
        inner = smapped

        def smapped(params, opt_state, batch):
            batch = {k: (v[:, perm] if k in ("tokens", "labels", "mask")
                         else v) for k, v in batch.items()}
            return inner(params, opt_state, batch)

    in_sh = (_shardings(mesh, specs), _shardings(mesh, opt_specs),
             _shardings(mesh, batch_specs_))
    out_sh = (_shardings(mesh, specs), _shardings(mesh, opt_specs),
              _shardings(mesh, metric_specs))
    fn = jax.jit(smapped, donate_argnums=(0, 1), in_shardings=in_sh,
                 out_shardings=out_sh)

    if use_zero:
        zt = jax.tree.map(lambda lay: lay.abstract(), layouts)
        abs_opt = {"m": zt, "v": zt,
                   "step": jax.ShapeDtypeStruct((), jnp.int32),
                   **({"master": zt} if opt_master else {})}
    else:
        abs_opt = jax.eval_shape(partial(adamw.adamw_init, master=opt_master),
                                 abs_params)
    red_tree = jax.tree.map(
        lambda s, t: tuple(sorted(pvary_axes(s, t))), specs, is_tess)
    return StepBundle(
        fn=fn,
        abstract_inputs=(abs_params, abs_opt, batch_sds),
        in_shardings=in_sh, out_shardings=out_sh, mesh=mesh, plan=plan,
        opt_layouts=layouts,
        shardcheck_meta=_shardcheck_meta(mesh, specs, red_tree, is_tess,
                                         layouts))


# ---------------------------------------------------------------------------
# pipelined train step (1F1B over a [pipe x data x depth x row x col] mesh)
# ---------------------------------------------------------------------------

def _build_pipeline_train_step(model, mesh, shape: ShapeSpec,
                               accum_steps: int = 1,
                               fault_port: bool = False):
    """Train step with pipeline parallelism OUTSIDE the Tesseract TP group
    (paper §3.4): stage-sharded block params/opt state over the mesh's
    ``pipe`` axis, 1F1B microbatch schedule (runtime/pipeline.py), loss and
    grad reduction on the last stage, deferred replication-axis grad psums
    extended with the pipe axis for the stage-replicated leaves (embed /
    head / final norm).  ``accum_steps`` folds into the microbatch count —
    in PP, gradient accumulation IS more microbatches through the same
    flush, which also shrinks the bubble.
    """
    from .pipeline import pipeline_1f1b_grads

    ctx: ParallelContext = model.ctx
    run: RunConfig = model.run
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    S_pipe = int(sizes["pipe"])
    if shape.kind != "train":
        raise ValueError(f"pipeline step only supports train shapes, "
                         f"got {shape.kind!r}")
    if not getattr(model, "supports_pipeline", False):
        raise NotImplementedError(
            f"{type(model).__name__} does not support the pipeline stage "
            f"API (supports_pipeline=False)")
    if model.batch_extras(shape):
        raise NotImplementedError("pipelined training with modality extras "
                                  "is not supported")
    if run.optimizer != "adamw":
        raise NotImplementedError("pipelined training supports "
                                  "optimizer='adamw' only")
    if ctx.mode not in ("tesseract", "summa2d"):
        raise NotImplementedError(f"pipeline requires a tesseract/summa2d "
                                  f"TP group, got {ctx.mode!r}")
    L = model.cfg.num_layers
    if L % S_pipe:
        raise ValueError(f"num_layers={L} not divisible by pipe={S_pipe}")
    M = (run.pipeline_microbatches or 2 * S_pipe) * accum_steps
    B, S_seq = shape.global_batch, shape.seq_len
    tok_shards = ctx.data * ctx.depth   # host-layout batch-dim sharding
    if B % (tok_shards * M):
        raise ValueError(
            f"global_batch={B} not divisible by data*depth*microbatches="
            f"{tok_shards}*{M}")
    mb_host = B // (tok_shards * M)
    if mb_host % ctx.rows:
        raise ValueError(f"microbatch rows {mb_host} not divisible by the "
                         f"row factor {ctx.rows} (embed reduce-scatter)")
    if model.cfg.d_model % max(ctx.cols, 1):
        raise ValueError(f"d_model={model.cfg.d_model} not divisible by "
                         f"cols={ctx.cols}")

    plan = make_plan(ctx, shape)
    ops = make_ops(ctx, plan)
    specs = model.specs(ops)
    tess_names = getattr(model, "tess_weight_names", lambda: set())()
    inop = ctx.reduce_dgrad_in_op and ctx.mode in ("tesseract", "summa2d")
    is_tess = (mark_by_name(specs, tess_names) if inop
               else jax.tree.map(lambda _: False, specs))
    pipe_sharded = mark_by_name(specs, {"blocks"})

    def _pipe_spec(sp):
        entries = tuple(sp)
        if not entries or entries[0] is not None:
            raise ValueError(f"block spec {sp} is not stacked (dim0 must be "
                             f"the layer dim)")
        return P(*(("pipe",) + entries[1:]))

    pspecs = dict(specs)
    pspecs["blocks"] = jax.tree.map(_pipe_spec, specs["blocks"],
                                    is_leaf=lambda x: isinstance(x, P))
    rep_tree = jax.tree.map(
        lambda s, psh: rep_factor(ctx, s) * (1 if psh else S_pipe),
        specs, pipe_sharded)

    from ..optim import zero as zopt
    use_zero = run.zero_enabled
    # ZeRO-1 on the pipe mesh: "pipe" joins the candidate partition axes, so
    # stage-replicated leaves (embed/head/final norm) shard their state over
    # (data, depth, pipe) while stage-sharded blocks shard over (data,
    # depth) within their stage (DESIGN.md §9).
    zcand = zopt.ZERO_CANDIDATE_AXES + ("pipe",)
    axis_sizes = dict(data=ctx.data, depth=ctx.depth, row=ctx.rows,
                      col=ctx.cols, pipe=S_pipe)
    abs_params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    layouts = (zopt.build_layouts(pspecs, abs_params, axis_sizes,
                                  candidates=zcand) if use_zero else None)

    # deferred grad reductions: replication axes of each leaf, plus pipe for
    # the stage-replicated leaves; in-op tesseract weights already reduced
    # (data, depth) inside the matmul bwd and are stage-sharded -> ().
    # Under ZeRO-1 the leaf's zaxes are left UNREDUCED here — the
    # reduce_scatter in the optimizer section reduces them.
    def _red_axes(s, t, psh):
        ax = () if t else replicated_axes(s)
        ax = ax if psh else ax + ("pipe",)
        if use_zero:
            ax = tuple(a for a in ax if a not in zcand)
        return ax
    red_axes = jax.tree.map(_red_axes, specs, is_tess, pipe_sharded)

    mb_can = mb_host // ctx.rows
    h_loc = model.cfg.d_model // ctx.cols
    cdt = model.cdt
    opt_master = run.master_weights
    from .pipeline import schedule_1f1b
    sched = schedule_1f1b(M, S_pipe)   # simulated once, shared with the step

    def local_step(params, opt_state, batch):
        fscale = batch["fault_scale"] if fault_port else None
        tokens, labels = batch["tokens"], batch["labels"]
        tok_mb = tokens.reshape((M, tokens.shape[0] // M) + tokens.shape[1:])
        lab_mb = labels.reshape((M, labels.shape[0] // M) + labels.shape[1:])
        # CE count is label-count (no mask on this path): static, so the
        # backward seed 1/total is available before the first fwd finishes.
        # run.loss_scale folds into the seed; grads are unscaled below.
        seed = jnp.float32(run.loss_scale) / jnp.float32(B * S_seq)

        def stage_step(p, a, m_idx):
            tok = lax.dynamic_index_in_dim(tok_mb, m_idx, 0, keepdims=False)
            lab = lax.dynamic_index_in_dim(lab_mb, m_idx, 0, keepdims=False)
            x0 = model.pipe_embed(p, tok, ops)
            sid = lax.axis_index("pipe")
            x_in = jnp.where(sid == 0, x0, a)
            y = model.pipe_blocks(p, x_in, ops)
            ls, cnt = model.pipe_loss_sums(p, y, lab, ops)
            return y, ls, cnt

        # the stage vjps differentiate w.r.t. params made varying over every
        # axis their grads are reduced on below (red() psums, the ZeRO-1
        # reduce_scatter), so they hand back unreduced partial sums there
        vparams = jax.tree.map(
            lambda x, ax, t, z: pvary(x, ax if t else ax + z), params,
            red_axes, is_tess,
            jax.tree.map(lambda lay: lay.zaxes, layouts) if use_zero
            else jax.tree.map(lambda _: (), params))
        a_proto = jnp.zeros((mb_can, S_seq, h_loc), cdt)
        loss_sum, cnt_sum, grads, _ = pipeline_1f1b_grads(
            stage_step, vparams, a_proto, M, axis="pipe", loss_seed=seed,
            schedule=sched)
        loss_sum = lax.psum(loss_sum, (ctx.axis_data, "pipe"))
        cnt = lax.psum(cnt_sum, (ctx.axis_data, "pipe"))
        loss = loss_sum / jnp.maximum(cnt, 1.0)

        def red(g, ax):
            if not ax:
                return g
            if run.grad_compression == "bf16":
                return lax.psum(g.astype(jnp.bfloat16),
                                tuple(ax)).astype(g.dtype)
            return lax.psum(g, tuple(ax))
        grads = jax.tree.map(red, grads, red_axes)
        if run.loss_scale != 1.0:
            grads = jax.tree.map(lambda g: g / run.loss_scale, grads)
        if fscale is not None:
            grads = jax.tree.map(lambda g: g * fscale, grads)

        with jax.named_scope(OPTIMIZER):
            lr = adamw.cosine_lr(opt_state["step"], base_lr=run.lr,
                                 warmup=100, total=10000)
            if use_zero:
                new_params, new_state, gnorm = zero_optimizer_step(
                    params, opt_state, grads, layouts=layouts,
                    is_tess=is_tess, specs=specs, axis_sizes=axis_sizes,
                    run=run, update_fn=adamw.adamw_update, lr=lr,
                    gnorm_axes=LOGICAL_AXES + ("pipe",))
            else:
                # --- global grad-norm clip (layout + stage aware) ---
                def leaf_sq(g, rep, s, psh):
                    val = jnp.sum(g.astype(jnp.float32) ** 2) / rep
                    return pvary(val, replicated_axes(s) + (() if psh
                                                            else ("pipe",)))
                sq = sum(jax.tree.leaves(jax.tree.map(
                    leaf_sq, grads, rep_tree, specs, pipe_sharded)))
                gnorm = jnp.sqrt(psum_v(sq, LOGICAL_AXES + ("pipe",)))
                scale = jnp.minimum(1.0, run.grad_clip / (gnorm + 1e-6))
                grads = jax.tree.map(lambda g: g * scale, grads)
                new_params, new_state = adamw.adamw_update(
                    params, grads, opt_state, lr=lr,
                    weight_decay=run.weight_decay)
            # non-finite update guard (same contract as the flat-mesh step)
            finite = jnp.isfinite(loss) & jnp.isfinite(gnorm)
            new_params = jax.tree.map(lambda n, o: jnp.where(finite, n, o),
                                      new_params, params)
            new_state = jax.tree.map(lambda n, o: jnp.where(finite, n, o),
                                     new_state, opt_state)
        metrics = {"loss": loss, "grad_norm": gnorm, "lr": lr,
                   "skipped": 1.0 - finite.astype(jnp.float32)}
        return new_params, new_state, metrics

    if use_zero:
        zspec_tree = jax.tree.map(lambda lay: lay.state_spec(), layouts)
        opt_specs = {"m": zspec_tree, "v": zspec_tree, "step": P(),
                     **({"master": zspec_tree} if opt_master else {})}
    else:
        opt_specs = {
            "m": pspecs, "v": pspecs, "step": P(),
            **({"master": pspecs} if opt_master else {}),
        }
    batch_sds, batch_specs_ = batch_abstract(ops, shape, ctx, model)
    if fault_port:
        batch_sds = dict(batch_sds,
                         fault_scale=jax.ShapeDtypeStruct((), jnp.float32))
        batch_specs_ = dict(batch_specs_, fault_scale=P())
    metric_specs = {"loss": P(), "grad_norm": P(), "lr": P(),
                    "skipped": P()}

    smapped = shard_map(
        local_step, mesh=mesh,
        in_specs=(pspecs, opt_specs, batch_specs_),
        out_specs=(pspecs, opt_specs, metric_specs))
    in_sh = (_shardings(mesh, pspecs), _shardings(mesh, opt_specs),
             _shardings(mesh, batch_specs_))
    out_sh = (_shardings(mesh, pspecs), _shardings(mesh, opt_specs),
              _shardings(mesh, metric_specs))
    fn = jax.jit(smapped, donate_argnums=(0, 1), in_shardings=in_sh,
                 out_shardings=out_sh)
    if use_zero:
        zt = jax.tree.map(lambda lay: lay.abstract(), layouts)
        abs_opt = {"m": zt, "v": zt,
                   "step": jax.ShapeDtypeStruct((), jnp.int32),
                   **({"master": zt} if opt_master else {})}
    else:
        abs_opt = jax.eval_shape(partial(adamw.adamw_init, master=opt_master),
                                 abs_params)
    return StepBundle(
        fn=fn,
        abstract_inputs=(abs_params, abs_opt, batch_sds),
        in_shardings=in_sh, out_shardings=out_sh, mesh=mesh, plan=plan,
        pipe_info=sched[3], opt_layouts=layouts,
        shardcheck_meta=_shardcheck_meta(mesh, pspecs, red_axes, is_tess,
                                         layouts))


# ---------------------------------------------------------------------------
# serve steps
# ---------------------------------------------------------------------------

def build_prefill_step(model, mesh, shape: ShapeSpec, *,
                       with_lengths: bool = False):
    """Prefill step.  With ``with_lengths=True`` the batch gains a
    ``lengths`` [B] input (true prompt lengths of right-padded prompts) and
    the first output is full-vocab LOGITS at each request's own last
    position instead of greedy ids — the serve engine's bucketed prefill."""
    ctx = model.ctx
    plan = make_plan(ctx, shape)
    ops = make_ops(ctx, plan)
    specs = model.specs(ops)

    def local_step(params, batch):
        ids, cache = model.prefill(params, batch, ops)
        if ids.ndim == 1:
            ids = ids[:, None]
        return ids, cache

    # prefill-layout cache: [L, B/data(loc), S, kvh_loc, D]
    cache_specs = model.prefill_cache_specs(ops)
    ids_spec = P("data", None) if plan.kind != "long_decode" else P(None, None)
    batch_sds, batch_specs_ = batch_abstract(ops, shape, ctx, model)
    if with_lengths:
        batch_sds["lengths"] = jax.ShapeDtypeStruct((shape.global_batch,),
                                                    jnp.int32)
        batch_specs_["lengths"] = P("data")

    in_sh = (_shardings(mesh, specs), _shardings(mesh, batch_specs_))
    out_sh = (NamedSharding(mesh, ids_spec), _shardings(mesh, cache_specs))
    smapped = shard_map(local_step, mesh=mesh,
                            in_specs=(specs, batch_specs_),
                            out_specs=(ids_spec, cache_specs))
    fn = jax.jit(smapped, in_shardings=in_sh, out_shardings=out_sh)
    abs_params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return StepBundle(fn=fn, abstract_inputs=(abs_params, batch_sds),
                      in_shardings=in_sh, out_shardings=out_sh,
                      mesh=mesh, plan=plan)


def build_decode_step(model, mesh, shape: ShapeSpec):
    ctx = model.ctx
    plan = make_plan(ctx, shape)
    ops = make_ops(ctx, plan)
    specs = model.specs(ops)
    cache_sds, cache_specs = model.cache_abstract(shape.global_batch,
                                                  shape.seq_len, plan)

    def local_step(params, cache, ids, pos):
        nids, new_cache = model.decode(params, cache, ids, pos, ops)
        nids = unshard_ids(ops, ctx, nids, plan)
        return nids, new_cache

    ids_sds = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    ids_spec = ops.spec_tokens_in()
    pos_sds = jax.ShapeDtypeStruct((), jnp.int32)

    in_sh = (_shardings(mesh, specs), _shardings(mesh, cache_specs),
             NamedSharding(mesh, ids_spec), NamedSharding(mesh, P()))
    out_sh = (NamedSharding(mesh, ids_spec), _shardings(mesh, cache_specs))
    smapped = shard_map(local_step, mesh=mesh,
                            in_specs=(specs, cache_specs, ids_spec, P()),
                            out_specs=(ids_spec, cache_specs))
    fn = jax.jit(smapped, donate_argnums=(1,), in_shardings=in_sh,
                 out_shardings=out_sh)
    abs_params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return StepBundle(fn=fn,
                      abstract_inputs=(abs_params, cache_sds, ids_sds, pos_sds),
                      in_shardings=in_sh, out_shardings=out_sh,
                      mesh=mesh, plan=plan)


# ---------------------------------------------------------------------------
# paged serving steps (serve/ continuous batching; DESIGN.md §7)
# ---------------------------------------------------------------------------

def _group_spec(gaxes, *extra):
    return P(gaxes if gaxes else None, *extra)


def build_paged_decode_step(model, mesh, n_slots: int, num_blocks: int,
                            block_size: int, max_blocks: int):
    """Decode step against a mesh-sharded paged KV pool.

    fn(params, pool, tables, pos, ids) -> (logits, pool)

    - pool: {"k","v": [L, P, bs, Hkv, D]} (donated), block axis sharded over
      the plan's KV group axes, heads over col.
    - tables: [n_slots, max_blocks] int32 GLOBAL block ids (each slot's
      entries point into its own group's partition; the local step subtracts
      the group offset).
    - pos: [n_slots] int32 per-request positions (mixed lengths).
    - ids: [n_slots, 1] int32 host-layout input tokens.
    - logits: [n_slots, v_pad] float32 full-vocab rows for the sampler.

    Attention data path per ctx.attn_impl (DESIGN.md §10): the jnp fallback
    gathers each slot's table view per layer; "pallas" walks the LOCAL
    tables inside the block-table decode kernel (scalar-prefetched, pages
    stream HBM->VMEM, no gather) — the offset subtraction below keeps the
    kernel's local-id contract on every KV group.
    """
    from ..core.ops import kv_group_axes
    from ..core import collectives as col_mod

    ctx = model.ctx
    plan = make_plan(ctx, ShapeSpec("paged", 1, n_slots, "decode"))
    ops = make_ops(ctx, plan)
    specs = model.specs(ops)
    pool_sds, pool_specs = model.paged_cache_abstract(num_blocks, block_size,
                                                      plan)
    gaxes = kv_group_axes(ctx, plan)
    sizes = dict(data=ctx.data, depth=ctx.depth, row=ctx.rows, col=ctx.cols)
    n_groups = 1
    for a in gaxes:
        n_groups *= sizes[a]
    bpg = num_blocks // n_groups

    table_spec = _group_spec(gaxes, None)
    pos_spec = _group_spec(gaxes)
    logits_spec = _group_spec(gaxes, None)
    ids_spec = ops.spec_tokens_in()

    def local_step(params, pool, tables, pos, ids):
        if gaxes:
            tables = tables - col_mod.axis_linear_index(gaxes) * bpg
        logits, new_pool = model.decode_paged(params, pool, tables, ids,
                                              pos, ops)
        return logits, new_pool

    tables_sds = jax.ShapeDtypeStruct((n_slots, max_blocks), jnp.int32)
    pos_sds = jax.ShapeDtypeStruct((n_slots,), jnp.int32)
    ids_sds = jax.ShapeDtypeStruct((n_slots, 1), jnp.int32)

    in_specs = (specs, pool_specs, table_spec, pos_spec, ids_spec)
    out_specs = (logits_spec, pool_specs)
    in_sh = (_shardings(mesh, specs), _shardings(mesh, pool_specs),
             NamedSharding(mesh, table_spec), NamedSharding(mesh, pos_spec),
             NamedSharding(mesh, ids_spec))
    out_sh = (NamedSharding(mesh, logits_spec), _shardings(mesh, pool_specs))
    smapped = shard_map(local_step, mesh=mesh, in_specs=in_specs,
                        out_specs=out_specs)
    fn = jax.jit(smapped, donate_argnums=(1,), in_shardings=in_sh,
                 out_shardings=out_sh)
    abs_params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return StepBundle(fn=fn,
                      abstract_inputs=(abs_params, pool_sds, tables_sds,
                                       pos_sds, ids_sds),
                      in_shardings=in_sh, out_shardings=out_sh,
                      mesh=mesh, plan=plan)


def build_chunk_prefill_step(model, mesh, n_slots: int, chunk: int,
                             num_blocks: int, block_size: int,
                             max_blocks: int):
    """Chunked prefill against the SAME mesh-sharded paged pool as decode.

    fn(params, pool, tables, pos, lens, ids) -> (logits, pool)

    - ids: [n_slots, chunk] int32 host-layout prompt tokens (0-padded).
    - pos: [n_slots] int32 chunk start (== tokens already cached).
    - lens: [n_slots] int32 valid positions this chunk (0 = idle slot).
    - logits: [n_slots, v_pad] rows taken at each slot's last valid chunk
      position — the sampler reads them only for slots whose prompt
      completes this chunk.

    One compile per chunk width; the engine reuses the decode plan's
    sharding (tables/pos/lens group-sharded, ids over the token axes), so
    interleaving chunk and decode steps never reshards the pool.
    """
    from ..core.ops import kv_group_axes
    from ..core import collectives as col_mod

    ctx = model.ctx
    plan = make_plan(ctx, ShapeSpec("paged", 1, n_slots, "decode"))
    ops = make_ops(ctx, plan)
    specs = model.specs(ops)
    pool_sds, pool_specs = model.paged_cache_abstract(num_blocks, block_size,
                                                      plan)
    gaxes = kv_group_axes(ctx, plan)
    sizes = dict(data=ctx.data, depth=ctx.depth, row=ctx.rows, col=ctx.cols)
    n_groups = 1
    for a in gaxes:
        n_groups *= sizes[a]
    bpg = num_blocks // n_groups

    table_spec = _group_spec(gaxes, None)
    pos_spec = _group_spec(gaxes)
    logits_spec = _group_spec(gaxes, None)
    ids_spec = ops.spec_tokens_in()

    def local_step(params, pool, tables, pos, lens, ids):
        if gaxes:
            tables = tables - col_mod.axis_linear_index(gaxes) * bpg
        logits, new_pool = model.prefill_chunk_paged(params, pool, tables,
                                                     ids, pos, lens, ops)
        return logits, new_pool

    tables_sds = jax.ShapeDtypeStruct((n_slots, max_blocks), jnp.int32)
    pos_sds = jax.ShapeDtypeStruct((n_slots,), jnp.int32)
    lens_sds = jax.ShapeDtypeStruct((n_slots,), jnp.int32)
    ids_sds = jax.ShapeDtypeStruct((n_slots, chunk), jnp.int32)

    in_specs = (specs, pool_specs, table_spec, pos_spec, pos_spec, ids_spec)
    out_specs = (logits_spec, pool_specs)
    in_sh = (_shardings(mesh, specs), _shardings(mesh, pool_specs),
             NamedSharding(mesh, table_spec), NamedSharding(mesh, pos_spec),
             NamedSharding(mesh, pos_spec), NamedSharding(mesh, ids_spec))
    out_sh = (NamedSharding(mesh, logits_spec), _shardings(mesh, pool_specs))
    smapped = shard_map(local_step, mesh=mesh, in_specs=in_specs,
                        out_specs=out_specs)
    fn = jax.jit(smapped, donate_argnums=(1,), in_shardings=in_sh,
                 out_shardings=out_sh)
    abs_params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return StepBundle(fn=fn,
                      abstract_inputs=(abs_params, pool_sds, tables_sds,
                                       pos_sds, lens_sds, ids_sds),
                      in_shardings=in_sh, out_shardings=out_sh,
                      mesh=mesh, plan=plan)


def build_spec_verify_step(model, mesh, n_slots: int, width: int,
                           num_blocks: int, block_size: int,
                           max_blocks: int):
    """Batched multi-token speculative VERIFY over the paged pool.

    fn(params, pool, tables, pos, lens, ids) -> (logits, pool)

    - ids: [n_slots, width] int32 — per slot [last_token, draft_1..draft_k]
      (0-padded; width = spec_k + 1).
    - pos: [n_slots] int32 — first write position (== num_cached).
    - lens: [n_slots] int32 — 1 + proposals this round (0 = idle slot).
    - logits: [n_slots, width, v_pad] — row c is the target distribution
      for the token at position pos+c+1, bit-matching what a plain decode
      step at that position would produce (the spec_decode mdcheck pins
      this).

    The trunk is prefill_chunk_paged's (update-then-attend), so accepted
    tokens' K/V are ALREADY committed in-place when the host reads the
    logits; rollback is just not advancing cur_pos past the rejection
    point (position masking + later overwrites make the stale suffix
    unobservable — the eviction-replay argument).  Sharding is identical
    to the chunk-prefill step; only the logits keep the chunk axis.
    """
    from ..core.ops import kv_group_axes
    from ..core import collectives as col_mod

    ctx = model.ctx
    plan = make_plan(ctx, ShapeSpec("paged", 1, n_slots, "decode"))
    ops = make_ops(ctx, plan)
    specs = model.specs(ops)
    pool_sds, pool_specs = model.paged_cache_abstract(num_blocks, block_size,
                                                      plan)
    gaxes = kv_group_axes(ctx, plan)
    sizes = dict(data=ctx.data, depth=ctx.depth, row=ctx.rows, col=ctx.cols)
    n_groups = 1
    for a in gaxes:
        n_groups *= sizes[a]
    bpg = num_blocks // n_groups

    table_spec = _group_spec(gaxes, None)
    pos_spec = _group_spec(gaxes)
    logits_spec = _group_spec(gaxes, None, None)
    ids_spec = ops.spec_tokens_in()

    def local_step(params, pool, tables, pos, lens, ids):
        if gaxes:
            tables = tables - col_mod.axis_linear_index(gaxes) * bpg
        logits, new_pool = model.verify_chunk_paged(params, pool, tables,
                                                    ids, pos, lens, ops)
        return logits, new_pool

    tables_sds = jax.ShapeDtypeStruct((n_slots, max_blocks), jnp.int32)
    pos_sds = jax.ShapeDtypeStruct((n_slots,), jnp.int32)
    lens_sds = jax.ShapeDtypeStruct((n_slots,), jnp.int32)
    ids_sds = jax.ShapeDtypeStruct((n_slots, width), jnp.int32)

    in_specs = (specs, pool_specs, table_spec, pos_spec, pos_spec, ids_spec)
    out_specs = (logits_spec, pool_specs)
    in_sh = (_shardings(mesh, specs), _shardings(mesh, pool_specs),
             NamedSharding(mesh, table_spec), NamedSharding(mesh, pos_spec),
             NamedSharding(mesh, pos_spec), NamedSharding(mesh, ids_spec))
    out_sh = (NamedSharding(mesh, logits_spec), _shardings(mesh, pool_specs))
    smapped = shard_map(local_step, mesh=mesh, in_specs=in_specs,
                        out_specs=out_specs)
    fn = jax.jit(smapped, donate_argnums=(1,), in_shardings=in_sh,
                 out_shardings=out_sh)
    abs_params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return StepBundle(fn=fn,
                      abstract_inputs=(abs_params, pool_sds, tables_sds,
                                       pos_sds, lens_sds, ids_sds),
                      in_shardings=in_sh, out_shardings=out_sh,
                      mesh=mesh, plan=plan)


def build_page_copy(model, mesh, num_blocks: int, block_size: int,
                    decode_plan):
    """Device-side COW page copy: pool pages ``src`` -> pages ``dst``.

    Returns copy(pool, src, dst) -> pool with
    ``pool[leaf][:, dst] = pool[leaf][:, src]`` (every layer at once).
    src/dst are [n] GLOBAL block ids replicated to every device; a src/dst
    pair lives inside ONE KV group, whose shard performs the real copy —
    on every other group the pair falls outside the local block range and
    degenerates to a scratch->scratch no-op.  The prefix cache uses this
    to clone a shared donor page into a request's private block before the
    divergent suffix overwrites it.
    """
    from jax.sharding import PartitionSpec as P

    from ..core.ops import kv_group_axes
    from ..core import collectives as col_mod

    ctx = model.ctx
    _, pool_specs = model.paged_cache_abstract(num_blocks, block_size,
                                               decode_plan)
    gaxes = kv_group_axes(ctx, decode_plan)
    sizes = dict(data=ctx.data, depth=ctx.depth, row=ctx.rows, col=ctx.cols)
    n_groups = 1
    for a in gaxes:
        n_groups *= sizes[a]
    bpg = num_blocks // n_groups
    ids_spec = P()

    def local_copy(pool, src, dst):
        if gaxes:
            off = col_mod.axis_linear_index(gaxes) * bpg
            src = src - off
            dst = dst - off
            mine = (dst >= 0) & (dst < bpg) & (src >= 0) & (src < bpg)
            src = jnp.where(mine, src, 0)
            dst = jnp.where(mine, dst, 0)
        return jax.tree.map(lambda a: a.at[:, dst].set(a[:, src]), pool)

    in_sh = (_shardings(mesh, pool_specs), NamedSharding(mesh, ids_spec),
             NamedSharding(mesh, ids_spec))
    smapped = shard_map(local_copy, mesh=mesh,
                       in_specs=(pool_specs, ids_spec, ids_spec),
                       out_specs=pool_specs)
    return jax.jit(smapped, donate_argnums=(0,), in_shardings=in_sh,
                   out_shardings=_shardings(mesh, pool_specs))


def build_paged_reshard(model, mesh, n_pre: int, bucket: int,
                        num_blocks: int, block_size: int, decode_plan):
    """Prefill->paged-pool cache reshard (replaces the prompt-replay hack).

    Returns reshard(pool, prefill_cache, tables) -> pool: scatters the
    prefill-layout cache [L, B, S_bucket, Hkv, D] into the paged pool
    through per-request scatter tables [B, S_bucket/bs] of GLOBAL block ids
    (rows/tail blocks without a real target point at a scratch block).  A
    plain jitted global scatter: XLA inserts the cross-layout collectives,
    exactly one compile per prefill bucket.
    """
    ctx = model.ctx
    pplan = make_plan(ctx, ShapeSpec("pre", bucket, n_pre, "prefill"))
    pops = make_ops(ctx, pplan)
    pcache_specs = model.prefill_cache_specs(pops)
    pool_sds, pool_specs = model.paged_cache_abstract(num_blocks, block_size,
                                                      decode_plan)
    nb = bucket // block_size
    L = model.cfg.num_layers

    def f(pool, pcache, tables):
        idx = tables.reshape(-1)                        # [B*nb]
        out = dict(pool)
        for leaf in ("k", "v"):
            src = pcache[leaf].reshape((L, n_pre * nb, block_size)
                                       + pool[leaf].shape[3:])
            out[leaf] = pool[leaf].at[:, idx].set(
                src.astype(pool[leaf].dtype))
        return out

    in_sh = (_shardings(mesh, pool_specs), _shardings(mesh, pcache_specs),
             NamedSharding(mesh, P(None, None)))
    out_sh = _shardings(mesh, pool_specs)
    return jax.jit(f, donate_argnums=(0,), in_shardings=in_sh,
                   out_shardings=out_sh)


def build_dense_cache_reshard(model, mesh, prefill_shape: ShapeSpec,
                              total_len: int):
    """Prefill->dense-decode cache reshard for the static decode loop.

    Returns reshard(prefill_cache) -> decode cache [L, B, total_len, ...]:
    the prompt K/V land in positions [0, S_prompt) of a zeroed decode-layout
    cache; decode then continues from pos = S_prompt instead of replaying
    the prompt token by token (examples/serve_decode.py).
    """
    ctx = model.ctx
    pplan = make_plan(ctx, prefill_shape)
    pops = make_ops(ctx, pplan)
    pcache_specs = model.prefill_cache_specs(pops)
    B = prefill_shape.global_batch
    dplan = make_plan(ctx, ShapeSpec("d", total_len, B, "decode"))
    cache_sds, cache_specs = model.cache_abstract(B, total_len, dplan)
    S_p = prefill_shape.seq_len

    def f(pcache):
        out = {}
        for leaf in ("k", "v"):
            z = jnp.zeros(cache_sds[leaf].shape, cache_sds[leaf].dtype)
            out[leaf] = z.at[:, :, :S_p].set(
                pcache[leaf].astype(z.dtype))
        return out

    in_sh = (_shardings(mesh, pcache_specs),)
    out_sh = _shardings(mesh, cache_specs)
    return jax.jit(f, in_shardings=in_sh, out_shardings=out_sh), dplan


def unshard_ids(ops, ctx, ids, plan):
    """[B_loc] canonical-sharded -> [B', 1] host token layout.

    Uses a zero-padded psum over row rather than all_gather so the result is
    vma-invariant over row (all_gather conservatively keeps axes varying)."""
    if plan.kind in ("long_decode", "decode_dp") or ctx.mode == "megatron1d":
        return ids[:, None]
    b_loc = ids.shape[0]
    buf = jnp.zeros((b_loc * ctx.rows,), ids.dtype)
    i = lax.axis_index(ctx.axis_row)
    buf = lax.dynamic_update_slice_in_dim(buf, ids, i * b_loc, 0)
    buf = lax.psum(buf, ctx.axis_row)
    return buf[:, None]
