"""Fault-tolerant training loop.

Responsibilities at fleet scale, all exercised by tests on this container:
  * checkpoint/restart: periodic async checkpoints; on failure, rebuild the
    step and restore the latest checkpoint (reshard-on-restore supports a
    different mesh after an elastic re-plan)
  * crash-consistent recovery: manifests are checksummed (checkpoint/
    ckpt.py), restore falls back across corrupted checkpoints to the last
    durable one instead of dying or silently loading garbage
  * non-finite step recovery: the step bundle's where-select guard keeps
    params/opt bit-identical on a NaN/Inf step; the loop retries the same
    (step-keyed) batch a bounded number of times, then backs the loss scale
    off (halving run.loss_scale, the §9 mixed-precision lever), then falls
    back to restore-and-replay
  * deterministic data: the stream is keyed by step, so a restart replays
    exactly the batches after the restored step
  * deterministic fault injection (runtime/faults.py): hook points
    ``train.step`` (device loss, straggler delay), ``train.grads`` (NaN/Inf
    grads via the step bundle's fault port) and ``ckpt.write`` (checkpoint
    corruption) fire replayably by (seed, step)
  * straggler monitoring hooks (per-step timing -> StragglerMonitor)
  * retry budget so a poisoned batch / flaky host cannot loop forever
"""
from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass, field

import jax
import numpy as np

from ..checkpoint.ckpt import CheckpointManager
from ..data.pipeline import Prefetcher, SyntheticLMStream
from ..optim.adamw import adamw_init
from . import faults as faults_mod
from .faults import DeviceLostError
from .steps import build_train_step
from .stragglers import StragglerMonitor


@dataclass
class TrainResult:
    losses: list = field(default_factory=list)
    grad_norms: list = field(default_factory=list)
    restarts: int = 0
    last_step: int = -1
    step_times: list = field(default_factory=list)
    # resilience accounting (DESIGN.md §11)
    nan_skips: int = 0             # non-finite steps where-selected away
    loss_scale_backoffs: int = 0   # loss-scale halvings after skip storms
    ckpt_fallbacks: int = 0        # corrupt checkpoints skipped on restore
    fault_log: list = field(default_factory=list)  # injector firing order


def train(model, mesh, shape, *, steps: int, ckpt_dir=None, ckpt_every: int = 50,
          log_every: int = 10, max_restarts: int = 3, fault_hook=None,
          seed: int = 0, stream=None, monitor=None,
          accum_steps: int | None = None, injector=None) -> TrainResult:
    """Run ``steps`` optimizer steps with checkpoint/restart fault tolerance.

    fault_hook(step) may raise to simulate a failure (tests use this).
    accum_steps (default ``model.run.accum_steps``) accumulates gradients
    over that many microbatches per optimizer step — the knob an elastic
    re-plan (``runtime/elastic.replan(...).accum_steps``) supplies so a
    device shrink keeps the global batch and the loss trajectory intact
    under the step-keyed data stream.

    injector (``runtime/faults.FaultInjector``) enables deterministic
    chaos: defaults to the plan on ``model.run.fault_plan`` / ``fault_seed``
    (the launcher config surface), restricted to the train/ckpt sites.  A
    ``device_loss`` firing raises DeviceLostError THROUGH the restart
    budget — recovery needs an elastic re-plan by the driver, not a
    same-mesh restart.
    """
    run = model.run
    if accum_steps is None:
        accum_steps = run.accum_steps
    if injector is None:
        injector = faults_mod.injector_from_run(run, sites=("train", "ckpt"))
    fault_port = injector is not None
    loss_scale = run.loss_scale

    def make_bundle(scale):
        m = model
        if scale != run.loss_scale:
            from ..models.registry import build_model
            m = build_model(model.cfg, model.ctx,
                            dataclasses.replace(run, loss_scale=scale))
        return build_train_step(m, mesh, shape, accum_steps=accum_steps,
                                fault_port=fault_port)

    bundle = make_bundle(loss_scale)
    mgr = CheckpointManager(ckpt_dir) if ckpt_dir else None
    # ZeRO-1: record the optimizer-state layout in every checkpoint and
    # re-shard on restore (dp-degree changes after an elastic replan, or a
    # replicated <-> ZeRO layout switch).
    from ..optim.zero import make_ckpt_converter
    opt_layout_meta = bundle.opt_layouts_json()
    save_meta = {"opt_layout": opt_layout_meta} if opt_layout_meta else None
    opt_convert = make_ckpt_converter(opt_layout_meta)
    monitor = monitor or StragglerMonitor()
    result = TrainResult()
    if injector is not None:
        result.fault_log = injector.fired   # live view, shared list

    batch_sh = bundle.in_shardings[2]
    if stream is None:
        extras = {k: (sd, sp) for k, (sd, sp) in model.batch_extras(shape).items()}
        stream = SyntheticLMStream(model.cfg.vocab_size, shape.global_batch,
                                   shape.seq_len, seed=seed, extras=extras)

    def init_state():
        import jax.numpy as jnp
        params = model.init(jax.random.PRNGKey(seed))
        params = jax.device_put(params, bundle.in_shardings[0])
        if model.run.zero_enabled:
            from ..optim.zero import zero_opt_init
            opt = zero_opt_init(bundle)
        else:
            opt = adamw_init(params, master=model.run.master_weights)
        opt = jax.device_put(opt, bundle.in_shardings[1])
        return params, opt

    def restore_or_init():
        if mgr is not None:
            try:
                mgr.wait()   # flush an in-flight async save before reading
            except RuntimeError as e:
                print(f"[ckpt] pending async save failed: {e}")
            abs_p, abs_o, _ = bundle.abstract_inputs
            # newest-first with integrity checks: a corrupted checkpoint
            # (bit flip, truncation, torn manifest) is skipped, not loaded
            state, last = mgr.restore_latest(
                {"params": abs_p, "opt": abs_o},
                {"params": bundle.in_shardings[0],
                 "opt": bundle.in_shardings[1]},
                convert=opt_convert)
            result.ckpt_fallbacks += mgr.last_fallbacks
            if state is not None:
                return state["params"], state["opt"], last + 1
        p, o = init_state()
        return p, o, 0

    def run_step(params, opt, batch, step):
        """One optimizer step with bounded non-finite retry + loss-scale
        backoff.  Returns (params, opt, metrics, loss_scale)."""
        nonlocal bundle, loss_scale
        attempts = 0
        while True:
            fb = batch
            if fault_port:
                g = 1.0
                for spec in injector.fire("train.grads", step):
                    g = np.nan if spec.kind == "nan" else np.inf
                fb = dict(batch, fault_scale=np.float32(g))
            params, opt, metrics = bundle.fn(params, opt, fb)
            if not float(metrics.get("skipped", 0.0)):   # sync point
                return params, opt, metrics
            # non-finite step: params/opt came back bit-identical (the
            # in-step guard) — retry the SAME step-keyed batch
            attempts += 1
            result.nan_skips += 1
            print(f"[fault] step {step}: non-finite grads/loss, update "
                  f"skipped (retry {attempts}/{run.nan_skip_limit}, "
                  f"loss_scale={loss_scale:g})")
            if attempts <= run.nan_skip_limit:
                continue
            if loss_scale > 1.0:
                # mixed-precision overflow: halve the static loss scale
                # (rebuild the step — the scale is folded into the jit)
                loss_scale = max(1.0, loss_scale / 2.0)
                result.loss_scale_backoffs += 1
                print(f"[fault] step {step}: backing loss_scale off to "
                      f"{loss_scale:g} and rebuilding the step")
                bundle = make_bundle(loss_scale)
                attempts = 0
                continue
            raise FloatingPointError(
                f"non-finite grads persist at step {step} after "
                f"{run.nan_skip_limit} retries and loss-scale backoff")

    params, opt, start = restore_or_init()
    step = start
    budget_used = 0        # restarts within the current replay window
    window_start = start   # where the last restore landed us
    while step < steps:
        try:
            pf = Prefetcher(stream, batch_sh, start_step=step)
            try:
                while step < steps:
                    got_step, batch = pf.next()
                    assert got_step == step
                    if fault_hook is not None:
                        fault_hook(step)
                    if injector is not None:
                        for spec in injector.fire("train.step", step):
                            if spec.kind == "device_loss":
                                raise DeviceLostError(
                                    int(spec.arg),
                                    f"injected device loss at step {step}: "
                                    f"{int(spec.arg)} devices survive")
                            elif spec.kind == "straggler":
                                time.sleep(spec.arg)
                    t0 = time.time()
                    params, opt, metrics = run_step(params, opt, batch, step)
                    loss = float(metrics["loss"])  # sync point
                    dt = time.time() - t0
                    monitor.record(jax.process_index(), dt)
                    result.step_times.append(dt)
                    if not np.isfinite(loss):
                        raise FloatingPointError(f"non-finite loss at {step}")
                    result.losses.append(loss)
                    result.grad_norms.append(float(metrics["grad_norm"]))
                    result.last_step = step
                    if log_every and step % log_every == 0:
                        print(f"step {step} loss {loss:.4f} "
                              f"gnorm {float(metrics['grad_norm']):.3f} "
                              f"({dt*1e3:.0f} ms)")
                    step += 1
                    if mgr is not None and step % ckpt_every == 0:
                        mgr.save(step - 1, {"params": params, "opt": opt},
                                 meta=save_meta)
                        if injector is not None:
                            for spec in injector.fire("ckpt.write", step - 1):
                                mgr.wait()   # corrupt the DURABLE artifact
                                p = faults_mod.corrupt_checkpoint(
                                    ckpt_dir, step - 1,
                                    mode=spec.mode or "bit_flip",
                                    leaf_index=int(spec.arg),
                                    seed=injector.plan.seed)
                                print(f"[fault] injected ckpt corruption "
                                      f"({spec.mode or 'bit_flip'}): {p}")
            finally:
                pf.stop()
        except DeviceLostError as e:
            # a lost device cannot be fixed by a same-mesh restart: the
            # driver must elastic-replan (runtime/elastic.replan) onto the
            # survivors and call train() again on the new mesh (passing the
            # same injector so spent faults stay spent)
            result.restarts += 1
            e.partial_result = result
            raise
        except (FloatingPointError, RuntimeError, ValueError) as e:
            result.restarts += 1
            if mgr is not None:
                # A checkpoint that LANDED since the last restore starts a
                # fresh replay window, so N spread-out recovered faults over
                # a long run never add up to a fatal max_restarts.  Judged
                # by the durable latest VALID step (after flushing the
                # async writer), never by save() calls having been made: a
                # persistently failing/corrupting checkpoint dir plus a
                # recurring fault must still trip the budget, not loop
                # forever.
                try:
                    mgr.wait()
                except RuntimeError as werr:
                    print(f"[ckpt] pending async save failed: {werr}")
                latest = mgr.latest_valid_step()
                if latest is not None and latest + 1 > window_start:
                    budget_used = 0
                    window_start = latest + 1
            budget_used += 1
            print(f"[fault] step {step}: {type(e).__name__}: {e}; "
                  f"restart {budget_used}/{max_restarts} in this replay "
                  f"window ({result.restarts} total)")
            if budget_used > max_restarts:
                raise
            params, opt, step = restore_or_init()
    if mgr is not None:
        mgr.save(steps - 1, {"params": params, "opt": opt},
                 blocking=True, meta=save_meta)
        mgr.wait()
    return result
