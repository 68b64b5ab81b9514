"""Closed loop of optimizer steps: the trainer's own step and feed.

Set-up builds the jitted train step (``runtime.steps.build_train_step``),
its parameters from the seed and its AdamW state, and feeds it through the
program's ``Prefetcher`` (a thread that device-puts batches ahead), as
``runtime.train_loop.train`` does.  The first three steps run in set-up;
they compile the step, and their readings decide ``correct``.  The window
then runs the same object on further batches for ``seconds``; each step
ends at the host sync on its loss.

Traffic keys: ``seq_len``, ``global_batch``, ``ref_rows`` (sequences per
block of the reference), ``trace_steps`` (window steps traced in a
``--trace 1`` run) and ``limits`` ({check name: limit}).
"""
from __future__ import annotations

import gc
import math
import time
from functools import partial

import jax
import numpy as np

from chip import check_train as C
from chip import reference as R
from chip import system
from chip import trace_reduce
from chip import weights as W
from chip.harness import RUN_DIR, Check, RunRecord, note, span


class TokenStream:
    """Seeded token rows, a new batch for every step (the benchmark's own
    data, in the shape the program's ``Prefetcher`` pulls)."""

    def __init__(self, vocab: int, batch: int, seq: int, seed: int):
        self.vocab, self.B, self.S, self.seed = vocab, batch, seq, int(seed)

    def host(self, step: int):
        rng = np.random.default_rng((self.seed, step))
        ids = rng.integers(0, self.vocab, (self.B, self.S), dtype=np.int32)
        return ids, np.roll(ids, -1, axis=1)

    def batch(self, step: int, *, train: bool = True) -> dict:
        ids, labels = self.host(step)
        return {"tokens": ids, "labels": labels}


class Trainer:
    """The one object set-up builds and the window runs: the compiled step
    with its parameters, optimizer state and feed."""

    def __init__(self, cfg: dict, traffic: dict, devices):
        from repro.configs.base import ShapeSpec
        from repro.runtime.steps import build_train_step
        self.arch = R.Arch.from_config(cfg)
        self.recipe = R.Recipe.from_config(cfg)
        self.S, self.B = traffic["seq_len"], traffic["global_batch"]
        self.model, mesh = system.build(cfg, "train", devices)
        self.bundle = build_train_step(
            self.model, mesh, ShapeSpec("bench", seq_len=self.S,
                                        global_batch=self.B, kind="train"))
        self.pf = None

    def start(self, seed: int) -> None:
        """Parameters from ``seed``, fresh AdamW state, the feed at step 0."""
        from repro.data.pipeline import Prefetcher
        from repro.optim.adamw import adamw_init
        b = self.bundle
        self.seed = seed
        self.params = W.program_params(self.arch, seed, b.abstract_inputs[0],
                                       b.in_shardings[0])
        self.opt = jax.jit(
            partial(adamw_init, master=self.model.run.master_weights),
            out_shardings=b.in_shardings[1])(self.params)
        self.stream = TokenStream(self.arch.vocab, self.B, self.S, seed)
        self.pf = Prefetcher(self.stream, b.in_shardings[2], start_step=0)

    def step(self):
        """One optimizer step through the feed; returns its loss, after
        the host has synced on it."""
        with span("prefetch_next"):
            _, batch = self.pf.next()
        with span("train_step"):
            self.params, self.opt, m = self.bundle.fn(self.params, self.opt,
                                                      batch)
            jax.block_until_ready(m["loss"])
        return m["loss"]

    def first_steps(self) -> dict:
        """The first three steps, and what the check reads from them."""
        losses, grad = [], None
        for step in range(C.STEPS):
            losses.append(float(self.step()))
            if step == 0:
                grad = {k: v / (1.0 - self.recipe.b1) for k, v in
                        C.program_leaf_norms(self.opt["m"]).items()}
        b = self.bundle
        delta = C.program_delta_norms(self.arch, self.seed, self.params,
                                      b.abstract_inputs[0], b.in_shardings[0])
        return {"losses": losses, "grad": grad, "delta": delta}

    def stop(self) -> None:
        """Stop the feed and drop the state (the reference needs the room)."""
        if self.pf is not None:
            self.pf.stop()
            self.pf = None
        self.params = self.opt = None
        gc.collect()

    def reference(self, devices, rows: int, **kw) -> dict:
        return C.reference_readings(
            self.arch, self.recipe, self.seed,
            [self.stream.host(s) for s in range(C.STEPS)], devices,
            rows=rows, **kw)


def run(rec: RunRecord, seconds: float, trace: bool, t_start: float,
        counter) -> None:
    cell, tr = rec.cell, rec.cell.traffic
    t = time.perf_counter()
    tn = Trainer(cell.config, tr, rec.devices)
    t1 = time.perf_counter()
    tn.start(rec.seed)
    try:
        # set-up: the first three steps through the window's own call and
        # feed; they compile the step and are what the reference checks
        t2 = time.perf_counter()
        prog = tn.first_steps()
        note(f"set-up: import {t - t_start:.1f} s, step built "
             f"{t1 - t:.1f} s, state made {t2 - t1:.1f} s, first "
             f"steps and their readings {time.perf_counter() - t2:.1f} "
             f"s; losses {prog['losses']}")

        n_trace = tr["trace_steps"] if trace else 0
        trace_from = 2                      # leave the first steps untraced
        tdir = RUN_DIR / "trace" / cell.name
        losses = []
        counter.armed = True
        t0 = time.perf_counter()
        rec.setup_s = t0 - t_start
        t_last, done, traced, tspan = t0, 0, None, None
        while True:
            if n_trace and done == trace_from:
                trace_reduce.start(tdir)
                tspan = span("window")
                tspan.__enter__()
            loss = tn.step()
            t = time.perf_counter()
            if tspan is not None and done == trace_from + n_trace - 1:
                tspan.__exit__(None, None, None)
                traced, tspan = trace_reduce.stop(tdir), None
            if t - t0 > seconds:
                break
            done += 1
            t_last = t
            losses.append(loss)
        counter.armed = False
        if tspan is not None:
            tspan.__exit__(None, None, None)
            trace_reduce.stop(tdir)
            raise RuntimeError(f"the window ended inside the traced steps "
                               f"{trace_from}..{trace_from + n_trace - 1}")
        rec.window_s = t_last - t0
        rec.attempted = done
        wl = [float(x) for x in losses]
        rec.failed = sum(not math.isfinite(x) for x in wl)
        tokens = done * tn.B * tn.S
        rec.end_to_end["train_tokens_per_s"] = (
            tokens / rec.window_s if done else 0.0)
        rec.counters.update(steps=done, tokens=tokens,
                            step_s=rec.window_s / max(done, 1))
        note(f"window: {done} steps, {tokens} tokens in "
             f"{rec.window_s:.3f} s; last loss {wl[-1] if wl else None}")
        rec.trace = traced
        rec.memory_peak_bytes = max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in rec.devices)
    finally:
        tn.stop()

    # the reference, once the program's state is freed
    t = time.perf_counter()
    ref = tn.reference(rec.devices, tr["ref_rows"])
    got = C.compare(prog, ref)
    note(f"reference: losses {ref['losses']} in "
         f"{time.perf_counter() - t:.1f} s; worst grad leaf "
         f"{got['grad_gap_leaf']}, worst update leaf "
         f"{got['update_gap_leaf']} of {got['update_leaves']} "
         f"({got['update_leaves_out']} left out)")
    lim = tr["limits"]
    rec.checks = [Check(k, got[k], lim[k])
                  for k in ("loss_gap", "grad_gap", "update_gap")]
