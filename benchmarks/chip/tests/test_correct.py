"""The check that decides ``correct``: it passes the program, and fails
the control and each fault a cell can have.

Run on the CPU at a tiny size (``tiny.py``), through ``run.execute``: the
whole run after the harness's look for a chip, with the timed path broken
underneath where a fault is planted.  The tiny traffic's limits stand in
for a cell's; the cells' own limits were set from readings on the chip
(PERF.md).
"""
import dataclasses
import importlib.util
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny
from chip import check_serve, check_train
from chip.harness import Cell

RUN = pathlib.Path(__file__).resolve().parents[1] / "run.py"


def execute(traffic, seed=2 ** 33 + 7, seconds=2.0):
    spec = importlib.util.spec_from_file_location("chip_run", RUN)
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    cell = Cell(name="tiny", chips=1, config=tiny.config(), traffic=traffic,
                end_to_end=[], per_layer=[])
    return run.execute(cell, jax.devices()[:1], seed, seconds, False,
                       time.perf_counter())


def checks(result):
    return {k: v["value"] for k, v in result["checks"].items()}


# ------------------------------------------------------------------ train

def test_train_program_is_correct():
    r = execute(tiny.TRAIN)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0


def _patch_train_step(monkeypatch, make):
    import repro.runtime.steps as steps
    real = steps.build_train_step

    def patched(model, mesh, shape, **kw):
        return make(real, model, mesh, shape, **kw)
    monkeypatch.setattr(steps, "build_train_step", patched)


def test_train_state_unchanged_fails(monkeypatch):
    def make(real, model, mesh, shape, **kw):
        b = real(model, mesh, shape, **kw)

        def fn(p, o, batch):
            keep = jax.tree.map(jnp.copy, (p, o))
            _, _, m = b.fn(p, o, batch)
            return keep[0], keep[1], m
        return dataclasses.replace(b, fn=fn)
    _patch_train_step(monkeypatch, make)
    r = execute(tiny.TRAIN)
    assert not r["correct"]
    assert checks(r)["update_gap"] == pytest.approx(1.0, rel=1e-3)


def test_train_half_batch_fails(monkeypatch):
    def make(real, model, mesh, shape, **kw):
        b = real(model, mesh, shape, **kw)
        half = real(model, mesh, dataclasses.replace(
            shape, global_batch=shape.global_batch // 2), **kw)

        def fn(p, o, batch):
            n = shape.global_batch // 2
            return half.fn(p, o, {k: v[:n] for k, v in batch.items()})
        return dataclasses.replace(b, fn=fn)
    _patch_train_step(monkeypatch, make)
    r = execute(tiny.TRAIN)
    assert not r["correct"], r["checks"]


def test_train_control_fails():
    """The reference at fp8 in the program's place reads over the limits
    on every seed tried."""
    from chip import reference as R
    from chip.loops.train import TokenStream
    cfg = tiny.config()
    a, rc = R.Arch.from_config(cfg), R.Recipe.from_config(cfg)
    tr = tiny.TRAIN
    for seed in (3, 2 ** 32 + 9):
        st = TokenStream(a.vocab, tr["global_batch"], tr["seq_len"], seed)
        b = [st.host(s) for s in range(check_train.STEPS)]
        dev = jax.devices()[:1]
        ref = check_train.reference_readings(a, rc, seed, b, dev,
                                             rows=tr["ref_rows"])
        ctl = check_train.reference_readings(a, rc, seed, b, dev,
                                             rows=tr["ref_rows"], mode="fp8")
        got = check_train.compare(ctl, ref)
        assert any(got[k] > v for k, v in tr["limits"].items()), got


# ------------------------------------------------------------------ serve

def test_serve_program_is_correct():
    r = execute(tiny.SERVE)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0


def test_serve_token_altered_fails(monkeypatch):
    import repro.serve.engine as eng
    real = eng.sample_tokens
    vocab = tiny.TINY_MODEL["vocab_size"]

    def altered(*a, **kw):
        return (np.asarray(real(*a, **kw)) + 1) % vocab
    monkeypatch.setattr(eng, "sample_tokens", altered)
    assert not execute(tiny.SERVE)["correct"]


def _patch_decode(monkeypatch, wrap):
    import repro.serve.engine as eng
    real = eng.build_paged_decode_step

    def patched(*a, **kw):
        b = real(*a, **kw)
        return dataclasses.replace(b, fn=wrap(b.fn))
    monkeypatch.setattr(eng, "build_paged_decode_step", patched)


def test_serve_state_unchanged_fails(monkeypatch):
    def wrap(fn):
        def step(params, pool, *rest):
            keep = jax.tree.map(jnp.copy, pool)
            logits, _ = fn(params, pool, *rest)
            return logits, keep
        return step
    _patch_decode(monkeypatch, wrap)
    assert not execute(tiny.SERVE)["correct"]


def test_serve_half_batch_fails(monkeypatch):
    def wrap(fn):
        def step(*args):
            logits, pool = fn(*args)
            # the first half of the slots (the busiest: the scheduler
            # fills the lowest free slot) served the second half's rows
            n = logits.shape[0] // 2
            return logits.at[:n].set(logits[n:2 * n]), pool
        return step
    _patch_decode(monkeypatch, wrap)
    assert not execute(tiny.SERVE)["correct"]


def test_serve_control_fails():
    """bf16 numerics in the program's place: at some served position the
    control puts first a token the reference ranks lower, on every seed."""
    from chip import reference as R
    cfg = tiny.config()
    # near ties need a vocabulary of some thousands
    cfg["model"].update(vocab_size=8192, hidden_size=128,
                        intermediate_size=256)
    a = R.Arch.from_config(cfg)
    rng = np.random.default_rng(0)
    sample = [(rng.integers(0, a.vocab, 20).tolist(),
               rng.integers(0, a.vocab, 40).tolist()) for _ in range(4)]
    for seed in (1, 2 ** 31 + 3):
        g = check_serve.served_gaps(a, seed, sample, 64, jax.devices()[:1],
                                    control="bf16")
        assert max(float(np.max(x)) for x in g) > \
            tiny.SERVE["limits"]["logit_gap"]
