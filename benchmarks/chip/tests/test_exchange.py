"""The four-chip train check catches the exchange between chips left out.

On four virtual CPU devices (a subprocess: the device count is fixed when
JAX starts), the tiny model trains on a [2,2,1] Tesseract grid through
``run.execute``: the program passes the check, and fails it when the
gradient reduction between chips keeps only the first chip's part.
"""
import os
import pathlib
import subprocess
import sys

HERE = pathlib.Path(__file__).resolve().parent

SCRIPT = r'''
import dataclasses, importlib.util, json, pathlib, sys, time
here = pathlib.Path(sys.argv[1])
sys.path[:0] = [str(here.parents[1]), str(here.parents[2] / "src"), str(here)]
import jax, jax.numpy as jnp
from jax import lax
import tiny
from chip.harness import Cell

if sys.argv[2] == "broken":
    import repro.runtime.steps as steps
    from repro.core.collectives import pvary

    from functools import partial

    @partial(jax.custom_vjp, nondiff_argnums=(1, 2))
    def grad_sync(x, axes, compress="none"):
        return pvary(x, axes)

    def fwd(x, axes, compress):
        return pvary(x, axes), None

    def bwd(axes, compress, _, g):
        # the reduction keeps only the first chip's partial gradient
        if not axes:
            return (g,)
        first = jnp.all(jnp.array([lax.axis_index(a) == 0 for a in axes]))
        return (lax.psum(jnp.where(first, g, 0.0), tuple(axes)),)

    grad_sync.defvjp(fwd, bwd)
    steps.grad_sync = grad_sync

spec = importlib.util.spec_from_file_location("r", here.parent / "run.py")
run = importlib.util.module_from_spec(spec); spec.loader.exec_module(run)
cfg = tiny.config({"mode": "tesseract", "depth": 1, "rows": 2, "cols": 2})
cell = Cell(name="tiny.2x2", chips=4, config=cfg, traffic=tiny.TRAIN,
            end_to_end=[], per_layer=[])
r = run.execute(cell, jax.devices()[:4], 12345, 2.0, False,
                time.perf_counter())
print(json.dumps({"correct": r["correct"], "checks": r["checks"]}))
'''


def _run(mode):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", SCRIPT, str(HERE), mode],
                       env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    import json
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_exchange_left_out_fails():
    good = _run("program")
    assert good["correct"], good
    bad = _run("broken")
    assert not bad["correct"], bad
    print(good["checks"], bad["checks"])
