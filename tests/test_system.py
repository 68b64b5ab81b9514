"""End-to-end behaviour tests: train -> checkpoint -> restore -> serve on
the public API (single device; multi-device parity lives in
tests/test_multidevice*.py)."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import RunConfig, ShapeSpec
from repro.core.api import ParallelContext
from repro.core.mesh import logical_mesh
from repro.models.registry import build_model, get_reduced
from repro.runtime.steps import build_decode_step
from repro.runtime.train_loop import train

CTX = ParallelContext(mode="tesseract", data=1, depth=1, rows=1, cols=1)
RUN = RunConfig(param_dtype="float32", compute_dtype="float32", loss_chunk=16,
                q_chunk=8, kv_chunk=8, lr=3e-3)


def test_end_to_end_train_ckpt_serve(tmp_path):
    arch = get_reduced("yi-6b")
    mesh = logical_mesh(CTX)
    model = build_model(arch.model, CTX, RUN)
    shape = ShapeSpec("t", seq_len=32, global_batch=4, kind="train")

    # i.i.d.-random tokens sit at the entropy floor (ln V) — use a repeated
    # batch so there is something to learn (memorization)
    from repro.data.pipeline import SyntheticLMStream

    class RepeatStream(SyntheticLMStream):
        def _tokens_for(self, step):
            return super()._tokens_for(0)

    stream = RepeatStream(model.cfg.vocab_size, 4, 32, seed=0)
    res = train(model, mesh, shape, steps=24, ckpt_dir=tmp_path,
                ckpt_every=12, log_every=0, stream=stream)
    assert len(res.losses) == 24
    assert np.mean(res.losses[-4:]) < np.mean(res.losses[:4]) - 5e-3

    # restore the final params and serve with them
    from repro.checkpoint.ckpt import CheckpointManager
    from repro.runtime.steps import build_train_step
    bundle = build_train_step(model, mesh, shape)
    mgr = CheckpointManager(tmp_path)
    last = mgr.latest_step()
    state = mgr.restore(last, {"params": bundle.abstract_inputs[0],
                               "opt": bundle.abstract_inputs[1]},
                        {"params": bundle.in_shardings[0],
                         "opt": bundle.in_shardings[1]})
    params = state["params"]

    dshape = ShapeSpec("d", seq_len=16, global_batch=4, kind="decode")
    dec = build_decode_step(model, mesh, dshape)
    cache_sds, _ = model.cache_abstract(4, 16, dec.plan)
    cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), cache_sds)
    ids = jnp.array([[1], [2], [3], [4]], jnp.int32)
    for t in range(4):
        ids, cache = dec.fn(params, cache, ids, jnp.int32(t))
    out = np.asarray(ids)
    assert out.shape == (4, 1) and np.isfinite(out).all()
    assert (out >= 0).all() and (out < model.cfg.vocab_size).all()
