"""``correct`` for a serving cell: served greedy tokens against the
reference's logits.

For each sampled request the reference runs once over its prompt and its
served tokens (one causal pass, padded at the end to the context, which
changes no earlier position).  At the position before each served token it
reads the reference's best logit and the served token's logit; the number
compared, ``logit_gap``, is the widest gap between them over every token of
the sample.  A greedy token that the reference agrees with reads 0; a near
tie decided the other way reads the width of the tie.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import reference as R
from . import weights as W


@functools.lru_cache(maxsize=None)
def _fns(arch: R.Arch, context: int, mode: str, control: str | None):
    num = R.Numerics(mode)
    make = jax.jit(lambda k: W.make_logical(arch, k))

    def gaps(params, ids, nxt):
        """ids [1, context]; nxt [context] the token served after each
        position.  Returns per position (best - served) under the
        reference, and (best - top of the control) where a control runs."""
        h = R.hidden(arch, num, params, ids)[0]
        lg = num.mm("th,vh->tv", h, params["head"])
        best = jnp.max(lg, axis=-1)
        served = jnp.take_along_axis(lg, nxt[:, None], axis=-1)[:, 0]
        out = best - served
        if control is None:
            return out, out
        cnum = R.Numerics(control)
        ch = R.hidden(arch, cnum, params, ids)[0]
        top = jnp.argmax(cnum.mm("th,vh->tv", ch, params["head"]), axis=-1)
        ctl = best - jnp.take_along_axis(lg, top[:, None], axis=-1)[:, 0]
        return out, ctl
    return make, jax.jit(gaps)


def served_gaps(arch: R.Arch, seed: int, sample, context: int, devices,
                *, control: str | None = None):
    """Per sampled request, the gaps of its served tokens.  ``sample``:
    [(prompt ids, served ids)].  With ``control`` ("bf16"), returns instead
    the gaps of the tokens that the control's numerics would put first at
    the same positions."""
    make, gaps = _fns(arch, context, "highest", control)
    params = make(W.seed_key(seed))
    out = []
    for prompt, served in sample:
        seq = list(prompt) + list(served)
        if len(seq) > context:
            raise ValueError(f"sequence of {len(seq)} > context {context}")
        ids = np.zeros((1, context), np.int32)
        ids[0, :len(seq) - 1] = seq[:-1]
        nxt = np.zeros((context,), np.int32)
        nxt[:len(seq) - 1] = seq[1:]
        g, c = gaps(params, ids, nxt)
        lo, hi = len(prompt) - 1, len(seq) - 1
        out.append(np.asarray(c if control else g)[lo:hi])
    del params
    return out
