"""Shared collective helpers used by the op sets and models.

Everything here runs *inside* ``jax.shard_map`` and operates on per-device
local views, communicating via named mesh axes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

# varying -> invariant gather (precise vma; values are identical copies)
from jax._src.lax.parallel import all_gather_invariant as _agi


def all_gather_inv(x, axes, *, axis=0, tiled=False):
    """all_gather whose output is vma-INVARIANT over the gathered axes
    (every member of the group holds the same gathered value).  Axes that
    ``x`` does not vary on are skipped: its copies there are already equal."""
    if isinstance(axes, str):
        axes = (axes,)
    vma = vma_of(x)
    ax = tuple(a for a in axes if a in vma)
    if not ax:
        return x
    return _agi(x, ax, axis=axis, tiled=tiled)


def pvary(x, axes):
    """Mark ``x`` as varying over ``axes`` (idempotent ``lax.pcast``).

    Used at the step level on replicated params: pvary's transpose is a psum
    over ``axes``, which is exactly the deferred (fused) gradient reduction —
    one collective per (stacked) param leaf per step.
    """
    if isinstance(axes, str):
        axes = (axes,)
    axes = tuple(dict.fromkeys(a for a in axes if a not in vma_of(x)))
    if not axes:
        return x
    return lax.pcast(x, axes, to="varying")


def unify_vma(*xs):
    """pvary every array to the union of their vma.  A ``pallas_call``
    inside ``shard_map`` declares one vma per output, so its operands are
    brought to a common one first; outside ``shard_map`` this is a no-op."""
    axes = tuple(sorted(frozenset().union(*map(vma_of, xs))))
    return tuple(pvary(x, axes) for x in xs)


def tree_pvary(tree, axes_tree):
    """pvary each leaf over its (possibly empty) axes tuple."""
    return jax.tree.map(lambda x, a: pvary(x, a), tree, axes_tree,
                        is_leaf=lambda t: t is None)


from functools import partial as _partial


@_partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def grad_sync(x, axes: tuple, compress: str = "none"):
    """Replication boundary for params: forward is a vma-only pvary; backward
    is ONE fused psum of the cotangent over ``axes`` (the deferred Tesseract
    depth reduction + DP all-reduce), optionally in a compressed wire format.

    Applied to scan-stacked param leaves this reduces all layers' grads in a
    single collective per leaf — the fused alternative to the paper's
    per-layer all_reduce (see EXPERIMENTS.md §Perf).
    """
    return pvary(x, axes)


def _gs_fwd(x, axes, compress):
    return pvary(x, axes), None


def _gs_bwd(axes, compress, _res, g):
    if not axes:
        return (g,)
    if compress == "bf16":
        return (lax.psum(g.astype(jnp.bfloat16), tuple(axes)).astype(g.dtype),)
    return (lax.psum(g, tuple(axes)),)


grad_sync.defvjp(_gs_fwd, _gs_bwd)


def vma_of(x) -> frozenset:
    return jax.typeof(x).vma



def _vary_axes(x, axes) -> tuple:
    """Subset of ``axes`` that x varies on."""
    if isinstance(axes, str):
        axes = (axes,)
    vma = vma_of(x)
    return tuple(a for a in axes if a in vma)


def psum_v(x, axes):
    """psum over the subset of ``axes`` that x actually varies on.

    Ops stay correct whether params were pvary'd (train: grad_sync boundary)
    or not (serve steps): reducing over an axis the value is replicated on
    would either error (vma) or double-count."""
    ax = _vary_axes(x, axes)
    return lax.psum(x, ax) if ax else x


def pmax_v(x, axes):
    ax = _vary_axes(x, axes)
    return lax.pmax(x, ax) if ax else x


def pmin_v(x, axes):
    ax = _vary_axes(x, axes)
    return lax.pmin(x, ax) if ax else x


def pmean_v(x, axes):
    ax = _vary_axes(x, axes)
    return lax.pmean(x, ax) if ax else x


def axis_size(axes):
    if isinstance(axes, str):
        axes = (axes,)
    s = 1
    for a in axes:
        s *= lax.axis_size(a)
    return s


def axis_linear_index(axes):
    """Lexicographic device index over a tuple of axes (first axis major)."""
    if isinstance(axes, str):
        axes = (axes,)
    idx = jnp.int32(0)
    for a in axes:
        idx = idx * lax.axis_size(a) + lax.axis_index(a)
    return idx


def all_gather_cat(x, axes, axis=0):
    """all_gather over (possibly multiple) axes, concatenated along ``axis``.

    Gathered order is lexicographic in ``axes`` (first axis outermost),
    matching the (data, depth, row) token ordering used framework-wide.
    """
    if isinstance(axes, str):
        axes = (axes,)
    return all_gather_inv(x, axes, tiled=True, axis=axis)


def psum_scatter_dim(x, axes, dim):
    """reduce-scatter over ``axes`` tiling dimension ``dim``."""
    if isinstance(axes, str):
        axes = (axes,)
    return lax.psum_scatter(x, tuple(axes), scatter_dimension=dim, tiled=True)


def last_shard_value(x, axes):
    """Return the value held by the LAST shard (lexicographic) of ``axes``,
    replicated (vma-invariant) over those axes — used for recurrent final
    states in sequence-sharded prefill."""
    if isinstance(axes, str):
        axes = (axes,)
    n = axis_size(axes)
    idx = axis_linear_index(axes)
    keep = (idx == n - 1).astype(x.dtype)
    return lax.psum(x * keep, tuple(axes))


def unvary_concat(x, axes, dim: int):
    """Concatenate shards along ``dim`` across ``axes`` like a tiled
    all_gather, but via a zero-padded psum so the result is vma-INVARIANT
    over ``axes`` (all_gather conservatively keeps axes varying).  Costs
    ~2x all_gather bytes; use only for small tensors that must satisfy a
    replicated out_spec (e.g. decode-cache writes)."""
    if isinstance(axes, str):
        axes = (axes,)
    n = axis_size(axes)
    idx = axis_linear_index(axes)
    shape = list(x.shape)
    shape[dim] = shape[dim] * n
    buf = jnp.zeros(shape, x.dtype)
    start = [0] * x.ndim
    zero = jnp.int32(0)
    starts = [zero] * x.ndim
    starts[dim] = idx * x.shape[dim]
    buf = lax.dynamic_update_slice(buf, x, tuple(starts))
    return lax.psum(buf, tuple(axes))


def halo_exchange_left(x, axes, halo: int, axis: int):
    """Fetch the last ``halo`` elements (along ``axis``) from the previous
    shard in the lexicographic (axes) order; first shard receives zeros.

    Used by: depthwise causal conv across sequence shards (mamba2) and
    windowed local attention (recurrentgemma).
    """
    if isinstance(axes, str):
        axes = (axes,)
    sizes = [lax.axis_size(a) for a in axes]
    n = 1
    for s in sizes:
        n *= s
    tail = lax.slice_in_dim(x, x.shape[axis] - halo, x.shape[axis], axis=axis)
    # linearize the multi-axis shard index into a chain 0 -> 1 -> ... -> n-1
    # and shift the tail forward by one position along the chain.
    # Implemented as a sequence of ppermutes on the factored axes.
    idx = axis_linear_index(axes)
    flat_perm_src = [(i, i + 1) for i in range(n - 1)]
    recv = _ppermute_linear(tail, axes, flat_perm_src)
    is_first = (idx == 0)
    recv = jnp.where(is_first, jnp.zeros_like(recv), recv)
    return recv


def _ppermute_linear(x, axes, perm):
    """ppermute over the linearized index of a tuple of mesh axes.

    jax.lax.ppermute accepts a single axis name or a tuple; with a tuple the
    permutation indices refer to the lexicographic linear index.
    """
    return lax.ppermute(x, tuple(axes), perm)


# ---------------------------------------------------------------------------
# Distributed linear recurrence:  h_t = a_t * h_{t-1} + b_t   (elementwise)
# across sequence shards on ``axes`` — used by RG-LRU and Mamba2 inter-chunk
# state passing when the sequence is sharded (prefill / long-context).
# ---------------------------------------------------------------------------

def distributed_linear_scan_carry(a_prod, b_red, axes):
    """Given per-shard cumulative coefficients, return the incoming carry.

    a_prod : product of a_t over this shard's steps  [...]
    b_red  : reduced rhs over this shard: sum_t (prod_{s>t} a_s) b_t  [...]
    Returns h_in, the state entering this shard (zeros for the first shard).

    Comm: one all_gather of the (tiny) per-shard summaries over ``axes``,
    then a local exclusive prefix combine.
    """
    if isinstance(axes, str):
        axes = (axes,)
    ap = all_gather_inv(a_prod, axes)          # [n, ...]
    bp = all_gather_inv(b_red, axes)           # [n, ...]
    n = ap.shape[0]

    def combine(carry, xs):
        a_i, b_i = xs
        h = carry
        return a_i * h + b_i, h  # emit the state *entering* shard i

    _, h_ins = lax.scan(combine, b_red * 0, (ap, bp))
    idx = axis_linear_index(axes)
    return lax.dynamic_index_in_dim(h_ins, idx, axis=0, keepdims=False)


# ---------------------------------------------------------------------------
# Distributed categorical sampling over a sharded vocab (gumbel-max).
# ---------------------------------------------------------------------------

def distributed_argmax(values, index_offset, axes):
    """argmax over the last dim of ``values`` where each device holds a
    distinct shard; returns global indices, *invariant* over ``axes``.

    values: [..., v_loc]; index_offset: scalar global offset of this shard.
    Implemented with pmax/pmin (which clear the varying-manifest axes, unlike
    all_gather); ties broken toward the smallest global index.
    """
    if isinstance(axes, str):
        axes = (axes,)
    axes = tuple(axes)
    loc_val = jnp.max(values, axis=-1)
    loc_idx = jnp.argmax(values, axis=-1) + index_offset
    gmax = pmax_v(loc_val, axes)
    big = jnp.iinfo(jnp.int32).max
    cand = jnp.where(loc_val >= gmax, loc_idx.astype(jnp.int32), big)
    return pmin_v(cand, axes)
