"""Pipeline parallelism (paper §3.4: Tesseract composes with PP outermost).

Two schedules over a dedicated ``pipe`` mesh axis:

* ``pipeline_apply`` — the GPipe scan kept as the differentiable *reference*
  oracle: a single lax.scan of M + S - 1 ticks whose reverse-mode transpose
  is the backward pipeline (all forwards, then all backwards).  Simple, but
  it holds all M microbatches' activations live through the flush.

* ``pipeline_1f1b_grads`` — the production 1F1B (PipeDream-flush) schedule
  used by ``runtime/steps.build_train_step`` on a [pipe x data x depth x row
  x col] mesh.  The schedule is simulated host-side (``schedule_1f1b``) into
  per-tick (stage -> microbatch) tables; the device program is one lax.scan
  over 2(M+S-1) ticks in which every stage runs one forward unit and one
  backward unit per tick (masked when its table entry is idle).  Backward
  units rematerialize their stage forward from the saved *input* activation
  (the same trade as run.remat="full"), so in-flight storage is bounded by
  the 1F1B window (<= S microbatch inputs per stage) instead of GPipe's M.
  Activations move stage-to-stage with collective_permute; cotangents ride
  the reverse permute.

The measured bubble fraction of the simulated schedule is asserted against
the analytic ``bubble_fraction(M, S)`` at build time (within 10%).
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax


def pipeline_apply(stage_fn, stage_params, x_mb, *, axis: str = "pipe"):
    """Run ``stage_fn(params, x)`` as an S-stage GPipe pipeline over M
    microbatches (reference schedule; reverse-mode AD trains it).

    stage_params : this stage's params (stage-sharded over ``axis``)
    x_mb         : [M, mb, ...] microbatch inputs (used on stage 0; other
                   stages ignore their copy)
    Returns [M, mb, ...] outputs, valid on the LAST stage (replicated there
    via the caller's reduction; other stages hold garbage).
    """
    S = lax.axis_size(axis)
    sid = lax.axis_index(axis)
    M = x_mb.shape[0]
    T = M + S - 1
    fwd_perm = [(i, i + 1) for i in range(S - 1)]

    def tick(carry, t):
        buf, outputs = carry
        mb_i = jnp.clip(t, 0, M - 1)
        inject = lax.dynamic_index_in_dim(x_mb, mb_i, 0, keepdims=False)
        inp = jnp.where(sid == 0, inject, buf)
        y = stage_fn(stage_params, inp)
        out_i = jnp.clip(t - (S - 1), 0, M - 1)
        take = (sid == S - 1) & (t >= S - 1)
        outputs = lax.dynamic_update_index_in_dim(
            outputs,
            jnp.where(take, y, lax.dynamic_index_in_dim(outputs, out_i, 0,
                                                        keepdims=False)),
            out_i, 0)
        buf_next = lax.ppermute(y, axis, fwd_perm)
        return (buf_next, outputs), None

    buf0 = jnp.zeros(x_mb.shape[1:], x_mb.dtype)
    outs0 = jnp.zeros_like(x_mb)
    # seed vma so the carry matches the loop body: the pipeline buffer varies
    # over the pipe axis (stage params differ per stage, ppermute shifts)
    from ..models.common import vma_like
    seed = jax.tree.leaves(stage_params)[0]
    buf0 = vma_like(buf0, x_mb, seed)
    outs0 = vma_like(outs0, x_mb, seed)
    (_, outputs), _ = lax.scan(tick, (buf0, outs0), jnp.arange(T))
    return outputs


def bubble_fraction(n_micro: int, n_stages: int) -> float:
    """Pipeline bubble overhead (S-1)/(M+S-1).

    Identical for GPipe and 1F1B when a backward unit costs the same as a
    forward unit (the schedules differ in peak activation memory, not in
    flush length); 1F1B's measured tick tables reproduce it exactly."""
    return (n_stages - 1) / (n_micro + n_stages - 1)


# ---------------------------------------------------------------------------
# 1F1B (PipeDream-flush) schedule
# ---------------------------------------------------------------------------

def schedule_1f1b(n_micro: int, n_stages: int):
    """Simulate the 1F1B schedule into per-tick dispatch tables.

    Per stage s the action list is the classic PipeDream-flush order:
    W = min(S-1-s, M) warmup forwards, then (M - W) steady [fwd, bwd]
    pairs, then W cooldown backwards.  Each tick every stage attempts the
    head of its list and idles unless its dependency completed at a
    *strictly earlier* tick (activations/cotangents arrive at end-of-tick).

    Returns (fwd_tbl, bwd_tbl, n_slots, info):
      fwd_tbl/bwd_tbl : [T, S] int32, microbatch index or -1 (idle)
      n_slots         : in-flight buffer depth K needed by the executor
                        (the 1F1B memory bound, <= S+1; GPipe would need M)
      info            : dict with n_ticks / measured_bubble / predicted_bubble
    """
    M, S = n_micro, n_stages
    if M < 1 or S < 1:
        raise ValueError(f"need n_micro >= 1 and n_stages >= 1, got {M}, {S}")
    actions = []
    for s in range(S):
        W = min(S - 1 - s, M)
        acts = [("F", m) for m in range(W)]
        for m in range(W, M):
            acts.append(("F", m))
            acts.append(("B", m - W))
        for m in range(M - W, M):
            acts.append(("B", m))
        actions.append(acts)

    ptr = [0] * S
    t_fwd: dict = {}
    t_bwd: dict = {}
    fwd_rows, bwd_rows = [], []
    t = 0
    while any(ptr[s] < len(actions[s]) for s in range(S)):
        frow, brow = [-1] * S, [-1] * S
        for s in range(S):
            if ptr[s] >= len(actions[s]):
                continue
            kind, m = actions[s][ptr[s]]
            if kind == "F":
                ready = s == 0 or t_fwd.get((s - 1, m), t) < t
            else:
                if s == S - 1:
                    ready = t_fwd.get((s, m), t) < t
                else:
                    ready = t_bwd.get((s + 1, m), t) < t
            if ready:
                (frow if kind == "F" else brow)[s] = m
        progressed = False
        for s in range(S):
            if frow[s] >= 0:
                t_fwd[(s, frow[s])] = t
                ptr[s] += 1
                progressed = True
            elif brow[s] >= 0:
                t_bwd[(s, brow[s])] = t
                ptr[s] += 1
                progressed = True
        if not progressed:
            raise AssertionError(f"1F1B schedule deadlock at tick {t} "
                                 f"(M={M}, S={S})")
        fwd_rows.append(frow)
        bwd_rows.append(brow)
        t += 1
        if t > 4 * (M + S) + 8:
            raise AssertionError(f"1F1B schedule did not drain (M={M}, S={S})")

    T = len(fwd_rows)
    # in-flight input-activation window per stage: a microbatch's saved input
    # is live from the upstream forward (receive) until this stage's backward
    n_slots = 1
    for s in range(S):
        src = s - 1 if s > 0 else s
        for tt in range(T):
            live = [m for m in range(M)
                    if t_fwd[(src, m)] <= tt <= t_bwd[(s, m)]]
            if live:
                n_slots = max(n_slots, max(live) - min(live) + 1)

    busy = 2 * M * S
    info = {
        "n_ticks": T,
        "n_micro": M,
        "n_stages": S,
        "n_slots": n_slots,
        "measured_bubble": 1.0 - busy / (T * S),
        "predicted_bubble": bubble_fraction(M, S),
    }
    return (np.asarray(fwd_rows, np.int32), np.asarray(bwd_rows, np.int32),
            n_slots, info)


def expected_ring_transfers(schedule) -> dict:
    """Pipe-axis transfer counts implied by a ``schedule_1f1b`` result.

    The 1F1B executor below issues exactly TWO ppermutes per tick (one
    activation forward, one cotangent backward, unconditionally — masked
    ticks still permute garbage slots), so a traced step must contain
    ``2 * n_ticks`` pipe-axis ppermute occurrences once the executing scan's
    multiplicity is unrolled.  repro.analysis.shardcheck diffs the extracted
    IR against this; a drift means the schedule tables and the device
    program disagree."""
    fwd_tbl, bwd_tbl, _k, info = schedule
    return {
        "n_ticks": int(info["n_ticks"]),
        "ppermutes": 2 * int(info["n_ticks"]),
        "busy_fwd": int((np.asarray(fwd_tbl) >= 0).sum()),
        "busy_bwd": int((np.asarray(bwd_tbl) >= 0).sum()),
    }


def pipeline_1f1b_grads(stage_step, params, a_proto, n_micro: int, *,
                        axis: str = "pipe", loss_seed=1.0, schedule=None):
    """Value-and-grad of an S-stage 1F1B pipeline (manual per-stage vjp).

    stage_step(params, a, m) -> (y, loss_sum_m, cnt_m)
        the uniform per-stage forward: ``a`` is the previous stage's
        activation (stage 0 re-derives its input from microbatch index ``m``
        and ignores ``a``), ``y`` is the activation handed downstream, and
        (loss_sum_m, cnt_m) are this stage's local CE sums for microbatch
        ``m`` (meaningful on the last stage; garbage elsewhere).
    params    : stage-local param tree (pipe-sharded leaves already local)
    a_proto   : zeros template of the activation's local shape/dtype
    n_micro   : number of microbatches M
    loss_seed : dL/d(loss_sum_m) — 1/total_token_count for a mean CE
    schedule  : optional precomputed ``schedule_1f1b(n_micro, S)`` result
                (the builder passes it so the simulation runs once)
    Returns (loss_sum, cnt_sum, grads, info): the sums accumulate the LAST
    stage's microbatch losses (zero elsewhere; caller psums over ``axis`` and
    the data axis), grads are this stage's summed raw contributions
    (unreduced over replication axes — the caller applies the deferred
    psums), info is the schedule stats dict from ``schedule_1f1b``.

    Backward units recompute their stage forward from the saved input
    activation (rematerialization), so per-stage live state is K = S-ish
    microbatch inputs + cotangents, never all M (the 1F1B memory bound).
    """
    from ..core import collectives as col

    S = lax.axis_size(axis)
    M = int(n_micro)
    fwd_tbl, bwd_tbl, K, info = schedule or schedule_1f1b(M, S)
    if info["n_micro"] != M or info["n_stages"] != S:
        raise ValueError(f"schedule was built for (M={info['n_micro']}, "
                         f"S={info['n_stages']}), executing (M={M}, S={S})")
    if info["measured_bubble"] > info["predicted_bubble"] + 0.10:
        raise AssertionError(
            f"1F1B schedule bubble {info['measured_bubble']:.3f} exceeds "
            f"prediction {info['predicted_bubble']:.3f} + 10% "
            f"(M={M}, S={S})")
    sid = lax.axis_index(axis)
    is_last = sid == S - 1
    fwd_perm = [(i, i + 1) for i in range(S - 1)]
    bwd_perm = [(i + 1, i) for i in range(S - 1)]

    # vma discipline: learn every carried leaf's varying axes from one
    # throwaway forward + zero-cotangent vjp (exact zeros, right vma).
    mesh_axes = jax.sharding.get_abstract_mesh().axis_names
    a0 = col.pvary(a_proto, tuple(
        a for a in (axis, "data", "depth", "row", "col") if a in mesh_axes))
    out0 = stage_step(params, a0, jnp.int32(0))
    seeds0 = jax.tree.map(
        lambda o: col.pvary(jnp.zeros(o.shape, o.dtype),
                            tuple(col.vma_of(o))), out0)
    _, pull0 = jax.vjp(lambda p, a: stage_step(p, a, jnp.int32(0)),
                       params, a0)
    grads0, cot0 = pull0(seeds0)
    # a tick's contribution is selected by this stage's schedule slot, so
    # the accumulated grads vary over the pipe axis for every leaf
    grads0 = jax.tree.map(lambda g: col.pvary(g, (axis,)), grads0)
    a_store = jnp.zeros((K,) + a_proto.shape, a_proto.dtype) \
        + (a0 * 0)[None]
    cot_store = jnp.zeros((K,) + cot0.shape, cot0.dtype) + (cot0 * 0)[None]
    zero_ld = col.pvary(jnp.float32(0), tuple(
        a for a in ("data", axis) if a in mesh_axes))
    loss_acc, cnt_acc = zero_ld, zero_ld

    seed_val = jnp.float32(loss_seed)

    def tick(carry, xs):
        a_store, cot_store, loss_acc, cnt_acc, grads = carry
        mf_row, mb_row = xs

        # ---- forward unit ----
        mf = mf_row[sid]
        act_f = mf >= 0
        mfc = jnp.clip(mf, 0, M - 1)
        a_in = lax.dynamic_index_in_dim(a_store, mfc % K, 0, keepdims=False)
        y, ls, cnt = stage_step(params, a_in, mfc)
        take = act_f & is_last
        loss_acc = loss_acc + jnp.where(take, ls, 0.0)
        cnt_acc = cnt_acc + jnp.where(take, cnt, 0.0)

        # ---- backward unit (remat: re-linearize from the saved input) ----
        mb = mb_row[sid]
        act_b = mb >= 0
        mbc = jnp.clip(mb, 0, M - 1)
        a_sav = lax.dynamic_index_in_dim(a_store, mbc % K, 0, keepdims=False)
        dy = lax.dynamic_index_in_dim(cot_store, mbc % K, 0, keepdims=False)
        dy = jnp.where(is_last, jnp.zeros_like(dy), dy)
        dls = jnp.where(act_b & is_last, seed_val, 0.0)
        dls = col.pvary(dls, tuple(col.vma_of(ls)))
        dcnt = col.pvary(jnp.zeros_like(cnt), tuple(col.vma_of(cnt)))
        _, pull = jax.vjp(lambda p, a: stage_step(p, a, mbc), params, a_sav)
        dp, da = pull((dy, dls, dcnt))
        grads = jax.tree.map(
            lambda g, d: g + jnp.where(act_b, d, jnp.zeros_like(d)),
            grads, dp)

        # ---- communicate (end of tick) ----
        if S > 1:
            y_recv = lax.ppermute(y, axis, fwd_perm)
            da_recv = lax.ppermute(da, axis, bwd_perm)
            # what did my neighbours dispatch this tick?
            m_left = mf_row[jnp.clip(sid - 1, 0, S - 1)]
            wr_a = (sid > 0) & (m_left >= 0)
            slot_a = jnp.clip(m_left, 0, M - 1) % K
            old_a = lax.dynamic_index_in_dim(a_store, slot_a, 0,
                                             keepdims=False)
            a_store = lax.dynamic_update_index_in_dim(
                a_store, jnp.where(wr_a, y_recv.astype(a_store.dtype), old_a),
                slot_a, 0)
            m_right = mb_row[jnp.clip(sid + 1, 0, S - 1)]
            wr_c = (sid < S - 1) & (m_right >= 0)
            slot_c = jnp.clip(m_right, 0, M - 1) % K
            old_c = lax.dynamic_index_in_dim(cot_store, slot_c, 0,
                                             keepdims=False)
            cot_store = lax.dynamic_update_index_in_dim(
                cot_store,
                jnp.where(wr_c, da_recv.astype(cot_store.dtype), old_c),
                slot_c, 0)
        return (a_store, cot_store, loss_acc, cnt_acc, grads), None

    xs = (jnp.asarray(fwd_tbl), jnp.asarray(bwd_tbl))
    (a_store, cot_store, loss_acc, cnt_acc, grads), _ = lax.scan(
        tick, (a_store, cot_store, loss_acc, cnt_acc, grads0), xs)
    return loss_acc, cnt_acc, grads, info
