"""shardcheck: trace every jitted entry point, extract its collective IR,
and enforce the comm model as invariants (DESIGN.md §13).

    python -m repro.analysis.shardcheck --check            # CI gate
    python -m repro.analysis.shardcheck --update           # re-baseline
    python -m repro.analysis.shardcheck --config serve     # subset sweep
    python -m repro.analysis.shardcheck --entry pipe2      # name filter

Per entry: AOT-trace the jitted step on 8 fake CPU devices (no compile, no
execution), walk the jaxpr into the normalized collective IR
(collective_ir.extract_ir), run the rule catalog (rules.run_all: mesh /
layout / grad-sync / replication), and summarize into the committed
SHARDCHECK.json contract (baseline.diff: exact — new or drifted
collectives fail).  Separately, the standalone tesseract_matmul is traced
per schedule and its wire bytes must match core/summa.matmul_comm_bytes
EXACTLY (the implementation-derived model), and the Pallas kernels get the
GridMapping lint (pallas_lint).  Exit is non-zero on any rule finding, any
conformance mismatch, or (--check) any baseline drift.
"""
from __future__ import annotations

import argparse
import os
import sys

if "XLA_FLAGS" not in os.environ:   # before jax initializes the backend
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp

DEFAULT_BASELINE = "SHARDCHECK.json"
SEQ, BATCH = 32, 8


def _model_for(ctx, *, attn_impl="jnp", zero=False, pipe_mb=0):
    from ..configs.base import RunConfig
    from ..models.registry import build_model, get_reduced

    run = RunConfig(param_dtype="float32", compute_dtype="float32",
                    loss_chunk=32, q_chunk=16, kv_chunk=16,
                    attn_impl=attn_impl, zero1=zero,
                    pipeline_microbatches=pipe_mb)
    arch = get_reduced("yi-6b")
    return build_model(arch.model, ctx, run)


def _train_entry(*, data=1, depth=1, rows=1, cols=1, schedule="fused",
                 inop=False, attn_impl="jnp", zero=False, pipe=1, seq=1,
                 attn_schedule="local"):
    """Trace one train-step variant -> (closed_jaxpr, meta, bundle, info)."""
    from ..configs.base import ShapeSpec
    from ..core.api import ParallelContext
    from ..core.mesh import logical_mesh, pipeline_mesh
    from ..runtime.steps import build_train_step

    ctx = ParallelContext(mode="tesseract", data=data, depth=depth,
                          rows=rows, cols=cols, reduce_dgrad_in_op=inop,
                          matmul_schedule=schedule, attn_impl=attn_impl,
                          seq=seq, attn_schedule=attn_schedule)
    n = pipe * data * seq * depth * rows * cols
    mesh = (pipeline_mesh(ctx, pipe, jax.devices()[:n]) if pipe > 1
            else logical_mesh(ctx, jax.devices()[:n]))
    model = _model_for(ctx, attn_impl=attn_impl, zero=zero)
    shape = ShapeSpec("t", seq_len=SEQ, global_batch=BATCH, kind="train")
    bundle = build_train_step(model, mesh, shape)
    tr = bundle.fn.trace(*bundle.abstract_inputs)
    return tr.jaxpr, bundle.shardcheck_meta, bundle, dict(ctx=ctx,
                                                          model=model)


def _serve_entries():
    """All serve entry points on one q=2, dp=2 layout."""
    from ..configs.base import ShapeSpec
    from ..core.api import ParallelContext
    from ..core.mesh import logical_mesh
    from ..runtime import steps as rs

    ctx = ParallelContext(mode="tesseract", data=2, depth=1, rows=2, cols=2)
    mesh = logical_mesh(ctx, jax.devices()[:8])
    model = _model_for(ctx)
    meta = {"mesh_axes": tuple(str(a) for a in mesh.axis_names),
            "axis_sizes": dict(zip([str(a) for a in mesh.axis_names],
                                   mesh.devices.shape))}
    B, S_p, bs, num_blocks, nb = 8, 16, 4, 32, 8
    out = {}

    pre = rs.build_prefill_step(model, mesh,
                                ShapeSpec("p", S_p, B, "prefill"))
    out["serve_prefill_q2_dp2"] = (
        pre.fn.trace(*pre.abstract_inputs).jaxpr, dict(meta))

    pdec = rs.build_paged_decode_step(model, mesh, B, num_blocks, bs, nb)
    out["serve_paged_decode_q2_dp2"] = (
        pdec.fn.trace(*pdec.abstract_inputs).jaxpr, dict(meta))

    chk = rs.build_chunk_prefill_step(model, mesh, B, S_p, num_blocks,
                                      bs, nb)
    out["serve_chunk_prefill_q2_dp2"] = (
        chk.fn.trace(*chk.abstract_inputs).jaxpr, dict(meta))

    ver = rs.build_spec_verify_step(model, mesh, B, 4, num_blocks, bs, nb)
    out["serve_spec_verify_q2_dp2"] = (
        ver.fn.trace(*ver.abstract_inputs).jaxpr, dict(meta))

    copy_fn = rs.build_page_copy(model, mesh, num_blocks, bs, pdec.plan)
    pool_sds, _ = model.paged_cache_abstract(num_blocks, bs, pdec.plan)
    ids = jax.ShapeDtypeStruct((4,), jnp.int32)
    out["serve_page_copy_q2_dp2"] = (
        copy_fn.trace(pool_sds, ids, ids).jaxpr, dict(meta))

    resh = rs.build_paged_reshard(model, mesh, B, S_p, num_blocks, bs,
                                  pdec.plan)
    pcache_sds = jax.eval_shape(pre.fn, *pre.abstract_inputs)[1]
    tables = jax.ShapeDtypeStruct((B, S_p // bs), jnp.int32)
    out["serve_paged_reshard_q2_dp2"] = (
        resh.trace(pool_sds, pcache_sds, tables).jaxpr, dict(meta))
    return out


# name -> (group, builder kwargs); q in {1, 2} x {flat, pipe, zero1} plus
# schedule / attn_impl / in-op variants on the richest layouts
TRAIN_SWEEP = {
    "train_flat_q1_dp2": dict(data=2),
    "train_flat_q2_dp2": dict(data=2, rows=2, cols=2),
    "train_flat_q2_dp2_ring": dict(data=2, rows=2, cols=2,
                                   schedule="ring"),
    "train_flat_q2_d2_inop": dict(depth=2, rows=2, cols=2, inop=True),
    "train_flat_q2_dp2_pallas": dict(data=2, rows=2, cols=2,
                                     attn_impl="pallas"),
    "train_zero1_q1_dp4": dict(data=4, zero=True),
    "train_zero1_q2_dp2": dict(data=2, rows=2, cols=2, zero=True),
    "train_pipe2_q1_dp2": dict(data=2, pipe=2),
    "train_pipe2_q2": dict(rows=2, cols=2, pipe=2),
    # ring/striped flash attention over the seq axis (DESIGN.md §15): the
    # seq-axis ppermute count and wire bytes are gated EXACTLY against
    # core/ring_attention.ring_ppermute_{counts,bytes}
    "train_ring_attn_q1_seq2": dict(seq=2, attn_schedule="striped"),
    "train_ring_attn_q2_seq2": dict(rows=2, cols=2, seq=2,
                                    attn_schedule="striped"),
}


def _ring_attn_gate(prog, ctx, model, name):
    """Exact seq-axis ppermute conformance for a ring-attention train entry.

    Prediction mirrors models/transformer._ring_attn: each layer streams
    K/V blocks of the locally resident kv heads (GQA-sharded over col when
    num_kv_heads divides q, else expanded to the local q heads) and fp32
    dK/dV accumulators of the same shape, with counts from
    ring_ppermute_counts (remat="full" replays the fwd ring in the bwd).
    Returns (findings, got_count, got_bytes)."""
    from ..core.ring_attention import (ring_ppermute_bytes,
                                      ring_ppermute_counts)
    from .rules import Finding

    cfg = model.cfg
    n = ctx.seq
    L = SEQ // n
    kv_shard = cfg.num_kv_heads % ctx.q == 0
    h_stream = (cfg.num_kv_heads if kv_shard else cfg.num_heads) // ctx.cols
    b_loc = BATCH // (ctx.data * ctx.depth * ctx.rows)
    dh = cfg.d_model // cfg.num_heads
    # _model_for pins compute_dtype=float32, so K/V blocks and the fp32
    # accumulators are the same 4-byte block
    blk = b_loc * h_stream * L * dh * 4
    counts = ring_ppermute_counts(n, train=True, remat_replay=True)
    per_layer = ring_ppermute_bytes(n, kv_block_bytes=blk,
                                    acc_block_bytes=blk,
                                    train=True, remat_replay=True)
    exp_n = cfg.num_layers * counts["total"]
    exp_b = cfg.num_layers * per_layer["total"]
    seq_pp = [c for c in prog.collectives
              if c.kind == "ppermute" and c.axes == (ctx.axis_seq,)]
    got_n = sum(c.mult for c in seq_pp)
    got_b = int(round(sum(c.total_wire_bytes for c in seq_pp)))
    findings = []
    if got_n != exp_n or got_b != exp_b:
        findings.append(Finding(
            "commmodel", name,
            f"seq-axis ppermutes {got_n} / {got_b}B != ring model "
            f"{exp_n} / {exp_b}B ({cfg.num_layers} layers x "
            f"{counts['total']} permutes x {blk}B blocks)"))
    return findings, got_n, got_b


def matmul_conformance() -> tuple:
    """Trace tesseract_matmul fwd+bwd per schedule; wire bytes must equal
    core/summa.matmul_comm_bytes exactly.  Returns (findings, results)."""
    from jax.sharding import PartitionSpec as P

    from ..core import summa
    from ..core.api import ParallelContext
    from jax import shard_map
    from ..core.collectives import pvary
    from ..core.mesh import logical_mesh
    from ..roofline.analysis import wire_time_s
    from .collective_ir import extract_ir
    from .rules import Finding

    findings, results = [], {}
    B, E, F, G = 2, 64, 64, 64
    for sched in ("fused", "ring"):
        for inop in (False, True):
            name = f"matmul_{sched}{'_inop' if inop else ''}_q2_d2"
            ctx = ParallelContext(mode="tesseract", data=1, depth=2,
                                  rows=2, cols=2, reduce_dgrad_in_op=inop,
                                  matmul_schedule=sched)
            mesh = logical_mesh(ctx, jax.devices()[:8])
            a_spec = P(None, ("data", "depth", "row"), "col")
            w_spec = P("row", "col")

            # deferred dW reduction: the train step's grad_sync pvary over
            # (data, depth) precedes the op, and its psum is not part of
            # the op's comm, so dW leaves unreduced (stacked per replica)
            red = () if inop else ("data", "depth")

            def local(a, w, s, red=red):
                def loss(a_, w_):
                    return jnp.sum(summa.tesseract_matmul(ctx, a_, w_) * s)
                _, (ga, gw) = jax.value_and_grad(loss, (0, 1))(
                    a, pvary(w, red))
                return ga, gw[None]

            f = shard_map(local, mesh=mesh,
                          in_specs=(a_spec, w_spec, a_spec),
                          out_specs=(a_spec, P(red or None, "row", "col")))
            sds = jax.ShapeDtypeStruct
            tr = jax.jit(f).trace(sds((B, E, F), jnp.float32),
                                  sds((F, G), jnp.float32),
                                  sds((B, E, G), jnp.float32))
            traced = extract_ir(tr.jaxpr).total_wire_bytes()
            e_loc = E // (ctx.data * ctx.depth * ctx.rows)
            pred = summa.matmul_comm_bytes(
                ctx, e_loc, F // ctx.q, G // ctx.q, batch=B, train=True,
                itemsize=4, schedule=sched)["total"]
            results[name] = {"traced_bytes": int(round(traced)),
                             "predicted_bytes": int(round(pred)),
                             "wire_time_us": round(
                                 wire_time_s(traced) * 1e6, 3)}
            if int(round(traced)) != int(round(pred)):
                findings.append(Finding(
                    "commmodel", name,
                    f"traced wire bytes {traced:.0f} != "
                    f"summa.matmul_comm_bytes prediction {pred:.0f}"))
    return findings, results


def run_sweep(config: str = "all", entry_filter: str = ""):
    """Returns (findings, entries{name: summary}, kernel_stats)."""
    from ..roofline.analysis import wire_time_s
    from ..runtime.pipeline import expected_ring_transfers, schedule_1f1b
    from . import baseline as bl
    from . import pallas_lint, rules
    from .collective_ir import extract_ir

    findings, entries = [], {}

    def want(name):
        return (not entry_filter) or entry_filter in name

    if config in ("all", "train"):
        for name, kw in TRAIN_SWEEP.items():
            if not want(name):
                continue
            jaxpr, meta, bundle, info = _train_entry(**kw)
            prog = extract_ir(jaxpr)
            findings += rules.run_all(prog, meta, jaxpr, entry=name)
            summ = bl.summarize(prog)
            summ["wire_time_us"] = round(
                wire_time_s(prog.total_wire_bytes()) * 1e6, 3)
            if info["ctx"].seq > 1:
                f, got_n, got_b = _ring_attn_gate(prog, info["ctx"],
                                                  info["model"], name)
                findings += f
                summ["seq_ppermutes"] = got_n
                summ["seq_ppermute_bytes"] = got_b
            if bundle.pipe_info is not None:
                info = bundle.pipe_info
                exp = expected_ring_transfers(
                    schedule_1f1b(info["n_micro"], info["n_stages"]))
                got = sum(c.mult for c in prog.collectives
                          if c.kind == "ppermute" and c.axes == ("pipe",))
                if got != exp["ppermutes"]:
                    findings.append(rules.Finding(
                        "commmodel", name,
                        f"pipe-axis ppermutes {got} != 1F1B schedule's "
                        f"{exp['ppermutes']} (2 per tick x "
                        f"{exp['n_ticks']} ticks)"))
                summ["pipe_ppermutes"] = got
            entries[name] = summ

    if config in ("all", "serve"):
        for name, (jaxpr, meta) in _serve_entries().items():
            if not want(name):
                continue
            prog = extract_ir(jaxpr)
            findings += rules.check_mesh(prog, meta["mesh_axes"], name)
            findings += rules.check_divergence(jaxpr, name)
            summ = bl.summarize(prog)
            summ["wire_time_us"] = round(
                wire_time_s(prog.total_wire_bytes()) * 1e6, 3)
            entries[name] = summ

    if config in ("all", "matmul"):
        f, results = matmul_conformance()
        findings += f
        for name, r in results.items():
            if want(name):
                entries[name] = r

    kernel_stats = {}
    if config in ("all", "kernels"):
        f, kernel_stats = pallas_lint.lint_default_kernels()
        findings += f
    return findings, entries, kernel_stats


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="repro.analysis.shardcheck", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--check", action="store_true",
                    help="diff the sweep against the committed baseline")
    ap.add_argument("--update", action="store_true",
                    help="rewrite the baseline from this sweep")
    ap.add_argument("--config", default="all",
                    choices=("all", "train", "serve", "matmul", "kernels"),
                    help="sweep subset")
    ap.add_argument("--entry", default="",
                    help="only entries whose name contains this substring")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE)
    args = ap.parse_args(argv)

    from . import baseline as bl
    from . import lint

    findings, entries, kernel_stats = run_sweep(args.config, args.entry)
    for name in sorted(entries):
        e = entries[name]
        if "collectives" in e:
            print(f"{name}: {sum(c['count'] for c in e['collectives'].values())} "
                  f"collectives, {e['total_wire_bytes']} wire bytes")
        else:
            print(f"{name}: traced={e['traced_bytes']} "
                  f"predicted={e['predicted_bytes']} bytes")
    for k, s in sorted(kernel_stats.items()):
        print(f"{k}: grid={s['grid']} vmem={s['vmem_bytes']}B")

    rc = 0
    for f in findings:
        print(f"FINDING {f}", file=sys.stderr)
        rc = 1

    payload = dict(entries)
    for k, s in kernel_stats.items():
        payload[f"kernel:{k}"] = s

    if args.update:
        # lint findings still fail an --update run: the baseline is a
        # contract for CONFORMANT programs only
        bl.write(args.baseline, payload)
        print(f"baseline written: {args.baseline} ({len(payload)} entries)")
    elif args.check:
        if args.config != "all" or args.entry:
            print("--check requires the full sweep (no --entry/--config "
                  "subset): partial sweeps always diff as missing entries",
                  file=sys.stderr)
            return 2
        try:
            base = bl.load(args.baseline)
        except FileNotFoundError:
            print(f"no baseline at {args.baseline}; run --update first",
                  file=sys.stderr)
            return 2
        drift = bl.diff(base, payload)
        for line in drift:
            print(f"DRIFT {line}", file=sys.stderr)
            rc = 1
        if not drift:
            print(f"baseline conformant: {len(payload)} entries")

    # the AST lint rides every invocation: it is cheap and the CI job
    # calls this module once
    lint_findings = lint.lint_paths(["src"]) if os.path.isdir("src") else []
    for path, line, code, msg in lint_findings:
        print(f"FINDING [lint] {path}:{line}: {code} {msg}",
              file=sys.stderr)
        rc = 1
    if rc == 0:
        print("shardcheck: OK")
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
