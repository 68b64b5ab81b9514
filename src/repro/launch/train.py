"""Cluster training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch yi-6b --mode tesseract \
        --steps 100 [--reduced] [--data 2 --rows 2 --cols 2 --depth 2] \
        [--seq 256 --batch 8] [--ckpt /path]

On a real pod, jax.distributed.initialize() is called when the usual cluster
env vars are present; on this container it runs single-process.  With
--reduced it trains the reduced config on however many devices exist;
without, it expects the full production mesh (use the dry-run to validate
that first).
"""
from __future__ import annotations

import argparse
import os


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--mode", default="tesseract",
                    choices=("tesseract", "summa2d", "megatron1d"))
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--rows", type=int, default=1)
    ap.add_argument("--cols", type=int, default=1)
    ap.add_argument("--depth", type=int, default=1)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--zero1", action="store_true")
    ap.add_argument("--zero-stage", type=int, default=0, choices=(0, 1),
                    help="ZeRO stage: 1 shards AdamW state over the "
                         "data/depth replica axes (same as --zero1)")
    ap.add_argument("--param-dtype", default="float32",
                    choices=("float32", "bfloat16", "float16"))
    ap.add_argument("--compute-dtype", default="float32",
                    choices=("float32", "bfloat16", "float16"),
                    help="bf16 compute + fp32 master weights is the "
                         "mixed-precision recipe (DESIGN.md §9)")
    ap.add_argument("--loss-scale", type=float, default=1.0,
                    help="static loss scaling (float16 numerics lever; "
                         "grads are unscaled before clip/optimizer)")
    ap.add_argument("--matmul-schedule", default="fused",
                    choices=("fused", "ring", "auto"))
    ap.add_argument("--attn-impl", default="auto",
                    choices=("jnp", "pallas", "auto"),
                    help="attention data path: fused Pallas kernels, the "
                         "jnp reference, or per-backend auto (DESIGN.md §10)")
    ap.add_argument("--seq-shards", type=int, default=1,
                    help="sequence-axis mesh shards for ring/striped flash "
                         "attention (DESIGN.md §15); seq_len must divide")
    ap.add_argument("--attn-schedule", default=None,
                    choices=("local", "ring", "striped", "auto"),
                    help="attention schedule across seq shards (default: "
                         "'auto' when --seq-shards > 1, else 'local')")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--pipe", type=int, default=1,
                    help="pipeline stages OUTSIDE the TP group (1F1B)")
    ap.add_argument("--microbatches", type=int, default=0,
                    help="1F1B microbatches per step (0 -> 2*pipe)")
    ap.add_argument("--accum", type=int, default=1,
                    help="gradient-accumulation microsteps per optimizer "
                         "step (elastic re-plans raise this on a shrink)")
    ap.add_argument("--fault-plan", default="",
                    help="deterministic fault schedule (DESIGN.md §11 DSL), "
                         "e.g. 'train.grads@5:nan;ckpt.write@9:corrupt"
                         "(0,bit_flip)'")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed for the fault schedule (replays identically)")
    args = ap.parse_args()

    if "COORDINATOR_ADDRESS" in os.environ:  # multi-host pod
        import jax
        jax.distributed.initialize()

    from ..configs.base import RunConfig, ShapeSpec
    from ..core.api import ParallelContext
    from ..core.mesh import pipeline_mesh
    from .compile_cache import enable_compile_cache
    from ..models.registry import build_model, get_arch, get_reduced
    from ..runtime.train_loop import train

    enable_compile_cache()
    arch = get_reduced(args.arch) if args.reduced else get_arch(args.arch)
    run = RunConfig(param_dtype=args.param_dtype,
                    compute_dtype=args.compute_dtype,
                    loss_chunk=128, q_chunk=64, kv_chunk=64, lr=args.lr,
                    zero1=args.zero1, zero_stage=args.zero_stage,
                    loss_scale=args.loss_scale,
                    matmul_schedule=args.matmul_schedule,
                    attn_impl=args.attn_impl,
                    seq_shards=args.seq_shards,
                    attn_schedule=(args.attn_schedule or
                                   ("auto" if args.seq_shards > 1
                                    else "local")),
                    pipe_stages=args.pipe,
                    pipeline_microbatches=args.microbatches,
                    accum_steps=args.accum,
                    fault_plan=args.fault_plan, fault_seed=args.fault_seed)
    # RunConfig is the config surface; the per-op dispatch for both knobs
    # lives on ParallelContext (DESIGN.md §2b / §10)
    ctx = ParallelContext(mode=args.mode, data=args.data, depth=args.depth,
                          rows=args.rows, cols=args.cols,
                          seq=run.seq_shards,
                          attn_schedule=run.attn_schedule,
                          matmul_schedule=run.matmul_schedule,
                          attn_impl=run.attn_impl)
    mesh = pipeline_mesh(ctx, run.pipe_stages)
    model = build_model(arch.model, ctx, run)
    shape = ShapeSpec("train", seq_len=args.seq, global_batch=args.batch,
                      kind="train")
    res = train(model, mesh, shape, steps=args.steps, ckpt_dir=args.ckpt,
                log_every=10, accum_steps=args.accum)
    print(f"final loss {res.losses[-1]:.4f} after {len(res.losses)} steps "
          f"({res.restarts} restarts)")
    if args.fault_plan:
        print(f"resilience: nan_skips={res.nan_skips} "
              f"loss_scale_backoffs={res.loss_scale_backoffs} "
              f"ckpt_fallbacks={res.ckpt_fallbacks} "
              f"faults_fired={len(res.fault_log)}")


if __name__ == "__main__":
    main()
