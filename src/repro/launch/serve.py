"""Serving launcher: continuous-batching engine over the paged KV cache.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
        python -m repro.launch.serve --arch yi-6b --reduced \
        --depth 2 --rows 2 --cols 2 --requests 8 --n-slots 8 \
        --prompt-lens 8,16 --new-tokens 16

Requests with mixed prompt/output lengths are admitted into a fixed slot
batch, prefilled in buckets, resharded into the mesh-sharded block pool and
decoded one fixed-shape step at a time; finished sequences retire in place
(src/repro/serve/, DESIGN.md §7).

For production decode the 1-D serve layout is the measured winner
(EXPERIMENTS.md §Perf B1): pass --mode megatron1d.  matmul-schedule "auto"
resolves ring-vs-fused per op (ring for prefill-sized token blocks on
q >= 4 grids, fused for decode steps — DESIGN.md §2b).
"""
from __future__ import annotations

import argparse


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--mode", default="tesseract",
                    choices=("tesseract", "summa2d", "megatron1d"))
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--n-slots", type=int, default=4)
    ap.add_argument("--block-size", type=int, default=8)
    ap.add_argument("--num-blocks", type=int, default=128)
    ap.add_argument("--max-seq-len", type=int, default=256)
    ap.add_argument("--prompt-lens", default="8,16",
                    help="comma list cycled over requests (mixed lengths)")
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--rows", type=int, default=1)
    ap.add_argument("--cols", type=int, default=1)
    ap.add_argument("--depth", type=int, default=1)
    ap.add_argument("--matmul-schedule", default="fused",
                    choices=("fused", "ring", "auto"))
    ap.add_argument("--attn-impl", default="auto",
                    choices=("jnp", "pallas", "auto"),
                    help="attention data path: block-table paged decode "
                         "kernel + flash prefill, the jnp gather reference, "
                         "or per-backend auto (DESIGN.md §10)")
    ap.add_argument("--replan-to", type=int, default=0,
                    help="simulate an elastic device-count change after 2 "
                         "steps (rebuild mesh + reshard live KV blocks)")
    ap.add_argument("--fault-plan", default="",
                    help="deterministic fault schedule (DESIGN.md §11 DSL), "
                         "e.g. 'serve.logits@2:nan(1);serve.step@4:"
                         "drop_step'")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed for the fault schedule (replays identically)")
    ap.add_argument("--deadline-s", type=float, default=0.0,
                    help="per-request completion deadline (0 = none)")
    ap.add_argument("--ttft-budget-s", type=float, default=0.0,
                    help="per-request time-to-first-token budget (0 = none)")
    ap.add_argument("--max-waiting", type=int, default=0,
                    help="bound on the admission queue (0 = unbounded)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="radix prefix cache: shared prompt prefixes reuse "
                         "refcounted pool pages, divergent tails split "
                         "copy-on-write (DESIGN.md §12)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked prefill width interleaved with decode "
                         "steps (0 = monolithic bucketed prefill; the "
                         "prefix cache auto-chunks when 0)")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding: proposals verified per "
                         "engine step (0 = plain decode; DESIGN.md §14)")
    ap.add_argument("--spec-mode", default="auto",
                    choices=("auto", "draft", "ngram"),
                    help="proposal source: a draft model (--draft-config) "
                         "or the model-free n-gram prompt-lookup fallback; "
                         "auto picks draft when one is configured")
    ap.add_argument("--draft-config", default="",
                    help="arch name of the draft model (e.g. smollm-360m); "
                         "built reduced iff --reduced, on the same mesh, "
                         "with vocab_size aligned to the target")
    args = ap.parse_args()

    import jax
    import numpy as np

    from ..configs.base import RunConfig
    from ..core.api import ParallelContext
    from ..core.mesh import logical_mesh
    from ..models.registry import build_model, get_arch, get_reduced
    from ..serve import EngineConfig, InferenceEngine, SamplingParams
    from .compile_cache import enable_compile_cache

    enable_compile_cache()
    arch = get_reduced(args.arch) if args.reduced else get_arch(args.arch)
    run = RunConfig(param_dtype="float32", compute_dtype="float32",
                    loss_chunk=64, q_chunk=32, kv_chunk=32,
                    matmul_schedule=args.matmul_schedule,
                    attn_impl=args.attn_impl,
                    fault_plan=args.fault_plan, fault_seed=args.fault_seed)
    # megatron1d + ring/auto raises in ParallelContext, same as launch.train
    ctx = ParallelContext(mode=args.mode, data=args.data, depth=args.depth,
                          rows=args.rows, cols=args.cols,
                          matmul_schedule=run.matmul_schedule,
                          attn_impl=run.attn_impl)
    mesh = logical_mesh(ctx)
    model = build_model(arch.model, ctx, run)

    draft_model = draft_params = None
    if args.draft_config:
        import dataclasses
        darch = (get_reduced(args.draft_config) if args.reduced
                 else get_arch(args.draft_config))
        # the verify step judges draft proposals in the target's vocab, so
        # the draft head must emit the same token space
        dcfg = dataclasses.replace(darch.model,
                                   vocab_size=model.cfg.vocab_size)
        draft_model = build_model(dcfg, ctx, run)
        draft_params = draft_model.init(jax.random.PRNGKey(7))

    engine = InferenceEngine(
        model, mesh, model.init(jax.random.PRNGKey(0)), EngineConfig(
            n_slots=args.n_slots, block_size=args.block_size,
            num_blocks=args.num_blocks, max_seq_len=args.max_seq_len,
            max_waiting=args.max_waiting, prefix_cache=args.prefix_cache,
            prefill_chunk=args.prefill_chunk, spec_k=args.spec_k,
            spec_mode=args.spec_mode),
        draft_model=draft_model, draft_params=draft_params)

    plens = [int(x) for x in args.prompt_lens.split(",")]
    rng = np.random.RandomState(0)
    vocab = min(250, model.cfg.vocab_size)
    reqs = []
    for i in range(args.requests):
        prompt = rng.randint(0, vocab, (plens[i % len(plens)],)).tolist()
        reqs.append(engine.add_request(
            prompt,
            SamplingParams(temperature=args.temperature, top_k=args.top_k,
                           top_p=args.top_p, seed=i,
                           max_new_tokens=args.new_tokens),
            deadline_s=args.deadline_s or None,
            ttft_budget_s=args.ttft_budget_s or None))

    if args.replan_to:
        engine.step()
        engine.step()
        rp = engine.replan_to(args.replan_to)
        print(f"replanned to {rp.n_used} devices: data={rp.ctx.data} "
              f"[q={rp.ctx.rows},{rp.ctx.cols},d={rp.ctx.depth}] "
              f"(idle={rp.n_idle})")

    results = engine.run()
    for r in reqs:
        print(f"req {r.rid} (prompt {r.orig_prompt_len}, "
              f"preempted {r.preemptions}x): {results[r.rid]}")
    s = engine.stats
    lat = s.latency_percentiles()
    ttft, itl = s.ttft_percentiles(), s.itl_percentiles()
    print(f"steps={s.steps} prefills={s.prefills} "
          f"preemptions={s.preemptions} tokens={s.tokens} "
          f"tokens/s={s.tokens_per_s():.1f} "
          f"p50={lat['p50_ms']:.1f}ms p95={lat['p95_ms']:.1f}ms "
          f"p99={lat['p99_ms']:.1f}ms "
          f"attn_impl={engine.attn_impl} "
          f"(CPU wall-clock: indicative only)")
    print(f"slo: health={s.health} "
          f"ttft p50={ttft['p50_ms']:.1f}ms p99={ttft['p99_ms']:.1f}ms "
          f"itl p50={itl['p50_ms']:.1f}ms p99={itl['p99_ms']:.1f}ms "
          f"shed={s.shed} failed={s.failed} "
          f"nan_quarantines={s.nan_quarantines} "
          f"batch_shrinks={s.batch_shrinks} "
          f"dropped_steps={s.dropped_steps}")
    if args.prefix_cache or args.prefill_chunk:
        print(f"prefix: hit_rate={s.cache_hit_rate():.3f} "
              f"hits={s.prefix_hits}/{s.prefix_lookups} "
              f"tokens_reused={s.prefix_tokens_reused}/"
              f"{s.prefix_tokens_total} cow_splits={s.cow_splits} "
              f"evictions={s.cache_evictions} "
              f"prefill_chunks={s.prefill_chunks} "
              f"cached_nodes={len(engine.prefix) if engine.prefix else 0}")
    if args.spec_k:
        print(f"spec: mode={engine.spec_mode} k={args.spec_k} "
              f"rounds={s.spec_rounds} proposed={s.spec_proposed} "
              f"accepted={s.spec_accepted} committed={s.spec_committed} "
              f"acceptance={s.acceptance_rate():.3f} "
              f"tokens/slot-round={s.tokens_per_round():.3f}")


if __name__ == "__main__":
    main()
