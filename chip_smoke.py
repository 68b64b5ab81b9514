"""Chip smoke test: the trainer and the paged server on a TPU, end to end.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # a v5e:2x2 host, layouts compared

One process, no children.  It drives the same builders as
``repro.launch.train`` (``build_model`` + ``runtime.train_loop.train``) and
``repro.launch.serve`` (``InferenceEngine``) at the full published width
and depth of smollm-360m with random weights made from a seed, and checks
what comes out.  Step times and memory are printed as information only;
they are not benchmark results.

It exits non-zero, and prints no result line, when JAX finds no TPU or any
phase fails.  On success the last line of stdout is one JSON object naming
the device.
"""
from __future__ import annotations

import argparse
import json
import math
import pathlib
import sys
import time

ARCH = "smollm-360m"
SEED = 0
# train phase (one chip): fp32 params, bf16 compute, seq 2048, batch 8
TRAIN_SEQ, TRAIN_BATCH, TRAIN_STEPS = 2048, 8, 5
# serve phase: 8 greedy requests, prompts alternating 128 / 512 tokens
PROMPT_LENS, NEW_TOKENS, N_REQUESTS = (128, 512), 32, 8
N_SLOTS, BLOCK_SIZE = 8, 16

# jnp vs pallas forward loss, same params and batch (bf16 compute).  Both
# paths accumulate attention in fp32 but round their bf16 outputs at
# different points; bf16 keeps 8 significant bits (relative step 2^-8),
# and a mean cross-entropy over 16k tokens averages those roundings, so a
# relative gap above 2^-8 / 4 ~ 1e-3 means the kernel computes something
# else, not that it rounds differently.
NUMERICS_RTOL = 1e-3
# [2,2,1] tesseract vs megatron1d [1,4,1], per-step training loss (bf16
# compute).  The layouts split every matmul's reduction over different
# shards, so bf16 partial sums round differently; five AdamW steps at
# lr 3e-4 carry that into the loss.  2^-8 relative (0.4%) bounds a few
# bf16 roundings of the loss itself; a layout bug (a missing or doubled
# reduction) moves the loss by whole percents.
LAYOUT_RTOL = 2.0 ** -8


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


def build(arch, devices, layout, *, attn_impl, param_dtype, compute_dtype):
    """Model + mesh the way the launchers build them.

    layout: (mode, rows, cols, depth) on ``devices``."""
    from repro.configs.base import RunConfig
    from repro.core.api import ParallelContext
    from repro.core.mesh import logical_mesh
    from repro.models.registry import build_model

    mode, rows, cols, depth = layout
    run = RunConfig(param_dtype=param_dtype, compute_dtype=compute_dtype,
                    loss_chunk=128, q_chunk=512, kv_chunk=512,
                    attn_impl=attn_impl)
    ctx = ParallelContext(mode=mode, data=1, depth=depth, rows=rows,
                          cols=cols, attn_impl=run.attn_impl)
    return build_model(arch.model, ctx, run), logical_mesh(ctx, devices)


def train_phase(arch, devices, layout, *, attn_impl="auto", steps=TRAIN_STEPS,
                seq=TRAIN_SEQ, batch=TRAIN_BATCH, check_kernel=False):
    """``steps`` training steps through runtime.train_loop.train.  Any
    restart counts as a failure (max_restarts=0 re-raises the first).
    Returns the per-step losses."""
    import jax
    import numpy as np

    from repro.configs.base import ShapeSpec
    from repro.runtime.steps import build_train_step
    from repro.runtime.train_loop import train

    model, mesh = build(arch, devices, layout, attn_impl=attn_impl,
                        param_dtype="float32", compute_dtype="bfloat16")
    shape = ShapeSpec("train", seq_len=seq, global_batch=batch, kind="train")
    tag = f"train {layout[0]} {layout[1:]} attn={attn_impl}"
    if check_kernel:
        bundle = build_train_step(model, mesh, shape)
        t0 = time.perf_counter()
        hlo = bundle.fn.lower(*bundle.abstract_inputs).compile().as_text()
        n_kernels = hlo.count("tpu_custom_call")
        print(f"{tag}: compiled step in {time.perf_counter() - t0:.1f} s, "
              f"{n_kernels} tpu_custom_call ops")
        check(n_kernels > 0, "compiled train step runs no Pallas kernel")
    res = train(model, mesh, shape, steps=steps, max_restarts=0,
                log_every=0, seed=SEED)
    check(res.restarts == 0, f"{tag}: {res.restarts} restarts")
    check(len(res.losses) == steps, f"{tag}: {len(res.losses)} steps ran")
    for i, (loss, gn, dt) in enumerate(zip(res.losses, res.grad_norms,
                                           res.step_times)):
        print(f"{tag}: step {i} loss {loss!r} grad_norm {gn!r} "
              f"wall {dt * 1e3:.1f} ms (info only)")
        check(math.isfinite(loss) and math.isfinite(gn),
              f"{tag}: step {i} loss {loss} grad_norm {gn}")
    return np.array(res.losses)


def serve_phase(arch, devices, layout, *, attn_impl="auto",
                n_requests=N_REQUESTS, prompt_lens=PROMPT_LENS,
                new_tokens=NEW_TOKENS):
    """Greedy requests through the paged engine; returns {rid: tokens}."""
    import jax
    import numpy as np

    from repro.serve import EngineConfig, InferenceEngine, SamplingParams

    # the serve launcher's precision: fp32 weights and compute
    model, mesh = build(arch, devices, layout, attn_impl=attn_impl,
                        param_dtype="float32", compute_dtype="float32")
    max_seq = max(prompt_lens) + new_tokens
    blocks_per_req = -(-max_seq // BLOCK_SIZE)
    # no reference to the init kept: the engine holds only its placed copy
    engine = InferenceEngine(
        model, mesh, model.init(jax.random.PRNGKey(SEED)), EngineConfig(
            n_slots=N_SLOTS, block_size=BLOCK_SIZE,
            num_blocks=N_SLOTS * blocks_per_req + 8 * len(devices),
            max_seq_len=blocks_per_req * BLOCK_SIZE))
    tag = f"serve {layout[0]} {layout[1:]} attn={engine.attn_impl}"
    rng = np.random.RandomState(SEED)
    vocab = model.cfg.vocab_size
    reqs = [engine.add_request(
        rng.randint(0, vocab, (prompt_lens[i % len(prompt_lens)],)).tolist(),
        SamplingParams(temperature=0.0, seed=i, max_new_tokens=new_tokens))
        for i in range(n_requests)]
    t0 = time.perf_counter()
    out = engine.run()
    wall = time.perf_counter() - t0
    s = engine.stats
    print(f"{tag}: {len(reqs)} requests, {s.tokens} tokens, {s.steps} "
          f"steps, {s.prefills} prefills in {wall:.1f} s incl. compile "
          f"(info only); failed={s.failed} shed={s.shed} "
          f"nan_quarantines={s.nan_quarantines}")
    check(s.failed == 0 and s.shed == 0 and s.nan_quarantines == 0,
          f"{tag}: failed={s.failed} shed={s.shed} "
          f"nan_quarantines={s.nan_quarantines}")
    for r in reqs:
        check(len(out[r.rid]) == new_tokens,
              f"{tag}: request {r.rid} produced {len(out[r.rid])} tokens")
    used = [(d.memory_stats() or {}).get("bytes_in_use", 0)
            for d in mesh.devices.flat]
    print(f"{tag}: bytes_in_use per device {used} (engine live)")
    return [out[r.rid] for r in reqs], used


def peak_memory(devices):
    return [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in devices]


def one_chip(arch, devices):
    from repro.kernels.ops import effective_attn_impl
    check(effective_attn_impl("auto") == "pallas",
          f"attn_impl auto resolves to {effective_attn_impl('auto')}")
    print("kernel path: attn_impl auto -> pallas")
    layout = ("tesseract", 1, 1, 1)

    losses = train_phase(arch, devices, layout, check_kernel=True)
    print(f"train: peak_bytes_in_use {peak_memory(devices)} (info only)")

    # numerics: the first step's loss is the forward loss at the seeded
    # init params and the first batch of the seeded stream on both paths
    ref = train_phase(arch, devices, layout, attn_impl="jnp", steps=1)
    rel = abs(losses[0] - ref[0]) / abs(ref[0])
    print(f"numerics: forward loss pallas {losses[0]!r} jnp {ref[0]!r} "
          f"rel diff {rel:.3e} (tolerance {NUMERICS_RTOL:g})")
    check(rel <= NUMERICS_RTOL, f"pallas vs jnp loss rel diff {rel:.3e}")

    got, _ = serve_phase(arch, devices, layout)
    want, _ = serve_phase(arch, devices, layout, attn_impl="jnp",
                          n_requests=2)
    for i in range(2):
        check(got[i][0] == want[i][0],
              f"request {i}: first greedy token pallas {got[i][0]} "
              f"jnp {want[i][0]}")
    same = sum(a == b for i in range(2) for a, b in zip(got[i][1:],
                                                          want[i][1:]))
    print(f"serve: first greedy token agrees on 2/2 requests; later tokens "
          f"agree {same}/{2 * (NEW_TOKENS - 1)}")


def four_chips(arch, devices):
    import numpy as np
    tess = train_phase(arch, devices, ("tesseract", 2, 2, 1))
    meg = train_phase(arch, devices, ("megatron1d", 1, 4, 1))
    rel = np.abs(tess - meg) / np.abs(meg)
    print(f"layouts: tesseract [2,2,1] {tess.tolist()} megatron1d [1,4,1] "
          f"{meg.tolist()} max rel diff {rel.max():.3e} "
          f"(tolerance {LAYOUT_RTOL:g})")
    check(rel.max() <= LAYOUT_RTOL,
          f"[2,2,1] vs megatron1d losses differ by {rel.max():.3e}")
    _, used = serve_phase(arch, devices, ("tesseract", 2, 2, 1))
    check(min(used) > 0.5 * max(used),
          f"parameters not spread over the devices: bytes_in_use {used}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip layouts phase")
    args = ap.parse_args(argv)
    n = 4 if args.four_chips else 1

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"no TPU: JAX found {dev.platform} devices")
    if len(devices) < n:
        fail(f"need {n} TPU devices, found {len(devices)}")
    devices = devices[:n]
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}")

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models.registry import get_arch
    print(f"compile cache: {enable_compile_cache()}")
    arch = get_arch(ARCH)
    (four_chips if args.four_chips else one_chip)(arch, devices)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
