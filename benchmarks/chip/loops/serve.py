"""Open loop of requests into the paged continuous-batching engine.

Arrivals are Poisson at the traffic file's fixed ``rate_per_s``.  The
multiset of inter-arrival gaps, prompt lengths and output lengths is drawn
once from the file's ``shape_seed``, so every ``--seed`` offers the same
work; ``--seed`` orders it and draws the prompt tokens (random ids, no
shared prefixes).  Every request is greedy.

Set-up builds ``serve.InferenceEngine`` as ``launch/serve.py`` does, with
weights made from the seed, and warms every prefill bucket that the
prompt-length range can reach plus the decode step.  The window offers the
requests due in its ``seconds``; a request's time to first token runs from
when it was due, using the engine's own ``first_token_t`` stamps.  After
the window the engine keeps stepping, without new arrivals, until every
request due in the window has its first token (``drain_s`` at most); one
that fails, is shed or gets none counts as missing (+inf).

Traffic keys: ``rate_per_s``, ``shape_seed``, ``prompt_len`` {median,
sigma, min}, ``output_len`` {min, max}, ``engine`` (EngineConfig fields),
``sample_requests``, ``drain_s``, ``trace_seconds``, ``limits``.
"""
from __future__ import annotations

import gc
import math
import time

import jax
import numpy as np

from chip import check_serve as C
from chip import reference as R
from chip import system
from chip import trace_reduce
from chip import weights as W
from chip.harness import RUN_DIR, Check, RunRecord, note, span


class OpenLoop:
    """The requests of one run: due times (s from the window's start),
    prompt token lists and output lengths."""

    def __init__(self, traffic: dict, vocab: int, context: int,
                 seconds: float, seed: int):
        base = np.random.default_rng(traffic["shape_seed"])
        rate = traffic["rate_per_s"]
        gaps = []
        while sum(gaps) <= seconds:
            gaps.append(base.exponential(1.0 / rate))
        gaps = np.array(gaps[:-1])
        n = len(gaps)
        pl, ol = traffic["prompt_len"], traffic["output_len"]
        outs = base.integers(ol["min"], ol["max"] + 1, n)
        prompts = np.exp(base.normal(math.log(pl["median"]), pl["sigma"], n))
        prompts = np.clip(prompts.astype(np.int64), pl["min"], context - outs)
        rng = np.random.default_rng(int(seed))
        self.due = np.cumsum(rng.permutation(gaps))
        order = rng.permutation(n)
        self.out_len = outs[order].tolist()
        self.prompt_len = prompts[order].tolist()
        self.prompts = [rng.integers(0, vocab, p, dtype=np.int32).tolist()
                        for p in self.prompt_len]

    def __len__(self):
        return len(self.due)


def prompt_buckets(engine, lo: int, hi: int) -> list:
    """Every prefill bucket a prompt of lo..hi tokens can land in."""
    return sorted({engine._bucket(n) for n in range(lo, hi + 1)})


def build_engine(cfg: dict, traffic: dict, devices, seed: int):
    from repro.core.ops import make_ops
    from repro.serve import EngineConfig, InferenceEngine
    from repro.runtime.steps import make_plan
    from repro.configs.base import ShapeSpec
    from jax.sharding import NamedSharding, PartitionSpec as P

    model, mesh = system.build(cfg, "serve", devices)
    ecfg = EngineConfig(**traffic["engine"])
    arch = R.Arch.from_config(cfg)
    # the weights straight into the layout the engine's steps take
    plan = make_plan(model.ctx, ShapeSpec("serve", 1, ecfg.n_slots, "decode"))
    specs = model.specs(make_ops(model.ctx, plan))
    shard = jax.tree.map(lambda sp: NamedSharding(mesh, sp), specs,
                         is_leaf=lambda x: isinstance(x, P))
    abstract = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = W.program_params(arch, seed, abstract, shard)
    return InferenceEngine(model, mesh, params, ecfg), arch


def warm_up(engine, traffic: dict, context: int, vocab: int) -> int:
    """Compile every program the window can call: each reachable prefill
    bucket (and its reshard) and the decode step with its sampler."""
    from repro.serve import SamplingParams
    lo = traffic["prompt_len"]["min"]
    hi = context - traffic["output_len"]["min"]
    buckets = prompt_buckets(engine, lo, hi)
    rng = np.random.default_rng(0)
    for b in buckets:
        n = min(b, hi)
        engine.add_request(rng.integers(0, vocab, n).tolist(),
                           SamplingParams(temperature=0.0, max_new_tokens=2))
    engine.run()
    return len(buckets)


def _pct(vals, q: float) -> float:
    """Nearest-rank percentile (inf values sort last)."""
    if not vals:
        return math.inf
    s = sorted(vals)
    return float(s[max(0, math.ceil(q / 100 * len(s)) - 1)])


def drive(engine, load: OpenLoop, seconds: float, drain_s: float, *,
          trace_at: float | None = None, trace_s: float = 0.0,
          tdir=None) -> dict:
    """Offer ``load`` to ``engine`` in an open loop; returns what the
    metrics and the check need."""
    from repro.serve import SamplingParams
    stats = engine.stats
    s0 = dict(steps=stats.steps, wall=stats.wall, tokens=stats.tokens,
              itls=len(stats.itls), prefills=stats.prefills)
    reqs, late, steps = [], [], []
    itl_end = None
    traced, tracing = None, False
    tspan = None
    n = len(load)
    t0 = time.perf_counter()
    i = 0
    while True:
        now = time.perf_counter() - t0
        while i < n and load.due[i] <= now:
            with span("add_request"):
                reqs.append(engine.add_request(
                    load.prompts[i], SamplingParams(
                        temperature=0.0, seed=i,
                        max_new_tokens=load.out_len[i])))
            late.append(now - load.due[i])
            i += 1
        if now > seconds:
            if itl_end is None:
                itl_end = len(stats.itls)
                s1 = dict(steps=stats.steps, wall=stats.wall,
                          tokens=stats.tokens, prefills=stats.prefills)
                t_close, n_window_steps = now, len(steps)
            pending = [r for r in reqs
                       if r.first_token_t is None and r.state != "failed"]
            if not pending or now > seconds + drain_s:
                break
        if trace_at is not None and not tracing and traced is None \
                and now >= trace_at:
            trace_reduce.start(tdir)
            tspan = span("window")
            tspan.__enter__()
            tracing, t_tr = True, now
            tsteps0 = len(steps)
        if tracing and now >= t_tr + trace_s:
            tspan.__exit__(None, None, None)
            traced = trace_reduce.stop(tdir)
            tracing = False
            traced_steps = steps[tsteps0:]
        if engine.sched.has_work:
            dec = [r.num_cached + 1 for r in engine.sched.running
                   if r.last_token is not None]
            fresh = [r for r in engine.sched.waiting]
            ts = time.perf_counter()
            with span("engine_step"):
                engine.step()
            wall = time.perf_counter() - ts
            pre = [r.orig_prompt_len for r in fresh
                   if r.first_token_t is not None]
            steps.append((wall, dec, pre))
        else:
            nxt = load.due[i] if i < n else seconds + drain_s
            with span("generator_wait"):
                time.sleep(max(0.0, min(nxt - now, 0.05)))
    if tracing:
        tspan.__exit__(None, None, None)
        traced = trace_reduce.stop(tdir)
        traced_steps = steps[tsteps0:]
    due_abs = t0 + load.due[:len(reqs)]
    ttft = [(r.first_token_t - d) if (r.first_token_t is not None
                                      and r.state != "failed") else math.inf
            for r, d in zip(reqs, due_abs)]
    itls = stats.itls[s0["itls"]:itl_end]
    return {
        "reqs": reqs, "t0": t0, "ttft": ttft, "itls": itls, "late": late,
        "window_s": t_close, "steps": steps[:n_window_steps],
        "engine_steps": s1["steps"] - s0["steps"],
        "engine_wall": s1["wall"] - s0["wall"],
        "tokens": s1["tokens"] - s0["tokens"],
        "prefills": s1["prefills"] - s0["prefills"],
        "trace": traced,
        "traced_steps": traced_steps if traced is not None else None,
    }


def pick_sample(reqs, seed: int, k: int) -> list:
    """[(prompt, served)] of ``k`` finished requests drawn from the seed,
    the longest of them always among them."""
    done = [r for r in reqs if r.state != "failed"
            and len(r.generated) == r.sampling.max_new_tokens]
    if not done:
        return []
    longest = max(range(len(done)), key=lambda j: len(done[j].seq_tokens))
    rest = [j for j in range(len(done)) if j != longest]
    rng = np.random.default_rng(int(seed))
    pick = [longest] + [int(j) for j in rng.choice(
        rest, size=min(k - 1, len(rest)), replace=False)]
    return [(list(done[j].prompt[:done[j].orig_prompt_len]),
             list(done[j].generated)) for j in pick]


def run(rec: RunRecord, seconds: float, trace: bool, t_start: float,
        counter) -> None:
    cell, tr = rec.cell, rec.cell.traffic
    cfg = cell.config
    context = tr["engine"]["max_seq_len"]
    t = time.perf_counter()
    engine, arch = build_engine(cfg, tr, rec.devices, rec.seed)
    load = OpenLoop(tr, arch.vocab, context, seconds, rec.seed)
    t1 = time.perf_counter()
    nb = warm_up(engine, tr, context, arch.vocab)
    note(f"set-up: import {t - t_start:.1f} s, engine built "
         f"{t1 - t:.1f} s, {nb} prefill buckets and decode warmed "
         f"{time.perf_counter() - t1:.1f} s; {len(load)} requests due "
         f"in {seconds} s at {tr['rate_per_s']}/s")

    counter.armed = True
    rec.setup_s = time.perf_counter() - t_start
    got = drive(engine, load, seconds, tr["drain_s"],
                trace_at=(seconds / 2 if trace else None),
                trace_s=tr["trace_seconds"],
                tdir=RUN_DIR / "trace" / cell.name)
    counter.armed = False
    reqs = got["reqs"]
    rec.window_s = got["window_s"]
    rec.attempted = len(reqs)
    rec.failed = sum(not math.isfinite(t) for t in got["ttft"])
    rec.end_to_end["ttft_p95_ms"] = _pct(got["ttft"], 95) * 1e3
    rec.end_to_end["itl_p95_ms"] = _pct(got["itls"], 95) * 1e3
    late = got["late"]
    note(f"generator: {len(late)} requests sent, lateness p50 "
         f"{_pct(late, 50) * 1e3:.3f} ms p95 {_pct(late, 95) * 1e3:.3f} "
         f"ms max {max(late) * 1e3 if late else 0:.3f} ms")
    note(f"window: {got['engine_steps']} engine steps, "
         f"{got['prefills']} prefills, {got['tokens']} tokens, "
         f"{len(got['itls'])} gaps; ttft p50 "
         f"{_pct(got['ttft'], 50) * 1e3:.3f} ms; failed {rec.failed}")
    rec.counters.update(
        engine_steps=got["engine_steps"], engine_wall=got["engine_wall"],
        tokens=got["tokens"], prefills=got["prefills"],
        steps=got["steps"], traced_steps=got["traced_steps"],
        n_slots=tr["engine"]["n_slots"])
    rec.trace = got["trace"]
    rec.memory_peak_bytes = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in rec.devices)

    sample = pick_sample(reqs, rec.seed, tr["sample_requests"])
    del engine, reqs, got
    gc.collect()
    t = time.perf_counter()
    gaps = C.served_gaps(arch, rec.seed, sample, context, rec.devices)
    worst = max((float(np.max(g)) for g in gaps), default=math.inf)
    note(f"reference: {len(sample)} requests, "
         f"{sum(len(g) for g in gaps)} served tokens compared in "
         f"{time.perf_counter() - t:.1f} s")
    rec.checks = [Check("logit_gap", worst, tr["limits"]["logit_gap"])]


def sweep(engine, arch, traffic: dict, rates, seconds: float) -> list:
    """The knee, found once when the cell is defined: the open loop at each
    rate in turn, rising, on one warmed engine, until the backlog at the
    window's close (requests due but not yet given a first token) passes a
    tenth of the requests.  Per rate: requests due, tokens per second
    completed in the window, the tails and the backlog."""
    context = traffic["engine"]["max_seq_len"]
    rows = []
    for rate in rates:
        load = OpenLoop(dict(traffic, rate_per_s=rate), arch.vocab, context,
                        seconds, 0)
        got = drive(engine, load, seconds, traffic["drain_s"])
        close = got["t0"] + got["window_s"]
        backlog = sum(1 for r in got["reqs"]
                      if r.first_token_t is None or r.first_token_t > close)
        rows.append({"rate_per_s": rate, "requests": len(got["reqs"]),
                     "tokens_per_s": got["tokens"] / got["window_s"],
                     "ttft_p95_ms": _pct(got["ttft"], 95) * 1e3,
                     "itl_p95_ms": _pct(got["itls"], 95) * 1e3,
                     "backlog_at_close": backlog,
                     "engine_step_ms": 1e3 * got["engine_wall"]
                     / max(got["engine_steps"], 1)})
        if backlog > 0.1 * len(got["reqs"]):
            break
        engine.run()
    return rows
