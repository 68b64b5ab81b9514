"""Continuous-batching inference engine over the Tesseract mesh.

One ``InferenceEngine.step`` is: admit waiting requests into free slots,
prefill them (bucketed fixed shapes, per-request true lengths), reshard the
prefill cache into the paged pool, run ONE fixed-shape paged decode step for
the whole slot batch (mixed lengths, block-table gather/scatter), sample
per-request, retire finished sequences in place.  See DESIGN.md §7.

The decode batch shape never changes across steps — batch composition does:
retired slots point at their group's scratch block until re-admission, so
the step function compiles exactly once per engine.

SLO guardrails + chaos hardening (DESIGN.md §11): bounded admission queue
(QueueFullError), per-request deadlines and TTFT budgets enforced against
an injectable engine clock, a NaN/Inf logit guard that quarantines only
the poisoned slot (re-prefill via the position-keyed PRNG replay keeps its
tokens bit-exact), graceful decode-batch shrink after repeated pool-OOM
preemption storms, and a healthy/degraded state in EngineStats.  A
``runtime/faults.FaultInjector`` (default: ``model.run.fault_plan``) drives
all of it deterministically at the ``serve.step`` / ``serve.logits`` sites.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs.base import ShapeSpec
from ..core.ops import make_ops
from ..runtime import faults as faults_mod
from ..runtime.steps import (build_chunk_prefill_step, build_page_copy,
                             build_paged_decode_step, build_paged_reshard,
                             build_prefill_step, make_plan)
from .kv_cache import PagedCacheConfig, PagedKVCache
from .prefix_cache import RadixPrefixCache
from .sampling import (SamplingParams, sample_tokens, slot_arrays,
                       spec_accept, spec_target_probs)
from .scheduler import FAILED, RUNNING, WAITING, Request, Scheduler


class QueueFullError(RuntimeError):
    """Bounded admission queue is full — the caller must back off or shed
    load upstream (admission control beats queueing collapse under the
    ROADMAP's 'heavy traffic' regime)."""


@dataclass(frozen=True)
class EngineConfig:
    n_slots: int = 4
    block_size: int = 8
    num_blocks: int = 64         # global, across all KV groups
    max_seq_len: int = 256
    prefill_batch: int = 0       # 0 -> ctx.data (smallest valid)
    eos_id: int = -1
    # --- SLO / resilience knobs (DESIGN.md §11) ---
    max_waiting: int = 0         # bound on the waiting queue (0 = unbounded)
    nan_retry_limit: int = 2     # quarantine->re-prefill rounds before FAILED
    oom_shrink_after: int = 2    # consecutive preemption-storm steps -> shrink
    oom_recover_after: int = 8   # consecutive calm steps -> grow back
    # --- shared-prompt serving (DESIGN.md §12) ---
    prefix_cache: bool = False   # radix prefix index over the block pool
    prefill_chunk: int = 0       # chunked prefill width (0 = monolithic;
    #                              prefix_cache implies the chunked path
    #                              with an auto-sized chunk)
    # --- speculative decoding (DESIGN.md §14) ---
    spec_k: int = 0              # proposals per round (0 = plain decode)
    spec_mode: str = "auto"      # auto | draft | ngram: auto takes draft
    #                              when a draft model is attached, else the
    #                              model-free n-gram prompt-lookup fallback
    spec_ngram_max: int = 3      # longest n-gram the fallback matches


def _pcts(vals, qs=(50, 95, 99)):
    if not vals:
        return {f"p{q}_ms": 0.0 for q in qs}
    t = np.array(vals) * 1e3
    return {f"p{q}_ms": float(np.percentile(t, q)) for q in qs}


@dataclass
class EngineStats:
    steps: int = 0
    prefills: int = 0
    preemptions: int = 0
    tokens: int = 0
    token_times: list = field(default_factory=list)  # seconds per emitted token
    wall: float = 0.0
    # --- SLO latency breakdown (engine clock) ---
    ttfts: list = field(default_factory=list)    # arrival -> first token
    itls: list = field(default_factory=list)     # inter-token latencies
    # --- resilience counters (DESIGN.md §11) ---
    health: str = "healthy"      # healthy | degraded
    shed: int = 0                # deadline / TTFT-budget sheds
    failed: int = 0              # requests terminally FAILED (incl. sheds)
    nan_quarantines: int = 0     # poisoned-slot quarantine -> re-prefill
    batch_shrinks: int = 0       # max_active reductions after OOM storms
    pool_exhaust_events: int = 0 # injected KV-pool exhaustion windows
    dropped_steps: int = 0       # injected lost engine iterations
    # --- shared-prompt serving (DESIGN.md §12) ---
    prefix_lookups: int = 0      # admissions that consulted the radix cache
    prefix_hits: int = 0         # admissions that reused cached pages
    prefix_tokens_reused: int = 0  # prompt positions served from shared pages
    prefix_tokens_total: int = 0   # prompt positions admitted while cache on
    cow_splits: int = 0          # copy-on-write donor-page copies
    cache_evictions: int = 0     # cold cache leaves dropped for capacity
    prefill_chunks: int = 0      # chunked-prefill steps doing NEW work
    #                              (replays after eviction don't count)
    # --- speculative decoding (DESIGN.md §14) ---
    spec_rounds: int = 0         # verify-step invocations
    spec_proposed: int = 0       # draft tokens judged by the target
    spec_accepted: int = 0       # draft tokens accepted verbatim
    spec_committed: int = 0      # tokens committed by verify rounds
    spec_slot_rounds: int = 0    # per-slot verify participations

    def tokens_per_s(self) -> float:
        return self.tokens / self.wall if self.wall else 0.0

    def acceptance_rate(self) -> float:
        """Fraction of judged proposals accepted verbatim."""
        return (self.spec_accepted / self.spec_proposed
                if self.spec_proposed else 0.0)

    def tokens_per_round(self) -> float:
        """Mean committed tokens per slot per verify round — the decode
        speedup factor: a plain decode step commits exactly 1 token per
        active slot, a verify round commits 1 + accepted (+ bonus)."""
        return (self.spec_committed / self.spec_slot_rounds
                if self.spec_slot_rounds else 0.0)

    def cache_hit_rate(self) -> float:
        """Fraction of admitted prompt tokens served from shared pages."""
        return (self.prefix_tokens_reused / self.prefix_tokens_total
                if self.prefix_tokens_total else 0.0)

    def latency_percentiles(self):
        return _pcts(self.token_times)

    def ttft_percentiles(self):
        return _pcts(self.ttfts)

    def itl_percentiles(self):
        return _pcts(self.itls)


class InferenceEngine:
    def __init__(self, model, mesh, params, cfg: EngineConfig,
                 injector=None, clock=None, draft_model=None,
                 draft_params=None):
        self.model, self.mesh, self.cfg = model, mesh, cfg
        # injectable wall clock: deadline/TTFT tests drive a fake clock
        self.clock = clock or time.perf_counter
        self.injector = injector if injector is not None else \
            faults_mod.injector_from_run(model.run, sites=("serve",))
        self._hostage = None     # injected pool-exhaustion hold
        self._oom_streak = 0     # consecutive steps with preemptions
        self._calm_streak = 0    # consecutive steps without
        self._evict_carry = 0    # cache evictions from pre-replan cache objs
        # speculative decoding: the draft rides the same mesh; its params
        # are kept as host arrays so elastic replans can re-place them on
        # the rebuilt mesh exactly like the target's
        self.draft_model = draft_model
        self._draft_params_host = None
        if draft_model is not None and cfg.spec_k > 0:
            self._draft_params_host = jax.tree.map(np.asarray, draft_params)
        self._build()
        self.params = self._place_params(params)

    def _place_params(self, params):
        """Params laid out as the steps take them, so that no step call
        reshards them (an array built on one device would otherwise stay
        there whole, beside its sharded copies)."""
        specs = self.model.specs(make_ops(self.model.ctx, self.plan))
        shardings = jax.tree.map(lambda sp: NamedSharding(self.mesh, sp),
                                 specs, is_leaf=lambda x: isinstance(x, P))
        return jax.tree.map(jax.device_put, params, shardings)

    # ---------------------------------------------------------------- build
    def _build(self):
        model, mesh, cfg = self.model, self.mesh, self.cfg
        ctx = model.ctx
        if not hasattr(model, "decode_paged"):
            raise NotImplementedError(
                f"{type(model).__name__} has no paged decode path")
        # resolved attention data path (DESIGN.md §10) — surfaced so
        # operators can see which decode kernel a serve process runs;
        # elastic replans re-resolve (replace() preserves ctx.attn_impl)
        from ..kernels.ops import effective_attn_impl
        self.attn_impl = effective_attn_impl(ctx.attn_impl)
        self.plan = make_plan(ctx, ShapeSpec("serve", 1, cfg.n_slots,
                                             "decode"))
        if self.plan.kind == "decode" and cfg.n_slots % ctx.batch_shards:
            raise ValueError(
                f"n_slots={cfg.n_slots} must divide over "
                f"{ctx.batch_shards} token shards (or be < them to "
                f"downgrade the plan)")
        self.cache = PagedKVCache(
            model, mesh, self.plan,
            PagedCacheConfig(num_blocks=cfg.num_blocks,
                             block_size=cfg.block_size,
                             max_seq_len=cfg.max_seq_len))
        self.sched = Scheduler(self.cache, cfg.n_slots)
        self.pool = self.cache.init_arrays()
        self.dec = build_paged_decode_step(
            model, mesh, cfg.n_slots, cfg.num_blocks, cfg.block_size,
            self.cache.max_blocks)
        self._prefill_bundles = {}   # bucket_len -> (prefill, reshard)
        self._b_pre = cfg.prefill_batch or max(1, ctx.data)
        if self._b_pre % max(1, ctx.data):
            raise ValueError("prefill_batch must divide over data")
        # sequence-shard divisor for prefill buckets
        if ctx.mode == "megatron1d":
            self._seq_div = ctx.cols
        else:
            self._seq_div = ctx.depth * ctx.rows
        # shared-prompt serving (DESIGN.md §12): the prefix cache implies
        # the chunked paged prefill path — a hit resumes mid-prompt, which
        # the monolithic bucketed prefill cannot do without rewriting the
        # shared pages it is supposed to reuse.
        self._chunked = bool(cfg.prefix_cache or cfg.prefill_chunk > 0)
        self.prefix = None
        self._page_copy = None
        if cfg.prefix_cache:
            self.prefix = RadixPrefixCache(self.cache.pool, cfg.block_size)
            self.sched.prefix_cache = self.prefix
            self._page_copy = build_page_copy(
                model, mesh, cfg.num_blocks, cfg.block_size, self.plan)
        self._chunk_bundles = {}     # chunk width -> StepBundle
        # speculative decoding (DESIGN.md §14): one verify bundle of fixed
        # width spec_k + 1 plus either a DraftRunner (parallel draft pool
        # over the SAME block tables) or the n-gram fallback proposer
        self._spec_on = cfg.spec_k > 0
        self._draft = None
        self._ngram = None
        self._verify = None
        if self._spec_on:
            from ..runtime.steps import build_spec_verify_step
            from .spec import DraftRunner, NgramProposer
            mode = cfg.spec_mode
            if mode == "auto":
                mode = "draft" if self.draft_model is not None else "ngram"
            if mode not in ("draft", "ngram"):
                raise ValueError(f"spec_mode={cfg.spec_mode!r} not in "
                                 f"(auto, draft, ngram)")
            if mode == "draft" and self.draft_model is None:
                raise ValueError("spec_mode='draft' needs a draft model")
            self.spec_mode = mode
            self._verify = build_spec_verify_step(
                model, mesh, cfg.n_slots, cfg.spec_k + 1, cfg.num_blocks,
                cfg.block_size, self.cache.max_blocks)
            if mode == "draft":
                if self.draft_model.cfg.vocab_size != model.cfg.vocab_size:
                    raise ValueError(
                        f"draft vocab {self.draft_model.cfg.vocab_size} != "
                        f"target vocab {model.cfg.vocab_size}")
                self._draft = DraftRunner(
                    self.draft_model, mesh, self._draft_params_host,
                    cfg.n_slots, cfg.num_blocks, cfg.block_size,
                    self.cache.max_blocks)
            else:
                self._ngram = NgramProposer(max_n=cfg.spec_ngram_max)
        if not hasattr(self, "stats"):      # survives replan rebuilds
            self.stats = EngineStats()
            self.requests = []
        elif self._spec_on:
            # replan rebuild: the draft pool is fresh (zeroed) and block
            # ids moved — every draft watermark is stale
            for r in self.requests:
                r.draft_cached = 0

    def _bucket(self, n: int) -> int:
        """Prefill bucket covering ``n`` tokens: power-of-two multiples of
        lcm(block_size, seq shards) — divisible by both the reshard's block
        split and the sequence sharding — clamped to the pool's maximum
        resident length (Scheduler.add guarantees n fits that)."""
        import math
        base = math.lcm(self.cfg.block_size, self._seq_div)
        cap = -(-self.cache.max_blocks * self.cfg.block_size // base) * base
        b = base
        while b < n and b < cap:
            b = min(b * 2, cap)
        return b

    def _prefill_for(self, bucket: int):
        if bucket not in self._prefill_bundles:
            shape = ShapeSpec("ep", bucket, self._b_pre, "prefill")
            pre = build_prefill_step(self.model, self.mesh, shape,
                                     with_lengths=True)
            reshard = build_paged_reshard(
                self.model, self.mesh, self._b_pre, bucket,
                self.cfg.num_blocks, self.cfg.block_size, self.plan)
            self._prefill_bundles[bucket] = (pre, reshard)
        return self._prefill_bundles[bucket]

    # ------------------------------------------------------------- requests
    def add_request(self, prompt, sampling: SamplingParams | None = None,
                    rid=None, deadline_s: float | None = None,
                    ttft_budget_s: float | None = None) -> Request:
        """sampling defaults PER CALL (None -> fresh SamplingParams(); a
        shared default instance would alias state across requests).
        Raises QueueFullError when cfg.max_waiting bounds the queue."""
        if self.cfg.max_waiting and \
                len(self.sched.waiting) >= self.cfg.max_waiting:
            raise QueueFullError(
                f"admission queue full ({self.cfg.max_waiting} waiting)")
        req = Request(prompt, sampling, eos_id=self.cfg.eos_id, rid=rid,
                      deadline_s=deadline_s, ttft_budget_s=ttft_budget_s,
                      arrival_t=self.clock())
        self.requests.append(req)
        return self.sched.add(req)

    def _fail(self, req: Request, reason: str) -> None:
        """Terminally fail one request, releasing whatever it holds."""
        if req.state == RUNNING:
            self.cache.pool.free(req.block_ids)
            req.block_ids = []
            self.sched.slots[req.slot] = None
            req.slot = None
        elif req.state == WAITING and req in self.sched.waiting:
            self.sched.waiting.remove(req)
        req.state = FAILED
        req.fail_reason = reason
        self.stats.failed += 1

    def _shed_expired(self) -> None:
        """Deadline / TTFT-budget enforcement: shed ONLY the expired
        requests (waiting or running); survivors are untouched."""
        now = self.clock()
        for req in list(self.sched.waiting) + self.sched.running:
            age = now - req.arrival_t
            if req.deadline_s is not None and age > req.deadline_s:
                self._fail(req, f"deadline ({req.deadline_s:g}s) exceeded")
                self.stats.shed += 1
            elif (req.ttft_budget_s is not None and req.first_token_t is None
                  and age > req.ttft_budget_s):
                self._fail(req, f"ttft budget ({req.ttft_budget_s:g}s) "
                                f"exceeded")
                self.stats.shed += 1

    def _record_emit(self, req: Request, now: float | None = None) -> None:
        """TTFT / inter-token latency accounting on the engine clock.

        ``now`` is the emit stamp read ONCE per engine step, immediately
        after the sampled tokens of the completing chunk / decode batch
        materialize (the device sync point).  Stamping inside the
        per-request loop instead would leak admission bookkeeping, COW
        copies and radix inserts of EARLIER slots into LATER slots' TTFT
        (ISSUE 9 satellite): all tokens of one batch are produced by the
        same computation and must carry the same stamp."""
        if now is None:
            now = self.clock()
        if req.first_token_t is None:
            req.first_token_t = now
            self.stats.ttfts.append(now - req.arrival_t)
        elif req.last_emit_t is not None:
            self.stats.itls.append(now - req.last_emit_t)
        req.last_emit_t = now

    def _quarantine(self, req: Request) -> None:
        """NaN/Inf logits in this request's slot: evict ONLY that slot and
        re-prefill it later — the position-keyed PRNG replays its trajectory
        bit-exactly.  Bounded by cfg.nan_retry_limit, then FAILED."""
        req.nan_retries += 1
        self.stats.nan_quarantines += 1
        if req.nan_retries > self.cfg.nan_retry_limit:
            self._fail(req, f"non-finite logits persisted through "
                            f"{self.cfg.nan_retry_limit} re-prefills")
            return
        self.sched.slots[req.slot] = None
        self.sched.preempt(req)

    @staticmethod
    def _finite_rows(logits) -> np.ndarray:
        """(rows,) bool: row i of the logit batch is sane.  -inf is a LEGIT
        logit value (vocab-shard padding, top-k/top-p masks); only NaN and
        +inf mark a poisoned row."""
        lg = np.asarray(logits)
        bad = np.isnan(lg) | np.isposinf(lg)
        return ~bad.any(axis=tuple(range(1, lg.ndim)))

    # -------------------------------------------------------------- prefill
    def _run_prefills(self, admitted):
        """Bucketed, batched prefill of newly admitted requests + reshard of
        their caches into the paged pool.  Returns the number of tokens
        emitted (one per request — counted here because a same-step
        preemption folds out_tokens away before step()'s accounting)."""
        admitted = sorted(admitted, key=lambda r: len(r.seq_tokens))
        emitted = 0
        for i in range(0, len(admitted), self._b_pre):
            chunk = admitted[i:i + self._b_pre]
            bucket = self._bucket(max(len(r.seq_tokens) for r in chunk))
            pre, reshard = self._prefill_for(bucket)
            tokens = np.zeros((self._b_pre, bucket), np.int32)
            lengths = np.ones((self._b_pre,), np.int32)
            nb_bucket = bucket // self.cfg.block_size
            # scatter table: rows/blocks without a real target hit scratch
            tables = np.zeros((self._b_pre, nb_bucket), np.int32)
            tables[:, :] = self.cache.pool.scratch(0)
            for j, req in enumerate(chunk):
                seq = req.seq_tokens
                tokens[j, :len(seq)] = seq
                lengths[j] = len(seq)
                nb_req = self.cache.blocks_for(len(seq))
                tables[j, :nb_req] = req.block_ids[:nb_req]
            logits, pcache = pre.fn(self.params,
                                    {"tokens": tokens, "lengths": lengths})
            self.pool = reshard(self.pool, pcache, tables)
            temps, ks, ps, seeds = slot_arrays([r.sampling for r in chunk]
                                               + [SamplingParams()]
                                               * (self._b_pre - len(chunk)))
            toks = np.asarray(sample_tokens(logits, temps, ks, ps, seeds,
                                            lengths))
            ok = self._finite_rows(logits)
            now = self.clock()    # one stamp for the whole sampled batch
            for j, req in enumerate(chunk):
                if not ok[j]:
                    # poisoned prefill: quarantine just this request; its
                    # pages are freed and a later re-prefill replays it
                    self._quarantine(req)
                    continue
                req.num_cached = len(req.seq_tokens)
                req.prefill_high = max(req.prefill_high, req.num_cached)
                tok = int(toks[j])
                req.out_tokens.append(tok)
                req.last_token = tok
                self._record_emit(req, now)
                emitted += 1
            self.stats.prefills += 1
        # a prefilled request may already be done (max_new_tokens == 1 after
        # a late preemption, or eos right away)
        for req in admitted:
            if req.state == RUNNING and req.finished:
                self.sched.retire(req)
        return emitted

    # ---------------------------------------------------- chunked prefill
    def _chunk_width(self, remaining: int) -> int:
        """Chunked-prefill width: the configured chunk, or (auto, when the
        prefix cache turned chunking on) the smallest power-of-two multiple
        of block_size covering the longest pending suffix, capped at the
        pool's maximum resident length."""
        if self.cfg.prefill_chunk > 0:
            return self.cfg.prefill_chunk
        cap = self.cache.max_blocks * self.cfg.block_size
        c = self.cfg.block_size
        while c < remaining and c < cap:
            c = min(c * 2, cap)
        return c

    def _chunk_for(self, width: int):
        if width not in self._chunk_bundles:
            self._chunk_bundles[width] = build_chunk_prefill_step(
                self.model, self.mesh, self.cfg.n_slots, width,
                self.cfg.num_blocks, self.cfg.block_size,
                self.cache.max_blocks)
        return self._chunk_bundles[width]

    def _apply_prefix_hits(self, admitted) -> None:
        """Consume the PrefixHit the scheduler attached at admission: count
        reuse, copy the COW donor page into the request's first private
        block, and mark the shared prefix as already materialized so the
        chunked prefill starts at the divergence point."""
        for req in admitted:
            hit = req.prefix_hit
            self.stats.prefix_lookups += 1
            # Once-per-request token accounting (ISSUE 9 satellite): a
            # request evicted mid-chunk-prefill re-enters admission with
            # the same prompt positions — counting them again would
            # double-count the replayed work in prefix_tokens_total (and
            # let reuse of positions this request itself already paid for
            # inflate the hit rate past 1).  prefill_counted is the
            # per-request high-water mark of positions already counted;
            # only growth beyond it is new.
            seq_len = len(req.seq_tokens)
            self.stats.prefix_tokens_total += max(
                0, seq_len - req.prefill_counted)
            if hit is None or hit.tokens == 0:
                req.prefill_counted = max(req.prefill_counted, seq_len)
                continue
            if hit.cow_len:
                # the suffix prefill overwrites positions >= cow_len; the
                # causal mask hides the stale donor tail until then
                dst = req.block_ids[len(hit.full_blocks)]
                self.pool = self._page_copy(
                    self.pool, np.array([hit.cow_src], np.int32),
                    np.array([dst], np.int32))
                self.stats.cow_splits += 1
            req.num_cached = hit.tokens
            self.stats.prefix_hits += 1
            self.stats.prefix_tokens_reused += max(
                0, hit.tokens - req.prefill_counted)
            req.prefill_counted = max(req.prefill_counted, seq_len)

    def _run_chunk_prefills(self) -> int:
        """One fixed-shape chunked-prefill step for every mid-prefill slot
        (running requests with no last_token yet).  Interleaves with
        decode: each engine step advances every pending prompt by one
        chunk, then the decode batch runs for the slots that already hold
        a token.  Prompts that complete this chunk sample their first
        token (at position len(seq), like the monolithic prefill) and are
        indexed into the radix tree."""
        pending = [r for r in self.sched.running if r.last_token is None]
        if not pending:
            return 0
        n = self.cfg.n_slots
        width = self._chunk_width(
            max(len(r.seq_tokens) - r.num_cached for r in pending))
        ids = np.zeros((n, width), np.int32)
        pos = np.zeros((n,), np.int32)
        lens = np.zeros((n,), np.int32)
        slot_blocks = [[] for _ in range(n)]
        groups = [self.sched.group_of_slot(s) for s in range(n)]
        samplings = [SamplingParams()] * n
        take = {}
        for req in pending:
            s = req.slot
            seq = req.seq_tokens
            t = min(width, len(seq) - req.num_cached)
            ids[s, :t] = seq[req.num_cached:req.num_cached + t]
            pos[s] = req.num_cached
            lens[s] = t
            slot_blocks[s] = req.block_ids
            samplings[s] = req.sampling
            take[req.rid] = t
        tables = self.cache.make_table(slot_blocks, groups)
        bundle = self._chunk_for(width)
        logits, self.pool = bundle.fn(self.params, self.pool, tables,
                                      pos, lens, ids)
        # A chunk step counts as prefill work only when some slot advances
        # past its prefill_high watermark: a slot evicted mid-prefill and
        # re-admitted REPLAYS positions it already materialized once
        # (restarting from the prefix-cache hit point), and those replayed
        # chunks must not double-count (ISSUE 9 satellite).
        if any(req.num_cached + take[req.rid] > req.prefill_high
               for req in pending):
            self.stats.prefill_chunks += 1
        finishing = [r for r in pending
                     if r.num_cached + take[r.rid] == len(r.seq_tokens)]
        emitted = 0
        if finishing:
            ok = self._finite_rows(logits)
            temps, ks, ps, seeds = slot_arrays(samplings)
            toks = np.asarray(sample_tokens(logits, temps, ks, ps, seeds,
                                            pos + lens))
            # stamp ONCE at the completing chunk's sampled tokens — before
            # the per-slot retire/radix-insert bookkeeping below, so later
            # slots' TTFT doesn't absorb earlier slots' host work
            now = self.clock()
        for req in pending:
            if req not in finishing:
                req.num_cached += take[req.rid]
                req.prefill_high = max(req.prefill_high, req.num_cached)
                continue
            if not ok[req.slot]:
                # poisoned chunk: quarantine just this request (bounded
                # re-prefill replay); every other slot proceeds
                self._quarantine(req)
                continue
            req.num_cached = len(req.seq_tokens)
            req.prefill_high = max(req.prefill_high, req.num_cached)
            if self.prefix is not None:
                # only fully-covered prompt blocks are indexed (insert
                # stops at len // block_size), so decode's appends at
                # positions >= len(seq) never touch a shared page
                self.prefix.insert(groups[req.slot], req.seq_tokens,
                                   req.block_ids)
            tok = int(toks[req.slot])
            req.out_tokens.append(tok)
            req.last_token = tok
            self._record_emit(req, now)
            emitted += 1
        for req in finishing:
            if req.state == RUNNING and req.finished:
                self.sched.retire(req)
        return emitted

    # ------------------------------------------------------ fault plumbing
    def _exhaust_pool(self, idx: int, hold_steps: int) -> None:
        """Injected KV-pool exhaustion: take every free block hostage for
        ``hold_steps`` engine steps (drives the preemption-storm -> batch-
        shrink recovery path)."""
        held = []
        for g in range(self.cache.n_groups):
            n = self.cache.pool.available(g)
            if n:
                held.extend(self.cache.pool.alloc(g, n))
        self._hostage = {"blocks": held, "until": idx + max(1, hold_steps)}
        self.stats.pool_exhaust_events += 1

    def _release_hostages(self, idx: int) -> None:
        if self._hostage is not None and idx >= self._hostage["until"]:
            self.cache.pool.free(self._hostage["blocks"])
            self._hostage = None

    def _fire_step_faults(self, idx: int):
        """Run the serve.step injections due at engine step ``idx``;
        returns True when this iteration is dropped entirely."""
        dropped = False
        for spec in self.injector.fire("serve.step", idx):
            if spec.kind == "drop_step":
                dropped = True
            elif spec.kind == "straggler":
                time.sleep(spec.arg)
            elif spec.kind == "pool_exhaust":
                self._exhaust_pool(idx, int(spec.arg))
            elif spec.kind == "device_loss":
                print(f"[fault] serve step {idx}: device loss -> replan to "
                      f"{int(spec.arg)} devices")
                self.replan_to(int(spec.arg))
        for spec in self.injector.fire("serve.prefix", idx):
            if self.prefix is None:
                continue
            if spec.kind == "flush":
                n = self.prefix.flush()
                print(f"[fault] serve step {idx}: prefix-cache flush "
                      f"dropped {n} pages")
            elif spec.kind == "evict":
                # forced eviction pressure: only refcount-1 leaves may go,
                # so pages shared with running requests must survive this
                want = max(1, int(spec.arg))
                for g in range(self.cache.n_groups):
                    self.prefix.evict(g, want)
        return dropped

    def _poison_logits(self, logits, idx: int):
        """serve.logits injections: overwrite the target slot's logit row
        with NaN/Inf (what a flaky accelerator hands the sampler)."""
        specs = self.injector.fire("serve.logits", idx)
        if not specs:
            return logits
        lg = np.array(logits)
        for spec in specs:
            lg[int(spec.arg) % lg.shape[0]] = \
                np.nan if spec.kind == "nan" else np.inf
        return lg

    def _update_health(self) -> None:
        degraded = (self.sched.max_active < self.cfg.n_slots
                    or self._hostage is not None)
        self.stats.health = "degraded" if degraded else "healthy"
        if self.prefix is not None:
            self.stats.cache_evictions = (self._evict_carry
                                          + self.prefix.evictions)

    # --------------------------------------------------------- speculation
    def _judge(self, req, rows, toks, proposals):
        """Accept/reject one slot's verify rows -> (committed, n_accepted).

        rows: [W, v_pad] target logits (row c governs position
        num_cached + c + 1); toks: [W] the plain sampler's draw at each
        row's position (the identical jitted code path plain decode uses,
        so greedy acceptance is bit-exact by construction); proposals:
        the judged draft tokens.

        Greedy: accept while proposal c equals the argmax draw, commit the
        first mismatching draw as the correction, bonus-commit the final
        row's draw on full acceptance.  temperature > 0: Leviathan
        rejection sampling against the post-mask target distribution
        (point-mass proposals — the draft proposes greedily), residual
        resampling on rejection; the committed token at every position is
        marginally EXACTLY the plain sampler's distribution."""
        if req.sampling.temperature <= 0.0:
            committed, m = [], 0
            for c, d in enumerate(proposals):
                t = int(toks[c])
                committed.append(t)
                if t != int(d):
                    return committed, m
                m += 1
            committed.append(int(toks[len(proposals)]))
            return committed, m
        if not proposals:
            return [int(toks[0])], 0
        sp = req.sampling
        probs = np.asarray(spec_target_probs(
            np.asarray(rows[:len(proposals)]), sp.temperature, sp.top_k,
            sp.top_p))
        committed, m = spec_accept(probs, proposals, None, sp.seed,
                                   req.num_cached)
        if m == len(proposals):
            committed.append(int(toks[len(proposals)]))
        return committed, m

    def _spec_round(self, running, idx: int):
        """One speculative decode round: propose k tokens per slot, verify
        them all in ONE batched multi-token forward over the block tables,
        commit the accepted prefix (+1 correction/bonus token) in place.

        Rollback is implicit: a rejected suffix's K/V stays in the pool
        but num_cached never advances past the rejection point, so it is
        masked by position and overwritten by the next round's writes —
        the same replay argument the scheduler's eviction parity proves.
        Returns the [(rid, token)] list step() reports."""
        n = self.cfg.n_slots
        W = self.cfg.spec_k + 1
        groups = [self.sched.group_of_slot(s) for s in range(n)]
        slot_blocks = [[] for _ in range(n)]
        for r in running:
            slot_blocks[r.slot] = r.block_ids
        tables = self.cache.make_table(slot_blocks, groups)
        k_eff = {r.rid: max(0, r.spec_lookahead - 1) for r in running}
        if self._draft is not None:
            props = self._draft.propose(running, tables, k_eff)
        else:
            props = {r.rid: self._ngram.propose(r.seq_tokens,
                                                k_eff[r.rid])
                     for r in running}
        ids = np.zeros((n, W), np.int32)
        pos = np.zeros((n,), np.int32)
        lens = np.zeros((n,), np.int32)
        samplings = [SamplingParams()] * n
        for r in running:
            s = r.slot
            pr = [int(t) for t in props[r.rid][:k_eff[r.rid]]]
            props[r.rid] = pr
            ids[s, 0] = r.last_token
            if pr:
                ids[s, 1:1 + len(pr)] = pr
            pos[s] = r.num_cached
            lens[s] = 1 + len(pr)
            samplings[s] = r.sampling
        logits, self.pool = self._verify.fn(self.params, self.pool, tables,
                                            pos, lens, ids)
        if self.injector is not None:
            logits = self._poison_logits(logits, idx)
        ok = self._finite_rows(logits)
        lg = np.asarray(logits)                       # [n, W, v_pad]
        temps, ks, ps, seeds = slot_arrays(samplings)
        posmat = pos[:, None] + 1 + np.arange(W, dtype=np.int32)[None, :]
        toks = np.asarray(sample_tokens(
            lg.reshape(n * W, -1), np.repeat(temps, W), np.repeat(ks, W),
            np.repeat(ps, W), np.repeat(seeds, W),
            posmat.reshape(-1))).reshape(n, W)
        now = self.clock()    # one stamp for the whole verified batch
        emitted = []
        for r in running:
            s = r.slot
            if not ok[s]:
                self._quarantine(r)
                continue
            pr = props[r.rid]
            committed, m_acc = self._judge(r, lg[s], toks[s], pr)
            self.stats.spec_proposed += len(pr)
            self.stats.spec_accepted += m_acc
            self.stats.spec_slot_rounds += 1
            n0 = r.num_cached
            for t in committed:
                r.num_cached += 1
                t = int(t)
                r.out_tokens.append(t)
                r.last_token = t
                self._record_emit(r, now)
                emitted.append((r.rid, t))
                self.stats.spec_committed += 1
                if r.finished:
                    break     # eos / budget: drop the committed tail
            # draft watermark: positions <= n0 + m_acc hold draft K/V for
            # the tokens actually committed; the correction token's
            # position does not (the draft wrote the REJECTED proposal
            # there), and position n0 + k_eff was never draft-written
            r.draft_cached = min(n0 + m_acc + 1, r.num_cached,
                                 n0 + k_eff[r.rid])
            if r.finished and r.state == RUNNING:
                if self.prefix is not None:
                    # accepted tokens that completed full blocks become
                    # shareable prefix pages; insert stops at
                    # len // block_size, and rolled-back proposals never
                    # enter seq_tokens, so a rejected branch is never
                    # indexed.  [:-1]: the final committed token's K/V is
                    # the never-written pending position — it must not
                    # land inside an indexed block.
                    self.prefix.insert(self.sched.group_of_slot(r.slot),
                                       r.seq_tokens[:-1], r.block_ids)
                self.sched.retire(r)
        self.stats.spec_rounds += 1
        return emitted

    # ---------------------------------------------------------------- step
    def step(self):
        """One engine iteration; returns [(rid, token)] emitted this step."""
        t0 = time.perf_counter()
        idx = self.stats.steps
        self._release_hostages(idx)
        dropped = (self._fire_step_faults(idx)
                   if self.injector is not None else False)
        self._shed_expired()
        if dropped:
            # a lost engine iteration: no admission, no decode — survivors
            # just resume next step (position-keyed sampling keeps parity)
            self.stats.dropped_steps += 1
            self.stats.steps += 1
            self.stats.wall += time.perf_counter() - t0
            self._update_health()
            return []
        admitted = self.sched.admit()
        if self.sched.admission_failures:
            self.stats.failed += len(self.sched.admission_failures)
            self.sched.admission_failures.clear()
        if self._chunked:
            if self.prefix is not None and admitted:
                self._apply_prefix_hits(admitted)
            prefill_emitted = self._run_chunk_prefills()
        else:
            prefill_emitted = self._run_prefills(admitted) if admitted else 0
        if self._spec_on:
            # declare this round's write window BEFORE capacity runs: the
            # k in-flight draft tokens per slot need resident pages
            for r in self.sched.running:
                if r.last_token is not None:
                    remaining = (r.sampling.max_new_tokens
                                 - len(r.generated))
                    r.spec_lookahead = 1 + max(
                        0, min(self.cfg.spec_k, remaining - 1))
        preempted = self.sched.ensure_decode_capacity()
        self.stats.preemptions += len(preempted)
        # mid-chunk-prefill requests (last_token still None) sit out the
        # decode batch; their slots degrade to scratch like retired ones
        running = [r for r in self.sched.running
                   if r.last_token is not None]
        emitted = []
        if running and self._spec_on:
            emitted = self._spec_round(running, idx)
        elif running:
            n = self.cfg.n_slots
            ids = np.zeros((n, 1), np.int32)
            pos = np.zeros((n,), np.int32)
            slot_blocks = [[] for _ in range(n)]
            groups = [self.sched.group_of_slot(s) for s in range(n)]
            samplings = [SamplingParams()] * n
            for req in running:
                s = req.slot
                ids[s, 0] = req.last_token
                pos[s] = req.num_cached
                slot_blocks[s] = req.block_ids
                samplings[s] = req.sampling
            tables = self.cache.make_table(slot_blocks, groups)
            logits, self.pool = self.dec.fn(self.params, self.pool, tables,
                                            pos, ids)
            if self.injector is not None:
                logits = self._poison_logits(logits, idx)
            ok = self._finite_rows(logits)
            temps, ks, ps, seeds = slot_arrays(samplings)
            toks = np.asarray(sample_tokens(logits, temps, ks, ps, seeds,
                                            pos + 1))
            now = self.clock()    # one stamp for the whole decode batch
            for req in running:
                if not ok[req.slot]:
                    # poisoned slot: quarantine ONLY this request (bounded
                    # re-prefill replay); every other slot proceeds
                    self._quarantine(req)
                    continue
                req.num_cached += 1
                tok = int(toks[req.slot])
                req.out_tokens.append(tok)
                req.last_token = tok
                self._record_emit(req, now)
                emitted.append((req.rid, tok))
                if req.finished:
                    self.sched.retire(req)
        # pool-OOM pressure control: repeated preemption storms shrink the
        # admission cap (graceful decode-batch shrink); calm steps grow it
        # back toward n_slots
        if preempted:
            self._oom_streak += 1
            self._calm_streak = 0
        else:
            self._calm_streak += 1
            self._oom_streak = 0
        if (self._oom_streak >= self.cfg.oom_shrink_after
                and self.sched.max_active > 1):
            self.sched.max_active -= 1
            self.stats.batch_shrinks += 1
            self._oom_streak = 0
        if (self._calm_streak >= self.cfg.oom_recover_after
                and self.sched.max_active < self.cfg.n_slots):
            self.sched.max_active += 1
            self._calm_streak = 0
        dt = time.perf_counter() - t0
        self.stats.steps += 1
        self.stats.wall += dt
        new_tokens = len(emitted) + prefill_emitted
        self.stats.tokens += new_tokens
        if new_tokens:
            self.stats.token_times.extend([dt / new_tokens] * new_tokens)
        self._update_health()
        return emitted

    def run(self, max_steps: int = 100000):
        """Drive until every request finishes; returns {rid: out_tokens} for
        every request this engine has ever accepted."""
        for _ in range(max_steps):
            if not self.sched.has_work:
                break
            self.step()
        else:
            raise RuntimeError("engine did not drain (stuck scheduler?)")
        return {r.rid: list(r.generated) for r in self.requests}

    # -------------------------------------------------------------- elastic
    def replan_to(self, n_devices: int):
        """Rebuild the mesh for ``n_devices`` and reshard live KV blocks.

        Uses runtime.elastic.replan (TP group is atomic; data shrinks),
        copies every running request's resident blocks into its new group's
        partition, and recompiles the serve steps.  Waiting requests and all
        request state survive untouched."""
        from ..core.mesh import logical_mesh
        from ..models.registry import build_model
        from ..runtime.elastic import replan

        rp = replan(n_devices, self.model.ctx,
                    global_batch=self.cfg.n_slots)
        # injected pool-exhaustion hostages hold OLD pool block ids — drop
        # them rather than freeing stale ids into the rebuilt pool
        self._hostage = None
        # cached page ids die with the old pool: the rebuilt cache starts
        # empty; its eviction count carries into the stats
        if self.prefix is not None:
            self._evict_carry += self.prefix.evictions
        old_sched = self.sched
        old_pool_np = {k: np.asarray(v) for k, v in self.pool.items()}
        params_np = jax.tree.map(np.asarray, self.params)

        self.model = build_model(self.model.cfg, rp.ctx, self.model.run)
        if self.draft_model is not None:
            # the draft rides the same mesh: rebuild it for the new ctx;
            # _build re-places its host params and zeroes its pool (draft
            # KV is disposable — watermarks reset, parity unaffected)
            self.draft_model = build_model(self.draft_model.cfg, rp.ctx,
                                           self.draft_model.run)
        self.mesh = logical_mesh(rp.ctx, jax.devices()[:rp.n_used])
        self._build()    # stats/requests survive (guarded init in _build)

        self.params = self._place_params(params_np)   # the shrunken mesh

        # carry scheduler state over; reallocate pages in the new groups.
        # The admit clock must carry too: carried residents keep their old
        # admit_seq, and a reset clock would make every post-replan
        # admission look "older" than them, inverting eviction priority.
        self.sched.waiting = old_sched.waiting
        self.sched._admit_clock = old_sched._admit_clock
        self.sched.max_active = old_sched.max_active
        self.sched.admission_failures = old_sched.admission_failures
        new_pool_np = {k: np.array(v) for k, v in self.pool.items()}
        for slot in range(min(len(old_sched.slots), self.cfg.n_slots)):
            req = old_sched.slots[slot]
            if req is None:
                continue
            g = self.sched.group_of_slot(slot)
            old_blocks = req.block_ids
            blocks = self.cache.pool.alloc(g, len(old_blocks))
            if blocks is None:
                # shrunken pool can't host it: evict + re-prefill later
                req.block_ids = []
                self.sched.preempt(req)
                continue
            for leaf in ("k", "v"):
                new_pool_np[leaf][:, blocks] = old_pool_np[leaf][:, old_blocks]
            req.block_ids = blocks
            req.slot = slot
            self.sched.slots[slot] = req
        self.pool = jax.tree.map(jax.device_put, new_pool_np,
                                 dict(self.cache.shardings()))
        return rp
