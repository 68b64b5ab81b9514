"""Serve subsystem tests (single device; multi-device engine parity lives in
tests/test_multidevice_serve.py via the serve_engine / engine_elastic mdchecks)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import RunConfig, ShapeSpec
from repro.core.api import ParallelContext
from repro.core.mesh import logical_mesh
from repro.core.summa import effective_schedule
from repro.models.registry import build_model, get_reduced
from repro.serve import (BlockPool, EngineConfig, InferenceEngine,
                         SamplingParams)
from repro.serve.sampling import mask_top_k, mask_top_p, sample_tokens

CTX = ParallelContext(mode="tesseract", data=1, depth=1, rows=1, cols=1)
RUN = RunConfig(param_dtype="float32", compute_dtype="float32", loss_chunk=16,
                q_chunk=8, kv_chunk=8)


@pytest.fixture(scope="module")
def setup():
    arch = get_reduced("yi-6b")
    mesh = logical_mesh(CTX)
    model = build_model(arch.model, CTX, RUN)
    params = model.init(jax.random.PRNGKey(0))
    return mesh, model, params


def _prompts(seed=0, lens=(5, 9, 16, 12)):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, 250, (l,)).tolist() for l in lens]


# ---------------------------------------------------------------------------
# block pool / table accounting
# ---------------------------------------------------------------------------

def test_block_pool_accounting():
    pool = BlockPool(n_groups=2, blocks_per_group=4)
    assert pool.available(0) == pool.capacity(0) == 3
    assert pool.scratch(0) == 0 and pool.scratch(1) == 4
    got = pool.alloc(0, 2)
    assert got == [1, 2] and pool.available(0) == 1
    assert pool.alloc(0, 2) is None          # doesn't fit -> no partial alloc
    assert pool.available(0) == 1
    assert pool.alloc(1, 3) == [5, 6, 7]
    pool.free([2, 5])
    assert pool.available(0) == 2 and pool.available(1) == 1
    with pytest.raises(ValueError):
        pool.free([2])                        # double free
    with pytest.raises(ValueError):
        pool.free([0])                        # scratch is not freeable
    with pytest.raises(ValueError):
        BlockPool(n_groups=1, blocks_per_group=1)


def test_block_table_gather_roundtrip(setup):
    """paged_update writes and paged_gather reads through the same table:
    scattering a sequence block-by-block then gathering returns it exactly."""
    from repro.models.common import paged_gather, paged_update
    rng = np.random.RandomState(1)
    P_loc, bs, H, D, B, nb = 9, 4, 2, 8, 2, 3
    pool = {"k": jnp.zeros((P_loc, bs, H, D), jnp.float32),
            "v": jnp.zeros((P_loc, bs, H, D), jnp.float32)}
    # non-trivial tables: interleaved, out-of-order physical blocks
    table = jnp.array([[3, 1, 6], [2, 8, 4]], jnp.int32)
    ks = rng.randn(B, nb * bs, H, D).astype(np.float32)
    vs = rng.randn(B, nb * bs, H, D).astype(np.float32)
    for pos in range(nb * bs):
        pool = paged_update(pool, table, jnp.full((B,), pos, jnp.int32),
                            jnp.asarray(ks[:, pos:pos + 1]),
                            jnp.asarray(vs[:, pos:pos + 1]))
    k, v = paged_gather(pool["k"], pool["v"], table)
    np.testing.assert_array_equal(np.asarray(k), ks)
    np.testing.assert_array_equal(np.asarray(v), vs)


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------

def test_sampler_masks():
    lg = jnp.array([0.0, 3.0, 1.0, 2.0, -1.0])
    topk = np.asarray(mask_top_k(lg, 2))
    assert np.isfinite(topk[[1, 3]]).all() and np.isneginf(topk[[0, 2, 4]]).all()
    assert np.array_equal(np.asarray(mask_top_k(lg, 0)), np.asarray(lg))
    # top-p: probs ~ [.09 .66 .24 ...]; p=0.5 keeps only the top token,
    # p=0.95 keeps top-3
    topp = np.asarray(mask_top_p(lg, 0.5))
    assert np.isfinite(topp[1]) and np.isneginf(topp[[0, 2, 4]]).all()
    topp3 = np.asarray(mask_top_p(lg, 0.95))
    assert np.isfinite(topp3[[1, 2, 3]]).all()
    assert np.array_equal(np.asarray(mask_top_p(lg, 1.0)), np.asarray(lg))


def test_sampler_greedy_and_determinism():
    rng = np.random.RandomState(0)
    logits = jnp.asarray(rng.randn(4, 32).astype(np.float32))
    zeros = jnp.zeros((4,))
    t0 = sample_tokens(logits, zeros, jnp.zeros((4,), jnp.int32),
                       jnp.ones((4,)), jnp.zeros((4,), jnp.int32),
                       jnp.arange(4, dtype=jnp.int32))
    np.testing.assert_array_equal(np.asarray(t0),
                                  np.argmax(np.asarray(logits), -1))
    temps = jnp.full((4,), 0.7)
    seeds = jnp.array([1, 1, 2, 2], jnp.int32)
    pos = jnp.array([5, 5, 5, 9], jnp.int32)
    s1 = np.asarray(sample_tokens(logits, temps, jnp.zeros((4,), jnp.int32),
                                  jnp.ones((4,)), seeds, pos))
    s2 = np.asarray(sample_tokens(logits, temps, jnp.zeros((4,), jnp.int32),
                                  jnp.ones((4,)), seeds, pos))
    np.testing.assert_array_equal(s1, s2)   # same (seed, position) -> same
    # row 2 and 3: same logits/seed, different position -> streams decouple
    assert s1.shape == (4,)


# ---------------------------------------------------------------------------
# paged vs dense cache
# ---------------------------------------------------------------------------

def test_paged_vs_dense_kv_equality(setup):
    """Prefill cache resharded into the paged pool must hold exactly the
    same K/V per layer as the dense decode-layout reshard."""
    mesh, model, params = setup
    from repro.runtime.steps import (build_dense_cache_reshard,
                                     build_paged_reshard, build_prefill_step,
                                     make_plan)
    B, S_p, S_tot, bs = 4, 16, 32, 4
    pshape = ShapeSpec("p", S_p, B, "prefill")
    pre = build_prefill_step(model, mesh, pshape)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (B, S_p), 0, 250)
    _, pcache = pre.fn(params, {"tokens": prompts})

    dense_fn, dplan = build_dense_cache_reshard(model, mesh, pshape, S_tot)
    dense = dense_fn(pcache)

    nb, num_blocks = S_p // bs, 64
    paged_fn = build_paged_reshard(model, mesh, B, S_p, num_blocks, bs, dplan)
    pool_sds, _ = model.paged_cache_abstract(num_blocks, bs, dplan)
    pool = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), pool_sds)
    tables = np.arange(1, 1 + B * nb, dtype=np.int32).reshape(B, nb)
    pool = paged_fn(pool, pcache, jnp.asarray(tables))

    for leaf in ("k", "v"):
        paged = np.asarray(pool[leaf])      # [L, P, bs, H, D]
        want = np.asarray(dense[leaf])      # [L, B, S_tot, H, D]
        for b in range(B):
            got = paged[:, tables[b]].reshape(want.shape[0], S_p,
                                              *want.shape[3:])
            np.testing.assert_array_equal(got, want[:, b, :S_p],
                                          err_msg=f"{leaf} req {b}")
        # the pool's scratch block (0) stayed untouched
        np.testing.assert_array_equal(paged[:, 0], 0.0)


def test_paged_decode_writes_match_dense(setup):
    """Teacher-forced paged decode vs dense decode: per-layer K/V written to
    the pages match the dense cache to float tolerance, tokens bitwise."""
    mesh, model, params = setup
    from repro.runtime.steps import build_decode_step, build_paged_decode_step
    B, S, bs, T = 4, 16, 4, 6
    toks = np.asarray(
        jax.random.randint(jax.random.PRNGKey(3), (B, T), 0, 250), np.int32)

    dec = build_decode_step(model, mesh, ShapeSpec("d", S, B, "decode"))
    cache_sds, _ = model.cache_abstract(B, S, dec.plan)
    dense = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), cache_sds)
    dense_ids = []
    for t in range(T):
        nxt, dense = dec.fn(params, dense, jnp.asarray(toks[:, t:t + 1]),
                            jnp.int32(t))
        dense_ids.append(np.asarray(nxt).ravel())

    num_blocks, nb = 32, S // bs
    pdec = build_paged_decode_step(model, mesh, B, num_blocks, bs, nb)
    pool_sds, _ = model.paged_cache_abstract(num_blocks, bs, pdec.plan)
    pool = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), pool_sds)
    tables = np.arange(1, 1 + B * nb, dtype=np.int32).reshape(B, nb)
    paged_ids = []
    for t in range(T):
        logits, pool = pdec.fn(params, pool, jnp.asarray(tables),
                               jnp.full((B,), t, jnp.int32),
                               jnp.asarray(toks[:, t:t + 1]))
        paged_ids.append(np.argmax(np.asarray(logits), -1))
    np.testing.assert_array_equal(np.stack(paged_ids), np.stack(dense_ids))

    for leaf in ("k", "v"):
        paged = np.asarray(pool[leaf])
        want = np.asarray(dense[leaf])
        for b in range(B):
            got = paged[:, tables[b]].reshape(want.shape[0], S,
                                              *want.shape[3:])
            np.testing.assert_allclose(got[:, :T], want[:, b, :T],
                                       rtol=1e-5, atol=1e-6,
                                       err_msg=f"{leaf} req {b}")


# ---------------------------------------------------------------------------
# engine behaviour
# ---------------------------------------------------------------------------

def test_engine_greedy_matches_full_forward(setup):
    from repro.testing.mdchecks import full_forward_argmax
    mesh, model, params = setup
    prompts = _prompts(seed=2, lens=(5, 12))
    n_new = [5, 4]
    eng = InferenceEngine(model, mesh, params, EngineConfig(
        n_slots=2, block_size=4, num_blocks=32, max_seq_len=64))
    reqs = [eng.add_request(p, SamplingParams(max_new_tokens=n))
            for p, n in zip(prompts, n_new)]
    res = eng.run()
    for p, n, r in zip(prompts, n_new, reqs):
        want = full_forward_argmax(model, mesh, params, p, n)
        assert res[r.rid] == want, (res[r.rid], want)


def test_engine_eviction_reprefill_parity(setup):
    """A pool too small for the concurrent residents forces eviction +
    re-prefill; tokens must match the pressure-free run exactly."""
    mesh, model, params = setup
    prompts = _prompts(seed=4, lens=(5, 9, 16, 12, 7, 3, 21, 10))
    n_new = [6, 10, 4, 8, 5, 12, 3, 7]

    def run_with(num_blocks):
        eng = InferenceEngine(model, mesh, params, EngineConfig(
            n_slots=4, block_size=4, num_blocks=num_blocks, max_seq_len=64))
        reqs = [eng.add_request(p, SamplingParams(max_new_tokens=n))
                for p, n in zip(prompts, n_new)]
        res = eng.run()
        return [res[r.rid] for r in reqs], eng.stats

    ample, _ = run_with(64)
    tight, stats = run_with(9)
    assert stats.preemptions > 0, "tiny pool never triggered eviction"
    assert tight == ample
    assert all(len(t) == n for t, n in zip(tight, n_new))


def test_engine_rejects_impossible_request(setup):
    mesh, model, params = setup
    eng = InferenceEngine(model, mesh, params, EngineConfig(
        n_slots=2, block_size=4, num_blocks=8, max_seq_len=64))
    with pytest.raises(ValueError):
        eng.add_request(list(range(1, 30)), SamplingParams(max_new_tokens=8))


def test_engine_mixed_sampling_modes(setup):
    """Greedy and stochastic requests coexist in one batch; greedy rows are
    unaffected by their neighbours' sampling."""
    mesh, model, params = setup
    prompts = _prompts(seed=6, lens=(6, 6))

    eng = InferenceEngine(model, mesh, params, EngineConfig(
        n_slots=2, block_size=4, num_blocks=32, max_seq_len=64))
    g = eng.add_request(prompts[0], SamplingParams(max_new_tokens=5))
    s = eng.add_request(prompts[1], SamplingParams(
        temperature=0.8, top_k=20, top_p=0.9, seed=11, max_new_tokens=5))
    res = eng.run()

    eng2 = InferenceEngine(model, mesh, params, EngineConfig(
        n_slots=2, block_size=4, num_blocks=32, max_seq_len=64))
    g2 = eng2.add_request(prompts[0], SamplingParams(max_new_tokens=5))
    res2 = eng2.run()
    assert res[g.rid] == res2[g2.rid]
    assert len(res[s.rid]) == 5


# ---------------------------------------------------------------------------
# auto matmul schedule
# ---------------------------------------------------------------------------

def test_effective_schedule_resolution():
    base = dict(mode="tesseract", data=1, depth=1)
    q4 = ParallelContext(rows=4, cols=4, matmul_schedule="auto", **base)
    q2 = ParallelContext(rows=2, cols=2, matmul_schedule="auto", **base)
    assert effective_schedule(q4, 512) == "ring"     # train-sized block
    assert effective_schedule(q4, 2) == "fused"      # decode-sized block
    assert effective_schedule(q2, 512) == "fused"    # q=2: fused wins (§2b)
    ring = ParallelContext(rows=2, cols=2, matmul_schedule="ring", **base)
    assert effective_schedule(ring, 2) == "ring"     # explicit wins
    with pytest.raises(ValueError):
        ParallelContext(mode="megatron1d", cols=4, matmul_schedule="auto")
    with pytest.raises(ValueError):
        ParallelContext(matmul_schedule="bogus")


# ---------------------------------------------------------------------------
# scheduler edge cases under a fully exhausted block pool
# ---------------------------------------------------------------------------

class _FakeCache:
    """Minimal PagedKVCache stand-in for scheduler-only tests: real
    BlockPool freelists and block math, no device arrays."""

    def __init__(self, n_groups=1, blocks_per_group=5, block_size=4,
                 max_seq_len=64):
        self.n_groups = n_groups
        self.block_size = block_size
        self.max_seq_len = max_seq_len
        self.pool = BlockPool(n_groups=n_groups,
                              blocks_per_group=blocks_per_group)

    def blocks_for(self, n):
        return -(-n // self.block_size)

    def fits(self, n):
        return (n <= self.max_seq_len
                and self.blocks_for(n) <= self.pool.capacity(0))


def _sreq(plen, new=4, rid=None):
    from repro.serve.scheduler import Request
    return Request(list(range(1, plen + 1)),
                   SamplingParams(max_new_tokens=new), rid=rid)


def test_scheduler_zero_free_blocks_blocks_admission():
    from repro.serve.scheduler import Scheduler
    cache = _FakeCache(blocks_per_group=5)       # capacity 4 (1 scratch)
    sched = Scheduler(cache, n_slots=2)
    a = sched.add(_sreq(12, new=4))              # blocks_for(13) = 4: all
    assert sched.admit() == [a]
    assert cache.pool.available(0) == 0
    b = sched.add(_sreq(3, new=1))
    assert sched.admit() == []                   # zero free blocks: b waits
    assert b.state == "waiting" and b in sched.waiting


def test_scheduler_single_request_pool_self_evicts():
    """The only resident of a group that must grow into a dry freelist is
    its own eviction victim: it preempts ITSELF (blocks freed, trajectory
    folded for re-prefill) instead of deadlocking."""
    from repro.serve.scheduler import Scheduler
    cache = _FakeCache(blocks_per_group=5)       # capacity 4
    sched = Scheduler(cache, n_slots=1)
    a = sched.add(_sreq(12, new=4))              # target 16 = exactly 4 blk
    assert sched.admit() == [a]
    a.num_cached = 16                            # blocks full to the brim
    preempted = sched.ensure_decode_capacity()
    assert preempted == [a] and a.state == "waiting"
    assert a.block_ids == [] and a.slot is None
    assert sched.slots == [None]
    assert cache.pool.available(0) == 4          # everything back on free
    assert sched.waiting[0] is a                 # front of queue (replay)


def test_scheduler_retire_while_preempting():
    """Growth preempts the youngest co-resident; retiring the survivor
    right after must keep the freelist consistent (no double free) and let
    the evicted request re-admit."""
    from repro.serve.scheduler import Scheduler
    cache = _FakeCache(blocks_per_group=7)       # capacity 6
    sched = Scheduler(cache, n_slots=2)
    a = sched.add(_sreq(8, new=8))               # blocks_for(9) = 3
    assert sched.admit() == [a]
    b = sched.add(_sreq(8, new=8))               # 3 more: freelist dry
    assert sched.admit() == [b]
    assert cache.pool.available(0) == 0
    a.num_cached = 12                            # a must grow; b is younger
    preempted = sched.ensure_decode_capacity()
    assert preempted == [b] and b.state == "waiting" and b.block_ids == []
    assert len(a.block_ids) == 4                 # grew into b's freed pages
    sched.retire(a)
    assert a.state == "finished" and a.block_ids == [] and a.slot is None
    assert cache.pool.available(0) == 6          # full pool back, no leaks
    assert sched.admit() == [b]                  # evictee re-admits cleanly
    with pytest.raises(ValueError):              # double free still guarded
        cache.pool.free([b.block_ids[0], b.block_ids[0]])


def test_scheduler_fails_unresidentable_prompt_at_admission():
    """A waiting request whose prompt can never fit the (possibly shrunken)
    pool is FAILED at admission with a clear reason instead of wedging the
    engine loop forever."""
    from repro.serve.scheduler import Scheduler
    cache = _FakeCache(blocks_per_group=9)          # capacity 8
    sched = Scheduler(cache, n_slots=1)
    r = sched.add(_sreq(28, new=2))                 # blocks_for(29) = 8: ok
    # elastic shrink rebuilt a smaller pool under the same waiting queue
    sched.cache = _FakeCache(blocks_per_group=5)    # capacity 4
    assert sched.admit() == []
    assert r.state == "failed"
    assert "never be resident" in r.fail_reason
    assert sched.admission_failures == [r]
    assert not sched.waiting                        # queue drains cleanly


# ---------------------------------------------------------------------------
# prefill buckets / pool refcounts / radix prefix cache
# ---------------------------------------------------------------------------

def test_engine_bucket_edges(setup):
    """_bucket: length 1 -> smallest bucket; exact power-of-two boundaries
    stay put; anything above the largest bucket clamps to the pool cap."""
    mesh, model, params = setup
    eng = InferenceEngine(model, mesh, params, EngineConfig(
        n_slots=2, block_size=4, num_blocks=32, max_seq_len=64))
    base = 4                              # lcm(block_size=4, seq_div=1)
    assert eng._bucket(1) == base
    assert eng._bucket(base) == base      # boundary: no spill to next bucket
    assert eng._bucket(base + 1) == 2 * base
    assert eng._bucket(2 * base) == 2 * base
    cap = -(-eng.cache.max_blocks * 4 // base) * base
    assert eng._bucket(10 ** 6) == cap    # above the largest bucket


def test_block_pool_refcount_roundtrip():
    """ref/free round-trips: a page returns to the freelist only when the
    LAST holder releases it; over-release and ref-of-free are guarded."""
    pool = BlockPool(n_groups=1, blocks_per_group=6)     # capacity 5
    a, b = pool.alloc(0, 2)
    assert pool.refcount(a) == 1
    pool.ref([a])                         # second holder
    assert pool.refcount(a) == 2
    pool.free([a])                        # first release: still resident
    assert pool.refcount(a) == 1 and pool.available(0) == 3
    pool.free([a])                        # last release: back on freelist
    assert pool.refcount(a) == 0 and pool.available(0) == 4
    with pytest.raises(ValueError):
        pool.free([a])                    # over-release
    with pytest.raises(ValueError):
        pool.ref([a])                     # ref of an unallocated page
    pool.free([b])


def test_prefix_cache_cow_split_leaves_donor_intact():
    """A divergent prompt gets the cached block as a COW *donor*; the
    donor page itself is never freed or mutated while cached, and eviction
    only ever reclaims refcount-1 leaves."""
    from repro.serve import RadixPrefixCache
    pool = BlockPool(n_groups=1, blocks_per_group=8)     # capacity 7
    pc = RadixPrefixCache(pool, block_size=4)
    prompt = list(range(12))                             # 3 full blocks
    blocks = pool.alloc(0, 3)
    pc.insert(0, prompt, blocks)                         # cache holds too
    assert [pool.refcount(x) for x in blocks] == [2, 2, 2]
    pool.free(blocks)                                    # request retires
    assert [pool.refcount(x) for x in blocks] == [1, 1, 1]

    # shares 1 full block, then 2 tokens into the second cached block
    q = [0, 1, 2, 3, 4, 5, 99, 98, 97]
    hit = pc.lookup(0, q, len(q) - 1)
    assert hit.tokens == 6
    assert hit.full_blocks == blocks[:1]
    assert hit.cow_src == blocks[1] and hit.cow_len == 2

    pool.ref(hit.full_blocks)                            # request's hold
    freed = pc.evict(0, 10, protect={hit.cow_src})
    assert freed == 1                     # only the cold rc-1 leaf went
    assert pool.refcount(blocks[0]) == 2  # shared with the request: intact
    assert pool.refcount(blocks[1]) == 1  # protected donor: intact
    assert pool.refcount(blocks[2]) == 0  # the evicted leaf

    pool.free(hit.full_blocks)
    assert pc.flush() == 2                # drops the two remaining nodes
    assert pool.available(0) == pool.capacity(0)


# ---------------------------------------------------------------------------
# nucleus boundary + speculative decoding (ISSUE 9)
# ---------------------------------------------------------------------------

def test_mask_top_p_boundary_cases():
    """Regression for the nucleus boundary: the first token whose cumulative
    probability crosses p is kept, exact cumsum edges don't flip, ties at
    equal logits break toward the smaller vocab id, and the support is
    never empty."""
    # equal logits -> exactly uniform probs (0.25 is exact in binary), so
    # every p below sits exactly on a cumsum edge
    lg = jnp.zeros((4,))
    for p, keep_n in ((0.25, 1), (0.5, 2), (0.75, 3), (1.0, 4)):
        out = np.asarray(mask_top_p(lg, p))
        assert np.isfinite(out).sum() == keep_n, (p, out)
        assert np.isfinite(out[:keep_n]).all()      # smaller ids win ties
    # p = 0 degenerates to greedy (top token kept), not an empty support
    lg2 = jnp.array([0.0, 3.0, 1.0, 2.0, -1.0])
    out0 = np.asarray(mask_top_p(lg2, 0.0))
    assert np.isfinite(out0[1]) and np.isneginf(np.delete(out0, 1)).all()
    # all mass on one token: nothing else ever crosses p < 1
    out3 = np.asarray(mask_top_p(jnp.array([50.0, 0.0, 0.0]), 0.999))
    assert np.isfinite(out3[0]) and np.isneginf(out3[1:]).all()
    # tied logits: the smaller vocab id of the tie is the one kept
    out4 = np.asarray(mask_top_p(jnp.array([1.0, 2.0, 2.0, 0.0]), 0.3))
    assert np.isfinite(out4[1]) and np.isneginf(out4[2])
    # p >= 1 keeps everything bit-identically
    np.testing.assert_array_equal(np.asarray(mask_top_p(lg2, 1.0)),
                                  np.asarray(lg2))


def test_spec_rejection_sampling_distribution():
    """Leviathan accept/reject with a point-mass proposal commits tokens
    marginally distributed EXACTLY as the plain sampler draws them:
    empirical TV distance to the target distribution vanishes."""
    from repro.serve.sampling import spec_accept, spec_target_probs
    rng = np.random.RandomState(0)
    V = 8
    logits = (rng.randn(1, V) * 2.0).astype(np.float32)
    target = np.asarray(spec_target_probs(jnp.asarray(logits),
                                          0.8, 0, 0.9))[0]
    N = 2500
    counts = np.zeros(V)
    d = int(np.argsort(target)[-2])   # a plausible but not top proposal
    for i in range(N):
        toks, _ = spec_accept(target[None, :], [d], None, seed=17, pos0=i)
        counts[toks[0]] += 1
    tv = 0.5 * np.abs(counts / N - target).sum()
    assert tv < 0.05, (tv, counts / N, target)
    # an out-of-nucleus proposal (p[d] == 0) is always rejected and the
    # correction still follows the target
    d0 = int(np.argmin(target))
    if target[d0] == 0.0:
        toks, n_acc = spec_accept(target[None, :], [d0], None, seed=3,
                                  pos0=0)
        assert n_acc == 0 and target[toks[0]] > 0.0


def test_chunk_prefill_eviction_restart_and_stats(setup):
    """A slot evicted mid-chunk-prefill restarts from the prefix-cache hit
    point; replayed chunks don't inflate prefill_chunks and each prompt
    position enters prefix_tokens_total exactly once (satellite 2)."""
    mesh, model, params = setup

    def fresh():
        return InferenceEngine(model, mesh, params, EngineConfig(
            n_slots=2, block_size=4, num_blocks=32, max_seq_len=64,
            prefix_cache=True, prefill_chunk=4))

    shared = list(range(1, 25))               # 24 tokens = 6 full blocks
    long_p = shared + list(range(101, 109))   # 32 tokens

    # reference: no eviction
    ref = fresh()
    ra = ref.add_request(shared, SamplingParams(max_new_tokens=2))
    while not ra.finished:
        ref.step()
    rb = ref.add_request(long_p, SamplingParams(max_new_tokens=4))
    ref.run()
    want = list(rb.generated)
    chunks_ref = ref.stats.prefill_chunks

    eng = fresh()
    a = eng.add_request(shared, SamplingParams(max_new_tokens=2))
    while not a.finished:
        eng.step()
    base_chunks = eng.stats.prefill_chunks
    base_total = eng.stats.prefix_tokens_total
    b = eng.add_request(long_p, SamplingParams(max_new_tokens=4))
    eng.step()                                # admit (radix hit) + 1 chunk
    assert b.state == "running" and b.last_token is None, \
        "test setup: b should still be mid-chunk-prefill"
    assert b.num_cached > 20                  # restarted past the hit point
    # evict mid-prefill (what ensure_decode_capacity does under pressure)
    eng.sched.slots[b.slot] = None
    eng.sched.preempt(b)
    eng.run()
    assert b.preemptions == 1
    assert list(b.generated) == want          # replay parity
    # re-admission restarted from the radix hit (24 shared tokens), and the
    # replayed chunk over already-materialized positions was not re-counted
    assert (eng.stats.prefill_chunks - base_chunks
            == chunks_ref - base_chunks), \
        (eng.stats.prefill_chunks, chunks_ref)
    # b's 32 prompt positions counted once despite two admissions
    assert eng.stats.prefix_tokens_total - base_total == len(long_p)


class _TickClock:
    """Injectable engine clock: each read advances 1s, so stamp identity
    and ordering are exact."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


def test_ttft_stamped_at_completing_chunk(setup):
    """TTFT attribution (satellite 3): requests whose prefill completes in
    the same chunk step share ONE first-token stamp taken when the chunk's
    sampled tokens materialize — host-side work for earlier slots (radix
    insert, retire) never leaks into later slots' TTFT, and admission/COW
    time is not the stamp."""
    mesh, model, params = setup
    clock = _TickClock()
    eng = InferenceEngine(model, mesh, params, EngineConfig(
        n_slots=2, block_size=4, num_blocks=32, max_seq_len=64,
        prefix_cache=True, prefill_chunk=8), clock=clock)
    p1, p2 = _prompts(seed=9, lens=(8, 8))
    r1 = eng.add_request(p1, SamplingParams(max_new_tokens=3))
    r2 = eng.add_request(p2, SamplingParams(max_new_tokens=3))
    assert r1.arrival_t < r2.arrival_t
    t_admitted = clock.t
    eng.run()
    assert r1.first_token_t is not None and r2.first_token_t is not None
    # one batch = one stamp: identical TTFT clock for both slots
    assert r1.first_token_t == r2.first_token_t
    # stamped inside the completing chunk step, after admission
    assert r1.first_token_t > t_admitted
    assert len(eng.stats.ttfts) == 2


def test_prefix_cache_spec_refcounts_property(setup):
    """prefix_cache x speculation (satellite 4): over random accept/reject
    histories every pool page returns to baseline refcounts, committed
    sequences' full blocks are radix-indexed, and no rolled-back branch is
    ever indexed."""
    mesh, model, params = setup
    rng = np.random.RandomState(3)
    for trial in range(2):
        eng = InferenceEngine(model, mesh, params, EngineConfig(
            n_slots=2, block_size=4, num_blocks=32, max_seq_len=64,
            prefix_cache=True, spec_k=3, spec_mode="ngram"))
        reqs = []
        for i in range(4):
            base = rng.randint(0, 50, (4,)).tolist()
            prompt = (base * 4)[:int(rng.randint(8, 15))]
            sp = SamplingParams(temperature=0.7 if i % 2 else 0.0,
                                seed=trial * 10 + i,
                                max_new_tokens=int(rng.randint(4, 10)))
            reqs.append(eng.add_request(prompt, sp))
        eng.run()
        assert all(r.state == "finished" for r in reqs)
        assert eng.stats.spec_rounds > 0
        # committed tokens completing full blocks are shareable: the radix
        # covers every finished sequence's written prefix block-exactly
        for r in reqs:
            seq = r.seq_tokens[:-1]
            hit = eng.prefix.lookup(0, seq, len(seq))
            assert hit.tokens >= len(seq) // 4 * 4, (trial, r.rid)
        # a rolled-back branch is never indexed: every cached path spells a
        # prefix of some committed sequence
        def walk(node_map, prefix):
            for key, node in node_map.items():
                path = prefix + list(key)
                assert any(path == r.seq_tokens[:len(path)] for r in reqs), \
                    path
                walk(node.children, path)
        walk(eng.prefix._roots[0], [])
        # all request holds were released at retirement; dropping the cache
        # holds returns the pool to its pristine freelist
        eng.prefix.flush()
        pool = eng.cache.pool
        for g in range(pool.n_groups):
            assert pool.available(g) == pool.capacity(g), trial
