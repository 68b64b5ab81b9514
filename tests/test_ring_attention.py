"""Ring/striped flash attention statics (DESIGN.md §15): the striped
permutation, the per-step mask oracle, causal load balance, the ppermute
comm model, and the schedule/config validation surface.  The multi-device
numerics live in the ``ring_attention`` mdcheck (tests/test_multidevice_attention.py
runs it in a subprocess)."""
import numpy as np
import pytest

from repro.core.api import ParallelContext
from repro.core.ring_attention import (ring_ppermute_bytes,
                                       ring_ppermute_counts,
                                       shard_positions, stripe_permutation,
                                       unstripe_permutation)


# ---------------------------------------------------------------------------
# stripe permutation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("T,n", [(8, 2), (16, 4), (64, 8), (12, 3)])
def test_stripe_unstripe_roundtrip(T, n):
    s = stripe_permutation(T, n)
    u = unstripe_permutation(T, n)
    x = np.arange(T)
    np.testing.assert_array_equal(x[s][u], x)
    np.testing.assert_array_equal(x[u][s], x)
    # shard r of the striped layout holds global positions r + n*arange(L)
    L = T // n
    for r in range(n):
        np.testing.assert_array_equal(s[r * L:(r + 1) * L],
                                      r + n * np.arange(L))


def test_stripe_divisibility_checked():
    with pytest.raises(ValueError):
        stripe_permutation(10, 4)
    with pytest.raises(ValueError):
        unstripe_permutation(10, 4)


def test_shard_positions_match_permutation():
    T, n = 32, 4
    L = T // n
    s = stripe_permutation(T, n)
    for r in range(n):
        np.testing.assert_array_equal(
            np.asarray(shard_positions(L, n, r, "striped")),
            s[r * L:(r + 1) * L])
        np.testing.assert_array_equal(
            np.asarray(shard_positions(L, n, r, "ring")),
            np.arange(r * L, (r + 1) * L))


# ---------------------------------------------------------------------------
# causal load balance: striped spread is one KV block, contiguous is n-1
# ---------------------------------------------------------------------------

def _causal_work(positions, T):
    """Unmasked (q, kv) pairs a rank owning these global q positions scores
    against the full sequence under the causal mask."""
    return int(sum(int(p) + 1 for p in positions))


@pytest.mark.parametrize("T,n", [(64, 4), (128, 8)])
def test_striped_causal_work_balanced(T, n):
    L = T // n
    striped = [_causal_work(shard_positions(L, n, r, "striped"), T)
               for r in range(n)]
    contig = [_causal_work(shard_positions(L, n, r, "ring"), T)
              for r in range(n)]
    assert sum(striped) == sum(contig) == T * (T + 1) // 2
    # striped ranks differ by < 1 unmasked entry per owned row (< one
    # L-row block of work in total; adjacent global positions differ by
    # at most n-1 across ranks)
    assert max(striped) - min(striped) == L * (n - 1)
    assert max(striped) - min(striped) < L * L
    # contiguous ranks differ by (n-1) * L^2: the last rank does ~2x the
    # mean and the first almost nothing — the imbalance striping removes
    assert max(contig) - min(contig) == (n - 1) * L * L
    assert max(striped) - min(striped) < max(contig) - min(contig)


# ---------------------------------------------------------------------------
# per-step mask == dense oracle from global positions
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("variant", ["striped", "ring"])
def test_step_mask_matches_dense_oracle(variant):
    import jax.numpy as jnp
    from repro.core.ring_attention import RingSpec, _step_mask_args

    T, n = 32, 4
    L = T // n
    spec = RingSpec(axes=("s",), n=n, variant=variant, causal=True,
                    window=0, scale=1.0, impl="jnp", interpret=True)
    for rank in range(n):
        qpos = np.asarray(shard_positions(L, n, rank, variant))
        for src in range(n):
            kvpos = np.asarray(shard_positions(L, n, src, variant))
            oracle = qpos[:, None] >= kvpos[None, :]
            q_pos, q_start = _step_mask_args(spec, L, L, jnp.int32(rank),
                                             jnp.int32(src))
            q_pos = np.asarray(q_pos)
            # the kernel masks with relative positions: row i attends to
            # local kv col k iff q_pos[i] >= k (kv cols are 0..Lk-1)
            got = q_pos[:, None] >= np.arange(L)[None, :]
            np.testing.assert_array_equal(
                got, oracle,
                err_msg=f"{variant} rank={rank} src={src}")
            if q_start is not None:
                # static block-skip floor must not cut real work: every
                # unmasked col index stays >= q_start
                assert q_start == 0


# ---------------------------------------------------------------------------
# comm model: exact ppermute counts / bytes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [2, 4, 8])
def test_ppermute_counts(n):
    c = ring_ppermute_counts(n, train=True, remat_replay=True)
    # 2(n-1) K/V fwd; bwd = K/V re-stream + accumulator ring shifts + 2
    # final deliveries + the remat fwd replay
    assert c["fwd"] == 2 * (n - 1)
    assert c["bwd"] == 2 * (n - 1) + 2 * (n - 1) + 2 + 2 * (n - 1)
    assert c["total"] == c["fwd"] + c["bwd"]
    e = ring_ppermute_counts(n, train=False)
    assert e == {"fwd": 2 * (n - 1), "bwd": 0, "total": 2 * (n - 1)}


def test_ppermute_counts_degenerate():
    assert ring_ppermute_counts(1)["total"] == 0


def test_ppermute_bytes_match_counts():
    n, kvb, accb = 4, 1024, 2048
    c = ring_ppermute_counts(n, train=True, remat_replay=True)
    b = ring_ppermute_bytes(n, kv_block_bytes=kvb, acc_block_bytes=accb,
                            train=True, remat_replay=True)
    # all K/V-stream permutes move kvb, all accumulator permutes move accb
    kv_moves = 2 * (n - 1) * 3        # fwd + bwd re-stream + remat replay
    acc_moves = 2 * (n - 1) + 2
    assert b["total"] == kv_moves * kvb + acc_moves * accb
    assert c["total"] == kv_moves + acc_moves


def test_roofline_ring_traffic_consistent():
    from repro.roofline.analysis import ring_attention_traffic
    B, Hq, Hkv, T, D, seq = 2, 8, 4, 4096, 64, 4
    t = ring_attention_traffic(B, Hq, Hkv, T, D, seq=seq, num_layers=3,
                               compute_itemsize=2)
    L = T // seq
    kvb = B * Hkv * L * D * 2
    ref = ring_ppermute_bytes(seq, kv_block_bytes=kvb,
                              acc_block_bytes=B * Hkv * L * D * 4)
    assert t["per_layer_bytes"] == ref
    assert t["wire_bytes"] == 3 * ref["total"]
    with pytest.raises(ValueError):
        ring_attention_traffic(B, Hq, Hkv, 100, D, seq=3)


# ---------------------------------------------------------------------------
# satellite: effective_schedule accounts for the seq axis
# ---------------------------------------------------------------------------

def test_effective_schedule_seq_aware():
    from repro.core.summa import effective_schedule
    base = dict(mode="tesseract", data=1, depth=1, rows=4, cols=4,
                matmul_schedule="auto")
    ctx1 = ParallelContext(**base)
    ctx4 = ParallelContext(**base, seq=4, attn_schedule="striped")
    # train-sized blocks ride the ring on both
    assert effective_schedule(ctx1, 4096) == "ring"
    assert effective_schedule(ctx4, 4096) == "ring"
    # a block that clears the seq=1 threshold but only because the seq axis
    # shrank the local rows must NOT regress to a ring matmul
    e_loc = 2 * ctx1.q  # == 8: ring at seq=1, fused at seq=4
    assert effective_schedule(ctx1, e_loc) == "ring"
    assert effective_schedule(ctx4, e_loc) == "fused"
    # decode-shaped blocks stay fused everywhere
    assert effective_schedule(ctx4, 1) == "fused"


# ---------------------------------------------------------------------------
# validation surface
# ---------------------------------------------------------------------------

def test_ctx_seq_validation():
    with pytest.raises(ValueError, match="attn_schedule"):
        ParallelContext(mode="tesseract", seq=2)          # local + seq>1
    with pytest.raises(ValueError, match="seq"):
        ParallelContext(mode="megatron1d", cols=4, seq=2,
                        attn_schedule="ring")
    with pytest.raises(ValueError, match="attn_schedule"):
        ParallelContext(attn_schedule="diagonal")
    ctx = ParallelContext(mode="tesseract", seq=2, attn_schedule="auto")
    assert ctx.mesh_axes == ("data", "seq", "depth", "row", "col")
    assert ctx.train_attn_schedule() == "striped"
    assert ParallelContext().mesh_axes == ("data", "depth", "row", "col")
    assert ParallelContext().train_attn_schedule() == "local"


def test_mesh_rejects_pipe_with_seq():
    from repro.core.mesh import pipeline_mesh
    ctx = ParallelContext(mode="tesseract", seq=2, attn_schedule="ring")
    with pytest.raises(ValueError, match="pipe"):
        pipeline_mesh(ctx, 2)


def test_runconfig_attn_schedule_validation():
    from repro.configs.base import RunConfig
    with pytest.raises(ValueError, match="attn_schedule"):
        RunConfig(attn_schedule="zigzag")
    with pytest.raises(ValueError, match="seq_shards"):
        RunConfig(seq_shards=0)
    assert RunConfig(seq_shards=2, attn_schedule="auto").seq_shards == 2


def test_ring_attention_rejects_striped_window():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from jax import shard_map
    from repro.core.ring_attention import ring_attention

    mesh = Mesh(np.array(jax.devices()[:1]), ("s",))
    x = jnp.zeros((1, 1, 4, 4), jnp.float32)

    def f(a):
        return ring_attention(a, a, a, axes=("s",), variant="striped",
                              causal=True, local_window=2)

    with pytest.raises(ValueError, match="striped"):
        shard_map(f, mesh=mesh, in_specs=(P(),), out_specs=P())(x)
    with pytest.raises(ValueError, match="variant"):
        shard_map(lambda a: ring_attention(a, a, a, axes=("s",),
                                           variant="spiral"),
                  mesh=mesh, in_specs=(P(),), out_specs=P())(x)
