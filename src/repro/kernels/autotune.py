"""Tiny (bq, bk) tile autotuner for the flash-attention kernels.

Hillclimb-style loop (the benchmarks/hillclimb.py discipline scaled down to
one knob): measure the incumbent tiling, try each candidate, commit only
improvements.  Results are cached per shape signature in-process — the hot
path (`flash_tiles`) is a dict lookup, never a measurement — and can be
persisted/reloaded as JSON so `benchmarks/run.py` commits the sweep's
outcome in BENCH_attention.json.
"""
from __future__ import annotations

import json
import pathlib
import time

DEFAULT_TILES = (256, 256)
CANDIDATES = ((128, 128), (128, 256), (256, 128), (256, 256), (256, 512),
              (512, 256), (512, 512))

_CACHE: dict = {}

# Repo-committed tile choices, keyed backend -> "Tq,Tk,D,causal" -> [bq, bk]
# (autotune_cache.json next to this file).  Loaded once at import — pure
# json, no jax — and consulted by flash_tiles() AFTER the in-process cache
# (a fresh measurement on this machine beats the committed sweep) but
# BEFORE DEFAULT_TILES.  Regenerate with commit_cache() after a sweep.
COMMITTED_CACHE_PATH = pathlib.Path(__file__).with_name(
    "autotune_cache.json")
_COMMITTED: dict = {}
_BACKEND = None


def _load_committed(path=COMMITTED_CACHE_PATH) -> int:
    p = pathlib.Path(path)
    if not p.exists():
        return 0
    _COMMITTED.clear()
    for backend, table in json.loads(p.read_text()).items():
        per = _COMMITTED.setdefault(backend, {})
        for ks, v in table.items():
            tq, tk, d, causal = ks.split(",")
            per[(int(tq), int(tk), int(d), causal == "True")] = tuple(v)
    return sum(len(t) for t in _COMMITTED.values())


def _backend_name() -> str:
    global _BACKEND
    if _BACKEND is None:
        import jax
        _BACKEND = jax.default_backend()
    return _BACKEND


def commit_cache(path=COMMITTED_CACHE_PATH) -> None:
    """Merge the in-process cache into the committed per-backend JSON."""
    p = pathlib.Path(path)
    data = json.loads(p.read_text()) if p.exists() else {}
    table = data.setdefault(_backend_name(), {})
    for k, v in _CACHE.items():
        table[",".join(map(str, k))] = list(v)
    p.write_text(json.dumps(data, indent=2, sort_keys=True) + "\n")
    _load_committed(p)


def _sig(Tq: int, Tk: int, D: int, causal: bool) -> tuple:
    # batch/head counts replicate the per-block work and never change the
    # best tile, so the signature is the per-head shape only
    return (int(Tq), int(Tk), int(D), bool(causal))


def flash_tiles(Tq: int, Tk: int, D: int, *, causal: bool = True) -> tuple:
    """Cached best (bq, bk) for a flash shape; the default when untuned.

    Resolution order: in-process cache (this run's measurements) ->
    committed per-backend autotune_cache.json -> DEFAULT_TILES."""
    sig = _sig(Tq, Tk, D, causal)
    hit = _CACHE.get(sig)
    if hit is not None:
        return hit
    hit = _COMMITTED.get(_backend_name(), {}).get(sig)
    return hit if hit is not None else DEFAULT_TILES


def set_tiles(Tq: int, Tk: int, D: int, causal: bool, tiles) -> None:
    _CACHE[_sig(Tq, Tk, D, causal)] = (int(tiles[0]), int(tiles[1]))


def autotune_flash(B: int, H: int, Tq: int, Tk: int, D: int, *,
                   causal: bool = True, include_bwd: bool = True,
                   candidates=CANDIDATES, iters: int = 3,
                   dtype=None) -> dict:
    """Sweep tile candidates for one shape, cache the winner, return the
    full measurement table {"(bq,bk)": seconds, ...} plus the choice."""
    import jax
    import jax.numpy as jnp
    from .ops import flash_attention_op

    dtype = dtype or jnp.float32
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (B, H, Tq, D), jnp.float32).astype(dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, H, Tk, D),
                          jnp.float32).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, H, Tk, D),
                          jnp.float32).astype(dtype)

    def run(bq, bk):
        if include_bwd:
            f = jax.jit(jax.grad(lambda a, b_, c: jnp.sum(
                flash_attention_op(a, b_, c, causal=causal, bq=bq, bk=bk)
                .astype(jnp.float32)), argnums=(0, 1, 2)))
        else:
            f = jax.jit(lambda a, b_, c: flash_attention_op(
                a, b_, c, causal=causal, bq=bq, bk=bk))
        jax.block_until_ready(f(q, k, v))            # compile
        times = []
        for _ in range(iters):
            t0 = time.perf_counter()
            jax.block_until_ready(f(q, k, v))
            times.append(time.perf_counter() - t0)
        return min(times)

    # hillclimb: incumbent = current cache entry (or default), challengers
    # = the candidate list clipped to the shape; commit improvements only
    best = flash_tiles(Tq, Tk, D, causal=causal)
    seen = {}
    trial = [best] + [c for c in candidates if c != best]
    for bq, bk in trial:
        cq, ck = min(bq, Tq), min(bk, Tk)
        if (cq, ck) in seen:
            continue
        seen[(cq, ck)] = run(cq, ck)
    best = min(seen, key=seen.get)
    set_tiles(Tq, Tk, D, causal, best)
    return {"shape": {"B": B, "H": H, "Tq": Tq, "Tk": Tk, "D": D,
                      "causal": causal},
            "timings_s": {f"{bq}x{bk}": t for (bq, bk), t in seen.items()},
            "best": list(best)}


def autotune_ring_steps(B: int, H: int, T: int, D: int, *,
                        seq_shards=(2, 4, 8), causal: bool = True,
                        candidates=CANDIDATES, iters: int = 3,
                        include_bwd: bool = True, dtype=None) -> list:
    """Sweep the ring-STEP flash shapes of a seq-sharded sequence.

    Each seq shard's ring step runs the flash kernel on its resident
    [L, L] tile (L = T/n), so the signatures that matter are
    (L, L, D, causal) for every shard count n — the per-step entries
    (kernels/flash_attention.flash_{fwd,dq,dkv}_step) resolve their tiles
    through the same flash_tiles() cache this sweep fills.  Returns one
    autotune_flash record per shard count, each tagged with ``seq_shards``
    and the key block ``Tk`` the ring streams per step."""
    out = []
    for n in seq_shards:
        if T % n:
            raise ValueError(f"T={T} not divisible by seq_shards={n}")
        L = T // n
        rec = autotune_flash(B, H, L, L, D, causal=causal,
                             candidates=candidates, iters=iters,
                             include_bwd=include_bwd, dtype=dtype)
        rec["seq_shards"] = n
        rec["ring_step_Tk"] = L
        out.append(rec)
    return out


def save_cache(path) -> None:
    p = pathlib.Path(path)
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(json.dumps(
        {",".join(map(str, k)): list(v) for k, v in _CACHE.items()},
        indent=2) + "\n")


def load_cache(path) -> int:
    p = pathlib.Path(path)
    if not p.exists():
        return 0
    for ks, v in json.loads(p.read_text()).items():
        tq, tk, d, causal = ks.split(",")
        _CACHE[(int(tq), int(tk), int(d), causal == "True")] = tuple(v)
    return len(_CACHE)


_load_committed()
