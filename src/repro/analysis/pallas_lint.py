"""Pallas kernel lint (shardcheck rule e).

Traces the repo's Pallas entry points (flash attention fwd+bwd, paged
decode attention) to jaxprs, finds every ``pallas_call`` eqn, and checks
its ``GridMapping`` statically — no kernel is ever run:

* **index-map bounds** — each BlockSpec index map, evaluated at the corners
  of the grid, must return block indices inside the (padded) array: Pallas
  silently clamps out-of-range blocks on TPU, which turns an off-by-one
  index map into wrong data, not a crash.  Index maps that take
  scalar-prefetch refs (paged attention's block-table walk) cannot be
  evaluated from grid indices alone and are skipped — recorded, not failed.
* **tile divisibility** — block dims must divide the (padded) array dims;
  a partial trailing tile means the kernel reads/writes garbage lanes
  unless it masks, and every kernel in this repo pads instead.
* **TPU tiling** — a block's last two dims must be multiples of (8, 128)
  or the whole array dims, or the TPU compiler refuses the kernel.
* **VMEM budget** — sum of live block bytes (inputs + outputs, x2 for the
  pipeline's double buffering) per kernel against the ~16 MiB/core VMEM of
  the TPU generations the roofline models; a kernel whose resident tiles
  exceed it would stall on HBM and the flash_tiles autotune table should
  shrink its bq/bk instead.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from jax._src import core as jcore
from jax._src.pallas import core as pallas_core

from .rules import Finding

VMEM_BUDGET = 16 * 2 ** 20      # bytes/core; see /opt roofline + DESIGN §13
DOUBLE_BUFFER = 2               # pallas pipelines blocks in/out
# a block's last two dims must be multiples of the (8, 128) vreg tile or
# the whole array dims (TPU compiler rule; interpret mode does not check)
SUBLANES, LANES = 8, 128


def find_eqns(jaxpr, primitive: str = "pallas_call") -> list:
    """Every ``primitive`` eqn reachable from a (closed) jaxpr, through the
    sub-jaxprs in eqn params (a pallas_call's kernel body included)."""
    out = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == primitive:
                out.append(eqn)
            for v in eqn.params.values():
                for vi in (v if isinstance(v, (tuple, list)) else (v,)):
                    if isinstance(vi, jcore.ClosedJaxpr):
                        walk(vi.jaxpr)
                    elif isinstance(vi, jcore.Jaxpr):
                        walk(vi)

    walk(getattr(jaxpr, "jaxpr", jaxpr))
    return out


def _grid_corners(grid):
    """All corner index tuples of an integer grid (2^ndim points)."""
    pts = [()]
    for g in grid:
        pts = [p + (v,) for p in pts for v in ({0, int(g) - 1})]
    return pts


def lint_grid_mapping(gm, kernel: str = "") -> tuple:
    """(findings, stats) for one pallas_call's GridMapping."""
    findings = []
    grid = tuple(int(g) for g in gm.grid)
    vmem = 0
    n_skipped_maps = 0
    for bi, bm in enumerate(gm.block_mappings):
        arr = bm.array_aval
        block = tuple(map(pallas_core.get_block_size, bm.block_shape))
        vmem += math.prod(block) * arr.dtype.itemsize
        if len(block) != len(arr.shape):
            findings.append(Finding(
                "pallas", kernel,
                f"block #{bi}: block rank {len(block)} != array rank "
                f"{len(arr.shape)} ({block} vs {arr.shape})"))
            continue
        for d, (bs, ad) in enumerate(zip(block, arr.shape)):
            if bs <= 0 or ad % bs:
                findings.append(Finding(
                    "pallas", kernel,
                    f"block #{bi} dim {d}: tile {bs} does not divide "
                    f"array dim {ad} — partial tile would read/write "
                    f"unmasked garbage lanes"))
        for d, unit in ((-1, LANES), (-2, SUBLANES)):
            if len(block) >= -d and block[d] % unit and \
                    block[d] != arr.shape[d]:
                findings.append(Finding(
                    "pallas", kernel,
                    f"block #{bi} dim {len(block) + d}: tile {block[d]} "
                    f"is neither a multiple of {unit} nor the whole array "
                    f"dim {arr.shape[d]} — the TPU compiler refuses it"))
        imj = bm.index_map_jaxpr
        if len(imj.jaxpr.invars) != len(grid):
            n_skipped_maps += 1     # scalar-prefetch-driven map
            continue
        for pt in _grid_corners(grid):
            try:
                idx = jax.core.eval_jaxpr(
                    imj.jaxpr, imj.consts,
                    *[jnp.int32(v) for v in pt])
            except Exception as e:  # pragma: no cover - diagnostic path
                findings.append(Finding(
                    "pallas", kernel,
                    f"block #{bi}: index map failed to evaluate at grid "
                    f"point {pt}: {e}"))
                break
            for d, (b_idx, bs, ad) in enumerate(zip(idx, block, arr.shape)):
                b_idx = int(b_idx)
                n_blocks = -(-ad // bs)
                if not 0 <= b_idx < n_blocks:
                    findings.append(Finding(
                        "pallas", kernel,
                        f"block #{bi} dim {d}: index map returns block "
                        f"{b_idx} at grid point {pt}, valid range "
                        f"[0, {n_blocks}) for array dim {ad} / tile {bs}"))
    vmem *= DOUBLE_BUFFER
    if vmem > VMEM_BUDGET:
        findings.append(Finding(
            "pallas", kernel,
            f"resident block bytes {vmem} (x{DOUBLE_BUFFER} double-buffer) "
            f"exceed the {VMEM_BUDGET} VMEM budget — shrink bq/bk in "
            f"kernels/autotune.py"))
    stats = {"grid": list(grid), "n_blocks": len(gm.block_mappings),
             "vmem_bytes": int(vmem),
             "scalar_prefetch_maps": n_skipped_maps}
    return findings, stats


def lint_closed_jaxpr(closed_jaxpr, kernel: str = "") -> tuple:
    """(findings, {pallas_call_i: stats}) over one traced entry."""
    findings, stats = [], {}
    for i, eqn in enumerate(find_eqns(closed_jaxpr)):
        f, s = lint_grid_mapping(eqn.params["grid_mapping"],
                                 f"{kernel}/pallas_call_{i}")
        findings += f
        stats[f"{kernel}/pallas_call_{i}"] = s
    return findings, stats


def lint_default_kernels() -> tuple:
    """Trace + lint the repo's kernels at canonical shapes.

    Shapes mirror tests/test_kernels.py: GQA flash (fwd and the two-pass
    bwd via grad) and the paged decode kernel.  Returns (findings, stats).
    """
    from ..kernels.autotune import DEFAULT_TILES
    from ..kernels.flash_attention import flash_attention
    from ..kernels.paged_attention import paged_attention

    sds = jax.ShapeDtypeStruct
    findings, stats = [], {}

    q = sds((2, 4, 128, 64), jnp.float32)
    k = sds((2, 2, 128, 64), jnp.float32)
    v = sds((2, 2, 128, 64), jnp.float32)

    def floss(q, k, v):
        # the tiles a TPU resolves (the CPU rows of the autotune table are
        # interpret-mode timings and never reach the chip)
        return flash_attention(q, k, v, causal=True, bq=DEFAULT_TILES[0],
                               bk=DEFAULT_TILES[1]).sum()

    tr = jax.jit(jax.grad(floss, (0, 1, 2))).trace(q, k, v)
    f, s = lint_closed_jaxpr(tr.jaxpr, "flash_attention")
    findings += f
    stats.update(s)

    qd = sds((2, 4, 64), jnp.float32)
    pool = sds((8, 16, 2, 64), jnp.float32)
    tab = sds((2, 4), jnp.int32)
    pos = sds((2,), jnp.int32)
    kvm = sds((4,), jnp.int32)
    tr = jax.jit(lambda *a: paged_attention(*a)).trace(
        qd, pool, pool, tab, pos, kvm)
    f, s = lint_closed_jaxpr(tr.jaxpr, "paged_attention")
    findings += f
    stats.update(s)
    return findings, stats
