"""ZeRO stage-1 optimizer-state sharding (DESIGN.md §9).

The AdamW moments (and the fp32 master copy under mixed precision) are the
largest fully *replicated* state in the trainer: every device of the
``data`` axis (and, for depth-replicated leaves, the ``depth`` axis) holds
an identical fp32 copy.  ZeRO-1 partitions that state so each device owns a
1/dp slice, trading one parameter all-gather per step for a dp-fold memory
cut (PAPERS.md: ZeRO / ZeRO-Infinity; Eq. 8's "lowers the memory required
for each GPU" extended to optimizer state).

Partitioning rule (per leaf, not global):

* A leaf may be *sharded* over some mesh axes (its PartitionSpec) and
  *replicated* over the rest.  Only the replicated DP-like axes — the
  candidates ``("data", "depth")``, plus ``"pipe"`` for stage-replicated
  leaves on a pipeline mesh — are safe to partition optimizer state over:
  partitioning over an axis the leaf is sharded on would orphan chunks
  (e.g. ``head`` is sharded over ``depth`` via ``P(("depth","row","col"))``
  and must keep its state depth-local).  ``zaxes(leaf) = candidates \
  spec_axes(leaf)``.
* The device-local shard (under the leaf's own spec) is flattened,
  zero-padded to a multiple of ``zn = prod(|zaxes|)`` and cut into ``zn``
  equal slices of length ``k`` — flat-index partitioning, so uneven leaves
  (ln vectors, padded vocab rows) work without per-shape cases.
* The global optimizer leaf is ``[n_slices, k]`` with dim 0 laid out
  lexicographically as ``(zaxes..., spec_axes...)`` — each device owns
  exactly one row.

Collective sequence per step (runtime/steps.py):

  grads (partial sums over zaxes) --psum_scatter--> grad slice [k]
  AdamW on the slice (m/v/master all [k], fp32)
  new param slice --cast to param_dtype--> all_gather over zaxes -> leaf

The host-side helpers below re-slice checkpointed optimizer state across
dp-degree changes (elastic 8 -> 4 replans) and between the replicated and
ZeRO layouts; layout metadata rides the checkpoint manifest.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..core import collectives as col

# Axes whose replicated copies of optimizer state are partitioned away.
# "pipe" joins on pipeline meshes (stage-replicated embed/head leaves).
ZERO_CANDIDATE_AXES = ("data", "depth")


def spec_dim_axes(spec: P) -> tuple:
    """Per-dimension tuple of mesh-axis names from a PartitionSpec."""
    out = []
    for entry in spec:
        if entry is None:
            out.append(())
        elif isinstance(entry, (tuple, list)):
            out.append(tuple(entry))
        else:
            out.append((entry,))
    return tuple(out)


@dataclass(frozen=True)
class LeafLayout:
    """Static per-leaf ZeRO-1 layout (hashable: usable inside jit)."""
    param_shape: tuple          # global param shape
    dim_axes: tuple             # per-dim tuple of sharding axis names
    zaxes: tuple                # state-partition axes (replicated DP axes)
    sizes: tuple                # ((axis, size), ...) for every involved axis

    # ---- derived ----
    @property
    def _sz(self) -> dict:
        return dict(self.sizes)

    @property
    def extra_axes(self) -> tuple:
        """Leaf's own sharding axes, flattened in spec order."""
        return tuple(a for dim in self.dim_axes for a in dim)

    @property
    def local_shape(self) -> tuple:
        sz = self._sz
        out = []
        for d, axes in zip(self.param_shape, self.dim_axes):
            f = 1
            for a in axes:
                f *= sz[a]
            if d % f:
                raise ValueError(
                    f"dim {d} of {self.param_shape} not divisible by its "
                    f"sharding axes {axes} (x{f})")
            out.append(d // f)
        return tuple(out)

    @property
    def zn(self) -> int:
        sz = self._sz
        n = 1
        for a in self.zaxes:
            n *= sz[a]
        return n

    @property
    def k(self) -> int:
        loc = 1
        for d in self.local_shape:
            loc *= d
        return -(-loc // self.zn)

    @property
    def n_extra(self) -> int:
        sz = self._sz
        n = 1
        for a in self.extra_axes:
            n *= sz[a]
        return n

    @property
    def n_slices(self) -> int:
        return self.zn * self.n_extra

    def state_spec(self) -> P:
        """PartitionSpec of the [n_slices, k] global optimizer leaf."""
        entries = self.zaxes + self.extra_axes
        return P(entries if entries else None, None)

    def abstract(self):
        return jax.ShapeDtypeStruct((self.n_slices, self.k), jnp.float32)

    # ---- (de)serialization for checkpoint manifests ----
    def to_json(self) -> dict:
        return {"param_shape": list(self.param_shape),
                "dim_axes": [list(d) for d in self.dim_axes],
                "zaxes": list(self.zaxes),
                "sizes": [list(s) for s in self.sizes]}

    @staticmethod
    def from_json(d: dict) -> "LeafLayout":
        return LeafLayout(
            param_shape=tuple(d["param_shape"]),
            dim_axes=tuple(tuple(x) for x in d["dim_axes"]),
            zaxes=tuple(d["zaxes"]),
            sizes=tuple((a, int(n)) for a, n in d["sizes"]))


def layout_for(spec: P, shape: tuple, axis_sizes: dict,
               candidates: tuple = ZERO_CANDIDATE_AXES) -> LeafLayout:
    """Layout of one leaf: partition its optimizer state over the candidate
    axes the leaf is NOT sharded on (its true replication axes)."""
    dim_axes = spec_dim_axes(spec)
    used = {a for dim in dim_axes for a in dim}
    zaxes = tuple(a for a in candidates if a not in used)
    involved = tuple(dict.fromkeys(zaxes + tuple(a for dim in dim_axes
                                                 for a in dim)))
    sizes = tuple((a, int(axis_sizes[a])) for a in involved)
    return LeafLayout(param_shape=tuple(shape), dim_axes=dim_axes,
                      zaxes=zaxes, sizes=sizes)


def build_layouts(specs_tree, abs_params, axis_sizes: dict,
                  candidates: tuple = ZERO_CANDIDATE_AXES):
    """Tree of LeafLayout matching a specs tree + abstract param tree."""
    return jax.tree.map(
        lambda sp, ab: layout_for(sp, ab.shape, axis_sizes, candidates),
        specs_tree, abs_params, is_leaf=lambda x: isinstance(x, P))


def layouts_to_json(layouts_tree) -> dict:
    """Flat {'a/b/c': layout-json} dict (checkpoint manifest metadata)."""
    flat = {}

    def rec(tree, prefix):
        if isinstance(tree, dict):
            for k in sorted(tree):
                rec(tree[k], f"{prefix}{k}/")
        else:
            flat[prefix.rstrip("/")] = tree.to_json()
    rec(layouts_tree, "")
    return flat


def zero_opt_init(bundle):
    """Fresh ZeRO-1 optimizer state for a train-step bundle: every slice
    starts at zero (the fp32 master slices are lazily adopted from the
    params at step 0 inside the step — runtime/steps.py)."""
    return jax.tree.map(lambda sd: jnp.zeros(sd.shape, sd.dtype),
                        bundle.abstract_inputs[1])


# ---------------------------------------------------------------------------
# device-side helpers (inside shard_map; x is the leaf's LOCAL shard)
# ---------------------------------------------------------------------------

def _pad_flat(x, lay: LeafLayout):
    k, zn = lay.k, lay.zn
    flat = x.reshape(-1)
    pad = k * zn - flat.size
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat


def zslice(x, lay: LeafLayout):
    """This device's [k] slice of an already-reduced local value."""
    flat = _pad_flat(x, lay)
    if lay.zn == 1:
        return flat
    i = col.axis_linear_index(lay.zaxes)
    return lax.dynamic_slice_in_dim(flat, i * lay.k, lay.k, axis=0)


def zreduce_scatter(g, lay: LeafLayout, compress: str = "none"):
    """reduce_scatter of a gradient that is a PARTIAL SUM over ``zaxes``:
    each member contributes its padded flat grad, receives the fully
    reduced [k] slice it owns — the ZeRO-1 replacement for the data-axis
    grad psum (same wire bytes as the psum's reduce-scatter phase, no
    all-gather phase)."""
    flat = _pad_flat(g, lay)
    if lay.zn == 1:
        return flat
    if compress == "bf16" and flat.dtype == jnp.float32:
        return lax.psum_scatter(flat.astype(jnp.bfloat16), lay.zaxes,
                                scatter_dimension=0,
                                tiled=True).astype(jnp.float32)
    return lax.psum_scatter(flat, lay.zaxes, scatter_dimension=0, tiled=True)


def zgather(sl, lay: LeafLayout, dtype=None):
    """all_gather the updated slices back into the leaf's local shard.

    ``dtype`` casts BEFORE the gather (bf16 params ride the wire in bf16 —
    half the gather bytes of the fp32 master)."""
    if dtype is not None:
        sl = sl.astype(dtype)
    # size-1 zaxes gather nothing but still clear the slice's vma there
    flat = col.all_gather_inv(sl, lay.zaxes, tiled=True, axis=0)
    loc = lay.local_shape
    n = 1
    for d in loc:
        n *= d
    return flat[:n].reshape(loc)


# ---------------------------------------------------------------------------
# host-side re-sharding (checkpoint restore across layouts / dp degrees)
# ---------------------------------------------------------------------------

def _extra_strides(lay: LeafLayout):
    sizes = lay._sz
    axes = lay.extra_axes
    dims = [sizes[a] for a in axes]
    return axes, dims


def _block_slices(lay: LeafLayout, coords: dict):
    """Global-array slices of the local block at the given axis coords."""
    out = []
    for d, axes, loc in zip(lay.param_shape, lay.dim_axes, lay.local_shape):
        idx = 0
        for a in axes:
            idx = idx * lay._sz[a] + coords[a]
        out.append(slice(idx * loc, (idx + 1) * loc))
    return tuple(out)


def host_shard(full: np.ndarray, lay: LeafLayout) -> np.ndarray:
    """Full fp32 global array -> [n_slices, k] ZeRO layout (numpy)."""
    full = np.asarray(full)
    if tuple(full.shape) != lay.param_shape:
        raise ValueError(f"{full.shape} != layout {lay.param_shape}")
    zn, k, n_e = lay.zn, lay.k, lay.n_extra
    axes, dims = _extra_strides(lay)
    out = np.zeros((lay.n_slices, k), full.dtype)
    for lin_e, e in enumerate(np.ndindex(*dims) if dims else [()]):
        coords = dict(zip(axes, e))
        blk = full[_block_slices(lay, coords)].reshape(-1)
        flat = np.zeros(zn * k, full.dtype)
        flat[:blk.size] = blk
        out[np.arange(zn) * n_e + lin_e] = flat.reshape(zn, k)
    return out


def host_unshard(z: np.ndarray, lay: LeafLayout) -> np.ndarray:
    """[n_slices, k] ZeRO layout -> full global array (numpy)."""
    z = np.asarray(z)
    if tuple(z.shape) != (lay.n_slices, lay.k):
        raise ValueError(f"{z.shape} != layout ({lay.n_slices}, {lay.k})")
    zn, k, n_e = lay.zn, lay.k, lay.n_extra
    axes, dims = _extra_strides(lay)
    full = np.zeros(lay.param_shape, z.dtype)
    loc_n = 1
    for d in lay.local_shape:
        loc_n *= d
    for lin_e, e in enumerate(np.ndindex(*dims) if dims else [()]):
        coords = dict(zip(axes, e))
        flat = z[np.arange(zn) * n_e + lin_e].reshape(-1)
        full[_block_slices(lay, coords)] = \
            flat[:loc_n].reshape(lay.local_shape)
    return full


def convert_leaf(arr: np.ndarray, old_lay: LeafLayout | None,
                 new_lay: LeafLayout | None) -> np.ndarray:
    """Re-shard one optimizer leaf between layouts (None = replicated)."""
    if old_lay is None and new_lay is None:
        return arr
    if old_lay is not None and new_lay is not None \
            and old_lay.to_json() == new_lay.to_json():
        return arr
    full = host_unshard(arr, old_lay) if old_lay is not None else arr
    return host_shard(full, new_lay) if new_lay is not None else full


def make_ckpt_converter(target_layouts_json: dict | None,
                        state_key: str = "opt"):
    """``convert(path, arr, manifest_meta)`` for CheckpointManager.restore:
    re-shards ``opt/{m,v,master}/...`` leaves between the manifest's saved
    ZeRO layout and the restoring bundle's — across dp-degree changes
    (elastic replans) and to/from the replicated layout."""
    prefix = state_key + "/"

    def convert(path: str, arr, meta):
        if not path.startswith(prefix):
            return arr
        group, _, ppath = path[len(prefix):].partition("/")
        if group not in ("m", "v", "master") or not ppath:
            return arr
        old_json = ((meta or {}).get("opt_layout") or {}).get(ppath)
        new_json = (target_layouts_json or {}).get(ppath)
        if old_json == new_json:
            return arr
        old = LeafLayout.from_json(old_json) if old_json else None
        new = LeafLayout.from_json(new_json) if new_json else None
        return convert_leaf(np.asarray(arr), old, new)

    return convert
