"""Normalized collective IR extracted from closed jaxprs.

``benchmarks/comm_model.py`` and ``core/summa.py`` *predict* what each jitted
step should communicate; this module reads what it *actually* communicates.
Tracing a step builder's jitted fn to a closed jaxpr (``fn.trace(*abstract)``)
happens before XLA ever runs, so the walk is cheap, deterministic, and sees
the program post-AD — exactly the collective schedule the compiler is handed.

The walker descends every sub-jaxpr (``shard_map`` bodies, ``scan``/``while``
loops, ``cond`` branches, ``pjit``/``custom_vjp``/``remat`` calls) and
multiplies loop-body collectives by their trip count.  ``scan`` carries its
trip count in the eqn (``length``); ``while`` trip counts are recovered the
same way ``roofline/hlo.py`` does for HLO while loops — the largest integer
literal visible in the condition computation (one call level deep).

Every ``psum`` / ``psum_scatter`` / ``all_gather`` / ``ppermute`` /
``all_to_all`` (+ ``pmax``/``pmin``, which move all-reduce bytes) becomes one
:class:`Collective` record with named axes, local operand shape, dtype,
enclosing-loop multiplicity, and ring-model wire bytes (same byte formulas as
``roofline/hlo.py`` so jaxpr- and HLO-level accounting agree).

A second pass (:func:`replication_taints`) is a replication checker for
shard_maps traced with ``check_vma=False`` (jax checks the others itself):
values seeded by
``lax.axis_index`` or by a sharded input axis are tracked through the body;
reaching a shard_map *output* whose out_specs declare the value replicated
over an axis it still (conservatively) varies on is a divergence violation —
the bug class where per-device state leaks into a tensor the layout promises
is identical everywhere.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

from jax._src import core as jcore

# primitive name -> normalized collective kind
COLLECTIVE_PRIMS = {
    "psum": "psum",
    "psum_invariant": "psum",          # psum of a varying value
    "all_gather_invariant": "all_gather",
    "pmax": "pmax",
    "pmin": "pmin",
    "all_gather": "all_gather",
    "reduce_scatter": "psum_scatter",
    "ppermute": "ppermute",
    "all_to_all": "all_to_all",
}

# kinds whose output is invariant over the collective's axes (they erase
# per-device variation; ppermute / all_to_all / psum_scatter do not)
INVARIANT_KINDS = ("psum", "pmax", "pmin", "all_gather")


def _axes_of(eqn) -> tuple:
    """Named mesh axes of a collective eqn, sorted (positional ints dropped)."""
    ax = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    if isinstance(ax, str):
        ax = (ax,)
    return tuple(sorted(a for a in ax if isinstance(a, str)))


def _aval_bytes(aval) -> int:
    try:
        return math.prod(aval.shape) * aval.dtype.itemsize
    except (AttributeError, TypeError):
        return 0


@dataclass(frozen=True)
class Collective:
    """One collective op in the normalized IR."""
    kind: str            # psum | psum_scatter | all_gather | ppermute | ...
    axes: tuple          # sorted named mesh axes
    shape: tuple         # local operand shape (first array operand)
    dtype: str
    mult: int            # product of enclosing loop trip counts
    group: int           # devices participating (prod of axis sizes)
    operand_bytes: int   # all array operands, one occurrence
    path: tuple = ()     # enclosing-context labels, outermost first

    @property
    def wire_bytes(self) -> float:
        """Ring-model wire bytes per device for ONE occurrence (same formulas
        as roofline/hlo.py so jaxpr- and HLO-level accounting agree)."""
        n, ob = self.group, self.operand_bytes
        frac = (n - 1) / n if n > 1 else 0.0
        if self.kind == "all_gather":
            return ob * (n - 1)          # output is n x operand
        if self.kind in ("psum", "pmax", "pmin"):
            return 2 * ob * frac
        if self.kind in ("psum_scatter", "all_to_all"):
            return ob * frac
        return ob                         # ppermute

    @property
    def total_wire_bytes(self) -> float:
        return self.mult * self.wire_bytes

    def key(self) -> str:
        return f"{self.kind}@{'x'.join(self.axes) if self.axes else '-'}"


@dataclass
class IRProgram:
    """Extraction result for one traced entry point."""
    collectives: list = field(default_factory=list)
    axis_sizes: dict = field(default_factory=dict)
    n_axis_index: int = 0
    shard_map_eqns: list = field(default_factory=list)

    def total_wire_bytes(self) -> float:
        return sum(c.total_wire_bytes for c in self.collectives)

    def by_key(self) -> dict:
        """{kind@axes: {count, wire_bytes}} aggregate (multiplicity folded)."""
        out: dict = {}
        for c in self.collectives:
            d = out.setdefault(c.key(), {"count": 0, "wire_bytes": 0.0})
            d["count"] += c.mult
            d["wire_bytes"] += c.total_wire_bytes
        return out

    def psum_axis_counts(self) -> dict:
        """{sorted axes tuple: multiplicity-summed count} of psum reductions
        (psum + psum_scatter), the input to the grad-sync completeness rule."""
        out: dict = {}
        for c in self.collectives:
            if c.kind in ("psum", "psum_scatter") and c.axes:
                out[c.axes] = out.get(c.axes, 0) + c.mult
        return out


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------

def _as_jaxpr(v):
    """Unwrap ClosedJaxpr -> Jaxpr; return None for non-jaxpr values."""
    if isinstance(v, jcore.ClosedJaxpr):
        return v.jaxpr
    if isinstance(v, jcore.Jaxpr):
        return v
    return None


def _sub_jaxprs(params: dict):
    """All (name, jaxpr) sub-jaxprs referenced by an eqn's params."""
    out = []
    for k, v in params.items():
        j = _as_jaxpr(v)
        if j is not None:
            out.append((k, j))
        elif isinstance(v, (tuple, list)):
            for i, vi in enumerate(v):
                ji = _as_jaxpr(vi)
                if ji is not None:
                    out.append((f"{k}[{i}]", ji))
    return out


def _int_literals(jaxpr, depth: int = 1) -> list:
    """Integer literals visible in a jaxpr (+ ``depth`` call levels), the
    jaxpr analogue of roofline/hlo.py::_trip_count's constant scan."""
    out = []
    for eqn in jaxpr.eqns:
        for v in eqn.invars:
            if isinstance(v, jcore.Literal):
                try:
                    out.append(int(v.val))
                except (TypeError, ValueError, OverflowError):
                    pass
        if depth > 0:
            for _, sub in _sub_jaxprs(eqn.params):
                out.extend(_int_literals(sub, depth - 1))
    return out


def while_trip_count(eqn) -> int:
    """Trip-count bound for a ``while`` eqn: the largest integer literal in
    its condition computation (roofline/hlo.py discipline), default 1."""
    cond = _as_jaxpr(eqn.params.get("cond_jaxpr"))
    if cond is None:
        return 1
    lits = [l for l in _int_literals(cond) if 0 < l < 2 ** 31]
    return max(lits) if lits else 1


def mesh_axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def extract_ir(closed_jaxpr, axis_sizes: dict | None = None) -> IRProgram:
    """Walk a closed jaxpr into the normalized collective IR."""
    prog = IRProgram(axis_sizes=dict(axis_sizes or {}))

    def group_size(axes, sizes) -> int:
        n = 1
        for a in axes:
            n *= sizes.get(a, 1)
        return n

    def walk(jaxpr, mult: int, path: tuple, sizes: dict):
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name == "axis_index":
                prog.n_axis_index += 1
                continue
            if name in COLLECTIVE_PRIMS:
                axes = _axes_of(eqn)
                ob = sum(_aval_bytes(v.aval) for v in eqn.invars
                         if not isinstance(v, jcore.Literal)
                         or hasattr(v.aval, "shape"))
                first = next((v.aval for v in eqn.invars
                              if hasattr(v.aval, "shape")), None)
                prog.collectives.append(Collective(
                    kind=COLLECTIVE_PRIMS[name], axes=axes,
                    shape=tuple(first.shape) if first is not None else (),
                    dtype=str(first.dtype) if first is not None else "?",
                    mult=mult, group=group_size(axes, sizes),
                    operand_bytes=ob, path=path))
                continue
            if name == "shard_map":
                prog.shard_map_eqns.append((eqn, mult, path))
                sub_sizes = dict(sizes)
                mesh = eqn.params.get("mesh")
                if mesh is not None:
                    sub_sizes.update(mesh_axis_sizes(mesh))
                    prog.axis_sizes.update(mesh_axis_sizes(mesh))
                body = _as_jaxpr(eqn.params.get("jaxpr"))
                if body is not None:
                    walk(body, mult, path + ("shard_map",), sub_sizes)
                continue
            if name == "scan":
                length = int(eqn.params.get("length", 1))
                body = _as_jaxpr(eqn.params.get("jaxpr"))
                if body is not None:
                    walk(body, mult * length,
                         path + (f"scan[{length}]",), sizes)
                continue
            if name == "while":
                trips = while_trip_count(eqn)
                body = _as_jaxpr(eqn.params.get("body_jaxpr"))
                if body is not None:
                    walk(body, mult * trips,
                         path + (f"while[{trips}]",), sizes)
                cond = _as_jaxpr(eqn.params.get("cond_jaxpr"))
                if cond is not None:
                    walk(cond, mult * trips,
                         path + (f"while_cond[{trips}]",), sizes)
                continue
            # generic containers: pjit, cond branches, custom_vjp, remat, ...
            for label, sub in _sub_jaxprs(eqn.params):
                walk(sub, mult, path + (f"{name}:{label}",), sizes)

    walk(closed_jaxpr.jaxpr, 1, (), dict(axis_sizes or {}))
    return prog


# ---------------------------------------------------------------------------
# replication-divergence taint analysis (rule c)
# ---------------------------------------------------------------------------

def _spec_axes(spec) -> set:
    """Axis names appearing anywhere in a shard_map in/out PartitionSpec."""
    out: set = set()
    for axes in (spec or ()):
        if isinstance(axes, str):
            out.add(axes)
        elif axes is not None:
            out.update(axes)
    return out


def _taint_jaxpr(jaxpr, in_taints, env_consts=None) -> list:
    """Propagate per-axis variance taint through a jaxpr's eqns.

    Returns the taint sets of the jaxpr's outvars.  Collectives that make
    values invariant over their axes (psum/pmax/pmin/all_gather) clear those
    axes; ppermute/all_to_all/psum_scatter outputs still vary.  scan/while
    carries run to a fixpoint; every other sub-jaxpr is entered with its
    operand taints.
    """
    env: dict = {}

    def read(v) -> frozenset:
        if isinstance(v, jcore.Literal):
            return frozenset()
        return env.get(v, frozenset())

    def write(v, t: frozenset):
        env[v] = frozenset(t)

    for v, t in zip(jaxpr.invars, in_taints):
        write(v, t)
    for v in jaxpr.constvars:
        write(v, frozenset())

    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        joined = frozenset().union(*[read(v) for v in eqn.invars]) \
            if eqn.invars else frozenset()
        if name == "axis_index":
            ax = eqn.params.get("axis_name")
            ax = (ax,) if isinstance(ax, str) else tuple(ax or ())
            for ov in eqn.outvars:
                write(ov, frozenset(ax))
            continue
        if name in COLLECTIVE_PRIMS:
            kind = COLLECTIVE_PRIMS[name]
            axes = frozenset(_axes_of(eqn))
            if kind in INVARIANT_KINDS:
                out_t = joined - axes
            else:
                out_t = joined | axes
            for ov in eqn.outvars:
                write(ov, out_t)
            continue
        subs = _sub_jaxprs(eqn.params)
        if name == "scan" and subs:
            # per-position carry fixpoint (a tainted carry can taint itself
            # on the next trip).  scan invars are [consts, init_carry, xs]
            # and outvars [final_carry, ys]; num_consts/num_carry let us
            # thread taints positionally instead of smearing a union over
            # every output (which falsely taints e.g. all grads with the
            # layer body's position-id axis_index).
            body = _as_jaxpr(eqn.params.get("jaxpr")) or subs[-1][1]
            nc = int(eqn.params.get("num_consts", 0))
            ncar = int(eqn.params.get("num_carry", 0))
            in_t = [read(v) for v in eqn.invars]
            if len(body.invars) == len(in_t) and ncar <= len(body.outvars):
                carry = in_t[nc:nc + ncar]
                out_t = _taint_jaxpr(body, in_t)
                for _ in range(16):
                    new = [carry[i] | out_t[i] for i in range(ncar)]
                    if new == carry:
                        break
                    carry = new
                    out_t = _taint_jaxpr(
                        body, in_t[:nc] + carry + in_t[nc + ncar:])
                for ov, t in zip(eqn.outvars, out_t):
                    write(ov, t)
            else:  # unexpected arity: conservative union
                for ov in eqn.outvars:
                    write(ov, joined)
            continue
        if name == "while" and subs:
            # while invars are [cond_consts, body_consts, init_carry]; the
            # body maps [body_consts, carry] -> [carry].
            body = _as_jaxpr(eqn.params.get("body_jaxpr"))
            cn = int(eqn.params.get("cond_nconsts", 0))
            bn = int(eqn.params.get("body_nconsts", 0))
            in_t = [read(v) for v in eqn.invars]
            bconsts, carry = in_t[cn:cn + bn], in_t[cn + bn:]
            if body is not None and len(body.invars) == bn + len(carry) \
                    and len(body.outvars) == len(carry):
                out_t = _taint_jaxpr(body, bconsts + carry)
                for _ in range(16):
                    new = [carry[i] | out_t[i] for i in range(len(carry))]
                    if new == carry:
                        break
                    carry = new
                    out_t = _taint_jaxpr(body, bconsts + carry)
                for ov, t in zip(eqn.outvars, out_t):
                    write(ov, t)
            else:
                for ov in eqn.outvars:
                    write(ov, joined)
            continue
        if subs:
            # generic call-like eqn (pjit / custom_vjp / cond / remat):
            # enter the (first) sub-jaxpr with operand taints when arities
            # line up, else degrade to the conservative union
            handled = False
            if len(subs) == 1:
                sub = subs[0][1]
                in_t = [read(v) for v in eqn.invars]
                if len(sub.invars) == len(in_t):
                    out_t = _taint_jaxpr(sub, in_t)
                    if len(out_t) == len(eqn.outvars):
                        for ov, t in zip(eqn.outvars, out_t):
                            write(ov, t)
                        handled = True
            if not handled:
                sub_union = frozenset()
                for _, sub in subs:
                    in_t = [read(v) for v in eqn.invars]
                    pad = [joined] * max(0, len(sub.invars) - len(in_t))
                    out_t = _taint_jaxpr(sub,
                                         (in_t + pad)[: len(sub.invars)])
                    sub_union |= (frozenset().union(*out_t) if out_t
                                  else frozenset())
                for ov in eqn.outvars:
                    write(ov, joined | sub_union)
            continue
        for ov in eqn.outvars:
            write(ov, joined)

    return [read(v) for v in jaxpr.outvars]


def replication_taints(closed_jaxpr, *, seed_inputs: bool = True) -> list:
    """Run the divergence sanitizer over every shard_map in a closed jaxpr.

    Returns a list of violation dicts: shard_map outputs that (per the
    conservative dataflow) may still vary over an axis their out_specs
    declare replicated.  ``seed_inputs=False`` restricts seeding to
    ``axis_index`` (the ISSUE's literal rule c); the default additionally
    seeds each input's sharded axes, which makes the pass a full
    replication checker for ``check_vma=False`` shard_maps.
    """
    prog = extract_ir(closed_jaxpr)
    violations = []
    for eqn, _mult, path in prog.shard_map_eqns:
        body = _as_jaxpr(eqn.params.get("jaxpr"))
        if body is None or eqn.params.get("check_vma"):
            continue  # with check_vma, jax verified the out_specs itself
        mesh = eqn.params.get("mesh")
        sizes = mesh_axis_sizes(mesh) if mesh is not None else {}
        # a size-1 axis cannot diverge (axis_index over it is constant 0)
        mesh_axes = {a for a, n in sizes.items() if n > 1}
        in_specs = eqn.params.get("in_specs", ())
        out_specs = eqn.params.get("out_specs", ())
        in_taints = []
        for i, v in enumerate(body.invars):
            if seed_inputs and i < len(in_specs):
                in_taints.append(frozenset(_spec_axes(in_specs[i])))
            else:
                in_taints.append(frozenset())
        out_taints = _taint_jaxpr(body, in_taints)
        for i, t in enumerate(out_taints):
            declared = _spec_axes(out_specs[i]) if i < len(out_specs) \
                else set()
            bad = (set(t) & mesh_axes) - declared
            if bad:
                violations.append({
                    "output": i, "axes": tuple(sorted(bad)),
                    "declared": tuple(sorted(declared)),
                    "path": path,
                })
    return violations
