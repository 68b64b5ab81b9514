"""Structural analysis of compiled (SPMD-partitioned) HLO text.

Why not ``compiled.cost_analysis()``: XLA's cost analysis visits a while-loop
body ONCE, so anything under ``lax.scan`` (the layer stack, CE chunks,
attention KV blocks) is undercounted by its trip count.  The same applies to
collective bytes.  This module parses the HLO text into computations, builds
a per-computation symbol table (operands are %name references), finds
while-loop trip counts from their condition computations, and aggregates

    flops            — 2 * prod(out_dims) * prod(lhs contracting dims) per dot
    collective bytes — per collective kind: operand bytes + ring-model wire
                       bytes using the parsed replica-group size
    hbm bytes        — outputs + operands of top-level ops (fusion interiors
                       not double-counted)

multiplying every called computation by its trip count.  Elementwise flops
are ignored (these workloads are dot-dominated; noted in EXPERIMENTS.md).

``instructions`` lists the module's instructions with their op_name (the
JAX name stack), and ``scope_of`` reads an op's layer scope from it.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field

from ..models.common import LAYER_SCOPES

DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s4": 1, "u4": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "token": 0,
}

COLLECTIVE_KINDS = ("all-gather", "all-reduce", "reduce-scatter",
                    "all-to-all", "collective-permute")

_SHAPE = re.compile(r"\b(\w+?)\[([\d,]*)\]")
_COMP_HEADER = re.compile(r"^(ENTRY\s+)?%?([\w.\-_]+)\s*\(.*\{\s*$")
# output type: a tuple "(...)" (may contain /*index=N*/ comments, TPU tiled
# layouts such as {1,0:T(8,128)(2,1)} and a nested tuple) or an array type
# with its layout (no spaces)
_TUPLE = r"\((?:[^()]|\((?:[^()]|\([^()]*\))*\))*\)"
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-_]+)\s*=\s*"
                    r"(" + _TUPLE + r"|\S+)\s+([a-z][\w\-]*)\(")
_OPERANDS = re.compile(r"%([\w.\-_]+)")
_CONTRACT = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_CONST = re.compile(r"constant\((\d+)\)")
_GROUPS_BRACKET = re.compile(r"replica_groups=\{?\[(\d+),(\d+)\]")
_GROUPS_LIST = re.compile(r"replica_groups=\{\{([\d,]+)\}")


def _shapes_in(s: str):
    out = []
    for m in _SHAPE.finditer(s):
        dt, dims = m.group(1), m.group(2)
        if dt not in DTYPE_BYTES:
            continue
        d = [int(x) for x in dims.split(",")] if dims else []
        out.append((dt, d))
    return out


def _bytes_of(shapes) -> int:
    total = 0
    for dt, dims in shapes:
        n = 1
        for d in dims:
            n *= d
        total += n * DTYPE_BYTES[dt]
    return total


@dataclass
class CompStats:
    flops: float = 0.0
    hbm_bytes: float = 0.0
    dot_bytes: float = 0.0      # operand+output traffic of dot ops only
    coll: dict = field(default_factory=dict)
    calls: list = field(default_factory=list)   # (callee, cond_or_None, kind)
    constants: list = field(default_factory=list)


def _coll_add(coll, kind, count=0, ob=0.0, wb=0.0, mult=1.0):
    c = coll.setdefault(kind, dict(count=0.0, operand_bytes=0.0,
                                   wire_bytes=0.0))
    c["count"] += mult * count
    c["operand_bytes"] += mult * ob
    c["wire_bytes"] += mult * wb


def split_computations(text: str):
    comps = {}
    name, buf = None, []
    for line in text.splitlines():
        if name is None:
            st = line.strip()
            if st.endswith("{") and ("->" in st or st.startswith("ENTRY")):
                m = _COMP_HEADER.match(st)
                if m:
                    name = m.group(2)
                    buf = []
            continue
        if line.strip() == "}":
            comps[name] = buf
            name = None
        else:
            buf.append(line)
    return comps


_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
# attributes naming computations that run as ops of their own (loop bodies,
# branches, calls); fusions and reductions name computations that do not
_RUN_CALLS = re.compile(
    r"\b(?:body|condition|true_computation|false_computation)=%?([\w.\-]+)"
    r"|\bbranch_computations=\{([^}]*)\}")


@dataclass
class Instr:
    name: str          # the instruction: ``fusion.3`` of ``%fusion.3 = ...``
    opcode: str
    op_name: str       # its metadata's op_name (the JAX name stack), or ""
    fused: tuple       # a fusion's: the op_names of the ops fused into it
    runs: bool         # a top-level op: in a computation that runs as ops


def instructions(text: str) -> list:
    """Every instruction of an HLO module's text with its op_name.  The
    device trace shows the ``runs`` ones: those of the entry computation,
    of loop bodies and conditions, of branches and of called computations,
    not the inside of a fusion or a reduction's region."""
    parsed = {}
    for cname, lines in split_computations(text).items():
        rows = []
        for line in lines:
            mo = _INSTR.match(line)
            if mo:
                on = _OP_NAME.search(line)
                rows.append((mo.group(1), mo.group(3),
                             on.group(1) if on else "", line))
        parsed[cname] = rows

    def fused_names(line, seen=()):
        out = []
        for c in re.findall(r"\bcalls=%?([\w.\-]+)", line):
            for _n, op, o, inner in parsed.get(c, ()) if c not in seen else ():
                out += [o] if o else []
                if op == "fusion":
                    out += fused_names(inner, seen + (c,))
        return out

    m = re.search(r"^ENTRY\s+%?([\w.\-_]+)", text, re.M)
    run, todo = set(), [m.group(1)] if m else []
    while todo:
        c = todo.pop()
        if c in run or c not in parsed:
            continue
        run.add(c)
        for _n, op, _o, line in parsed[c]:
            for a, b in _RUN_CALLS.findall(line):
                todo += [a] if a else [x.strip().lstrip("%")
                                       for x in b.split(",")]
            if op in ("call", "async-start"):
                todo += re.findall(r"\b(?:to_apply|calls)=%?([\w.\-]+)",
                                   line)
    return [Instr(n, op, o,
                  tuple(fused_names(line)) if op == "fusion" else (),
                  c in run)
            for c, rows in parsed.items() for n, op, o, line in rows]


# --- an op's layer, from its name stack ---------------------------------
# the scope jax.checkpoint gives the ops it recomputes in the backward pass
REMAT_SCOPE = "rematted_computation"


def _name_stack(op_name: str) -> list:
    """The scopes of an op_name, transform wrappers taken off:
    ``jit(f)/transpose(jvp(attn))/dot_general`` -> [f, attn, dot_general]."""
    out = []
    for seg in op_name.split(";", 1)[0].split("/"):
        while seg.endswith(")") and "(" in seg:
            seg = seg[seg.index("(") + 1:-1]
        out.append(seg)
    return out


def layer_scope(op_name: str):
    """The innermost of the model's ``LAYER_SCOPES`` in an op's name
    stack, or None."""
    for seg in reversed(_name_stack(op_name)):
        if seg in LAYER_SCOPES:
            return seg
    return None


def rematted(op_name: str) -> bool:
    """True for an op that remat recomputes in the backward pass."""
    return REMAT_SCOPE in _name_stack(op_name)


def scope_of(op_name: str, fused=()) -> tuple:
    """(layer scope or None, rematted) of an op: from its own op_name where
    that names a layer, else from those of the ops fused into it (a
    multi-output fusion's tuple root has none; the SPMD partitioner names
    some ops after themselves) by what most of them that name a layer say,
    the first on a tie; (None, False) where none does."""
    if layer_scope(op_name) is not None:
        return layer_scope(op_name), rematted(op_name)
    votes = {}
    for o in fused:
        k = (layer_scope(o), rematted(o))
        if k[0] is not None:
            votes[k] = votes.get(k, 0) + 1
    return max(votes, key=votes.get) if votes else (None, False)


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_BRACKET.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_LIST.search(line)
    if m:
        return len(m.group(1).split(","))
    return default


def _analyze_comp(lines, n_devices):
    st = CompStats()
    symtab = {}
    # producing-op kind + pre-convert source shapes: XLA:CPU float
    # normalization promotes bf16 collectives to f32 via converts; on TPU
    # the wire stays bf16, so collectives resolve operands THROUGH converts
    # (one level) to reflect the intended wire dtype.
    conv_src = {}
    for line in lines:
        mo = _INSTR.match(line)
        if not mo:
            for c in _CONST.finditer(line):
                st.constants.append(int(c.group(1)))
            continue
        name, out_s, op = mo.group(1), mo.group(2), mo.group(3)
        out_shapes = _shapes_in(out_s)
        symtab[name] = out_shapes
        rest = line[mo.end():]
        # operand name references (stop before attribute section heuristics)
        opnames = []
        depth = 1
        arglist = []
        for ch_i, ch in enumerate(rest):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    arglist = rest[:ch_i]
                    break
        else:
            arglist = rest
        opnames = _OPERANDS.findall(arglist)
        operand_shapes = []
        for on in opnames:
            operand_shapes.extend(symtab.get(on, []))
        # typed operands (parameters appear typed inline in some dumps)
        if not operand_shapes:
            operand_shapes = _shapes_in(arglist)
        if op == "convert" or (op == "fusion" and "convert" in line):
            src = []
            for on in opnames:
                src.extend(conv_src.get(on, symtab.get(on, [])))
            if src:
                conv_src[name] = src

        for c in _CONST.finditer(line):
            st.constants.append(int(c.group(1)))

        if op == "dot":
            cm = _CONTRACT.search(line)
            lhs = symtab.get(opnames[0], None) if opnames else None
            if lhs is None:
                ls = _shapes_in(arglist)
                lhs = [ls[0]] if ls else None
            if out_shapes and lhs and cm is not None:
                out_elems = 1
                for d in out_shapes[0][1]:
                    out_elems *= d
                k = 1
                for ci in (int(x) for x in cm.group(1).split(",") if x):
                    dims = lhs[0][1]
                    if ci < len(dims):
                        k *= dims[ci]
                st.flops += 2.0 * out_elems * k
            db = _bytes_of(out_shapes) + _bytes_of(operand_shapes)
            st.hbm_bytes += db
            st.dot_bytes += db
            continue

        kind = op.replace("-start", "").replace("-done", "")
        if kind in COLLECTIVE_KINDS and not op.endswith("-done"):
            # wire-dtype intent: resolve operands through converts
            wire_shapes = []
            for on in opnames:
                wire_shapes.extend(conv_src.get(on, symtab.get(on, [])))
            if not wire_shapes:
                wire_shapes = operand_shapes
            ob = min(_bytes_of(operand_shapes), _bytes_of(wire_shapes)) \
                if wire_shapes else _bytes_of(operand_shapes)
            out_b = _bytes_of(out_shapes)
            n = _group_size(line, n_devices)
            frac = (n - 1) / n if n > 1 else 0.0
            if kind == "all-gather":
                wire = out_b * frac
            elif kind == "all-reduce":
                wire = 2 * ob * frac
            elif kind in ("reduce-scatter", "all-to-all"):
                wire = ob * frac
            else:
                wire = ob
            _coll_add(st.coll, kind, 1, ob, wire)
            st.hbm_bytes += out_b + ob
            continue

        if op == "while":
            bm = re.search(r"body=%?([\w.\-_]+)", line)
            cm2 = re.search(r"condition=%?([\w.\-_]+)", line)
            st.calls.append((bm.group(1) if bm else None,
                             cm2.group(1) if cm2 else None, "while"))
            continue
        called = re.findall(r"(?:calls=|to_apply=)%?([\w.\-_]+)", line)
        for c in called:
            st.calls.append((c, None, "call"))
        if op == "conditional":
            for c in re.findall(
                    r"(?:true_computation=|false_computation=|branch_computations=\{)%?([\w.\-_,%\s]+)",
                    line):
                for cc in re.split(r"[,\s]+", c):
                    cc = cc.strip().lstrip("%")
                    if cc:
                        st.calls.append((cc, None, "call"))
        # top-level op HBM traffic (fusion interiors handled via calls only
        # for flops/collectives; bytes use the fusion's own params/outputs)
        if op in ("fusion",):
            st.hbm_bytes += _bytes_of(out_shapes) + _bytes_of(operand_shapes)
        elif op not in ("parameter", "constant", "get-tuple-element",
                        "tuple", "bitcast", "while", "copy"):
            st.hbm_bytes += _bytes_of(out_shapes) + _bytes_of(operand_shapes)
    return st


def _trip_count(comps, raw, cond_name) -> int:
    """Max constant visible in the condition computation (+1 level deep)."""
    if cond_name not in raw:
        return 1
    consts = list(raw[cond_name].constants)
    for callee, _c, _k in raw[cond_name].calls:
        if callee in raw:
            consts.extend(raw[callee].constants)
    return max(consts) if consts else 1


def analyze_hlo(text: str, n_devices: int):
    comps = split_computations(text)
    raw = {n: _analyze_comp(l, n_devices) for n, l in comps.items()}
    memo = {}

    def total(name, stack=()):
        if name not in raw or name in stack:
            return CompStats()
        if name in memo:
            return memo[name]
        st = raw[name]
        agg = CompStats(flops=st.flops, hbm_bytes=st.hbm_bytes,
                        dot_bytes=st.dot_bytes)
        for k, v in st.coll.items():
            _coll_add(agg.coll, k, v["count"], v["operand_bytes"],
                      v["wire_bytes"])
        for callee, cond, kind in st.calls:
            if callee is None:
                continue
            sub = total(callee, stack + (name,))
            mult = _trip_count(comps, raw, cond) if kind == "while" else 1
            agg.flops += mult * sub.flops
            agg.hbm_bytes += mult * sub.hbm_bytes
            agg.dot_bytes += mult * sub.dot_bytes
            for k, v in sub.coll.items():
                _coll_add(agg.coll, k, v["count"], v["operand_bytes"],
                          v["wire_bytes"], mult=mult)
        memo[name] = agg
        return agg

    m = re.search(r"^ENTRY\s+%?([\w.\-_]+)", text, re.M)
    entry = m.group(1) if m else next((n for n in comps if "main" in n), None)
    agg = total(entry) if entry else CompStats()
    return dict(flops=agg.flops, hbm_bytes=agg.hbm_bytes,
                dot_bytes=agg.dot_bytes, collectives=dict(agg.coll))


# ---------------------------------------------------------------------------

def collective_stats(hlo_text: str, n_devices: int):
    return analyze_hlo(hlo_text, n_devices)["collectives"]


def total_collective_bytes(stats) -> tuple:
    ob = sum(s["operand_bytes"] for s in stats.values())
    wb = sum(s["wire_bytes"] for s in stats.values())
    return ob, wb
