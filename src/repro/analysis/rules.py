"""Rule checks over the extracted collective IR (DESIGN.md §13).

Each rule encodes one bug class this repo has actually shipped a fix for:

* ``check_mesh``       — collectives naming axes that do not exist on the
  declared mesh (caught at trace time for hand-written code, but synthetic /
  re-played IR and future lowering passes are not so protected).
* ``check_layouts``    — reductions or ZeRO partitions over a leaf's OWN
  sharding axes (PR 4: depth-sharded head/expert leaves were flat-sliced
  over ``depth`` again, orphaning chunks), and ZeRO leaves whose deferred
  psum still covers a zaxis (double reduction: the zreduce_scatter would
  re-reduce an already-reduced grad).
* ``check_grad_sync``  — the traced program must contain at least the fused
  grad reductions the step builder promised (StepBundle.shardcheck_meta):
  one psum per leaf per distinct replication axis-set, one reduce_scatter
  per ZeRO leaf.  PR 3's bug — the pipeline ``red()`` dropping ``pipe`` for
  stage-replicated leaves — shows up as the ``(..., 'pipe')`` set counting
  short.  Exact double-psum drift is caught by the SHARDCHECK.json baseline
  diff (counts here are >=: loss/metric psums legitimately share axis sets).
* ``check_divergence`` — the taint sanitizer: ``axis_index``-derived or
  input-sharded values flowing to a shard_map output declared replicated
  over an axis they still vary on (collective_ir.replication_taints).

Rules take the IR / meta as plain data so tests can feed deliberately
broken inputs that could never trace (jax rejects unknown axes itself).
"""
from __future__ import annotations

from dataclasses import dataclass

from .collective_ir import IRProgram, replication_taints


@dataclass(frozen=True)
class Finding:
    rule: str        # mesh | layout | gradsync | replication | commmodel
    entry: str       # swept entry-point name
    message: str

    def __str__(self):
        return f"[{self.rule}] {self.entry}: {self.message}"


def check_mesh(prog: IRProgram, mesh_axes, entry: str = "") -> list:
    """Every collective axis must exist on the declared mesh."""
    mesh_axes = set(mesh_axes)
    out = []
    for c in prog.collectives:
        unknown = [a for a in c.axes if a not in mesh_axes]
        if unknown:
            out.append(Finding(
                "mesh", entry,
                f"{c.key()} at {'/'.join(c.path) or '<top>'} names "
                f"axes {unknown} not on mesh {sorted(mesh_axes)}"))
    return out


def check_layouts(meta: dict, entry: str = "") -> list:
    """Per-leaf layout invariants from StepBundle.shardcheck_meta."""
    out = []
    for leaf in meta.get("leaves", ()):
        own = set(leaf["spec_axes"])
        red = set(leaf["reduce_axes"])
        zax = set(leaf["zaxes"])
        bad = red & own
        if bad:
            out.append(Finding(
                "layout", entry,
                f"{leaf['name']}: deferred grad psum over {sorted(bad)} "
                f"but the leaf is SHARDED over those axes (reducing would "
                f"sum distinct shards — PR 4 bug class)"))
        bad = zax & own
        if bad:
            out.append(Finding(
                "layout", entry,
                f"{leaf['name']}: ZeRO zaxes {sorted(bad)} overlap the "
                f"leaf's own sharding axes (flat-slicing a sharded leaf "
                f"over its shard axis orphans chunks — PR 4 bug class)"))
        bad = zax & red
        if bad:
            out.append(Finding(
                "layout", entry,
                f"{leaf['name']}: axes {sorted(bad)} appear in BOTH the "
                f"deferred grad psum and the ZeRO zaxes (double "
                f"reduction: zreduce_scatter re-reduces a reduced grad)"))
    return out


def check_grad_sync(prog: IRProgram, meta: dict, entry: str = "") -> list:
    """Extracted reductions must cover the builder's promised reductions."""
    out = []
    got_psum: dict = {}
    got_rs: dict = {}
    for c in prog.collectives:
        if c.kind == "psum" and c.axes:
            got_psum[c.axes] = got_psum.get(c.axes, 0) + c.mult
        elif c.kind == "psum_scatter" and c.axes:
            got_rs[c.axes] = got_rs.get(c.axes, 0) + c.mult
    for axes, want in meta.get("grad_psum_axes", {}).items():
        axes = tuple(sorted(axes))
        have = got_psum.get(axes, 0)
        if have < want:
            hint = (" — missing 'pipe' on a stage-replicated leaf?"
                    if "pipe" in axes else "")
            out.append(Finding(
                "gradsync", entry,
                f"expected >= {want} grad psum(s) over {axes}, traced "
                f"program has {have}{hint}"))
    for axes, want in meta.get("grad_rs_axes", {}).items():
        axes = tuple(sorted(axes))
        have = got_rs.get(axes, 0)
        if have < want:
            out.append(Finding(
                "gradsync", entry,
                f"expected >= {want} ZeRO reduce_scatter(s) over {axes}, "
                f"traced program has {have}"))
    return out


def check_divergence(closed_jaxpr, entry: str = "", *,
                      seed_inputs: bool = True) -> list:
    """Divergence sanitizer over every shard_map in the trace."""
    out = []
    for v in replication_taints(closed_jaxpr, seed_inputs=seed_inputs):
        out.append(Finding(
            "replication", entry,
            f"shard_map output #{v['output']} may vary over "
            f"{v['axes']} but its out_spec only shards {v['declared']} "
            f"(axis_index / sharded-input flow without an intervening "
            f"psum/all_gather)"))
    return out


def run_all(prog: IRProgram, meta: dict, closed_jaxpr=None,
            entry: str = "") -> list:
    """All structural rules for one traced entry point."""
    findings = check_mesh(prog, meta.get("mesh_axes", prog.axis_sizes),
                          entry)
    findings += check_layouts(meta, entry)
    findings += check_grad_sync(prog, meta, entry)
    if closed_jaxpr is not None:
        findings += check_divergence(closed_jaxpr, entry)
    return findings
