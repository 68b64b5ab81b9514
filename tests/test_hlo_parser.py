"""roofline/hlo.py parser edge cases on synthetic HLO text.

The parser's job is structural: split computations, resolve %name operands,
multiply while bodies by their condition bound, and NOT double-count fusion
interiors.  Real compiled HLO is exercised by the roofline benchmarks; these
tests pin the parsing corners that broke (or nearly broke) while landing
them: tuple-typed outputs carrying ``/*index=N*/`` comments, nested while
loops, and dots living inside fused computations.
"""
import textwrap

import pytest

from repro.roofline.hlo import (analyze_hlo, collective_stats,
                                split_computations, total_collective_bytes)


def _mod(body: str) -> str:
    return textwrap.dedent(body).strip() + "\n"


FUSION = _mod("""
    HloModule fusion_guard

    %fused_dot (p0.1: f32[4,8], p1.1: f32[8,4]) -> f32[4,4] {
      %p0.1 = f32[4,8]{1,0} parameter(0)
      %p1.1 = f32[8,4]{1,0} parameter(1)
      ROOT %dot.f = f32[4,4]{1,0} dot(%p0.1, %p1.1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
    }

    ENTRY %main (a: f32[4,8], b: f32[8,4]) -> f32[4,4] {
      %a = f32[4,8]{1,0} parameter(0)
      %b = f32[8,4]{1,0} parameter(1)
      ROOT %fus = f32[4,4]{1,0} fusion(%a, %b), kind=kOutput, calls=%fused_dot
    }
""")


def test_fusion_interior_counted_once():
    got = analyze_hlo(FUSION, n_devices=1)
    # one dot: 2 * 16 out elems * k=8 — via the fused computation ONLY, not
    # re-counted for the top-level fusion instruction
    assert got["flops"] == 2 * 16 * 8
    assert got["collectives"] == {}


NESTED_WHILE = _mod("""
    HloModule nested_while

    %inner_body (p.i: (s32[], f32[4,4])) -> (s32[], f32[4,4]) {
      %p.i = (s32[], f32[4,4]{1,0}) parameter(0)
      %i = s32[] get-tuple-element(%p.i), index=0
      %one = s32[] constant(1)
      %ip = s32[] add(%i, %one)
      %x = f32[4,4]{1,0} get-tuple-element(%p.i), index=1
      %d = f32[4,4]{1,0} dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
      ROOT %t.i = (s32[], f32[4,4]{1,0}) tuple(%ip, %d)
    }

    %inner_cond (p.ic: (s32[], f32[4,4])) -> pred[] {
      %p.ic = (s32[], f32[4,4]{1,0}) parameter(0)
      %i.c = s32[] get-tuple-element(%p.ic), index=0
      %five = s32[] constant(5)
      ROOT %lt.i = pred[] compare(%i.c, %five), direction=LT
    }

    %outer_body (p.o: (s32[], f32[4,4])) -> (s32[], f32[4,4]) {
      %p.o = (s32[], f32[4,4]{1,0}) parameter(0)
      %j = s32[] get-tuple-element(%p.o), index=0
      %one.o = s32[] constant(1)
      %jp = s32[] add(%j, %one.o)
      %y = f32[4,4]{1,0} get-tuple-element(%p.o), index=1
      %zero = s32[] constant(0)
      %init = (s32[], f32[4,4]{1,0}) tuple(%zero, %y)
      %w.i = (s32[], f32[4,4]{1,0}) while(%init), condition=%inner_cond, body=%inner_body
      %y2 = f32[4,4]{1,0} get-tuple-element(%w.i), index=1
      ROOT %t.o = (s32[], f32[4,4]{1,0}) tuple(%jp, %y2)
    }

    %outer_cond (p.oc: (s32[], f32[4,4])) -> pred[] {
      %p.oc = (s32[], f32[4,4]{1,0}) parameter(0)
      %j.c = s32[] get-tuple-element(%p.oc), index=0
      %three = s32[] constant(3)
      ROOT %lt.o = pred[] compare(%j.c, %three), direction=LT
    }

    ENTRY %main (v: f32[4,4]) -> f32[4,4] {
      %v = f32[4,4]{1,0} parameter(0)
      %zero.e = s32[] constant(0)
      %init.e = (s32[], f32[4,4]{1,0}) tuple(%zero.e, %v)
      %w.o = (s32[], f32[4,4]{1,0}) while(%init.e), condition=%outer_cond, body=%outer_body
      ROOT %out = f32[4,4]{1,0} get-tuple-element(%w.o), index=1
    }
""")


def test_nested_while_trip_counts_multiply():
    got = analyze_hlo(NESTED_WHILE, n_devices=1)
    # inner dot: 2 * 16 * 4 flops, x5 (inner bound) x3 (outer bound);
    # the body-local constant(1) counters must NOT leak into trip counts
    assert got["flops"] == 2 * 16 * 4 * 5 * 3


TUPLE_COLLECTIVES = _mod("""
    HloModule tuple_collectives

    ENTRY %main (x: f32[2,4], y: f32[2,4]) -> f32[8,4] {
      %x = f32[2,4]{1,0} parameter(0)
      %y = f32[2,4]{1,0} parameter(1)
      %ag = (f32[8,4]{1,0} /*index=0*/, f32[8,4]{1,0} /*index=1*/) all-gather(%x, %y), replica_groups={{0,1,2,3}}, dimensions={0}
      %g0 = f32[8,4]{1,0} get-tuple-element(%ag), index=0
      %g1 = f32[8,4]{1,0} get-tuple-element(%ag), index=1
      %s = f32[8,4]{1,0} add(%g0, %g1)
      ROOT %ar = f32[8,4]{1,0} all-reduce(%s), replica_groups=[2,4]<=[8]T(1,0), to_apply=%sum
    }
""")


def test_tuple_output_with_index_comments():
    comps = split_computations(TUPLE_COLLECTIVES)
    assert "main" in comps
    stats = collective_stats(TUPLE_COLLECTIVES, n_devices=8)
    # tuple-typed all-gather output: BOTH leaves (2 x f32[8,4] = 256 B)
    # count toward wire bytes, group size 4 parsed from the {{...}} list
    ag = stats["all-gather"]
    assert ag["count"] == 1
    assert ag["wire_bytes"] == 256 * (4 - 1) / 4
    # bracket-form replica_groups=[2,4]: group size is the SECOND number
    ar = stats["all-reduce"]
    ob = 8 * 4 * 4
    assert ar["wire_bytes"] == 2 * ob * (4 - 1) / 4
    ob_total, wb_total = total_collective_bytes(stats)
    assert ob_total == (2 * 2 * 4 * 4) + ob
    assert wb_total == ag["wire_bytes"] + ar["wire_bytes"]


def test_while_trip_count_defaults_to_one_without_condition_constant():
    mod = _mod("""
        HloModule degenerate

        %b (p: f32[2,2]) -> f32[2,2] {
          %p = f32[2,2]{1,0} parameter(0)
          ROOT %d = f32[2,2]{1,0} dot(%p, %p), lhs_contracting_dims={1}, rhs_contracting_dims={0}
        }

        %c (p.c: f32[2,2]) -> pred[] {
          %p.c = f32[2,2]{1,0} parameter(0)
          ROOT %k = pred[] custom-call(%p.c), custom_call_target="done"
        }

        ENTRY %main (v: f32[2,2]) -> f32[2,2] {
          %v = f32[2,2]{1,0} parameter(0)
          ROOT %w = f32[2,2]{1,0} while(%v), condition=%c, body=%b
        }
    """)
    assert analyze_hlo(mod, n_devices=1)["flops"] == 2 * 4 * 2


@pytest.mark.parametrize("mod", [FUSION, NESTED_WHILE, TUPLE_COLLECTIVES],
                         ids=["fusion", "nested_while", "tuple_collectives"])
def test_tpu_tiled_layouts_parse_as_plain_ones(mod):
    # the TPU compiler prints tiles inside the layout, parentheses and all
    tpu = (mod.replace("{1,0}", "{1,0:T(8,128)}")
           .replace("s32[] ", "s32[]{:T(128)} "))
    assert tpu != mod
    assert analyze_hlo(tpu, n_devices=8) == analyze_hlo(mod, n_devices=8)
