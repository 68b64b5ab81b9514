"""The trace reduction on a small recorded trace, checked by hand."""
from types import SimpleNamespace as NS

import pytest

from chip import trace_reduce as T


def ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur,
              stats=list(stats.items()))


def line(name, events):
    return NS(name=name, events=events)


def plane(name, lines):
    return NS(name=name, lines=lines)


# window 0..100 ns on the host; chip 0 runs a loop 10-70 around fusion
# 10-30, a flash kernel 30-50 and an all-reduce 45-70 (overlapping the
# kernel for 5), idle 70-90, fusion 90-95; chip 1 runs one op 0-100.  Host
# spans: the step 5-60, the prefetcher 62-88.
PLANES = [
    plane("/host:CPU", [line("python", [
        ev("bench.window", 0, 100), ev("bench.train_step", 5, 55),
        ev("bench.prefetch_next", 62, 26), ev("other", 0, 100)])]),
    plane("/device:TPU:0", [
        line("XLA Modules", [ev("jit_step", 10, 60), ev("jit_f", 90, 5)]),
        line("XLA Ops", [ev("%while.4 = (f32[2]) while(...)", 10, 60),
                         ev("%fusion.1 = f32[8] fusion(%x)", 10, 20),
                         ev("%_flash_jit.7 = bf16[8] custom-call()", 30, 20),
                         ev("%all-reduce.3 = f32[8] all-reduce(%_flash_jit.7)",
                            45, 25),
                         ev("%fusion.2 = f32[8] fusion(%_flash_jit.7)",
                            90, 5)])]),
    plane("/device:TPU:1", [
        line("XLA Ops", [ev("fusion.9", -10, 120)])]),
]


@pytest.fixture()
def red():
    return T.reduce_planes(PLANES)


def test_window_and_busy(red):
    assert red.window == (0, 100)
    assert red.window_s == pytest.approx(100e-9)
    # chip 0 busy 10-70 and 90-95 = 65; chip 1 clipped to 100
    assert red.busy_s() == pytest.approx((65 + 100) / 2 * 1e-9)


def test_idle_gaps_labelled_by_host_span(red):
    gaps = red.idle_gaps()
    assert gaps[0] == ["prefetch_next", pytest.approx(20e-9)]   # 70-90
    # 0-10: the step span covers 5 of it, more than any other span
    assert gaps[1] == ["train_step", pytest.approx(10e-9)]
    assert gaps[2] == ["none", pytest.approx(5e-9)]             # 95-100
    assert len(gaps) == 3


def test_kernel_sums_and_top_ops(red):
    # by the instruction's own name: fusion.2 only reads the kernel's output
    t = red.op_seconds(lambda e: "flash" in e.op)
    assert t == pytest.approx(20e-9 / 2)          # averaged over 2 chips
    top = red.top_ops()                           # the loop is not an op
    assert top[0] == ["%all-reduce.3 = f32[8] all-reduce(%_flash_jit.7)",
                      pytest.approx(25e-9)]
    assert len(top) == 4


def test_collective_exposure(red):
    # chip 0: all-reduce 45-70, compute until 50 -> exposed 20; chip 1: 0
    assert red.collective_exposed_s() == pytest.approx(20e-9 / 2)


def test_module_runs(red):
    runs = red.module_runs(0)
    assert [m.name for m, _ in runs] == ["jit_step", "jit_f"]
    assert [o.op for o in runs[0][1]] == ["fusion.1", "_flash_jit.7",
                                          "all-reduce.3"]
    assert [o.op for o in runs[1][1]] == ["fusion.2"]


def test_no_window_is_an_error():
    with pytest.raises(ValueError):
        T.reduce_planes(PLANES[1:])


def test_union_and_intersect():
    assert T.union([(5, 7), (1, 3), (2, 4)]) == [(1, 4), (5, 7)]
    assert T.intersect([(1, 4), (5, 9)], [(3, 6)]) == [(3, 4), (5, 6)]
