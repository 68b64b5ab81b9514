"""The program's names for the profiler: the layer scopes as an op's name
stack reads them and the HLO instructions that carry them (the compiled
train step's ops are checked against the scopes where the TPU compiler is
at hand: ``test_tpu_compile.py``); and the data feed, which makes each
step's batch once."""
import threading
import time

import numpy as np
import pytest

import jax

from repro.data.pipeline import Prefetcher, SyntheticLMStream
from repro.models.common import LAYER_SCOPES, TP_COMM
from repro.roofline.hlo import (in_scope, instructions, layer_scope,
                                rematted, scope_of)

STEP = "jit(local_step)/transpose(jvp(blocks))/while/body/closed_call"


@pytest.mark.parametrize("op_name,scope,remat", [
    (f"{STEP}/checkpoint/rematted_computation/attn/dot_general", "attn",
     True),
    (f"{STEP}/checkpoint/mlp/dot_general", "mlp", False),
    # innermost wins, through transform wrappers
    ("jit(local_step)/transpose(jvp(mlp))/mul", "mlp", False),
    (f"{STEP}/dynamic_update_slice", "blocks", False),
    ("jit(local_step)/jvp(embed)/jit(_take)/gather", "embed", False),
    ("jit(local_step)/transpose(jvp(head_loss))/reduce_sum", "head_loss",
     False),
    # the first of names joined by ';' speaks
    ("jit(local_step)/optimizer/mul;jit(local_step)/shard_map", "optimizer",
     False),
    ("jit(local_step)/jvp()/div", None, False),
    ("", None, False),
])
def test_layer_scope(op_name, scope, remat):
    assert layer_scope(op_name) == scope
    assert rematted(op_name) == remat
    assert scope_of(op_name) == (scope, remat)


def test_scope_of_fused_ops():
    attn = f"{STEP}/checkpoint/rematted_computation/attn/mul"
    mlp = f"{STEP}/checkpoint/mlp/mul"
    # its own op_name, where it names a layer, before the fused ops'
    assert scope_of(mlp, (attn, attn)) == ("mlp", False)
    # else the most of the fused ops' that name a layer
    assert scope_of("", (attn, mlp, attn)) == ("attn", True)
    assert scope_of("jit(local_step)/shard_map/convert.25",
                    ("jit(local_step)/shard_map/convert.26", mlp)) == \
        ("mlp", False)
    assert scope_of("", ("jit(local_step)/lt",)) == (None, False)
    assert set(LAYER_SCOPES) == {"embed", "blocks", "attn", "mlp",
                                 "head_loss", "optimizer"}


def test_in_scope():
    gather = (f"{STEP}/checkpoint/rematted_computation/attn/tp_comm/"
              "all_gather_invariant")
    assert in_scope(gather, (), TP_COMM)
    # an async start names no op itself; the collective fused into it does
    assert in_scope("", (f"{STEP}/mlp/mul", gather), TP_COMM)
    # through transform wrappers, a whole segment
    assert in_scope("jit(step)/transpose(jvp(tp_comm))/reduce_scatter", (),
                    TP_COMM)
    assert not in_scope(f"{STEP}/attn/tp_comm_x/mul", (), TP_COMM)
    assert not in_scope(f"{STEP}/attn/dot_general", (f"{STEP}/mlp/mul",),
                        TP_COMM)
    # not a layer: the op belongs to the layer scope around it
    assert TP_COMM not in LAYER_SCOPES
    assert scope_of(gather) == ("attn", True)


HLO = """HloModule jit_step, is_scheduled=true

%fused_computation.1 (param_0: bf16[4,128]) -> (bf16[4,128], bf16[4,128]) {
  %param_0 = bf16[4,128]{1,0:T(4,128)(2,1)} parameter(0)
  %mul.1 = bf16[4,128]{1,0:T(4,128)(2,1)} multiply(%param_0, %param_0), metadata={op_name="jit(step)/transpose(jvp(attn))/mul"}
  %neg.1 = bf16[4,128]{1,0:T(4,128)(2,1)} negate(%param_0), metadata={op_name="jit(step)/transpose(jvp(attn))/neg"}
  ROOT %tuple.1 = (bf16[4,128]{1,0:T(4,128)(2,1)}, bf16[4,128]{1,0:T(4,128)(2,1)}) tuple(%mul.1, %neg.1)
}

%body.2 (p: (s32[], bf16[4,128])) -> (s32[], bf16[4,128]) {
  %p = (s32[], bf16[4,128]{1,0:T(4,128)(2,1)}) parameter(0)
  %gte.1 = bf16[4,128]{1,0:T(4,128)(2,1)} get-tuple-element(%p), index=1
  %fusion.7 = (bf16[4,128]{1,0:T(4,128)(2,1)}, bf16[4,128]{1,0:T(4,128)(2,1)}) fusion(%gte.1), kind=kLoop, calls=%fused_computation.1
  %gte.0 = s32[] get-tuple-element(%p), index=0
  %gte.2 = bf16[4,128]{1,0:T(4,128)(2,1)} get-tuple-element(%fusion.7), index=0
  ROOT %tuple.2 = (s32[], bf16[4,128]{1,0:T(4,128)(2,1)}) tuple(%gte.0, %gte.2)
}

%cond.3 (p.1: (s32[], bf16[4,128])) -> pred[] {
  %p.1 = (s32[], bf16[4,128]{1,0:T(4,128)(2,1)}) parameter(0)
  %gte.3 = s32[] get-tuple-element(%p.1), index=0
  %c.1 = s32[] constant(4)
  ROOT %lt.1 = pred[] compare(%gte.3, %c.1), direction=LT
}

ENTRY %main.4 (x: bf16[4,128]) -> bf16[4,128] {
  %x = bf16[4,128]{1,0:T(4,128)(2,1)} parameter(0)
  %c.0 = s32[] constant(0)
  %t = (s32[], bf16[4,128]{1,0:T(4,128)(2,1)}) tuple(%c.0, %x)
  %while.5 = (s32[], bf16[4,128]{1,0:T(4,128)(2,1)}) while(%t), condition=%cond.3, body=%body.2
  %flash_fwd.1 = bf16[4,128]{1,0:T(4,128)(2,1)} custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(attn)/jit(_flash_jit)/flash_fwd/pallas_call"}
  ROOT %gte.9 = bf16[4,128]{1,0:T(4,128)(2,1)} get-tuple-element(%while.5), index=1
}
"""


def test_hlo_instructions():
    ins = {i.name: i for i in instructions(HLO)}
    # the loop body, its condition and the entry run; a fusion's inside not
    assert {n for n, i in ins.items() if i.runs} == {
        "x", "c.0", "t", "while.5", "flash_fwd.1", "gte.9", "p", "gte.1",
        "fusion.7", "gte.0", "gte.2", "tuple.2", "p.1", "gte.3", "c.1",
        "lt.1"}
    assert not ins["mul.1"].runs
    f = ins["fusion.7"]
    assert (f.opcode, f.op_name) == ("fusion", "")
    assert f.fused == ("jit(step)/transpose(jvp(attn))/mul",
                       "jit(step)/transpose(jvp(attn))/neg")
    assert scope_of(f.op_name, f.fused) == ("attn", False)
    k = ins["flash_fwd.1"]
    assert k.opcode == "custom-call"
    assert scope_of(k.op_name, k.fused) == ("attn", False)


class CountingStream(SyntheticLMStream):
    """Counts the batches made, per step."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.made: dict = {}
        self.lock = threading.Lock()

    def batch(self, step, *, train=True):
        with self.lock:
            self.made[step] = self.made.get(step, 0) + 1
        return super().batch(step, train=train)


def _shardings():
    s = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    return {"tokens": s, "labels": s}


def test_prefetcher_makes_each_batch_once():
    # depth 1 and a consumer slower than the producer's 1 s put timeout:
    # the queue is full when a put times out, and the batch it holds is
    # the one put again
    stream = CountingStream(64, 2, 8, seed=3)
    pf = Prefetcher(stream, _shardings(), depth=1)
    try:
        got = []
        for _ in range(3):
            time.sleep(1.3)
            step, batch = pf.next()
            got.append(step)
            np.testing.assert_array_equal(np.asarray(batch["tokens"]),
                                          stream.batch(step)["tokens"])
    finally:
        pf.stop()
    assert got == [0, 1, 2]
    # each step made once by the producer (and once more above, to compare)
    assert all(stream.made[s] == 2 for s in got)
    assert all(n == 1 for s, n in stream.made.items() if s not in got)

