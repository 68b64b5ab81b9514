"""Compile a cell's programs for a described TPU v5e and print their memory.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/aot_size.py <workload> \
        [--batch B] [--slots N --blocks P]

Nothing runs: the TPU compiler, which is installed without the chip,
compiles each program the cell's window calls (the train step; or the
paged decode step and the largest prefill bucket with its reshard) at the
cell's sizes on a described ``v5e:2x2`` (one of its chips, or all four),
and prints ``memory_analysis()``.  The sizes chosen with it, and the
bytes it gave, are written in the cell's configuration and traffic files.
"""
from __future__ import annotations

import argparse
import os
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]


def described_devices(n: int):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return list(topo.devices)[:n]


def report(tag: str, compiled) -> dict:
    ma = compiled.memory_analysis()
    out = {k: int(getattr(ma, k)) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "alias_size_in_bytes", "temp_size_in_bytes",
        "generated_code_size_in_bytes")}
    live = (out["argument_size_in_bytes"] + out["output_size_in_bytes"]
            - out["alias_size_in_bytes"] + out["temp_size_in_bytes"])
    kernels = compiled.as_text().count("tpu_custom_call")
    print(f"{tag}: {out} -> arguments + outputs - aliased + temporaries "
          f"= {live / 1e9:.3f} GB per chip; {kernels} kernel calls",
          flush=True)
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("workload")
    ap.add_argument("--batch", type=int, help="train: global batch")
    ap.add_argument("--slots", type=int, help="serve: n_slots")
    ap.add_argument("--blocks", type=int, help="serve: num_blocks")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(CHECKOUT / "benchmarks"), str(CHECKOUT / "src")]
    import jax
    if jax.default_backend() == "tpu":
        sys.exit("run this without the chip (JAX_PLATFORMS=cpu)")
    jax.config.update("jax_enable_compilation_cache", False)
    # steer the program's backend probes to the described chip: the
    # kernels then compile for the TPU instead of the CPU interpreter
    jax.default_backend = lambda: "tpu"

    from chip import system
    from chip.harness import Cell
    cell = Cell.find(args.workload)
    tr = cell.traffic
    devices = described_devices(cell.chips)
    if tr["loop"] == "train":
        from repro.configs.base import ShapeSpec
        from repro.runtime.steps import build_train_step
        B = args.batch or tr["global_batch"]
        model, mesh = system.build(cell.config, "train", devices)
        b = build_train_step(model, mesh, ShapeSpec(
            "aot", seq_len=tr["seq_len"], global_batch=B, kind="train"))
        t = time.perf_counter()
        c = b.fn.lower(*b.abstract_inputs).compile()
        print(f"compiled in {time.perf_counter() - t:.1f} s")
        report(f"train step seq {tr['seq_len']} batch {B}", c)
        return
    from repro.configs.base import ShapeSpec
    from repro.runtime.steps import (build_paged_decode_step,
                                     build_paged_reshard, build_prefill_step,
                                     make_plan)
    e = dict(tr["engine"])
    if args.slots:
        e["n_slots"] = args.slots
    if args.blocks:
        e["num_blocks"] = args.blocks
    model, mesh = system.build(cell.config, "serve", devices)
    max_blocks = -(-e["max_seq_len"] // e["block_size"])
    dec = build_paged_decode_step(model, mesh, e["n_slots"], e["num_blocks"],
                                  e["block_size"], max_blocks)
    report(f"paged decode {e['n_slots']} slots, {e['num_blocks']} blocks "
           f"of {e['block_size']}",
           dec.fn.lower(*dec.abstract_inputs).compile())
    bucket = max_blocks * e["block_size"]
    pre = build_prefill_step(model, mesh, ShapeSpec("aot", bucket, 1,
                                                    "prefill"),
                             with_lengths=True)
    abs_b = dict(pre.abstract_inputs[1])
    report(f"prefill bucket {bucket}",
           pre.fn.lower(pre.abstract_inputs[0], abs_b).compile())
    plan = make_plan(model.ctx, ShapeSpec("serve", 1, e["n_slots"],
                                          "decode"))
    rs = build_paged_reshard(model, mesh, 1, bucket, e["num_blocks"],
                             e["block_size"], plan)
    pool = model.paged_cache_abstract(e["num_blocks"], e["block_size"],
                                      plan)[0]
    pc = jax.eval_shape(lambda p, bt: pre.fn(p, bt)[1],
                        pre.abstract_inputs[0], abs_b)
    tables = jax.ShapeDtypeStruct((1, bucket // e["block_size"]), "int32")
    report(f"reshard bucket {bucket}", rs.lower(pool, pc, tables).compile())


if __name__ == "__main__":
    main()
