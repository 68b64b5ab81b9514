"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 benchmarks/chip/calibrate.py <workload> --seeds 11,12,... \
        [--control-seeds 3] [--seconds S]

In one process, for each seed: the program's readings, exactly as a run
takes them (the train cell's first three steps; the serve cell's sampled
requests after an open-loop window of ``--seconds`` at the cell's rate),
against the reference.  For the first ``--control-seeds`` seeds also the
control's readings (the reference put in the program's place at the next
lower precision: fp8 matmuls for a bf16 configuration, bf16 for a float32
one) and the faults a cell of its kind can have (train: half the batch
left out, the mean taken over the rest; serve: a served token altered).
A state left unchanged reads 1 on ``update_gap`` by construction and needs
no run.  One JSON line per seed, then a summary: per number the largest
program reading (the lower end for its limit) and the smallest control and
fault readings (the upper end).
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]


def emit(obj):
    print(json.dumps(obj), flush=True)


def train(cell, devices, seeds, n_control, rows):
    from chip import check_train as C
    from chip.loops.train import Trainer
    tn = Trainer(cell.config, cell.traffic, devices)
    keys = ("loss_gap", "grad_gap", "update_gap")
    out = {"program": [], "control": [], "half_batch": []}
    for i, seed in enumerate(seeds):
        t = time.perf_counter()
        tn.start(seed)
        prog = tn.first_steps()
        tn.stop()
        ref = tn.reference(devices, rows)
        row = {"seed": seed, "program": C.compare(prog, ref)}
        out["program"].append(row["program"])
        if i < n_control:
            ctl = tn.reference(devices, rows, mode="fp8")
            half = tn.reference(devices, rows, drop_half=True)
            row["control"] = C.compare(ctl, ref)
            row["half_batch"] = C.compare(half, ref)
            out["control"].append(row["control"])
            out["half_batch"].append(row["half_batch"])
        row["seconds"] = time.perf_counter() - t
        emit(row)
    return out, keys


def serve(cell, devices, seeds, n_control, seconds):
    import jax
    import numpy as np
    from chip import check_serve as C
    from chip import weights as W
    from chip.loops import serve as S
    tr = cell.traffic
    context = tr["engine"]["max_seq_len"]
    engine, arch = S.build_engine(cell.config, tr, devices, seeds[0])
    S.warm_up(engine, tr, context, arch.vocab)
    shard = jax.tree.map(lambda x: x.sharding, engine.params)
    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), engine.params)
    out = {"program": [], "control": [], "token_altered": []}
    for i, seed in enumerate(seeds):
        t = time.perf_counter()
        engine.params = None
        engine.params = W.program_params(arch, seed, abstract, shard)
        load = S.OpenLoop(tr, arch.vocab, context, seconds, seed)
        got = S.drive(engine, load, seconds, tr["drain_s"])
        sample = S.pick_sample(got["reqs"], seed, tr["sample_requests"])
        gaps = C.served_gaps(arch, seed, sample, context, devices)
        row = {"seed": seed, "requests": len(got["reqs"]),
               "served_compared": int(sum(len(g) for g in gaps)),
               "program": {"logit_gap": max(float(np.max(g)) for g in gaps)}}
        out["program"].append(row["program"])
        if i < n_control:
            ctl = C.served_gaps(arch, seed, sample, context, devices,
                                control="bf16")
            row["control"] = {"logit_gap": max(float(np.max(g))
                                               for g in ctl)}
            # fault: one served token of each sampled request altered
            rng = np.random.default_rng(seed)
            bad = []
            for prompt, served in sample:
                s = list(served)
                j = int(rng.integers(len(s)))
                s[j] = int((s[j] + 1 + rng.integers(arch.vocab - 1))
                           % arch.vocab)
                bad.append((prompt, s))
            alt = C.served_gaps(arch, seed, bad, context, devices)
            row["token_altered"] = {"logit_gap": max(float(np.max(g))
                                                     for g in alt)}
            out["control"].append(row["control"])
            out["token_altered"].append(row["token_altered"])
        # let the engine finish what the drain left, so the next seed
        # starts empty
        engine.run()
        row["seconds"] = time.perf_counter() - t
        emit(row)
    return out, ("logit_gap",)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("workload")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(CHECKOUT / "benchmarks"), str(CHECKOUT / "src")]
    from chip.harness import start_jax
    jax = start_jax()
    from chip.harness import Cell
    cell = Cell.find(args.workload)
    devices = jax.devices()[:cell.chips]
    emit({"device": devices[0].device_kind, "count": len(devices)})
    seeds = [int(s) for s in args.seeds.split(",")]
    if cell.traffic["loop"] == "train":
        out, keys = train(cell, devices, seeds, args.control_seeds,
                          cell.traffic["ref_rows"])
    else:
        out, keys = serve(cell, devices, seeds, args.control_seeds,
                          args.seconds)
    summary = {}
    for k in keys:
        s = {"program_max": max(r[k] for r in out["program"])}
        for kind, rows in out.items():
            if kind != "program" and rows:
                s[f"{kind}_min"] = min(r[k] for r in rows)
        summary[k] = s
    emit({"summary": summary})


if __name__ == "__main__":
    main()
