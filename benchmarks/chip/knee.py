"""Find the knee of a serving cell once, by a sweep on the chip.

    python3 benchmarks/chip/knee.py <workload> --rates 2,4,8,16 \
        [--seconds 20] [--slots N --blocks P]

One process, one warmed engine: the cell's open loop at each rate in turn
(``loops/serve.sweep``), one JSON line per rate.  The knee is the
highest rate at which the backlog at the window's close stays small and
the tokens completed per second still follow the rate; the cell then
fixes its rate, as a number in its traffic file, at about four fifths of
it.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("workload")
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--slots", type=int, help="n_slots to try in place of "
                    "the traffic file's")
    ap.add_argument("--blocks", type=int, help="num_blocks to try in place "
                    "of the traffic file's")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(CHECKOUT / "benchmarks"), str(CHECKOUT / "src")]
    from chip.harness import start_jax
    jax = start_jax()
    from chip.loops import serve as S
    from chip.harness import Cell
    cell = Cell.find(args.workload)
    tr = cell.traffic
    for key, v in (("n_slots", args.slots), ("num_blocks", args.blocks)):
        if v:
            tr["engine"][key] = v
    devices = jax.devices()[:cell.chips]
    engine, arch = S.build_engine(cell.config, tr, devices, 0)
    S.warm_up(engine, tr, tr["engine"]["max_seq_len"], arch.vocab)
    rates = [float(r) for r in args.rates.split(",")]
    for row in S.sweep(engine, arch, tr, rates, args.seconds):
        print(json.dumps(dict(row, **tr["engine"])), flush=True)


if __name__ == "__main__":
    main()
