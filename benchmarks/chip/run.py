"""Run one cell of the on-chip benchmark once.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The cell, its configuration, its traffic and its metrics are found by name
from ``BENCHMARK.json`` (see ``harness.py``).  The run needs a TPU with at
least as many chips as the cell asks for; on anything else it exits
non-zero and prints no result.  It keeps JAX's persistent compilation cache
in ``<checkout>/.jax_compile_cache``, so only a checkout's first run of a
cell compiles.

Standard output ends with one JSON line: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1`` a
``breakdown``, and last ``checks``: each number compared with its limit.
The same numbers are the last lines on standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]


def fail(msg: str, code: int = 1):
    print(f"benchmark: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seconds <= 0:
        ap.error("--seconds must be positive")
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")
    return args


def device_info(rec, trace) -> dict:
    d = rec.devices[0]
    out = {"platform": d.platform, "kind": d.device_kind,
           "count": len(rec.devices),
           "memory_peak_bytes": int(rec.memory_peak_bytes)}
    if trace is not None:
        out["busy_s"] = trace.busy_s()
        out["window_s"] = trace.window_s
    return out


def execute(cell, devices, seed: int, seconds: float, trace: bool,
            t_start: float) -> dict:
    """Everything after the look for the chip: the loop's run, the
    metrics and the checks.  Returns the result object."""
    from chip.harness import CompileCounter, RunRecord, load_loop, \
        load_reader, note
    counter = CompileCounter()
    rec = RunRecord(cell=cell, seed=seed, devices=devices)
    load_loop(cell.traffic["loop"]).run(
        rec, seconds, trace, t_start, counter)
    note(f"compilations in the window: {counter.count}")

    metrics = {}
    if trace:
        for m in cell.per_layer:
            v = load_reader(m["name"]).read(rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = dict(rec.end_to_end, setup_s=rec.setup_s)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    result = {"correct": rec.correct, "attempted": rec.attempted,
              "failed": rec.failed, "metrics": metrics,
              "device": device_info(rec, rec.trace if trace else None)}
    if trace and rec.trace is not None:
        result["breakdown"] = {"device_ops": rec.trace.top_ops(10),
                               "idle_gaps": rec.trace.idle_gaps(10)}
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in rec.checks}
    for c in rec.checks:
        print(f"check {c.name} {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr, flush=True)
    return result


def main(argv=None) -> int:
    args = parse(argv)
    sys.path[:0] = [str(CHECKOUT / "benchmarks"), str(CHECKOUT / "src")]
    if not (CHECKOUT / "src" / "repro").is_dir():
        fail(f"no system under test at {CHECKOUT / 'src'}")
    from chip.harness import Cell, note, start_jax
    from chip import peaks

    cell = Cell.find(args.workload)
    jax = start_jax()

    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"no TPU: JAX found {devices[0].platform} devices", 3)
    if len(devices) < cell.chips:
        fail(f"{cell.name} needs {cell.chips} chips, JAX found "
             f"{len(devices)}", 3)
    devices = devices[:cell.chips]
    peaks.peaks(devices[0].device_kind)       # unknown device: an error
    note(f"device: {devices[0].platform} {devices[0].device_kind} "
         f"x{len(devices)}")

    result = execute(cell, devices, args.seed, args.seconds, bool(args.trace),
                     T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
