"""What every cell shares: finding its files by name, the run's record,
the compile counter, the host spans, and the checks that decide
``correct``.

A cell is found by its name in ``BENCHMARK.json``.  Its configuration is
``configs/<config>.json``, its traffic ``traffic/<traffic>.json`` (whose
``loop`` names ``loops/<loop>.py``), each per-layer metric a reader
``metrics/<name>.py`` and each count ``counts/<name>.py``.  Adding a cell
adds files and entries; no file here names a cell.
"""
from __future__ import annotations

import importlib.util
import json
import math
import pathlib
import sys
from dataclasses import dataclass, field

HERE = pathlib.Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]
RUN_DIR = CHECKOUT / ".bench_run"          # traces, removed after reading
CACHE_DIR = CHECKOUT / ".jax_compile_cache"


def start_jax():
    """Import JAX with its persistent compilation cache at the checkout's
    fixed ``.jax_compile_cache`` (every program cached, however quick its
    compile, and nothing evicted, so that set-up stays the same from run
    to run), and the TPU runtime's logs off unless the caller set them."""
    import os
    CACHE_DIR.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return jax


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(CHECKOUT / "BENCHMARK.json")


def _load_module(path: pathlib.Path, name: str):
    if not path.is_file():
        raise FileNotFoundError(f"no {path.relative_to(CHECKOUT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str):
    """The reader of per-layer metric ``metric``: ``read(run) -> float|None``."""
    return _load_module(HERE / "metrics" / f"{metric}.py",
                        f"chip_metric_{metric}")


def load_count(name: str):
    return _load_module(HERE / "counts" / f"{name}.py", f"chip_count_{name}")


def load_loop(name: str):
    if not (HERE / "loops" / f"{name}.py").is_file():
        raise FileNotFoundError(f"no loop {name!r} in {HERE / 'loops'}")
    return importlib.import_module(f"chip.loops.{name}")


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list          # metric entries of BENCHMARK.json
    per_layer: list

    @classmethod
    def find(cls, name: str, bench: dict | None = None) -> "Cell":
        bench = bench or benchmark()
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                           f"(have {sorted(cells)})")
        w = cells[name]
        cfgs = {c["name"]: c for c in bench["configs"]}
        cfg = load_json(CHECKOUT / cfgs[w["config"]]["file"])
        traffic = load_json(HERE / "traffic" / f"{w['traffic']}.json")

        def mine(m):
            return name in m.get("workloads", [name])
        return cls(name=name, chips=w["chips"], config=cfg, traffic=traffic,
                   end_to_end=[m for m in bench["end_to_end"] if mine(m)],
                   per_layer=[m for m in bench["per_layer"] if mine(m)])


@dataclass
class Check:
    """One number compared against its limit (``value <= limit`` passes)."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclass
class RunRecord:
    """What a loop hands back to ``run.py`` and to the metric readers."""
    cell: Cell
    seed: int
    devices: list
    setup_s: float = 0.0
    window_s: float = 0.0
    attempted: int = 0
    failed: int = 0
    end_to_end: dict = field(default_factory=dict)   # name -> value
    counters: dict = field(default_factory=dict)      # program's own counts
    checks: list = field(default_factory=list)        # [Check]
    memory_peak_bytes: int = 0
    trace: object = None          # trace_reduce.Reduced of the traced part

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c.ok for c in self.checks)


def note(msg: str) -> None:
    """A line of the run's account on standard error."""
    print(msg, file=sys.stderr, flush=True)


class CompileCounter:
    """Counts XLA compilations (and persistent-cache loads) while armed."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax.monitoring
        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if self.armed and event in self.EVENTS:
            self.count += 1


def span(name: str):
    """A host span in the profiler's trace, around a call into a layer."""
    import jax
    return jax.profiler.TraceAnnotation(f"bench.{name}")


def leaf_gap(prog: dict, ref: dict):
    """Worst leaf of |‖prog‖ - ‖ref‖| / max(‖ref‖, median ‖ref‖).

    ``prog``/``ref``: {leaf name: norm}.  Returns (gap, leaf name, leaves
    compared)."""
    import numpy as np
    med = float(np.median(list(ref.values())))
    worst, which, n = 0.0, None, 0
    for k, r in ref.items():
        n += 1
        g = abs(prog[k] - r) / max(r, med)
        if not math.isfinite(g) or g > worst:
            worst, which = (g if math.isfinite(g) else math.inf), k
    return worst, which, n
