"""Run by path (``pytest benchmarks/chip/tests``), on the CPU."""
import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = pathlib.Path(__file__).resolve().parent
CHECKOUT = HERE.parents[2]
sys.path[:0] = [str(CHECKOUT / "benchmarks"), str(CHECKOUT / "src"),
                str(HERE)]
