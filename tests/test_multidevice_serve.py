"""Multi-device correctness of serving, pipelining and elastic re-plans
(8 fake CPU devices per subprocess, tests/mdcheck_run.py)."""
import pytest

from mdcheck_run import run_mdcheck

CHECKS = [
    "serve_engine",
    "engine_elastic",
    "pipeline_parity",
    "zero1_elastic",
    "train_elastic_accum",
]


@pytest.mark.parametrize("check", CHECKS)
def test_mdcheck(check):
    run_mdcheck(check)
