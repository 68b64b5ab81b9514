"""Multi-device correctness of the non-dense families and MoE (8 fake CPU
devices per subprocess, tests/mdcheck_run.py)."""
import pytest

from mdcheck_run import run_mdcheck

CHECKS = [
    "moe_parity",
    "moe_decode",
    "moe_local_layout",
    "families_parity",
    "families_serve",
]


@pytest.mark.parametrize("check", CHECKS)
def test_mdcheck(check):
    run_mdcheck(check)
