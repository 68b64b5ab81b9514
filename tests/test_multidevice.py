"""Multi-device correctness, dense models: each test spawns a subprocess
with 8 fake CPU devices (tests/mdcheck_run.py).  The other mdchecks live
in tests/test_multidevice_{families,attention,serve}.py; chaos_train /
chaos_serve in tests/test_chaos.py."""
import pytest

from mdcheck_run import run_mdcheck

CHECKS = [
    "summa_exact",
    "dense_parity",
    "inop_matches_deferred",
    "decode_parity",
    "prefill_parity",
    "zero1_parity",
]


@pytest.mark.parametrize("check", CHECKS)
def test_mdcheck(check):
    run_mdcheck(check)
