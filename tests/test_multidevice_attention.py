"""Multi-device correctness of the attention paths: Pallas vs jnp, ring and
striped attention over the seq axis, head padding (8 fake CPU devices per
subprocess, tests/mdcheck_run.py)."""
import pytest

from mdcheck_run import run_mdcheck

CHECKS = [
    "smollm_padding",
    "ring_train_parity",
    "attn_impl_parity",
    "ring_attention",
]


@pytest.mark.parametrize("check", CHECKS)
def test_mdcheck(check):
    run_mdcheck(check)
