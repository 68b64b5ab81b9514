"""Each count against a hand count at a small shape, and the peak table."""
import pytest

from chip import peaks
from chip.harness import load_count
from chip.reference import Arch

# 2 layers, hidden 8, 2 q heads / 1 kv head of 4, ffn 16, vocab 10
A = Arch(layers=2, hidden=8, heads=2, kv_heads=1, head_dim=4, ffn=16,
         vocab=10, rope_theta=1e4, eps=1e-5)


def test_dense_lm():
    lm = load_count("dense_lm")
    # per layer q,o 8x8 each, k,v 8x4 each, gate/up/down 8x16 each
    assert lm.matmul_params(A) == 2 * (128 + 64 + 384) + 80
    assert lm.weight_params(A) == 1232 + 5 * 8
    # 3 causal positions: 1+2+3 = 6 pairs, 4 FLOPs x 2 heads x 4 dims
    assert lm.attn_flops_fwd(A, 3) == 4 * 2 * 4 * 6 * 2
    # 2 queries after 5 cached keys: 2*5 + 1+2 = 13 pairs
    assert lm.attn_flops_fwd(A, 2, keys_from=5) == 4 * 8 * 13 * 2
    assert lm.train_flops_per_token(A, 3) == 6 * 1232 + 3 * 384 / 3


def test_flash_attention():
    flops, nbytes = load_count("flash_attention").train_step(A, 2, 3)
    assert flops == 3 * (4 * 2 * 4 * 6 * 2) * 2
    # q,o,dO,dq: 2x3x2x4 = 48 elems; k,v,dk,dv: 2x3x1x4 = 24
    assert nbytes == (48 + 48 + 48 + 48 + 48 + 96 + 48 + 48) * 2 * 2


def test_paged_decode():
    flops, nbytes = load_count("paged_decode").decode_step(A, [3, 5])
    assert flops == 4 * 2 * 4 * 8 * 2
    assert nbytes == (2 * 8 * 4 + 2 * 2 * 8) * 4 * 2


def test_serve_step():
    flops, nbytes = load_count("serve_step").engine_step(A, [3, 5], [4])
    decode = 2 * 1232 * 2 + 512
    prefill = 2 * (1232 - 80) * 4 + 2 * 80 + 4 * 8 * 10 * 2
    assert flops == decode + prefill
    assert nbytes == (1272 * 4 + 768) + (1272 * 4 + 4 * 64)


def test_peaks():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert peaks.least_seconds(197e12, 1.0, "TPU v5 lite") == (1.0, "flops")
    assert peaks.least_seconds(1.0, 819e9, "TPU v5 lite") == (1.0, "bytes")
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


def test_published_sizes():
    """smollm-360m: 362M matmul weights (2.55 GFLOP per trained token at
    seq 2048); yi-6b: 173M per layer and 262M in the head."""
    import json
    import pathlib
    lm = load_count("dense_lm")
    cfgs = pathlib.Path(__file__).resolve().parents[1] / "configs"
    sm = Arch.from_config(json.loads((cfgs / "smollm-360m.json").read_text()))
    assert lm.matmul_params(sm) == 361_758_720
    assert lm.train_flops_per_token(sm, 2048) == pytest.approx(2.55e9,
                                                              rel=0.01)
    yi = Arch.from_config(json.loads((cfgs / "yi-6b.json").read_text()))
    assert (lm.matmul_params(yi) - 64000 * 4096) / 8 == pytest.approx(
        173e6, rel=0.01)
