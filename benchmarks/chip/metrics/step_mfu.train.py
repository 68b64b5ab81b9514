"""step_mfu.train: trained tokens per second times the FLOPs each token
needs (6 per matmul weight plus causal attention, forward and backward,
recomputation not counted; ``counts/dense_lm``), over the chips' bf16 peak.
Moves train_tokens_per_s."""
from chip import peaks, reference as R
from chip.harness import load_count


def read(run):
    rate = run.end_to_end.get("train_tokens_per_s")
    if not rate:
        return None
    a = R.Arch.from_config(run.cell.config)
    fpt = load_count("dense_lm").train_flops_per_token(
        a, run.cell.traffic["seq_len"])
    p = peaks.peaks(run.devices[0].device_kind)["bf16_flops_per_s"]
    return 100.0 * rate * fpt / (len(run.devices) * p)
