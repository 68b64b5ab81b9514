"""flash_roofline.train: the least time the flash forward + backward work
of the traced steps needs (``counts/flash_attention``, the larger of its
FLOPs at peak and its bytes at HBM bandwidth), over the device time of the
flash kernels in the trace.  Moves train_tokens_per_s."""
import re

from chip import peaks, reference as R
from chip.harness import load_count

KERNEL = re.compile(r"flash", re.I)


def read(run):
    tr = run.trace
    if tr is None:
        return None
    t = tr.op_seconds(lambda e: bool(KERNEL.search(e.op)))
    if t <= 0:
        return None
    a = R.Arch.from_config(run.cell.config)
    traffic = run.cell.traffic
    flops, nbytes = load_count("flash_attention").train_step(
        a, traffic["global_batch"], traffic["seq_len"])
    steps = traffic["trace_steps"]
    least, bound = peaks.least_seconds(flops * steps / len(run.devices),
                                       nbytes * steps / len(run.devices),
                                       run.devices[0].device_kind)
    run.counters["flash_roofline_bound"] = bound
    return 100.0 * least / t
