"""paged_roofline.serve: over the traced engine steps, the least time the
decode attention work needs (``counts/paged_decode``: each slot's live KV
of its own kv heads read once, plus q and out) over the device time of
the paged decode kernel.  Moves itl_p95_ms."""
import re

from chip import peaks, reference as R
from chip.harness import load_count

KERNEL = re.compile(r"paged", re.I)


def read(run):
    tr, steps = run.trace, run.counters.get("traced_steps")
    if tr is None or not steps:
        return None
    t = tr.op_seconds(lambda e: bool(KERNEL.search(e.op)))
    if t <= 0:
        return None
    a = R.Arch.from_config(run.cell.config)
    count = load_count("paged_decode").decode_step
    kind = run.devices[0].device_kind
    least = 0.0
    for _, live, _ in steps:
        if live:
            f, b = count(a, live)
            least += peaks.least_seconds(f / len(run.devices),
                                         b / len(run.devices), kind)[0]
    return 100.0 * least / t
