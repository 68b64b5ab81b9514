"""prefill_ms.serve: mean device time per prefill, from the trace: each
run of a prefill program (a module whose ops include the flash kernel)
plus the run of the paged reshard that follows it.  Moves ttft_p95_ms."""
import re

FLASH = re.compile(r"flash", re.I)
PAGED = re.compile(r"paged", re.I)


def read(run):
    tr = run.trace
    if tr is None or not tr.devices:
        return None
    runs = tr.module_runs(0)
    total, n, after_prefill = 0.0, 0, False
    for mod, ops in runs:
        names = [o.op for o in ops]
        if any(FLASH.search(x) for x in names):
            total += (mod.end - mod.start) / 1e9
            n += 1
            after_prefill = True
        elif after_prefill and not any(PAGED.search(x) for x in names):
            # the reshard that scatters the prefill cache into the pool
            total += (mod.end - mod.start) / 1e9
            after_prefill = False
        else:
            after_prefill = False
    return 1e3 * total / n if n else None
