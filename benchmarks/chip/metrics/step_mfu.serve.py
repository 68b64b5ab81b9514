"""step_mfu.serve: over the engine steps of the window, the summed least
time the chip needs for each step's work (``counts/serve_step``: decode
of the active slots over their live KV, plus any prefills) over the summed
wall time of those steps.  Moves itl_p95_ms."""
from chip import peaks, reference as R
from chip.harness import load_count


def read(run):
    steps = run.counters.get("steps")
    if not steps:
        return None
    a = R.Arch.from_config(run.cell.config)
    count = load_count("serve_step").engine_step
    kind = run.devices[0].device_kind
    least = wall = 0.0
    for w, live, prompts in steps:
        f, b = count(a, live, prompts)
        least += peaks.least_seconds(f / len(run.devices),
                                     b / len(run.devices), kind)[0]
        wall += w
    return 100.0 * least / wall if wall > 0 else None
