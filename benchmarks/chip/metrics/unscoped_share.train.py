"""unscoped_share.train: share of the device's busy time in the traced
window spent on ops under none of the program's layer scopes
(``chip/scopes.py``): what the layer metrics cannot name.  Moves
train_tokens_per_s."""
from chip import scopes


def read(run):
    s = scopes.for_run(run)
    busy = run.trace.busy_s() if run.trace is not None else 0.0
    if s is None or busy <= 0:
        return None
    return 100.0 * s.scope_seconds(None) / busy
