"""head_loss_ms.train: device milliseconds a traced step spends in the
``head_loss`` scope, the final norm, the vocabulary head's matmul and the
chunked cross-entropy: the ops (not the loops around them) whose innermost
layer scope it is (``chip/scopes.py``), inside the window, averaged over chips.
Moves train_tokens_per_s."""
from chip import scopes


def read(run):
    s = scopes.for_run(run)
    if s is None:
        return None
    return 1e3 * s.scope_seconds("head_loss") / run.cell.traffic["trace_steps"]
