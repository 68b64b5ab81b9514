"""attn_ms.train: device milliseconds a traced step spends in the ``attn``
scope, the attention sublayer: ln1, the q/k/v projections, RoPE, the K/V take
for GQA, the flash kernels, the output projection and the residual: the ops
(not the loops around them) whose innermost layer scope it is
(``chip/scopes.py``), inside the window, averaged over chips. Moves
train_tokens_per_s."""
from chip import scopes


def read(run):
    s = scopes.for_run(run)
    if s is None:
        return None
    return 1e3 * s.scope_seconds("attn") / run.cell.traffic["trace_steps"]
