"""device_idle.train: share of the traced window in which no operation
runs on the chip (1 - busy union / window), averaged over chips.  Moves
train_tokens_per_s."""


def read(run):
    tr = run.trace
    if tr is None or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s() / tr.window_s)
