"""collective_exposed.train: share of the traced window in which a
collective runs on a chip and no compute op does, averaged over the chips.
Moves train_tokens_per_s."""


def read(run):
    tr = run.trace
    if tr is None or tr.window_s <= 0 or len(run.devices) < 2:
        return None
    return 100.0 * tr.collective_exposed_s() / tr.window_s
