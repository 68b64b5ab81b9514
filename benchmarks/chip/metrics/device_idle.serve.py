"""device_idle.serve: share of the traced window in which no operation
runs on the chip (1 - busy union / window).  Moves itl_p95_ms."""


def read(run):
    tr = run.trace
    if tr is None or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s() / tr.window_s)
