"""tp_comm_ms.train: device milliseconds a traced step spends in the
collectives of the Tesseract (SUMMA) matmul: the ops whose name stack
holds the program's ``tp_comm`` scope and that run no matrix product
(``chip/comm.py``), inside the window, averaged over chips.  Time that
overlaps compute counts too.  Moves train_tokens_per_s."""
from chip import comm


def read(run):
    t = comm.seconds(run)
    if t is None:
        return None
    return 1e3 * t / run.cell.traffic["trace_steps"]
