"""Device time of one decode step by the scheduler's own ledger, over the
whole window and with no profiler: the median over the window's seconds of
busy decode seconds over decode steps (``benchmark/ledger.py``)."""
import ledger


def read(run):
    return ledger.decode_step_ms(run)
