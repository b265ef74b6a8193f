"""``ledger.idle_vs_trace_pts`` in the cells judged on ``tpot_ms_p50``."""
import ledger


def read(run):
    return ledger.idle_vs_trace_pts(run)
