"""``ledger.idle_share`` in the cells judged on ``tpot_ms_p50``."""
import ledger


def read(run):
    return ledger.idle_share(run)
