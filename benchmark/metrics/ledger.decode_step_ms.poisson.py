"""``ledger.decode_step_ms`` in the cells judged on ``tpot_ms_p50``."""
import ledger


def read(run):
    return ledger.decode_step_ms(run)
