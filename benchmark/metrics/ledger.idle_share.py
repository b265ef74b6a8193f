"""The share of the window in which the device stood idle for the host's doing
(every part of the run loop but ``idle-park``), in %, by the scheduler's own
ledger (``benchmark/ledger.py``)."""
import ledger


def read(run):
    return ledger.idle_share(run)
