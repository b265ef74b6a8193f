"""Prompts' share of the device's busy seconds over the whole window, in %, by
the scheduler's own ledger (``benchmark/ledger.py``): where ``step.prefill_share``
reads a 3 s slice, this reads every second of the window but the profiler's."""
import ledger


def read(run):
    return ledger.prefill_share(run)
