"""The ledger's standing check against the device plane, in points: | its
idle share of the stretch the profiler traced - the trace's own | (``benchmark/
ledger.py``).  Only a traced run has it."""
import ledger


def read(run):
    return ledger.idle_vs_trace_pts(run)
