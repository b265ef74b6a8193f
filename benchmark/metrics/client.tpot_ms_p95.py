"""The tail beside the median: 95th percentile over requests of (done -
first token) / (tokens - 1) (what ``tpot_ms_p95`` reads), per layer in a
cell that is judged on ``tpot_ms_p50``.  Taken in the traced run, whose
profiler slows the served path: read it against other traced runs only."""
import metriclib as ml


def read(run):
    xs = ml.tpot_ms(run)
    return run.stats.percentile(xs, 95) if xs else None
