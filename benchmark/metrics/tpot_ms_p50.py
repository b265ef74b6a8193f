"""Median over requests of (done - first token) / (tokens - 1)."""
import metriclib as ml


def read(run):
    xs = ml.tpot_ms(run)
    return run.stats.percentile(xs, 50) if xs else None
