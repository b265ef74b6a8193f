"""Median wait of a request in the batcher's queue, from the engine's
cumulative ``queue-wait`` stage histogram, after - before."""
import metriclib as ml


def read(run):
    p = run.stats.hist_percentile_s(ml.stage(run, "queue-wait"), 50)
    return None if p is None else p * 1e3
