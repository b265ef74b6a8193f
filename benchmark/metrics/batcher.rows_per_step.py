"""Rows answered between the snapshots over the device steps made in
between (``device-step`` stage count): how full the batcher's steps are."""
import metriclib as ml


def read(run):
    steps = run.stats.hist_count(ml.stage(run, "device-step"))
    rows = sum(s.got for s in run.samples if s.ok)
    return rows / steps if steps else None
