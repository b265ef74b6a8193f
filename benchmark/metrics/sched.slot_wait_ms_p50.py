"""A request's wait for a slot (the scheduler's submit stamp -> taken into an
admission batch at a sync point), median, from the engine's cumulative
``slot-wait`` stage histogram, after - before.  A program without the stage
(one from before ISSUE 40) gives None."""
import metriclib as ml


def read(run):
    p = run.stats.hist_percentile_s(ml.stage(run, "slot-wait"), 50)
    return None if p is None else p * 1e3
