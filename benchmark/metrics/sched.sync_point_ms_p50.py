"""The time live streams had no decode block in flight at a sync point (block
N's tokens in hand -> the next decode block dispatched: delivery, admission
and the rebuild between them), median, from the engine's cumulative
``sync-point`` stage histogram, after - before.  A program without the stage
(one from before ISSUE 40) gives None."""
import metriclib as ml


def read(run):
    p = run.stats.hist_percentile_s(ml.stage(run, "sync-point"), 50)
    return None if p is None else p * 1e3
