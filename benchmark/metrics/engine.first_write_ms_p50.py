"""The first token's way out (the scheduler's first-token stamp -> the handler's
write of its SSE event returned: whatever the event loop does before it gets
to the write, then the write), median, from the engine's cumulative
``first-write`` stage histogram, after - before.  A program without the stage
(one from before ISSUE 40) gives None."""
import metriclib as ml


def read(run):
    p = run.stats.hist_percentile_s(ml.stage(run, "first-write"), 50)
    return None if p is None else p * 1e3
