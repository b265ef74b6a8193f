"""A stream request's way in (handler entry of ``/predictions/stream`` -> the
scheduler's submit stamp: admission control, the body, the response headers),
median, from the engine's cumulative ``ingress`` stage histogram, after -
before.  A program without the stage (one from before ISSUE 40) gives None.
(``engine.ingress_ms_p50`` in the cells judged on medians.)"""
import metriclib as ml


def read(run):
    p = run.stats.hist_percentile_s(ml.stage(run, "ingress"), 50)
    return None if p is None else p * 1e3
