"""Client TTFT median - engine TTFT median: what ingress, the wire and the
SSE encode add before the first token is read.
(``wire.ttft_gap_ms_p50`` in the cells judged on ``ttft_ms_p50``.)"""
import metriclib as ml


def read(run):
    xs = ml.ttft_ms(run)
    p = run.stats.hist_percentile_s(ml.stage(run, "ttft"), 50)
    if not xs or p is None:
        return None
    return run.stats.percentile(xs, 50) - p * 1e3
