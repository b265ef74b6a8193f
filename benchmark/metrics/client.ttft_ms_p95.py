"""The tail beside the median: 95th percentile of due instant -> first SSE
token event (what ``ttft_ms_p95`` reads), per layer in a cell that is judged
on ``ttft_ms_p50``.  Taken in the traced run, whose profiler slows the
served path: read it against other traced runs only."""
import metriclib as ml


def read(run):
    xs = ml.ttft_ms(run)
    return run.stats.percentile(xs, 95) if xs else None
