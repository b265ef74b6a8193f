"""95th percentile of due instant -> first SSE token event, over the stream
requests due in the window."""
import metriclib as ml


def read(run):
    xs = ml.ttft_ms(run)
    return run.stats.percentile(xs, 95) if xs else None
