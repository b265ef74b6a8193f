"""Median of due instant -> first SSE token event, over the stream requests
due in the window: the end-to-end TTFT of a cell whose 95th percentile (12
requests of 240) a host stall of a second or two moves by half or more."""
import metriclib as ml


def read(run):
    xs = ml.ttft_ms(run)
    return run.stats.percentile(xs, 50) if xs else None
