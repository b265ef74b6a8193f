"""Median device time of the programs dispatched under ``prefill:b*``
annotations in the traced slice.
(``step.prefill_ms_p50`` in the cells judged on ``ttft_ms_p50``.)"""
import metriclib as ml


def read(run):
    xs = [p["device_s"] * 1e3 for p in ml.programs(run, "prefill:")]
    return run.stats.percentile(xs, 50) if xs else None
