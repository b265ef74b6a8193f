"""The scheduler's own TTFT (submit -> first sampled token), median, from
the engine's cumulative ``ttft`` stage histogram, after - before.
(``sched.ttft_ms_p50`` in the cells judged on ``ttft_ms_p50``.)"""
import metriclib as ml


def read(run):
    p = run.stats.hist_percentile_s(ml.stage(run, "ttft"), 50)
    return None if p is None else p * 1e3
