"""One admission round (``_admit_batch``: the first prefill dispatched -> the
round's first tokens handed on; every first token of a round is released at
its end), median, from the engine's cumulative ``admit-round`` stage
histogram, after - before.  A program without the stage (one from before
ISSUE 40) gives None.
(``sched.admit_round_ms_p50`` in the cells judged on medians.)"""
import metriclib as ml


def read(run):
    p = run.stats.hist_percentile_s(ml.stage(run, "admit-round"), 50)
    return None if p is None else p * 1e3
