"""Device time of one decode step (a traced decode block over its k), in the
cells judged on ``tpot_ms_p50``."""
import metriclib as ml


def read(run):
    s = ml.decode_step_s(run)
    return None if s is None else s * 1e3
