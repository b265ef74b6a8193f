"""Sequences answered with status 200 in the window, over its seconds."""
import metriclib as ml


def read(run):
    if run.mix["route"] != "predict":
        return None
    return ml.rows_in_window(run) / run.window_s
