"""Output tokens of correctly completed streams that reached the client in
the window (a block credited over the interval it was made in: see
metriclib.tokens_in_window), over the window's seconds."""
import metriclib as ml


def read(run):
    if run.mix["route"] != "stream":
        return None
    return ml.tokens_in_window(run) / run.window_s
