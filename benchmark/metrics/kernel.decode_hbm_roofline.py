"""Share of the HBM roofline a decode step reaches: the bytes the step has
to read (layer weights + head + keys and values of the tokens in flight,
from shapes: costs.llama_decode_step_bytes) over the chips' peak bandwidth,
over the measured device time of a step.  Bound: memory bandwidth."""
import metriclib as ml


def read(run):
    step_s = ml.decode_step_s(run)
    if step_s is None or run.peaks is None:
        return None
    g = run.config["graph"]["parameters"]
    need = run.costs.llama_decode_step_bytes(g, ml.mean_context_tokens(run))
    least_s = need / (run.chips * run.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / step_s
