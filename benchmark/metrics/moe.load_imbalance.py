"""How uneven the routing is over the held experts in a decode step: the
busiest held expert's tokens (summed over layers and steps) over the mean
tokens a held expert sees (``moe.tokens_per_held_expert``).  1 is even;
with 2 tokens an expert the busiest of 16 holds three times that by chance
alone."""
import moe_counters


def read(run):
    found = moe_counters.held_layer_steps(run)
    if found is None:
        return None
    d, places = found
    g = run.config["graph"]["parameters"]
    layer_steps = g["n_layers"] * d["moe.steps"]
    mean = d["moe.pairs_held"] / places
    if mean <= 0:
        return None
    return (d["moe.max_tokens_on_expert"] / layer_steps) / mean
