"""Tokens a held expert sees in a decode step: (token, expert) pairs held
here over (held experts x layers x decode steps), from the program's
counters.  The deployment this share is cut from gives each expert 8 chips
x 32 sequences x 8 of 128 = 16 a step; one chip's 32 sequences give 32 x 8 /
128 = 2, an eighth: the step reads the same expert bytes at 2 rows as at 16,
so its time is the deployment's, and its arithmetic is not."""
import moe_counters


def read(run):
    found = moe_counters.held_layer_steps(run)
    if found is None:
        return None
    d, places = found
    return d["moe.pairs_held"] / places
