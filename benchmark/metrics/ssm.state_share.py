"""How much of what a decode step has to move is the slots' state: 100 x
(18.6 MB x ``ssm.slot_steps``: every live slot's 26 layers of state and
convolution tail, in and out) over the step's needed bytes by the same costs
(that, the weights once a step and 512 B a live K/V row:
``costs_jamba.decode_step_bytes``), from the program's device counters over
the load's decode steps (lead-in, window and drain: see ``ssm.live_slots``,
whose average this follows).  Near the 28 % of the configuration's arithmetic
it says the traffic works the state; falling, that slots stand empty.  A
program without the counters gives nothing."""
import costs_jamba as cj
import moe_counters


def read(run):
    d = moe_counters.delta(run)
    if not d or d.get("ssm.steps", 0) <= 0 or "ssm.slot_steps" not in d:
        return None
    g = run.config["graph"]["parameters"]
    steps = d["ssm.steps"]
    need = cj.decode_step_bytes(
        g, d["ssm.slot_steps"] / steps, d.get("attn.rows_live", 0) / steps
    )
    return 100.0 * cj.decode_state_bytes(g, d["ssm.slot_steps"] / steps) / need
