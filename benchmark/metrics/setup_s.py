"""Command start to the first measured instant: engine boot to /ready,
probes, the reference's weights, the lead-in load."""


def read(run):
    return run.w0 - run.t_command
