"""Collective time over busy time on the first device of the traced slice."""


def read(run):
    if not run.trace or run.chips < 2 or not run.trace.get("busy_s"):
        return None
    return 100.0 * run.trace["collective_s"] / run.trace["busy_s"]
