"""Share of the device's busy time that went to prompts: device time of the
traced ``prefill:`` and ``suffix:`` programs over the busy time of the
slice.  What is left is decode blocks; lower leaves more of the chip to the
tokens the cell counts."""
import metriclib as ml


def read(run):
    if not run.trace or not run.trace.get("busy_s"):
        return None
    prompts = ml.programs(run, "prefill:") + ml.programs(run, "suffix:")
    return 100.0 * sum(p["device_s"] for p in prompts) / run.trace["busy_s"]
