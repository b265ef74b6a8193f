"""Share of the HBM roofline the prompt's recurrence kernel reaches in the
traced slice: the bytes the recurrence has to move for the REAL tokens of
the traced ``prefill:b<rung>`` programs — a token a layer: ``c`` in and ``y``
out (bfloat16), ``D_t`` in (float32), ``B`` and ``C``: 41 KB; a prompt a
layer: ``A`` in and the state out (``costs_jamba.scan_bytes``) — over the
chip's peak bandwidth, over the device time of the ``ssm.scan.<n>`` calls
among the slice's device operations.

THE VECTOR UNIT BOUNDS THIS KERNEL, NOT HBM, and no peak of it is published:
a sound kernel reads well under 100 % here, and the number says how far the
recurrence is from being free (PERF.md has the vector operations a token
costs by Mosaic's lowered text).

The trace's labels carry a program's rung and not its prompt's length, so a
traced program's real rows are its rung's times the window's real share of
the rows its prompt programs ran (``ssm.prefill_tokens`` over
``ssm.prefill_rows``); a program cut by the slice's edge counts for the
share of its rung's usual time it was traced for.  The kernel's calls are
read from the reduced trace's ten longest device operations: where the
recurrence is not among them, or the program has no such kernel or
counters, the reader gives nothing."""
import re
import statistics

import costs_jamba as cj
import metriclib as ml
import moe_counters


def read(run):
    d = moe_counters.delta(run)
    if not run.trace or run.peaks is None or not d:
        return None
    ops = (run.trace.get("breakdown") or {}).get("device_ops") or []
    scan_s = sum(s for name, s in ops if name.startswith("ssm.scan"))
    tokens, rows = d.get("ssm.prefill_tokens", 0), d.get("ssm.prefill_rows", 0)
    prompts = [
        (int(m.group(1)), p["device_s"])
        for p in ml.programs(run, "prefill:")
        if (m := re.match(r"prefill:b(\d+)", p["label"])) and p["device_s"] > 0
    ]
    if scan_s <= 0 or tokens <= 0 or rows <= 0 or not prompts:
        return None
    # a rung's usual time: the median of its traced programs; one cut by the
    # slice's edge ran for less and counts for that part of a prompt
    usual = {
        rung: statistics.median_high(s for r, s in prompts if r == rung)
        for rung in {r for r, _ in prompts}
    }
    parts = [(rung, min(1.0, s / usual[rung])) for rung, s in prompts]
    real = min(1.0, tokens / rows)
    need = cj.scan_bytes(
        run.config["graph"]["parameters"],
        sum(rung * real * part for rung, part in parts),
        sum(part for _, part in parts),
    )
    return 100.0 * need / (run.chips * run.peaks["hbm_bytes_per_s"]) / scan_s
