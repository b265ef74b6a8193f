"""The expert layer's device counters over the window: ``/stats/summary``
after, less before (``breakdown.generation.<unit>.counters``: the program
sums them on the device and fetches them with each decode block's tokens).
Readers of ``moe.*`` metrics share this; a program that has no such
counters (one from before ISSUE 28) gives None, and the readers nothing."""

from __future__ import annotations


def _counters(snapshot: dict) -> dict | None:
    units = (snapshot.get("breakdown") or {}).get("generation") or {}
    for unit in units.values():
        found = unit.get("counters") if isinstance(unit, dict) else None
        if found:
            return found
    return None


def delta(run) -> dict | None:
    """``{counter: count inside the window}``, or None."""
    before, after = _counters(run.before), _counters(run.after)
    if after is None:
        return None
    return {k: int(v) - int((before or {}).get(k, 0)) for k, v in after.items()}


def held_layer_steps(run) -> tuple[dict, float] | None:
    """(the counters' delta, held experts x layers x decode steps: the
    (expert, layer, step) places a token could have landed on), or None
    where no decode step was counted."""
    import costs_cohere2_moe as cm

    d = delta(run)
    if not d or d.get("moe.steps", 0) <= 0:
        return None
    g = run.config["graph"]["parameters"]
    return d, float(cm.held(g) * g["n_layers"] * d["moe.steps"])
