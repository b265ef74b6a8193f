"""The share of the tiled prompt kernel's grid steps that had to be multiplied:
100 x live / stepped over the whole prompts admitted in the window, from the
engine's own sums (``/stats/summary`` after, less before:
``breakdown.generation.<unit>.programs.tile_plans.admitted``; host arithmetic
at each admission from the rung's traced plans and the prompt's real length,
``ops/flash_attention.py::tile_plan``).  The rest are the steps of query tiles
that lie wholly in a rung's padding, which the kernel takes without a product
or a copy.  It is the traffic's and the ladder's own: a finer ladder raises it,
a faster kernel does not.  A program without the sums (one from before ISSUE
58, or a family whose prompts do not run that kernel) gives nothing."""


def _admitted(snapshot: dict) -> dict | None:
    units = (snapshot.get("breakdown") or {}).get("generation") or {}
    for unit in units.values():
        programs = unit.get("programs") if isinstance(unit, dict) else None
        found = ((programs or {}).get("tile_plans") or {}).get("admitted")
        if found:
            return found
    return None


def read(run):
    after = _admitted(run.after)
    if after is None:
        return None
    before = _admitted(run.before) or {}
    stepped = int(after["stepped"]) - int(before.get("stepped", 0))
    live = int(after["live"]) - int(before.get("live", 0))
    return 100.0 * live / stepped if stepped > 0 else None
