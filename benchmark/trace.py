"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
benchmark reports.  Runs in a process of its own, pinned to the CPU, after
the engine has exited:

    python benchmark/trace.py <trace dir or .xplane.pb> <out.json>
    python benchmark/trace.py --dump <trace dir or .xplane.pb>

What it reads, on a TPU trace:

- device planes ``/device:TPU:<n>``: the line ``XLA Ops`` (one event per
  operation that ran on the device) and the line ``XLA Modules`` (one event
  per execution of a compiled program);
- host planes: the program's ``TraceAnnotation`` labels around every
  dispatch (``prefill:b<bucket>``, ``decode_k:k<k>:w<window>``, ...).

What it gives:

- ``window_s``: first to last instant of any event on a device plane (the
  host's events start earlier and end later: the profiler's own start and
  stop, which are not the served system's time);
- ``busy_s``: the union of the ``XLA Ops`` intervals, averaged over the
  device planes that ran anything; ``busy_by_device``;
- ``programs``: every program execution on the first device with its
  ``module`` name, its ``device_s`` and its ``label`` — the annotation of
  the dispatch that launched it.  Programs run in dispatch order: an
  execution that starts as its predecessor ends was queued, and takes the
  next annotation; one that starts after the device stood idle takes the
  latest annotation before its start.  Each module then takes the label
  most of its executions were paired with, which keeps a slip at the
  slice's edges out;
- ``collective_s``: time of collective operations on the first device;
- ``breakdown``: the ten device operations that took most time (by the
  operation's name; ``while`` and ``conditional``, which only contain
  others, left out), and the idle time of the first device by what it was
  waiting for: ``before:<label>`` is idle time that ended when the program
  dispatched under that annotation started (the host had not dispatched it
  yet, or its inputs were not there), ``within:<label>`` gaps between the
  operations of one running program.
"""

from __future__ import annotations

import bisect
import collections
import glob
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from stats import union_seconds  # noqa: E402

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
LABEL = re.compile(r"^(prefill|decode|decode_k|suffix|embed|draft_prefill):")
QUEUE_GAP_S = 50e-6  # a longer gap before a program: it was not queued
CONTAINER = re.compile(r"^(while|conditional|call)[.\d]*$")
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
)


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(find_xplane(path))


def op_name(event_name: str) -> str:
    """``%fusion.219 = bf16[32,14336]{...} fusion(...)`` -> ``fusion.219``:
    the trace names an operation by its whole HLO line."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def events_of(plane, line_name: str) -> list[tuple[float, float, str]]:
    """(start_s, end_s, name) of a plane's line, by start."""
    out = []
    for line in plane.lines:
        if line.name == line_name:
            for e in line.events:
                s = e.start_ns * 1e-9
                out.append((s, s + e.duration_ns * 1e-9, op_name(e.name)))
    out.sort()
    return out


def reduce(pd) -> dict:
    devices = sorted(
        (int(m.group(1)), p) for p in pd.planes
        if (m := DEVICE_PLANE.match(p.name))
    )
    t_min, t_max = float("inf"), float("-inf")
    labels = []  # host annotations of dispatches, by start
    for plane in pd.planes:
        is_device = DEVICE_PLANE.match(plane.name) is not None
        for line in plane.lines:
            for e in line.events:
                s = e.start_ns * 1e-9
                if is_device:
                    t_min, t_max = min(t_min, s), max(t_max, s + e.duration_ns * 1e-9)
                elif LABEL.match(e.name):
                    labels.append((s, s + e.duration_ns * 1e-9, e.name))
    labels.sort()
    out = {
        "window_s": max(0.0, t_max - t_min) if t_max > t_min else 0.0,
        "devices": len(devices), "busy_s": 0.0, "busy_by_device": [],
        "programs": [], "collective_s": 0.0,
        "breakdown": {"device_ops": [], "idle_gaps": []},
    }
    if not devices:
        return out
    busy = []
    for _, plane in devices:
        ops = events_of(plane, "XLA Ops")
        busy.append(union_seconds((s, e) for s, e, _ in ops))
    out["busy_by_device"] = busy
    ran = [b for b in busy if b > 0]
    out["busy_s"] = sum(ran) / len(ran) if ran else 0.0

    first = devices[0][1]
    ops = events_of(first, "XLA Ops")
    by_op: dict[str, float] = collections.defaultdict(float)
    for s, e, name in ops:
        if not CONTAINER.match(name):
            by_op[name] += e - s
    out["collective_s"] = sum(v for k, v in by_op.items() if COLLECTIVE.match(k))
    out["breakdown"]["device_ops"] = [
        [k, v] for k, v in sorted(by_op.items(), key=lambda kv: -kv[1])[:10]
    ]

    # programs on the first device, paired with the dispatch annotations
    modules = events_of(first, "XLA Modules")
    votes: dict[str, collections.Counter] = collections.defaultdict(collections.Counter)
    label_starts = [lab[0] for lab in labels]
    li, prev_end = 0, None
    for s, e, name in modules:
        if prev_end is None or s - prev_end > QUEUE_GAP_S:
            # the device stood idle before this program: nothing was queued,
            # so its dispatch is the latest one before it started
            li = max(li, bisect.bisect_right(label_starts, s) - 1)
        if 0 <= li < len(labels) and labels[li][0] <= s:
            votes[name][labels[li][2]] += 1
            li += 1
        prev_end = e
    label_of = {m: c.most_common(1)[0][0] for m, c in votes.items()}
    out["programs"] = [
        {"module": name, "label": label_of.get(name, ""), "device_s": e - s,
         "start_s": s - t_min}
        for s, e, name in modules
    ]

    # idle time on the first device, by what it was waiting for
    idle: dict[str, float] = collections.defaultdict(float)
    merged = []
    for s, e, _ in ops:
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    mod_starts = [m[0] for m in modules]
    edges = [t_min] + [t for iv in merged for t in iv] + [t_max]
    for g0, g1 in zip(edges[0::2], edges[1::2]):
        if g1 <= g0:
            continue
        i = bisect.bisect_right(mod_starts, g0) - 1
        if i >= 0 and modules[i][1] >= g1:
            name, kind = modules[i][2], "within:"
        else:
            j = bisect.bisect_left(mod_starts, g1 - 1e-6)
            if j >= len(modules):
                name, kind = "the end of the trace", "before:"
            else:
                name, kind = modules[j][2], "before:"
        idle[kind + (label_of.get(name) or name)] += g1 - g0
    out["breakdown"]["idle_gaps"] = [
        [k, v] for k, v in sorted(idle.items(), key=lambda kv: -kv[1])[:10]
    ]
    return out


def dump(pd) -> None:
    for plane in pd.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            names = collections.Counter(e.name for e in evs)
            print(f"  line {line.name!r}: {len(evs)} events")
            for name, n in names.most_common(12):
                print(f"    {n:6d}  {name[:110]}")
            for e in evs[:2]:
                print("    stats:", [(k, str(v)[:60]) for k, v in e.stats][:12])


def main(argv: list[str]) -> int:
    if argv[1] == "--dump":
        dump(load(argv[2]))
        return 0
    reduced = reduce(load(argv[1]))
    with open(argv[2], "w") as f:
        json.dump(reduced, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
