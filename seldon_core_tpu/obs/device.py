"""The device's time, kept by the scheduler that fills it (docs/OBSERVABILITY.md
"Device ledger").

A ``GenerationScheduler`` hands its ledger three kinds of instants, all on
``time.perf_counter`` and all taken where the run loop or its workers
already pass (the ledger reads no clock): a part of the run loop on its exit
(:meth:`DeviceLedger.part`), a dispatch of device work with the instant the
dispatch call returned (:meth:`sent`) and a completion with the instant the
wait for it returned on the worker's thread (:meth:`done`).  The loop folds
all three in, so the ledger is single-threaded and takes no lock.

The device runs what it is handed in order, so a program occupies ``[max(its
dispatch returned, its predecessor done), done]``; a program dispatched
with no wait of its own (a prompt's chunk but the last) is booked with the
next one that is waited for.  Where a dispatch returned after the
predecessor was done, the device stood idle in between, and that gap is
shared out over the parts of the run loop that overlap it (``sched:loop``
is the loop between two parts; ``idle-park`` is idle for want of demand).
What lies inside a program, and between a dispatch call's enqueue and its
return, is not seen.  Busy and idle between two done stamps add up to the
wall time between them.
"""

from __future__ import annotations

import collections
import math
from typing import NamedTuple

from seldon_core_tpu.obs.history import _Ring

KINDS = ("decode", "prefill", "other")
LOOP = "sched:loop"
# one-second buckets kept.  A reader wants a window that ended some time ago:
# the benchmark's snapshot comes after its drain and, in a traced run, after
# ``/profile/stop`` has returned, which took 272 s on four chips (PERF.md §6,
# PR 57) — a ring of 180 had lost the window by then
SECONDS = 600
COLUMNS = ("t", "busy_decode_s", "busy_prefill_s", "busy_other_s",
           "decode_steps", "idle_s", "profiler")
_PARTS_KEPT = 16  # a quiet device's parts are booked when so many are held
_INF = float("inf")


class _Sent(NamedTuple):
    """Device work dispatched and not yet heard done."""

    kind: str
    label: str
    steps: int
    n: int
    at: float  # the dispatch call returned
    waits: bool  # a ``done`` of its own will follow


class DeviceLedger:
    """Busy by kind and program, idle by host part: totals since boot, the
    last ``SECONDS`` seconds bucket by bucket, and the stretch a profiler
    trace covered."""

    def __init__(self):
        # every booked second under its column: ``busy:<kind>``, ``steps``,
        # ``idle:<part>`` — the totals, the rings and the traced stretch
        self._total: dict[str, float] = collections.defaultdict(float)
        self._rings: dict[str, _Ring] = {}
        self.programs: dict[str, dict] = {}
        # parts of the run loop that may yet explain an idle gap
        self._parts: list[tuple[str, float, float]] = []
        self._flying: list[_Sent] = []
        # device time is booked up to here; None until the first part
        self._free: float | None = None
        # profiler marks (code, from, to), the open one last
        self._marks: list[list] = []
        self._traced: dict | None = None

    # ------------------------------------------------------------ feeding

    def part(self, name: str, t0: float, t1: float) -> None:
        """A part of the run loop has ended."""
        if self._free is None:
            self._free = t0  # the books open with the first part
        self._parts.append((name, t0, t1))
        if len(self._parts) >= _PARTS_KEPT:
            if self._flying:
                del self._parts[0]  # a block in flight has few parts beside it
            else:
                self._quiet_until(t1)

    def sent(self, at: float, kind: str, label: str, steps: int = 0,
             n: int = 1, *, part: tuple[str, float], waits: bool = True) -> None:
        """Device work went out under ``part`` (its name and start), the
        dispatch call returning at ``at``: ``n`` programs of ``kind``, a
        decode block of ``steps``.  ``waits``: a :meth:`done` will follow."""
        if not self._flying:
            self._parts.append((part[0], part[1], at))
            self._quiet_until(at)
        self._flying.append(_Sent(kind, label, steps, n, at, waits))

    def done(self, at: float) -> float:
        """The oldest awaited program is done (and whatever went out before
        it) -> the seconds it occupied the device."""
        fly = self._flying
        upto = next((i for i, e in enumerate(fly) if e.waits), len(fly) - 1) + 1
        ents, self._flying = fly[:upto], fly[upto:]
        if not ents:
            return 0.0
        if ents[0].at > self._free:
            # it was dispatched after its predecessor had ended
            self._idle(self._free, ents[0].at)
        start, at = self._free, max(at, self._free)
        kind, label = ents[0].kind, ents[0].label
        for e in ents[1:]:  # an interval of two kinds or labels is neither's
            kind = kind if e.kind == kind else "other"
            label = label if e.label == label else "mixed"
        row = self.programs.setdefault(label, {"n": 0, "steps": 0, "busy_s": 0.0})
        steps = sum(e.steps for e in ents)
        row["n"] += sum(e.n for e in ents)
        row["steps"] += steps
        row["busy_s"] += at - start
        self._book(f"busy:{kind}", start, at)
        if kind == "decode":
            self._book("steps", start, at, steps)
        self._free = at
        self._parts = [p for p in self._parts if p[2] > at]
        return at - start

    def lost(self) -> None:
        """What was in flight failed: nothing is booked for it, and the
        books open again at the next dispatch."""
        self._flying.clear()
        self._parts.clear()
        self._free = None

    def profiler(self, state: str, at: float) -> None:
        """``/profile/start`` entered (``start``) and returned (``run``),
        ``/profile/stop`` entered (``stop``) and returned (``off``; a start
        that failed says it too)."""
        if state == "run":
            self._traced = {"t0": at, "t1": _INF,
                            "cols": collections.defaultdict(float)}
            return
        if self._marks and self._marks[-1][2] == _INF:
            self._marks[-1][2] = at
        if state == "off":
            return
        self._marks = self._marks[-7:] + [[1 if state == "start" else 2, at, _INF]]
        if state == "stop" and self._traced is not None:
            self._traced["t1"] = at

    # ------------------------------------------------------------ booking

    def _quiet_until(self, at: float) -> None:
        """Nothing is in flight: the device stood idle up to ``at``."""
        if self._free is None:
            self._free = at
        elif at > self._free:
            self._idle(self._free, at)
        self._parts.clear()

    def _idle(self, a: float, b: float) -> None:
        """Share the idle gap ``[a, b]`` over the parts by their overlap with
        it, and the loop between them."""
        c = a
        for name, t0, t1 in self._parts:
            if t1 <= c:
                continue
            if t0 >= b:
                break
            if t0 > c:
                self._book(f"idle:{LOOP}", c, t0)
                c = t0
            hi = min(t1, b)
            self._book(f"idle:{name}", c, hi)
            c = hi
        self._book(f"idle:{LOOP}", c, b)
        self._free = b

    def _book(self, col: str, a: float, b: float, amount: float | None = None) -> None:
        """``amount`` (the interval's own seconds if none is given) under
        ``col``, shared over the seconds ``[a, b]`` covers by overlap."""
        span = b - a
        if span <= 0:
            return
        if amount is None:
            amount = span
        self._total[col] += amount
        ring = self._rings.get(col)
        if ring is None:
            ring = self._rings[col] = _Ring(1.0, SECONDS)
        t = max(a, b - SECONDS)  # older seconds have left the ring
        while t < b:
            nxt = min(math.floor(t) + 1.0, b)
            ring.record(t, amount * (nxt - t) / span)
            t = nxt
        tr = self._traced
        if tr is not None:
            over = min(b, tr["t1"]) - max(a, tr["t0"])
            if over > 0:
                tr["cols"][col] += amount * over / span

    # ------------------------------------------------------------ showing

    @staticmethod
    def _shown(cols: dict) -> dict:
        out = {"busy_s": dict.fromkeys(KINDS, 0.0), "decode_steps": 0.0, "idle_s": {}}
        for col, v in cols.items():
            head, _, name = col.partition(":")
            if head == "steps":
                out["decode_steps"] = round(v, 6)
            else:
                out[f"{head}_s"][name] = round(v, 6)
        return out

    def snapshot(self, now: float, part: tuple[str, float]) -> dict:
        """``breakdown.generation.<unit>.device``.  ``part`` is the part the
        run loop is in and since when: a quiet device's idle time is booked
        up to ``now`` first, so the totals are the rows' sums."""
        if not self._flying and self._free is not None:
            self._parts.append((part[0], part[1], now))
            self._quiet_until(now)
        rows = []
        b_now = int(now // 1.0)
        for b in range(b_now - SECONDS + 1, b_now + 1):
            got = {c: v for c, r in self._rings.items() if (v := r.total(b))}
            mark = max((m[0] for m in self._marks if m[1] < b + 1 and m[2] > b),
                       default=0)
            if not got and not mark:
                continue
            s = self._shown(got)
            rows.append([b, *(s["busy_s"][k] for k in KINDS),
                         s["decode_steps"], s["idle_s"], mark])
        out = {"clock_s": now, **self._shown(self._total),
               "programs": {k: {**v, "busy_s": round(v["busy_s"], 6)}
                            for k, v in self.programs.items()},
               "columns": list(COLUMNS), "seconds": rows, "traced": None}
        tr = self._traced
        if tr is not None:
            out["traced"] = {
                "wall_s": round(min(tr["t1"], now) - tr["t0"], 6),
                "running": tr["t1"] == _INF, **self._shown(tr["cols"]),
            }
        return out
