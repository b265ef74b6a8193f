"""A stall names what the host was doing (docs/OBSERVABILITY.md, "the stall
line").

One run in ten of a chat cell stands still for seconds with nothing
compiled and every stream correct (PERF.md §7).  The latency recorders say
THAT it happened; this watchdog says what the process was in when it did.
The generation scheduler keeps the part of its run loop it is in and since
when; a daemon thread of its own wakes every ``WAKE_S`` and, when a part
that is no park has lasted over ``STALL_S`` with work at hand, writes ONE
log line: the part, how long so far, how late its own wake was (late by the
same seconds: the whole process stood still, the GIL or the host; on time:
only the event loop or a worker thread did), the loop-lag probe's last
reading, the full garbage collections since the last line with their
longest pause, and ``file:line function`` of the innermost frames of the
loop's thread and of each busy ``to_thread`` worker.  A second line when
the part ends, with its length.  Nothing on the served path: the scheduler
assigns one tuple a part.
"""

from __future__ import annotations

import gc
import logging
import os
import sys
import threading
import time
from typing import Callable

from seldon_core_tpu.obs.probes import LOOP_LAG

log = logging.getLogger(__name__)

WAKE_S = 0.25
STALL_S = 1.0
LINE_MAX = 1200  # the benchmark keeps the last 1,500 characters of the log
# parts in which the run loop waits for somebody else by design
PARKS = frozenset({"idle-park"})
_WORKER_PREFIX = "asyncio_"  # the default executor's threads (``to_thread``)


_OWN = os.sep + "seldon_core_tpu" + os.sep


def _where(frame, depth: int = 1) -> str:
    """``file:line function`` of a thread's innermost ``depth`` frames, and
    of its innermost frame in this package where those are all a library's
    (a worker inside ``jax`` says which of the program's calls it serves)."""
    out, own = [], False
    while frame is not None and (len(out) < depth or not own):
        code = frame.f_code
        mine = _OWN in code.co_filename
        if len(out) < depth or mine:
            own = own or mine
            out.append(
                f"{os.path.basename(code.co_filename)}:{frame.f_lineno} "
                f"{code.co_name}"
            )
        frame = frame.f_back
    return " < ".join(out)


class StallWatchdog:
    """``state()`` gives ``(part, since, busy)``: the run loop's current
    part, the ``time.perf_counter`` instant it was entered, and whether a
    slot is live or a request waits.  Started and stopped with the run
    task, as often as that is; the counts are kept across."""

    def __init__(self, unit: str, state: Callable[[], tuple[str, float, bool]]):
        self.unit = unit
        self._state = state
        self._loop_thread = 0
        self._stop = threading.Event()
        # the stall a first line was written for: (part, since)
        self._open: tuple[str, float] | None = None
        self.count = 0
        self.longest_s = 0.0
        self.last_part: str | None = None
        self._gc_t0 = 0.0
        self._gc_full = 0
        self._gc_longest_s = 0.0

    def start(self, loop_thread: int) -> None:
        """``loop_thread``: the ident of the thread the run loop's event
        loop runs on."""
        self.stop()
        self._loop_thread = loop_thread
        self._open = None
        self._stop = stop = threading.Event()
        gc.callbacks.append(self._on_gc)
        threading.Thread(
            target=self._watch, args=(stop,),
            name=f"stall-watchdog:{self.unit}", daemon=True,
        ).start()

    def stop(self) -> None:
        self._stop.set()
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)

    def snapshot(self) -> dict:
        return {
            "count": self.count,
            "longest_s": round(self.longest_s, 3),
            "last_part": self.last_part,
        }

    def _on_gc(self, phase: str, info: dict) -> None:
        if info.get("generation") != 2:
            return
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        else:
            self._gc_full += 1
            self._gc_longest_s = max(
                self._gc_longest_s, time.perf_counter() - self._gc_t0
            )

    def _watch(self, stop: threading.Event) -> None:
        due = time.perf_counter() + WAKE_S
        while not stop.wait(max(0.0, due - time.perf_counter())):
            now = time.perf_counter()
            late_s, due = now - due, now + WAKE_S
            try:
                self._look(now, late_s)
            except Exception:  # a watchdog must outlive what it watches
                log.exception("stall watchdog: a look failed")

    def _look(self, now: float, late_s: float) -> None:
        part, since, busy = self._state()
        if self._open is not None:
            if self._open == (part, since):
                return  # the stall already on the log goes on
            # the next part's start is the stalled part's end
            self._ended(self._open[0], max(since, self._open[1]) - self._open[1])
        if late_s > STALL_S:
            # this thread stood still as well: the part the loop was in may
            # have ended since, so the stall is named by the wake alone
            self._note(part, late_s)
            log.warning(self._line(part, late_s, late_s))
        elif busy and part not in PARKS and now - since > STALL_S:
            self._open = (part, since)
            self._note(part, now - since)
            log.warning(self._line(part, now - since, late_s))

    def _note(self, part: str, seconds: float) -> None:
        self.count += 1
        self.last_part = part
        self.longest_s = max(self.longest_s, seconds)

    def _ended(self, part: str, seconds: float) -> None:
        self._open = None
        self.longest_s = max(self.longest_s, seconds)
        log.warning(
            "stall-end unit=%s part=%s lasted=%.3fs", self.unit, part, seconds
        )

    def _line(self, part: str, seconds: float, late_s: float) -> str:
        frames = sys._current_frames()
        workers = [
            _where(frames.get(t.ident))
            for t in threading.enumerate()
            if t.name.startswith(_WORKER_PREFIX)
        ]
        # a worker with nothing to run sits in the executor's own loop
        busy = [w for w in workers if w and not w.endswith(" _worker")]
        gc_full, gc_longest = self._gc_full, self._gc_longest_s
        self._gc_full, self._gc_longest_s = 0, 0.0
        line = (
            f"stall unit={self.unit} part={part} for={seconds:.3f}s "
            f"watchdog_late={late_s:.3f}s"
            f"{' (the whole process stood still)' if late_s > STALL_S else ''} "
            f"loop_lag_last={LOOP_LAG.last_lag_s * 1e3:.1f}ms "
            f"gc_full={gc_full} gc_longest={gc_longest * 1e3:.1f}ms "
            f"loop=[{_where(frames.get(self._loop_thread), 3) or '?'}] "
            f"workers={len(workers)} busy=[{'; '.join(busy)}]"
        )
        return line[: LINE_MAX - 1]
