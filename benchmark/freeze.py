"""Observe when the measuring process is not run.

On the sealed one-chip machine every process stands still for 3-12 s while
the TPU client starts (set-up), and now and then a second time, later
(PERF.md §5).  A thread of the driver process — which does nothing else
that long — sleeps in short ticks; a tick that wakes more than LIMIT_S late
was a stall.  The watch only records: a window that held a stall is
measured and reported like any other, and the stalls go into the result
line (`stalls`) and the per-layer metric `host_stall_s`, so that a reader
of a far-off run can see what it held.  The watch also keeps every tick
that woke over LATE_S late (`late`), the long stalls among them:
`setup_net_s` is set-up less those (on PR 29's machines the short ones
added 0.0-0.6 s to the one long stop)."""

from __future__ import annotations

import threading
import time
from typing import List, Tuple

TICK_S = 0.05
LIMIT_S = 1.0
LATE_S = 0.1


class FreezeWatch:
    def __init__(self) -> None:
        self.stalls: List[Tuple[float, float]] = []   # (began, ended), wall
        self.late: List[Tuple[float, float]] = []     # the lateness alone
        self._halt = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self) -> None:
        last = time.time()
        while not self._halt.wait(TICK_S):
            now = time.time()
            if now - last - TICK_S > LIMIT_S:
                self.stalls.append((last, now))
            if now - last - TICK_S > LATE_S:
                self.late.append((last + TICK_S, now))
            last = now

    def close(self) -> None:
        self._halt.set()
        self._thread.join(1.0)


def seconds(stalls, since: float, until: float) -> float:
    """Seconds of `stalls` that fall inside [since, until]."""
    return sum(max(0.0, min(e, until) - max(b, since)) for b, e in stalls)
