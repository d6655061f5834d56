"""Where the main thread waits while a sweep runs long.

A daemon thread wakes every ``PERIOD`` seconds.  While the current sweep
has run longer than ten times the fastest sweep so far, it records the
main thread's innermost Python frames and the process's CPU seconds, so
a window's log line says which call a stalled sweep sat in and whether
the process worked meanwhile.  It reads nothing the metrics use.
"""
from __future__ import annotations

import collections
import os
import sys
import threading
import time
import traceback

PERIOD = 0.05
FACTOR = 10.0
DEPTH = 6


class StallWatch:

    def __init__(self):
        self._main = threading.get_ident()
        self._start = None            # perf_counter at the current sweep
        self._index = -1
        self.fastest = float("inf")
        self.stacks = collections.Counter()
        self.stalls = {}              # sweep -> [wall s, cpu s] seen stalled
        self.late_max = 0.0           # the watcher's own longest oversleep
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def begin(self, index: int) -> None:
        self._index, self._start = index, time.perf_counter()

    def end(self) -> float:
        took = time.perf_counter() - self._start
        self._start = None
        self.fastest = min(self.fastest, took)
        return took

    def _loop(self) -> None:
        last, cpu_last = time.perf_counter(), time.process_time()
        while not self._stop.wait(PERIOD):
            now, cpu = time.perf_counter(), time.process_time()
            self.late_max = max(self.late_max, now - last - PERIOD)
            start = self._start
            if start is not None and now - start > FACTOR * self.fastest:
                frame = sys._current_frames().get(self._main)
                if frame is not None:
                    st = traceback.extract_stack(frame)[-DEPTH:]
                    self.stacks[" < ".join(
                        f"{os.path.basename(f.filename)}:{f.lineno}:{f.name}"
                        for f in reversed(st))] += 1
                seen = self.stalls.setdefault(self._index, [0.0, 0.0])
                seen[0] += now - last
                seen[1] += cpu - cpu_last
            last, cpu_last = now, cpu

    def close(self) -> dict:
        """Stops the watcher; the stalls it saw, for the window's log."""
        self._stop.set()
        self._thread.join()
        return {"stalled_sweeps": {str(k): [round(w, 3), round(c, 3)]
                                   for k, (w, c) in self.stalls.items()},
                "stall_stacks": self.stacks.most_common(3),
                "watch_late_max_s": self.late_max}
