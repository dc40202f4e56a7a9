"""Host stalls inside the window, seen from the process itself.

A heartbeat thread wakes every ``period_s``; a wake later than ``limit_s``
means the interpreter lock or the whole process was held up that long.
Python's garbage collections that take longer than ``gc_s`` are timed
beside it.  With ``dump`` set, the heartbeat keeps ``faulthandler``'s
watchdog armed, which writes every thread's stack to that file whenever
the heartbeat misses ``limit_s``: where each thread stood in the stall.
"""
from __future__ import annotations

import faulthandler
import gc
import threading
import time


class StallWatch:
    def __init__(self, period_s: float = 0.02, limit_s: float = 0.2,
                 gc_s: float = 0.05, dump=None):
        self.period_s, self.limit_s, self.gc_s = period_s, limit_s, gc_s
        self.dump = dump
        self.stalls: list[tuple[float, float]] = []  # (start, seconds)
        self.collections: list[tuple[int, float]] = []  # (generation, s)
        self._gc_t0 = 0.0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._beat, daemon=True)

    def _beat(self) -> None:
        last = time.perf_counter()
        while not self._stop.wait(self.period_s):
            if self.dump is not None:
                faulthandler.dump_traceback_later(self.limit_s, file=self.dump)
            now = time.perf_counter()
            if now - last - self.period_s > self.limit_s:
                self.stalls.append((last - self.t0, now - last - self.period_s))
            last = now

    def _gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif (dt := time.perf_counter() - self._gc_t0) > self.gc_s:
            self.collections.append((info["generation"], dt))

    def __enter__(self) -> "StallWatch":
        self.t0 = time.perf_counter()
        gc.callbacks.append(self._gc)
        self._thread.start()
        return self

    def __exit__(self, *exc) -> None:
        self._stop.set()
        self._thread.join()
        if self.dump is not None:
            faulthandler.cancel_dump_traceback_later()
        gc.callbacks.remove(self._gc)

    def summary(self) -> str:
        longest = max(self.stalls, key=lambda s: s[1], default=(0.0, 0.0))
        gc_max = max(self.collections, key=lambda c: c[1], default=(0, 0.0))
        return (f"host stalls inside the window over {self.limit_s} s: "
                f"{len(self.stalls)}, longest {longest[1]:.4f} s at "
                f"+{longest[0]:.3f} s; garbage collections over {self.gc_s} s: "
                f"{len(self.collections)}, longest {gc_max[1]:.4f} s "
                f"(generation {gc_max[0]})")
