"""What the host was doing beside the blocks of a window, for the run's
file: a block that took far longer than the median block costs the rate
its whole excess, and this says where to look.

* a ticker thread that sleeps ``TICK_S`` at a time and records every
  wake-up that came late by ``PAUSE_S`` or more: the whole process (or
  machine) stood still, or another thread held the interpreter.  A long
  block with no such pause beside it was the main thread waiting, with
  the interpreter released, on the device or its runtime;
* every garbage collection: generation and seconds;
* at each block's end the kernel's counters: time any task stalled for
  want of CPU, I/O or memory (``/proc/pressure``), CPU time stolen from
  this machine (``/proc/stat``), this process's involuntary context
  switches and major page faults.
"""

from __future__ import annotations

import gc
import resource
import threading
import time

TICK_S = 0.005
PAUSE_S = 0.05
#: a block is slow when it exceeds the median block by this share
SLOW = 0.03


def _psi(kind: str):
    try:
        with open(f"/proc/pressure/{kind}") as f:
            return int(f.readline().rsplit("total=", 1)[1]) / 1e6
    except (OSError, IndexError, ValueError):
        return None


def _steal():
    try:
        with open("/proc/stat") as f:
            return int(f.readline().split()[8])  # jiffies
    except (OSError, IndexError, ValueError):
        return None


class HostWatch:
    def __init__(self):
        self.pauses, self.gcs, self.marks = [], [], []
        self._gc_t0 = None
        self._stop = threading.Event()
        gc.callbacks.append(self._on_gc)
        self._thread = threading.Thread(target=self._tick, daemon=True,
                                        name="kfb-hostwatch")
        self._thread.start()
        self.mark()

    def _tick(self):
        last = time.perf_counter()
        while not self._stop.wait(TICK_S):
            now = time.perf_counter()
            if now - last >= TICK_S + PAUSE_S:
                self.pauses.append((last, now - last))
            last = now

    def _on_gc(self, phase, info):
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            self.gcs.append((self._gc_t0, info["generation"],
                             time.perf_counter() - self._gc_t0))

    def mark(self):
        """The counters now: called at every block's end."""
        ru = resource.getrusage(resource.RUSAGE_SELF)
        self.marks.append((time.perf_counter(), _psi("cpu"), _psi("io"),
                           _psi("memory"), _steal(), ru.ru_nivcsw,
                           ru.ru_majflt))

    def stop(self, t0: float) -> dict:
        """End the watch; times come back relative to ``t0``."""
        self._stop.set()
        self._thread.join(timeout=1)
        gc.callbacks.remove(self._on_gc)
        return {
            "pauses": [(t - t0, s) for t, s in self.pauses],
            "gcs": [(t - t0, g, s) for t, g, s in self.gcs],
            "marks": [(m[0] - t0, *m[1:]) for m in self.marks],
            "mark_fields": ["t", "psi_cpu_s", "psi_io_s", "psi_memory_s",
                            "steal_jiffies", "involuntary_switches",
                            "major_faults"],
        }


def slow_blocks(block_s, host: dict, most: int = 5):
    """For the slowest few blocks that exceeded the median block by
    ``SLOW``: number, seconds, the median's, and what the watch saw."""
    import statistics

    med = statistics.median(block_s)
    out, start = [], 0.0
    marks = host["marks"]
    for i, b in enumerate(block_s):
        end = start + b
        if b > med * (1 + SLOW):
            delta = {}
            if len(marks) > i + 1:
                for k, name in enumerate(host["mark_fields"][1:], start=1):
                    if marks[i][k] is not None and marks[i + 1][k] is not None:
                        delta[name] = marks[i + 1][k] - marks[i][k]
            out.append({
                "block": i, "seconds": b, "median": med, "from": start,
                "pauses": [p for p in host["pauses"]
                           if start - p[1] < p[0] < end],
                "gcs": [g for g in host["gcs"] if start - g[2] < g[0] < end],
                "counters": delta})
        start = end
    return sorted(out, key=lambda s: -s["seconds"])[:most]
