"""What the host did during the window, to name a pause: the garbage
collector's passes (each with its start, length and generation), and the
process's CPU time, context switches and page faults.  A pause that a
collector pass covers is the process's own; one in which the process
spent no CPU time was the machine's."""
from __future__ import annotations

import gc
import resource
import time


class HostWatch:
    def __init__(self):
        self.passes = []              # (start s from t0, seconds, generation)
        self._t = 0.0

    def _collected(self, phase, info):
        now = time.perf_counter()
        if phase == "start":
            self._t = now
        else:
            self.passes.append((self._t - self.t0, now - self._t,
                                info["generation"]))

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.cpu0 = time.process_time()
        self.ru0 = resource.getrusage(resource.RUSAGE_SELF)
        gc.callbacks.append(self._collected)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._collected)
        self.wall = time.perf_counter() - self.t0
        self.cpu = time.process_time() - self.cpu0
        self.ru1 = resource.getrusage(resource.RUSAGE_SELF)
        return False

    def summary(self) -> str:
        d = lambda f: getattr(self.ru1, f) - getattr(self.ru0, f)
        longest = max(self.passes, key=lambda p: p[1], default=None)
        gc_s = sum(p[1] for p in self.passes)
        text = (f"{len(self.passes)} collector passes "
                f"({sum(p[2] == 2 for p in self.passes)} of generation 2), "
                f"{1e3 * gc_s:.1f} ms in all")
        if longest:
            text += (f", longest {1e3 * longest[1]:.1f} ms (generation "
                     f"{longest[2]}) at {longest[0]:.3f} s")
        return (text + f"; CPU {self.cpu:.2f} s in {self.wall:.2f} s; "
                f"context switches {d('ru_nvcsw')} voluntary, "
                f"{d('ru_nivcsw')} involuntary; page faults "
                f"{d('ru_majflt')} major, {d('ru_minflt')} minor")

    def overlap(self, start: float, end: float) -> float:
        """Seconds of collector passes inside [start, end] (clock values)."""
        a, b = start - self.t0, end - self.t0
        return sum(max(0.0, min(b, s + n) - max(a, s))
                   for s, n, _ in self.passes)
