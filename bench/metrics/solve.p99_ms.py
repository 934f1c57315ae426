"""The 99th percentile of every request's latency in the traced window,
from when it was due until its answer was back (ms).  It sits beside the
bounded p95: one pause of the machine in a window sets it (PERF.md)."""


def read(run):
    return run.facts.get("latency_p99_ms")
