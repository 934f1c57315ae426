"""exchange.exposed_ms: per epoch, the time of collective operations
(collective-permute, all-reduce, all-gather, ...) during which no other
operation runs on that device, averaged over the cell's chips."""
from harness import trace


def read(run):
    epochs = run.facts.get("epochs")
    if run.trace is None or not epochs:
        return None
    s = trace.exposed_collective_seconds(run.trace)
    if s is None:
        return None
    return 1e3 * s / epochs
