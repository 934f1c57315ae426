"""train.idle_share: 1 minus the union of the device's operation
intervals over the traced window, averaged over the cell's chips, in %."""
from harness import trace


def read(run):
    if run.trace is None or not run.facts.get("epochs"):
        return None
    idle = trace.per_device_idle(run.trace)
    return 100.0 * sum(idle) / len(idle) if idle else None
