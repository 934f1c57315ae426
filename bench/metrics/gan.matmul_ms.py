"""gan.matmul_ms: device time per epoch of the trace's convolution
category (XLA's dots and convolutions, fused or not), summed over the
window and averaged over the cell's chips, over the epochs the window
completed."""
from harness import trace


def read(run):
    epochs = run.facts.get("epochs")
    if run.trace is None or not epochs:
        return None
    s = trace.category_seconds(run.trace, trace.is_matmul)
    if s <= 0:
        return None
    return 1e3 * s / epochs
