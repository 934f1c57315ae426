"""solve.batch_fill: requests the drainer served over (its `step()`
calls that served any request x `max_batch`), in %.  Counted by the
harness around each call."""


def read(run):
    busy = run.facts.get("busy_steps")
    if not busy:
        return None
    return 100.0 * run.facts["drained"] / (busy * run.facts["max_batch"])
