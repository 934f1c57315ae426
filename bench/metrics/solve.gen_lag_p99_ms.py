"""solve.gen_lag_p99_ms: the 99th percentile of how late the load
generator sent each request's first attempt after it was due: a starved
generator reads high here while the service may be fast."""
import numpy as np


def read(run):
    lags = run.facts.get("gen_lag_s")
    if not lags:
        return None
    return 1e3 * float(np.percentile(np.asarray(lags), 99))
