"""Poisson arrivals, at one rate or in phases of their own rates.

    rate_per_s     the rate, where the mix has no `phases`
    phases         optional [[seconds, rate_per_s], ...], repeated over
                   the window: on/off or bursty load, as data

Each phase's requests are round(rate x its seconds); their gaps are the
exponential's quantiles at (i + 0.5) / n in the order `rng` gives, so
every seed offers the same multiset of gaps, in its own order.
"""
from __future__ import annotations

import numpy as np


def _gaps(n: int, rate: float, rng) -> np.ndarray:
    q = (np.arange(n) + 0.5) / n
    return rng.permutation(-np.log1p(-q) / rate)


def offsets(traffic: dict, seconds: float, rng) -> np.ndarray:
    """Sorted send times, in seconds from the window's start."""
    phases = traffic.get("phases") or [[seconds, traffic["rate_per_s"]]]
    out, t = [], 0.0
    while t < seconds - 1e-9:
        for length, rate in phases:
            length = min(float(length), seconds - t)
            n = int(round(rate * length))
            if n:
                out.append(t + np.cumsum(_gaps(n, rate, rng)))
            t += length
            if t >= seconds - 1e-9:
                break
    return np.sort(np.concatenate(out)) if out else np.zeros(0)
