"""The profiler trace, reduced to what the per-layer metrics read.

`reduce_xplane` keeps, for each of the cell's chips, the operations of
the device's "XLA Ops" line (start, duration, op name, class) and, from
the host, the harness's own `bench.*` spans.  An operation's class comes
from its HLO text:

    container   while, conditional, call: they hold other operations and
                are left out of every sum and of busy time
    matmul      convolution and dot, and fusions of kind kOutput, which
                on a TPU are the fusions built around a convolution (XLA
                lowers dots to convolutions there)
    collective  collective-permute, all-reduce, all-gather,
                reduce-scatter, all-to-all (their -start/-done halves too)
    other       everything else

Times are nanoseconds on the profiler's clock.  Host and device clocks
in one trace agree to about two milliseconds (measured on a TPU v5e),
which is what an idle gap's name can be off by.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re

CONTAINERS = ("while", "conditional", "call")
MATMUL_OPS = ("convolution", "dot")
COLLECTIVE = re.compile(r"^(collective-permute|all-reduce|all-gather|"
                        r"reduce-scatter|all-to-all|collective-broadcast)")


def opcode(text: str) -> str:
    """The HLO opcode of an op's text `%name = <shape> opcode(...)...`."""
    rest = text.split(" = ", 1)[1] if " = " in text else text
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rest = rest[i + 1:]
                break
    elif " " in rest:
        rest = rest.split(" ", 1)[1]
    return rest.strip().split("(", 1)[0]


def classify(text: str) -> str:
    op = opcode(text)
    if op in CONTAINERS:
        return "container"
    if COLLECTIVE.match(op):
        return "collective"
    if op in MATMUL_OPS or (op == "fusion" and "kind=kOutput" in text):
        return "matmul"
    return "other"


def short_name(text: str) -> str:
    return text.split(" = ", 1)[0] if " = " in text else text[:80]


def reduce_xplane(path: str, chips: int) -> dict:
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            idx = int(plane.name.rsplit(":", 1)[1])
            if idx >= chips:
                continue
            ops = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    cls = classify(e.name)
                    if cls != "container":
                        ops.append([e.start_ns, e.duration_ns,
                                    short_name(e.name), cls])
            devices[idx] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append([e.start_ns, e.duration_ns, e.name])
    win = [h for h in host if h[2] == "bench.window"]
    if win:
        t0, t1 = win[0][0], win[0][0] + win[0][1]
    else:
        every = [o for ops in devices.values() for o in ops]
        t0 = min(o[0] for o in every)
        t1 = max(o[0] + o[1] for o in every)
    return {"t0": t0, "t1": t1,
            "devices": [devices.get(i, []) for i in range(chips)],
            "host": sorted(host)}


def reduce_dir(trace_dir: str, chips: int) -> dict:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise RuntimeError(f"the profiler wrote no trace under {trace_dir}")
    return reduce_xplane(max(files, key=os.path.getmtime), chips)


def load(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# reductions


def union(intervals, t0, t1):
    """Merged [start, end) intervals clipped to [t0, t1)."""
    out = []
    for s, e in sorted((max(s, t0), min(s + d, t1)) for s, d in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _length(iv):
    return sum(e - s for s, e in iv)


def window_seconds(data) -> float:
    return (data["t1"] - data["t0"]) / 1e9


def busy_intervals(data, dev: int):
    return union([(o[0], o[1]) for o in data["devices"][dev]],
                 data["t0"], data["t1"])


def per_device_idle(data) -> list:
    span = data["t1"] - data["t0"]
    return [1.0 - _length(busy_intervals(data, i)) / span
            for i in range(len(data["devices"]))]


def busy_seconds(data) -> float:
    n = len(data["devices"])
    return sum(_length(busy_intervals(data, i)) for i in range(n)) / n / 1e9


def is_matmul(op) -> bool:
    return op[3] == "matmul"


def category_seconds(data, pred) -> float:
    """Device seconds of the ops `pred` selects, inside the window,
    averaged over chips."""
    n = len(data["devices"])
    total = 0
    for ops in data["devices"]:
        total += _length(union([(o[0], o[1]) for o in ops if pred(o)],
                               data["t0"], data["t1"]))
    return total / n / 1e9


def _minus(a, b):
    """Length of interval set `a` not covered by interval set `b`."""
    out, j = 0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out += e - cur
    return out


def exposed_collective_seconds(data):
    """Collective time with no other op running on that device, averaged
    over chips; None where the trace holds no collective at all."""
    n = len(data["devices"])
    total, seen = 0, False
    for ops in data["devices"]:
        coll = union([(o[0], o[1]) for o in ops if o[3] == "collective"],
                     data["t0"], data["t1"])
        seen = seen or any(o[3] == "collective" for o in ops)
        rest = union([(o[0], o[1]) for o in ops if o[3] != "collective"],
                     data["t0"], data["t1"])
        total += _minus(coll, rest)
    return total / n / 1e9 if seen else None


def breakdown(data, top: int = 10) -> dict:
    """The ops that took most device time (seconds, averaged over chips)
    and device 0's longest idle gaps, each named by the innermost
    `bench.*` host span open at its middle."""
    n = len(data["devices"])
    per_op = {}
    for ops in data["devices"]:
        for s, d, name, _ in ops:
            lo, hi = max(s, data["t0"]), min(s + d, data["t1"])
            if hi > lo:
                per_op[name] = per_op.get(name, 0) + (hi - lo)
    device_ops = sorted(([k, v / n / 1e9] for k, v in per_op.items()),
                        key=lambda x: -x[1])[:top]
    busy = busy_intervals(data, 0) if data["devices"] else []
    gaps, cur = [], data["t0"]
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = e
    if cur < data["t1"]:
        gaps.append((cur, data["t1"]))
    gaps.sort(key=lambda g: g[0] - g[1])
    named = []
    for s, e in gaps[:top]:
        mid = (s + e) / 2
        open_ = [h for h in data["host"]
                 if h[0] <= mid < h[0] + h[1] and h[2] != "bench.window"]
        name = min(open_, key=lambda h: h[1])[2] if open_ else "no span"
        named.append([name, (e - s) / 1e9])
    return {"device_ops": device_ops, "idle_gaps": named}
