"""One run of one cell: set-up, the measured window, the check of what
the window's path produced, and with `trace` the per-layer metrics."""
from __future__ import annotations

import contextlib
import gc
import shutil
import sys
import tempfile
import time

from . import compare
from .compile_watch import CompileWatch
from .device import check_devices, memory_peak_bytes, peaks_for
from .host import HostWatch
from .spec import Bench

def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


class Window:
    t0 = 0.0
    elapsed = 0.0


class Context:
    """What a runner sees of the run, and what it reports back."""

    def __init__(self, bench: Bench, workload: str, seed: int,
                 seconds: float, trace: bool, t_start: float,
                 require_tpu: bool = True, control: bool = False,
                 wrap_call=None):
        self.bench = bench
        self.workload = bench.workload(workload)
        self.config = bench.config(self.workload["config"])
        self.traffic = bench.traffic(self.workload["traffic"])
        self.limits = bench.limits(workload)
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.t_start, self.require_tpu = t_start, require_tpu
        self.control = control
        self.wrap_call = wrap_call or (lambda fn: fn)
        self.host = None
        self.chips = self.workload["chips"]
        self.e2e, self.window_facts = {}, {}
        self.attempted = self.failed = 0
        self.memory_peak = 0
        self.setup_s = None
        self.trace_dir = None
        self.window_s = None
        self.watch = CompileWatch()

    def span(self, name: str):
        import jax
        return jax.profiler.TraceAnnotation(name)

    def setup_done(self):
        # what set-up made lives to the end of the run: keep it out of the
        # collector's scans, so that no full collection over it pauses the
        # window
        gc.collect()
        gc.freeze()
        self.setup_s = time.perf_counter() - self.t_start
        n, hits, secs = self.watch.counts()
        log(f"[setup] {self.setup_s:.3f} s; {n} executables built or "
            f"loaded ({hits} from the persistent cache), XLA compile "
            f"{secs:.3f} s")

    def window_seconds(self) -> float:
        if self.trace:
            return min(self.seconds, self.traffic["trace_seconds"])
        return self.seconds

    @contextlib.contextmanager
    def window(self):
        import jax
        w = Window()
        n0, _, _ = self.watch.counts()
        if self.trace:
            self.trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(self.trace_dir)
        try:
            with HostWatch() as self.host:
                w.t0 = time.perf_counter()
                try:
                    yield w
                finally:
                    w.elapsed = time.perf_counter() - w.t0
        finally:
            if self.trace:
                jax.profiler.stop_trace()
            n1, _, _ = self.watch.counts()
            self.window_s = w.elapsed
            self.compiles_in_window = n1 - n0
            log(f"[window] {w.elapsed:.3f} s from {w.t0 - self.t_start:.3f}"
                f" s after the start; {n1 - n0} executables compiled "
                f"inside the window")
            log(f"[host] {self.host.summary()}")

    def read_memory(self):
        self.memory_peak = memory_peak_bytes(self.chips)


class RunInfo:
    """What a per-layer metric reader is given."""

    def __init__(self, ctx: Context, trace, peaks):
        self.trace = trace
        self.window_s = ctx.window_s
        self.facts = ctx.window_facts
        self.chips = ctx.chips
        self.peaks = peaks
        self.config = ctx.config
        self.traffic = ctx.traffic


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, bench: Bench | None = None,
             require_tpu: bool = True, control: bool = False,
             wrap_call=None, trace_sink=None) -> dict:
    """The result line's object.  Raises `DeviceError` before any work
    where the devices do not fit the cell."""
    bench = bench or Bench()
    ctx = Context(bench, workload, seed, seconds, trace, t_start,
                  require_tpu=require_tpu, control=control,
                  wrap_call=wrap_call)
    device = check_devices(ctx.chips, require_tpu=require_tpu)
    peaks = peaks_for(device["kind"]) if require_tpu else None
    numbers = bench.kind(ctx.traffic["kind"]).run(ctx)
    correct, checks = compare.judge(numbers, ctx.limits)
    device["memory_peak_bytes"] = ctx.memory_peak
    metrics = {}
    if trace:
        from . import trace as tr
        data = tr.reduce_dir(ctx.trace_dir, ctx.chips)
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        if trace_sink is not None:
            trace_sink(data, dict(ctx.window_facts, window_s=ctx.window_s))
        info = RunInfo(ctx, data, peaks)
        for m in bench.per_layer(workload):
            value = bench.metric_reader(m["name"])(info)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        busy = tr.busy_seconds(data)
        device["busy_s"] = busy
        device["window_s"] = tr.window_seconds(data)
        breakdown = tr.breakdown(data)
        for i, d in enumerate(tr.per_device_idle(data)):
            log(f"[trace] device {i}: idle share {100 * d:.3f}%")
    else:
        e2e = {m["name"]: m for m in bench.end_to_end(workload)}
        values = dict(ctx.e2e, setup_s=ctx.setup_s)
        for name, m in e2e.items():
            metrics[name] = {"value": values[name], "unit": m["unit"]}
    out = {"correct": correct, "attempted": ctx.attempted,
           "failed": ctx.failed, "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = breakdown
    out["checks"] = checks
    for name, c in checks.items():
        log(f"[check] {name} {c['value']!r} limit {c['limit']!r}")
    return out
