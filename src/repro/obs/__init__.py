"""Unified telemetry layer (ISSUE 10).

Three independent channels, one per execution surface:

  * ``obs.config``  — `ObsConfig`, the driver-facing knob bundle
    (``metrics`` / ``metrics_out`` / ``trace_dir`` / ``profile_dir``).
    Importable from EVERY layer: it is plain configuration.
  * jit-safe metrics — the schedule-owned obs pytree lives in
    `core/sync.py` (`SyncSchedule.exchange_with_obs` and friends) so the
    traced program never touches host code; ``obs.metrics`` holds only
    the HOST-side flush helpers (`MetricsWriter`, `chunk_row`) used by
    the drivers.  Host backends (`runtime/`, `serving/`) must not import
    it (repo-lint check 9).
  * ``obs.trace``   — the program's host spans: a profiler
    `TraceAnnotation` on every call (the device trace's clock), plus
    per-rank JSONL for the proc runtime when a `Tracer` is installed
    (Chrome-trace export).  Traced-core modules (`core/sync.py`,
    `core/workflow.py`, `core/ring.py`) must not import it (repo-lint
    check 9): inside jit, telemetry rides the metrics pytree and the
    layers' `jax.named_scope`s.
  * ``obs.counters``— thread-safe counters + latency histograms behind
    `SolveService.snapshot()`.

``obs.xplane`` reads a `jax.profiler` trace back by those spans and the
epoch program's layer scopes (`scripts/profview.py`); nothing in the
program imports it.

Layering is enforced by `scripts/repro_lint.py` check 9 and documented
in docs/observability.md.
"""
from .config import OBS_SCHEMA_VERSION, ObsConfig
from .trace import (Tracer, current_tracer, install, load_events,
                    merge_traces, span, uninstall, write_chrome_trace)

__all__ = [
    "OBS_SCHEMA_VERSION", "ObsConfig", "Tracer", "current_tracer",
    "install", "load_events", "merge_traces", "span",
    "uninstall", "write_chrome_trace",
]
