"""ObsConfig — the observability knob bundle threaded through drivers.

Frozen/hashable like every other config dataclass so `WorkflowConfig`
stays usable as a cache key.  The default config is COMPLETELY inert:
every obs code path in the traced program is gated on the Python-level
`metrics` flag, so a disabled run traces the literally-unchanged epoch
program and lowers to byte-identical HLO (pinned in tests/test_obs.py).
"""
import dataclasses
from typing import Optional

# Version stamp for the metrics JSONL schema and BENCH-row obs summaries
# (docs/observability.md documents the row fields per version).
OBS_SCHEMA_VERSION = 1


@dataclasses.dataclass(frozen=True)
class ObsConfig:
    """Per-run observability switches.

    metrics      enable the jit-safe metrics pytree (`state["obs"]`,
                 accumulated by the schedule at every exchange).  Rides
                 alongside the update — never feeds back into it, so the
                 golden proxy1d trajectory stays bitwise (pinned).
    metrics_out  JSONL path for chunk-boundary metric flushes
                 (`train_vmap`) / per-epoch rows (proc worker summary).
                 Requires ``metrics=True``.
    trace_dir    directory for per-rank JSONL span traces
                 (`trace_rank<r>.jsonl`, proc backend only; merge with
                 `scripts/obsview.py`).  Every span also reaches the
                 profiler, with or without this sink.
    profile_dir  `jax.profiler.start_trace` target wrapped around the
                 `train_vmap` epoch loop: the device's operations, tagged
                 with the epoch program's layer scopes, and on the same
                 clock the host spans (`sagips.train.chunk`/`.flush`/
                 `.checkpoint`, and any `obs.trace.span`).
    """
    metrics: bool = False
    metrics_out: Optional[str] = None
    trace_dir: Optional[str] = None
    profile_dir: Optional[str] = None

    def __post_init__(self):
        if self.metrics_out and not self.metrics:
            raise ValueError(
                "ObsConfig.metrics_out requires metrics=True — there is "
                "nothing to flush without the jit-safe metrics channel")
