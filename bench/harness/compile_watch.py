"""Compile accounting from `jax.monitoring` events (as in chip_smoke.py):
how many executables XLA built or read from the persistent cache, and
the seconds it spent.  The harness reads it around set-up and around the
measured window, where it should count none."""
from __future__ import annotations


class CompileWatch:
    def __init__(self):
        import jax
        self.executables = 0      # built by XLA or read from the disk cache
        self.disk_hits = 0        # read from the persistent cache
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.executables += 1
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.disk_hits += 1

    def counts(self):
        return self.executables, self.disk_hits, self.seconds
