"""Counts XLA compiles and persistent-cache hits through jax.monitoring
(the same listener as the repo's ``chip_smoke.py``)."""
from __future__ import annotations

import jax


class CompileLog:
    def __init__(self):
        self.n = self.hits = 0
        self.secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.secs += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
