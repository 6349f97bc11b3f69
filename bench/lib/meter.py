"""Compile counter: JAX's monitoring events, one per executable compiled
or loaded from the persistent cache (copied from the program's bring-up
check, ``chip_smoke.CompileMeter``)."""
from __future__ import annotations

import jax


class CompileMeter:
    def __init__(self):
        self.count, self.seconds, self.cache_hits = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += secs

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return self.count, self.seconds, self.cache_hits
