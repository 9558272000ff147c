"""Counts XLA backend compiles (persistent-cache loads included) in any
thread while installed; copied from the repository's ``chip_smoke.py``."""
from __future__ import annotations

import threading

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    def __init__(self):
        self._lock = threading.Lock()
        self.compiles = 0

    def _on_duration(self, event, duration, **kw):
        if event == _BACKEND_COMPILE:
            with self._lock:
                self.compiles += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
