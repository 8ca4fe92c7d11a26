"""Compile seconds and counts from JAX's monitoring events.

``backend_compile_duration`` fires for every program the backend
produces, persistent-cache reads included; seconds are summed over
threads (the executor compiles on warm-up threads beside the compute
threads). After the program's ``chip_smoke.CompileMeter``.
"""
from __future__ import annotations

import threading

_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileMeter:
    def __init__(self):
        import jax

        self.lock = threading.Lock()
        self.compile_s, self.compiles = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)

    def _dur(self, event, duration, **_):
        if event == _COMPILE:
            with self.lock:
                self.compile_s += duration
                self.compiles += 1

    def snapshot(self) -> dict:
        with self.lock:
            return {"compile_s": self.compile_s, "compiles": self.compiles}
