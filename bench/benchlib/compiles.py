"""Compile requests as jax.monitoring reports them (after
``chip_smoke.CompileLog``): a backend compile and a load from the persistent
cache alike."""

from __future__ import annotations

import threading
import time

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileLog:
    def __init__(self):
        import jax.monitoring as mon

        self._lock = threading.Lock()
        self.compiles: list[tuple[float, float]] = []  # (end perf_counter, seconds)
        mon.register_event_duration_secs_listener(self._on_duration)

    def _on_duration(self, event, duration, **_):
        if event == COMPILE_EVENT:
            with self._lock:
                self.compiles.append((time.perf_counter(), float(duration)))

    def between(self, t0: float, t1: float) -> dict:
        with self._lock:
            c = [d for t, d in self.compiles if t0 <= t <= t1]
        return {"compiles": len(c), "compile_s": sum(c)}

    def close(self) -> None:
        import jax.monitoring as mon

        mon.unregister_event_duration_listener(self._on_duration)
