"""Counts the programs jax builds, from jax's own monitoring events (a
copy of ``chip_smoke.py::CompileLog``): one ``backend_compile_duration``
event per program handed to the backend, whether XLA compiled it or the
persistent cache returned it, plus the cache's hit and miss events."""

from __future__ import annotations


class CompileLog:
    def __init__(self):
        import jax.monitoring as mon

        self.programs: list = []
        self.hits = 0
        self.misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs.append((str(kw.get("fun_name", "?")), secs))

    def _on_event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    @property
    def n(self) -> int:
        return len(self.programs)
