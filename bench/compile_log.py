"""What JAX's monitoring events report about compiles (from
``chip_smoke._CompileLog``): seconds in backend compiles, loads from
the persistent cache included, the number of compiles, and
persistent-cache hits."""
from __future__ import annotations


class CompileLog:
    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0

    def register(self) -> None:
        import jax.monitoring as mon

        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
