"""Counts this process's XLA compiles through ``jax.monitoring``.

Copied from ``chip_smoke.py::CompileBook``: the persistent cache's hits
and misses (a miss is a program compiled and written) and the seconds
spent in backend compile or cache retrieval.
"""

from __future__ import annotations

import jax


class CompileBook:
    def __init__(self):
        self.hits = self.misses = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _on_secs(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def snapshot(self) -> dict:
        return {
            "hits": self.hits,
            "misses": self.misses,
            "compile_s": self.compile_s,
        }
