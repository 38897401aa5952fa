"""What jax reports for getting executables: seconds spent compiling them or
loading them from the persistent cache, and the cache's hits and misses.
Copied from `chip_smoke.py` (`CompileClock`), which stays for its own use."""
from __future__ import annotations

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"


class CompileClock:
    """One instance for the process: jax has no call to drop a listener."""

    def __init__(self):
        import jax.monitoring as mon

        self.seconds = 0.0
        self.compiles = self.hits = self.misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == _COMPILE_EVENT:
            self.seconds += secs
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == _CACHE_HIT:
            self.hits += 1
        elif event == _CACHE_MISS:
            self.misses += 1

    def snapshot(self):
        return {"seconds": self.seconds, "compiles": self.compiles,
                "hits": self.hits, "misses": self.misses}


def since(before, after):
    return {k: after[k] - before[k] for k in after}
