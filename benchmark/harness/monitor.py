"""Compilation as `jax.monitoring` reports it (after `chip_smoke.
watch_compiles`).  Every program the process acquires — compiled, or read
from the persistent cache — fires `backend_compile_duration` once; the cache
says beside it whether it hit."""


class CompileWatch:
    """Counts from the moment it is made: `programs` acquired and the
    `seconds` that took, persistent-cache `hits` and `misses`."""

    def __init__(self):
        from jax import monitoring
        self.counts = {"programs": 0, "seconds": 0.0, "hits": 0, "misses": 0}
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.counts["hits"] += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.counts["misses"] += 1

    def _on_duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.counts["programs"] += 1
            self.counts["seconds"] += secs

    def snapshot(self) -> dict:
        return dict(self.counts)

    def since(self, before: dict) -> dict:
        return {k: self.counts[k] - before[k] for k in self.counts}
