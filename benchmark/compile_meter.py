"""What JAX built, and when: a copy of chip_smoke.CompileMeter (PR 21),
kept with the yardstick. One (epoch seconds, duration) entry per program
JAX had to produce, compiled afresh or fetched from the persistent cache,
plus the cache's own hit and miss counts."""

import time

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileMeter:
    def __init__(self):
        import jax

        self.events = []
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.events.append((time.time(), duration))

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def between(self, t0, t1):
        """Programs built in the epoch interval (t0, t1]."""
        return sum(1 for t, _ in self.events if t0 < t <= t1)

    def totals(self):
        return {"programs": len(self.events),
                "compile_s": sum(d for _, d in self.events),
                "cache_hits": self.hits, "cache_misses": self.misses}
