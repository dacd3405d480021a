"""Compile accounting from jax.monitoring (copied from chip_smoke.py's
_CompileClock, plus an event count so "nothing compiled" is a count, not a
float compare)."""


class CompileClock:
    def __init__(self):
        self.seconds = 0.0
        self.events = 0
        self.hits = 0
        self.misses = 0

    def install(self):
        import jax.monitoring as mon
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, name, secs, **_kw):
        if name.startswith("/jax/core/compile/"):
            self.seconds += secs
            self.events += 1

    def _on_event(self, name, **_kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snap(self):
        return {"seconds": self.seconds, "events": self.events,
                "hits": self.hits, "misses": self.misses}


COMPILE = CompileClock()


def delta(after, before):
    return {k: after[k] - before[k] for k in after}
