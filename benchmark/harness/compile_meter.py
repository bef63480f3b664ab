"""Counts what jax compiles, from jax.monitoring's own events (copied from
chip_smoke.CompileMeter): every program handed to the backend, the seconds
the backend took for it (XLA compiling, or reading the persistent cache),
and the cache's hits. A window brackets itself with ``snapshot``/``since``;
a program compiled inside a measured window makes the run incorrect."""
import time


class CompileMeter:
    def __init__(self):
        from jax import monitoring

        self.programs = 0
        self.seconds = 0.0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += secs

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return (self.programs, self.seconds, self.cache_hits, time.perf_counter())

    def since(self, snap):
        p, s, h, t = snap
        return {"programs_compiled": self.programs - p,
                "persistent_cache_hits": self.cache_hits - h,
                "compile_seconds": self.seconds - s,
                "wall_seconds": time.perf_counter() - t}
