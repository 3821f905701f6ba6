"""What XLA really compiled in this process, from JAX's own monitoring
events (a copy of chip_smoke.py's CompileLog, kept with the yardstick).
JAX's backend-compile event spans the persistent cache's lookup, so a hit
shows there with the seconds it took to read and load the executable; hits
and their read seconds are kept beside it, so that compiles less hits is
what XLA compiled anew."""

from __future__ import annotations


class CompileLog:
    def __init__(self) -> None:
        self.backend_s: list[float] = []
        self.cache_read_s: list[float] = []
        self.cache_hits = 0

    def install(self) -> "CompileLog":
        import jax.monitoring as mon

        def on_duration(name, seconds, **_):
            if name.endswith("backend_compile_duration"):
                self.backend_s.append(float(seconds))
            elif name.endswith("cache_retrieval_time_sec"):
                self.cache_read_s.append(float(seconds))

        def on_event(name, **_):
            if name.endswith("compilation_cache/cache_hits"):
                self.cache_hits += 1

        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)
        return self

    def mark(self) -> dict:
        return {
            "compiles": len(self.backend_s),
            "compile_s": sum(self.backend_s),
            "cache_hits": self.cache_hits,
            "cache_read_s": sum(self.cache_read_s),
        }

    @staticmethod
    def since(before: dict, after: dict) -> dict:
        return {k: after[k] - before[k] for k in after}
