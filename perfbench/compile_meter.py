"""Compile seconds and program counts from JAX's own monitoring events.

A copy of ``chip_smoke.CompileMeter`` (the yardstick lives with the
benchmark): sums the trace / lower / backend-compile durations and counts
programs built and persistent-cache hits and misses, so that set-up's
compilation prints apart from the train wall and any program built inside
the measured window is seen.  JAX wraps its backend-compile event round the
cache lookup AND the compile, so a program loaded from the persistent cache
raises it too (with the load's duration): ``programs`` counts every program
built, ``cache_hits`` how many of those were loaded and not compiled, and
``cache_misses`` how many were compiled and written to the cache
(``tests/perfbench`` pins this on a jitted function built twice).
"""
from __future__ import annotations

_DUR = ("/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration")


class CompileMeter:
    def __init__(self):
        import jax.monitoring as mon

        self.secs = 0.0
        self.backend_compiles = 0
        self.hits = 0
        self.misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_kw) -> None:
        if event in _DUR:
            self.secs += secs
            if event == _DUR[2]:
                self.backend_compiles += 1

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def mark(self) -> tuple:
        return (self.secs, self.backend_compiles, self.hits, self.misses)

    def since(self, mark: tuple) -> dict:
        """``compile_s`` (trace + lower + backend compile or cache load),
        ``programs`` (built: compiled or loaded), and of those the cache
        hits (loaded) and misses (compiled and written) since ``mark``."""
        return {"compile_s": self.secs - mark[0],
                "programs": self.backend_compiles - mark[1],
                "cache_hits": self.hits - mark[2],
                "cache_misses": self.misses - mark[3]}
