"""Compile accounting from JAX's monitoring events."""

from __future__ import annotations


class CompileClock:
    """Sums JAX's trace, lowering and backend-compile durations, counts
    backend compiles, and counts the persistent cache's hits and misses
    (``on_event``)."""

    EVENTS = frozenset({
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    })
    BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.total = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0

    def __call__(self, event: str, secs: float, **_):
        if event in self.EVENTS:
            self.total += secs
        if event == self.BACKEND:
            self.compiles += 1

    def on_event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1
