"""Compile counters from JAX's monitoring events.

``backend_compile`` fires once for every program JAX builds or loads from
the persistent cache; ``cache_hits`` counts the loads.  So
``programs - cache_hits`` programs were compiled anew by XLA.
"""

from __future__ import annotations

import dataclasses

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"
_HIT = "/jax/compilation_cache/cache_hits"


@dataclasses.dataclass(frozen=True)
class Counts:
    programs: int = 0       # backend compiles, cache loads included
    cache_hits: int = 0     # programs read from the persistent cache
    traces: int = 0         # functions traced to a jaxpr
    compile_s: float = 0.0  # seconds of tracing, lowering and compiling

    @property
    def compiled(self) -> int:
        """Programs XLA compiled anew (not found in the cache)."""
        return self.programs - self.cache_hits

    def __add__(self, other: "Counts") -> "Counts":
        return Counts(self.programs + other.programs,
                      self.cache_hits + other.cache_hits,
                      self.traces + other.traces,
                      self.compile_s + other.compile_s)

    def __sub__(self, other: "Counts") -> "Counts":
        return Counts(self.programs - other.programs,
                      self.cache_hits - other.cache_hits,
                      self.traces - other.traces,
                      self.compile_s - other.compile_s)


class CompileCounter:
    """Running totals of the compile events since construction."""

    def __init__(self) -> None:
        import jax

        self._c = Counts()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        c = self._c
        if event in (_TRACE, _LOWER, _BACKEND):
            c = dataclasses.replace(c, compile_s=c.compile_s + duration)
        if event == _TRACE:
            c = dataclasses.replace(c, traces=c.traces + 1)
        if event == _BACKEND:
            c = dataclasses.replace(c, programs=c.programs + 1)
        self._c = c

    def _event(self, event: str, **_) -> None:
        if event == _HIT:
            self._c = dataclasses.replace(self._c,
                                          cache_hits=self._c.cache_hits + 1)

    def now(self) -> Counts:
        return self._c
