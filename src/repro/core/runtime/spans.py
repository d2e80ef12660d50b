"""Program spans on the profiler's clock.

``span(name, **counts)`` times one layer boundary of a mine or a service
cycle.  It enters a ``jax.profiler.TraceAnnotation``, so under a profiler
trace the span sits on the host plane, on the same clock as the device
planes, with ``counts`` as its event arguments; and it reads
``perf_counter`` around the same stretch, so the caller takes its phase
time from ``.seconds`` after the block; counts known only once the work
is done join the arguments through ``.note(**counts)`` inside the block.
There is no switch and no log: with the profiler off a span costs the
annotation's enter and exit and two clock reads.  Names carry the prefix
``repro.``.  A span is host code: none may sit inside a jitted function,
where it would run only while tracing.
"""

from __future__ import annotations

import time

from jax.profiler import TraceAnnotation


class span:
    """``with span("repro.gen", k=3, rows=941) as s: ...``; then
    ``s.seconds`` is the block's wall time."""

    __slots__ = ("_note", "_t0", "seconds")

    def __init__(self, name: str, **counts: int) -> None:
        self._note = TraceAnnotation(name, **counts)
        self.seconds = 0.0

    def note(self, **counts: int) -> None:
        """Add ``counts`` to the span's event arguments (inside the block)."""
        self._note.set_metadata(**counts)

    def __enter__(self) -> "span":
        self._note.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        self._note.__exit__(*exc)
