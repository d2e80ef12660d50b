"""Host spans in a profiler trace: time per layer, and idle gaps put down
to the layer the host was in.

The program opens a span at each layer boundary of a mine and of a service
cycle (``repro.*``, ``src/repro/core/runtime/spans.py``); the benchmark
opens its own around each mine, ingest and query (``bench.*``).  Both are
``TraceAnnotation`` events on the ``/host:CPU`` plane, one line per
thread, on the clock of the device planes.  From a trace (``.xplane.pb``):

- ``table``: per span name, the seconds inside the window (the span
  ``bench.window``, else the whole trace), the self seconds (its duration
  less the union of the spans nested in it on the same line), the number
  of spans and the sum of each integer argument (``rows``, ``k``).  It
  reads the host plane only, so a CPU trace serves as well as a chip's;
- ``idle_gaps``: the longest stretches of the window in which chip 0 ran
  no operation, each labelled with the innermost span open at its middle:
  a ``bench.`` name without its prefix (``mine``, ``query``), a program
  span by its whole name (``repro.place.encode``), so that ``mine`` (the
  benchmark's own span) and ``repro.mine`` (the program's root) stay
  apart.

``bench/trace.py:reduce`` is the benchmark's reduction of the same file;
this module adds to it and changes nothing there.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

from bench.trace import WINDOW_SPAN, _clip, _events, _union

PREFIXES = ("bench.", "repro.")


@dataclasses.dataclass
class SpanStats:
    seconds: float = 0.0        # summed durations inside the window
    self_s: float = 0.0         # the same, less the nested spans
    count: int = 0
    args: Dict[str, int] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Span:
    name: str
    start: float                # ns, clipped to the window
    end: float
    args: Dict[str, int]
    children: List[Tuple[float, float]] = dataclasses.field(
        default_factory=list)


def _profile(path_or_data):
    from jax.profiler import ProfileData

    if isinstance(path_or_data, str):
        return ProfileData.from_file(path_or_data)
    return path_or_data


def host_lines(path_or_data) -> List[List[Span]]:
    """The ``bench.*`` and ``repro.*`` spans of each host line, unclipped,
    in order of start (an outer span before the spans it holds)."""
    lines = []
    for plane in _profile(path_or_data).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans = [Span(ev.name, float(ev.start_ns),
                          float(ev.start_ns + ev.duration_ns),
                          {k: v for k, v in ev.stats if isinstance(v, int)})
                     for ev in line.events if ev.name.startswith(PREFIXES)]
            if spans:
                spans.sort(key=lambda s: (s.start, -s.end))
                lines.append(spans)
    return lines


def window(lines: List[List[Span]]) -> Tuple[float, float]:
    """The ``bench.window`` span's extent, else that of every span."""
    marked = [s for ln in lines for s in ln if s.name == WINDOW_SPAN]
    every = marked or [s for ln in lines for s in ln]
    if not every:
        return 0.0, 0.0
    return min(s.start for s in every), max(s.end for s in every)


def _clipped(lines, lo: float, hi: float) -> List[List[Span]]:
    out = []
    for ln in lines:
        kept = []
        for s in ln:
            c = _clip(s.start, s.end, lo, hi)
            if c:
                kept.append(Span(s.name, c[0], c[1], s.args))
        out.append(kept)
    return out


def table(path_or_data) -> Dict[str, SpanStats]:
    """Per span name, its ``SpanStats`` inside the window."""
    lines = host_lines(path_or_data)
    lo, hi = window(lines)
    stats: Dict[str, SpanStats] = {}
    for ln in _clipped(lines, lo, hi):
        stack: List[Span] = []
        for s in ln:
            while stack and not (s.start >= stack[-1].start
                                 and s.end <= stack[-1].end):
                stack.pop()
            if stack:
                stack[-1].children.append((s.start, s.end))
            stack.append(s)
        for s in ln:
            st = stats.setdefault(s.name, SpanStats())
            covered = sum(e - b for b, e in _union(s.children))
            st.seconds += (s.end - s.start) * 1e-9
            st.self_s += (s.end - s.start - covered) * 1e-9
            st.count += 1
            for k, v in s.args.items():
                st.args[k] = st.args.get(k, 0) + v
    return stats


def label(name: Optional[str]) -> str:
    if name is None:
        return "other"
    if name.startswith("bench."):
        return name[len("bench."):]
    return name


def idle_gaps(path_or_data, top: int = 10) -> List[Tuple[str, float]]:
    """The ``top`` longest idle stretches of chip 0 inside the window, in
    seconds, longest first, each with the label of the innermost span open
    at its middle.  Raises if the trace holds no device plane."""
    pd = _profile(path_or_data)
    devices = [p for p in pd.planes
               if re.fullmatch(r"/device:(TPU|GPU):\d+", p.name)]
    if not devices:
        raise ValueError("no device plane")
    chip0 = min(devices, key=lambda p: int(p.name.rsplit(":", 1)[1]))
    lines = host_lines(pd)
    spans = [s for ln in lines for s in ln if s.name != WINDOW_SPAN]
    ops = [(s, e) for ln in chip0.lines if ln.name == "XLA Ops"
           for _, s, e in _events(ln)]
    if any(s.name == WINDOW_SPAN for ln in lines for s in ln):
        lo, hi = window(lines)
    else:
        lo, hi = min(s for s, _ in ops), max(e for _, e in ops)
    busy = _union([c for s, e in ops if (c := _clip(s, e, lo, hi))])
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (a + b) / 2
        inner = [s for s in spans if s.start <= mid < s.end]
        inner.sort(key=lambda s: (s.start, -s.end))
        out.append((label(inner[-1].name if inner else None),
                    (b - a) * 1e-9))
    return out
