"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device numbers.

On a TPU the profiler writes one plane per chip (``/device:TPU:<n>``) with
an ``XLA Modules`` line (one event per program run, named
``<jit name>(<program id>)``) and an ``XLA Ops`` line (one event per
operation, named by its HLO text), and a ``/host:CPU`` plane whose lines
hold the host spans the benchmark opens with ``TraceAnnotation``.  Both use
one clock.  From them:

- the window: the span ``bench.window`` (the whole trace if it is absent);
- busy seconds: the union of the operations' intervals inside the window,
  per chip, then the mean over the chips;
- seconds per program (the ``XLA Modules`` events), keyed by the full
  program name, and which of its operations' texts it ran;
- idle gaps: the stretches of the window in which chip 0 ran nothing,
  each labelled with the innermost benchmark span open at its middle.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import re
from typing import Dict, List, Optional, Set, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
# Operations that hold other operations (a ``lax.map`` loop): busy, but
# left out of the per-operation breakdown, which would count them twice.
CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                       # mean over chips
    n_devices: int
    program_s: Dict[str, float]         # full program name -> s, mean/chip
    program_ops: Dict[str, Set[str]]    # full program name -> op texts
    device_ops: List[Tuple[str, float]]  # "<program>/<op>" -> s, mean/chip
    idle_gaps: List[Tuple[str, float]]   # (host span, s) on chip 0

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def program_base(name: str) -> str:
    """``jit__blocked_count(2015402815260533866)`` -> ``jit__blocked_count``."""
    return name.split("(", 1)[0]


def op_short(text: str) -> str:
    """``%fusion.6 = u8[...] fusion(...)`` -> ``fusion.6``."""
    return text.split(" = ", 1)[0].lstrip("%").strip()


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(s: float, e: float, lo: float, hi: float) -> Optional[Tuple[float, float]]:
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


def _events(line):
    for ev in line.events:
        yield ev.name, float(ev.start_ns), float(ev.start_ns + ev.duration_ns)


def reduce(path: str, top: int = 10) -> TraceSummary:
    """Reduce the trace at ``path``; raises if it holds no device plane."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices = [p for p in pd.planes
               if re.fullmatch(r"/device:(TPU|GPU):\d+", p.name)]
    if not devices:
        raise ValueError(f"{path}: no device plane")
    devices.sort(key=lambda p: int(p.name.rsplit(":", 1)[1]))

    spans: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans += [(n, s, e) for n, s, e in _events(line)
                      if n.startswith(SPAN_PREFIX)]
    window = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]

    lines = [{ln.name: ln for ln in p.lines} for p in devices]
    if window:
        lo, hi = min(s for s, _ in window), max(e for _, e in window)
    else:
        ends = [(s, e) for ls in lines for key in ("XLA Ops",)
                if key in ls for _, s, e in _events(ls[key])]
        lo, hi = min(s for s, _ in ends), max(e for _, e in ends)

    busy = []
    program_s: Dict[str, float] = collections.defaultdict(float)
    program_ops: Dict[str, Set[str]] = collections.defaultdict(set)
    op_s: Dict[str, float] = collections.defaultdict(float)
    gaps: List[Tuple[float, float]] = []
    for i, ls in enumerate(lines):
        mods = []
        if "XLA Modules" in ls:
            for n, s, e in _events(ls["XLA Modules"]):
                c = _clip(s, e, lo, hi)
                if c:
                    program_s[n] += c[1] - c[0]
                    mods.append((s, e, n))
        mods.sort()
        starts = [m[0] for m in mods]
        ops = []
        if "XLA Ops" in ls:
            for n, s, e in _events(ls["XLA Ops"]):
                c = _clip(s, e, lo, hi)
                if not c:
                    continue
                ops.append(c)
                j = bisect.bisect_right(starts, s) - 1
                prog = mods[j][2] if j >= 0 and s < mods[j][1] else "?"
                program_ops[prog].add(n)
                short = op_short(n)
                if not short.startswith(CONTAINERS):
                    op_s[f"{program_base(prog)}/{short}"] += c[1] - c[0]
        merged = _union(ops)
        busy.append(sum(e - s for s, e in merged))
        if i == 0:
            edges = [lo] + [x for iv in merged for x in iv] + [hi]
            gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]

    n = len(devices)
    named = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (a + b) / 2
        inner = [(s, name) for name, s, e in spans
                 if s <= mid < e and name != WINDOW_SPAN]
        label = max(inner)[1][len(SPAN_PREFIX):] if inner else "other"
        named.append((label, (b - a) * 1e-9))
    ops_top = sorted(op_s.items(), key=lambda kv: -kv[1])[:top]
    return TraceSummary(
        window_s=(hi - lo) * 1e-9,
        busy_s=sum(busy) / n * 1e-9,
        n_devices=n,
        program_s={k: v / n * 1e-9 for k, v in program_s.items()},
        program_ops=dict(program_ops),
        device_ops=[(k, v / n * 1e-9) for k, v in ops_top],
        idle_gaps=named,
    )


def matching_seconds(summary: TraceSummary, patterns: List[dict]) -> float:
    """Device seconds of the programs one of ``patterns`` matches: a
    pattern is ``{"program": regex}`` on the program's name without its id,
    with an optional ``"with_op": regex`` that a program matches only when
    it ran an operation whose text matches it."""
    total = 0.0
    for name, sec in summary.program_s.items():
        for pat in patterns:
            if not re.fullmatch(pat["program"], program_base(name)):
                continue
            with_op = pat.get("with_op")
            if with_op and not any(re.search(with_op, op) for op
                                   in summary.program_ops.get(name, ())):
                continue
            total += sec
            break
    return total
