#!/usr/bin/env python3
"""Record profiler traces on the chip and describe what is in them.

    python bench/tools/record_trace.py [OUT_DIR]

Writes, under OUT_DIR (default ``chiprun_out/trace_probe``):

- ``small/``: a trace of a tiny jitted program run a few times inside a
  host ``TraceAnnotation``; its ``.xplane.pb`` is the recorded trace that
  ``tests/bench/test_bench_trace.py`` reduces;
- ``mine/``: a trace of one small host-loop mine through the program;
- ``planes.txt``: every plane and line of both traces, with the most
  frequent event names, so that the reduction in ``bench/trace.py`` can be
  checked against what the chip's profiler really writes.
"""

from __future__ import annotations

import collections
import glob
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def describe(path: str, out) -> None:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    print(f"== {path}", file=out)
    for plane in pd.planes:
        print(f"plane {plane.name!r}", file=out)
        for line in plane.lines:
            names = collections.Counter()
            first = None
            n = 0
            for ev in line.events:
                names[ev.name] += 1
                n += 1
                if first is None:
                    first = (ev.start_ns, ev.duration_ns)
            print(f"  line {line.name!r} events={n} first={first}", file=out)
            for name, c in names.most_common(12):
                print(f"    {c:6d} {name[:160]}", file=out)


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    out_dir = Path(sys.argv[1] if len(sys.argv) > 1
                   else ROOT / "chiprun_out" / "trace_probe")
    out_dir.mkdir(parents=True, exist_ok=True)
    dev = jax.devices()[0]
    print(f"platform={dev.platform} device_kind={dev.device_kind} "
          f"devices={jax.device_count()}", flush=True)

    @jax.jit
    def tiny_step(x):
        return jnp.sum(x * x, axis=0)

    x = jnp.ones((512, 512), jnp.float32)
    tiny_step(x).block_until_ready()
    with jax.profiler.trace(str(out_dir / "small")):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                tiny_step(x).block_until_ready()

    sys.path.insert(0, str(ROOT / "src"))
    from repro.core import FrequentItemsetMiner
    from repro.data import get_dataset

    db = get_dataset("T10I4D100K", scale=0.1, seed=42)
    FrequentItemsetMiner(min_support=0.005).mine(db)
    with jax.profiler.trace(str(out_dir / "mine")):
        with jax.profiler.TraceAnnotation("bench.mine"):
            res = FrequentItemsetMiner(min_support=0.005).mine(db)
    print(f"mine: {len(res.itemsets)} itemsets", flush=True)

    with open(out_dir / "planes.txt", "w") as f:
        for path in sorted(glob.glob(str(out_dir / "*" / "**" / "*.xplane.pb"),
                                     recursive=True)):
            print(f"{path} {os.path.getsize(path)} bytes", flush=True)
            describe(path, f)
    print((out_dir / "planes.txt").read_text()[:20000])
    return 0


if __name__ == "__main__":
    sys.exit(main())
