#!/usr/bin/env python3
"""Record a small profiler trace of program spans on the chip.

    python bench/tools/record_spans.py OUT_DIR

Runs a tiny jitted program three times inside the benchmark's
``bench.window``, each time in a ``bench.step`` holding two nested program
spans (``repro.outer`` with the argument ``rows``, ``repro.inner``), made
with the program's own ``span``.  Each of the three stretches sleeps on the
host (10, 20 and 30 ms) before it runs the program once and waits for it,
so the device's idle gaps fall in ``bench.step``, ``repro.outer`` and
``repro.inner`` in turn: the sleeps are long against the 2 ms or so a
blocking call takes to return on a v5e host, and against the skew between
the host's and the device's clocks.  Writes the
trace under OUT_DIR and copies its ``.xplane.pb`` to
``OUT_DIR/tiny_spans.xplane.pb``, the recorded trace that
``tests/bench/test_bench_spans.py`` reduces.
"""

from __future__ import annotations

import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
ROWS = 512
SLEEP_S = {"step": 0.010, "outer": 0.020, "inner": 0.030}


def main() -> int:
    import jax
    import jax.numpy as jnp

    sys.path.insert(0, str(ROOT / "src"))
    from repro.core.runtime.spans import span

    out_dir = Path(sys.argv[1])
    out_dir.mkdir(parents=True, exist_ok=True)
    dev = jax.devices()[0]
    print(f"platform={dev.platform} device_kind={dev.device_kind} "
          f"devices={jax.device_count()}", flush=True)

    @jax.jit
    def tiny_step(x):
        return jnp.sum(x * x, axis=0)

    x = jnp.ones((ROWS, 512), jnp.float32)
    tiny_step(x).block_until_ready()

    def stretch(name):
        time.sleep(SLEEP_S[name])
        tiny_step(x).block_until_ready()

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(out_dir / "trace"), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                stretch("step")
                with span("repro.outer", rows=ROWS):
                    stretch("outer")
                    with span("repro.inner"):
                        stretch("inner")
    jax.profiler.stop_trace()
    found = sorted((out_dir / "trace").rglob("*.xplane.pb"))
    dest = out_dir / "tiny_spans.xplane.pb"
    shutil.copyfile(found[-1], dest)
    print(f"{dest} {dest.stat().st_size} bytes", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
