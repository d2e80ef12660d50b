#!/usr/bin/env python3
"""Where a cell's host time goes, by program span, from one traced run.

    python bench/tools/span_breakdown.py --workload t10.batch --seed 7 \\
        --seconds 45 [--out spans-t10.batch.json]

Sets the cell up as ``bench/run.py`` does, runs its window with the
profiler on over the traffic's first ``trace_mines`` mines or
``trace_cycles`` cycles, and reduces that trace with ``bench/spans.py``
(the span table and the idle gaps labelled by program spans) and with
``bench/trace.py`` (device busy time).  It checks no output: the
benchmark's own run does.  Prints one JSON object, also written to
``--out``:

- ``per_unit``: for each span name, seconds, self seconds and count per
  traced mine (batch) or per traced query (stream), and its summed
  arguments per unit;
- ``layers``: the per-layer numbers the program spans give, per traced
  unit: ``ingest_s``, ``place_s``, ``fetch_s`` and ``unspanned_s`` (the
  seconds of ``bench.mine`` under no program span but the root
  ``repro.mine``) for a batch cell; ``walk_ms``, ``drain_ms`` and
  ``walk_rows`` for the stream;
- ``idle_gaps``: the ten longest idle stretches of the chip with their
  labels;
- ``traced_s`` and ``untraced_s``: each traced mine's (or cycle's)
  seconds, and those of the window's untraced ones, so that the cost of
  the spans with the profiler on can be read;
- ``span_cost_us``: the cost of entering and leaving one span with the
  profiler off, measured here over 100,000 spans.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def layers(tab: dict, batch: bool) -> dict:
    """The program spans' per-layer numbers per traced unit (a mine, or a
    query of the stream), from ``bench.spans.table``."""
    def s(name, field="seconds"):
        st = tab.get(name)
        return getattr(st, field) if st else 0.0

    if batch:
        n = tab["repro.mine"].count
        return {
            "ingest_s": s("repro.ingest") / n,
            "place_s": s("repro.place") / n,
            "fetch_s": s("repro.fetch") / n,
            "unspanned_s": (s("bench.mine", "self_s")
                            + s("repro.mine", "self_s")) / n,
        }
    n = tab["repro.svc.query"].count
    lookup = tab.get("repro.svc.lookup")
    return {
        "walk_ms": 1e3 * s("repro.svc.walk") / n,
        "drain_ms": 1e3 * s("repro.svc.drain") / n,
        "walk_rows": (lookup.args.get("rows", 0) if lookup else 0) / n,
    }


def span_cost_us(n: int = 100_000) -> float:
    from repro.core.runtime.spans import span

    t0 = time.perf_counter()
    for _ in range(n):
        with span("repro.probe", rows=1):
            pass
    return (time.perf_counter() - t0) / n * 1e6


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    from bench import data, harness, spans, trace

    c = harness.cell(args.workload)
    config, traffic = c["config"], c["traffic"]
    harness.device_info(c["workload"]["chips"], require_tpu=True)

    import jax
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    r = harness.Run(args.seed, log)
    r.tracer = harness.Tracer(True)
    base = data.base_dataset(config["name"], config["generator"])
    mat = data.seeded(base, args.seed, reorder="order_seed" not in traffic)
    kind = harness.driver(traffic["kind"])(r, config, traffic, mat)
    kind.window(args.seconds)
    for line in kind.summary():
        log(line)
    tab = spans.table(r.tracer.path)
    gaps = spans.idle_gaps(r.tracer.path)
    summary = trace.reduce(r.tracer.path)
    r.tracer.close()

    batch = traffic["kind"] == "batch"
    if batch:
        units = [m.seconds for m in kind.mines]
        n_traced = traffic["trace_mines"]
        root = "repro.mine"
    else:
        units = [y.ingest_s + y.query_s for y in kind.cycles]
        n_traced = traffic["trace_cycles"]
        root = "repro.svc.query"
    kind.free()
    n = tab[root].count
    out = {
        "workload": args.workload, "seed": args.seed,
        "device": jax.devices()[0].device_kind,
        "busy_s": summary.busy_s, "window_s": summary.window_s,
        "per_unit": {k: {"s": v.seconds / n, "self_s": v.self_s / n,
                         "count": v.count / n,
                         "args": {a: x / n for a, x in v.args.items()}}
                     for k, v in sorted(tab.items())},
        "layers": layers(tab, batch),
        "idle_gaps": gaps,
        "traced_s": units[:n_traced],
        "untraced_s": units[n_traced:],
        "untraced_median_s": (statistics.median(units[n_traced:])
                              if units[n_traced:] else None),
        "span_cost_us": span_cost_us(),
    }
    text = json.dumps(out, indent=1)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(text)
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
