#!/usr/bin/env python3
"""Run cells of the benchmark one after another, each in a process of its
own as a check runs them, and keep every run's output.

    python bench/tools/series.py [--stop-on-fail] OUT_DIR WORKLOAD:SEED:SECONDS:TRACE ...

Each run's standard output and error go to ``OUT_DIR/<n>-<workload>-<seed>
-t<trace>.{out,err}``; its result line and the end of its standard error
are printed as the runs end.  This process never imports JAX, so each run
has the chip to itself.  The end prints, for each (workload, trace), every
metric's values with their median and quartile spread (Python's
``statistics.quantiles``), as the bounds are set from.  With
``--stop-on-fail`` the series ends at the first run that exits non-zero or
reports ``correct`` false.
"""

from __future__ import annotations

import collections
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def spread(values):
    """(median, (q3 - q1) / median)."""
    med = statistics.median(values)
    if len(values) < 2 or med == 0:
        return med, float("nan")
    q1, _, q3 = statistics.quantiles(values, n=4)
    return med, (q3 - q1) / abs(med)


def main() -> int:
    args = sys.argv[1:]
    stop_on_fail = args[:1] == ["--stop-on-fail"]
    args = args[1:] if stop_on_fail else args
    out_dir = Path(args[0])
    out_dir.mkdir(parents=True, exist_ok=True)
    values = collections.defaultdict(lambda: collections.defaultdict(list))
    rc_all = 0
    for n, spec in enumerate(args[1:]):
        workload, seed, seconds, trace = spec.split(":")
        stem = out_dir / f"{n:02d}-{workload}-{seed}-t{trace}"
        t0 = time.time()
        with open(f"{stem}.out", "w") as fo, open(f"{stem}.err", "w") as fe:
            rc = subprocess.call(
                [sys.executable, str(ROOT / "bench" / "run.py"),
                 "--workload", workload, "--seed", seed,
                 "--seconds", seconds, "--trace", trace],
                cwd=ROOT, stdout=fo, stderr=fe)
        took = time.time() - t0
        rc_all = rc_all or rc
        lines = Path(f"{stem}.out").read_text().strip().splitlines()
        err = Path(f"{stem}.err").read_text().strip().splitlines()
        keep = [ln for ln in err if not ln.startswith(("W", "I", "E1"))
                and "warn" not in ln.lower()][-16:]
        print(f"=== {spec} rc={rc} wall={took:.1f}s", flush=True)
        print("\n".join(ln[:600] for ln in keep), flush=True)
        if lines and rc == 0:
            print(lines[-1], flush=True)
            res = json.loads(lines[-1])
            for name, m in res["metrics"].items():
                values[(workload, trace)][name].append(m["value"])
            values[(workload, trace)]["correct"].append(res["correct"])
        if stop_on_fail and (rc != 0 or not lines
                             or not json.loads(lines[-1])["correct"]):
            print("=== stopped at the first failed run", flush=True)
            break
    print("=== summary", flush=True)
    for (workload, trace), metrics in values.items():
        for name, vals in metrics.items():
            if name == "correct":
                print(f"{workload} t{trace} correct: {vals}")
                continue
            med, sp = spread(vals)
            print(f"{workload} t{trace} {name}: median {med} spread {sp} "
                  f"values {vals}")
    return rc_all


if __name__ == "__main__":
    sys.exit(main())
