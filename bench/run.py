#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chip, and print its result.

    python bench/run.py --workload t10.batch --seed 7 --seconds 45 --trace 0

The cells, their metrics and bounds are in ``BENCHMARK.json``.  Set-up
(data, warm-up, compilation) is timed as ``setup_s``; then the window runs
for ``--seconds`` and its outputs are compared with ``bench/reference.py``.
The last line of standard output is the result as one JSON object; the
numbers compared, each with its limit, are the last lines of standard
error.  Without a TPU, or with fewer chips than the cell asks for, the
command exits 2 and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.harness import NoDevice, run

    try:
        run(args.workload, args.seed, args.seconds, bool(args.trace),
            t_start=T_START)
    except NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
