#!/usr/bin/env python3
"""The control: the reference with bfloat16 counts, put in the program's
place at a cell's own size, compared as a run compares the program.

    python bench/control.py --workload t10.batch --seeds 1 2 3

Prints, for every seed, the numbers compared beside their limits and
whether the control passed; the control has to fail.  The windows it
mines are the ``control_windows`` of the cell's traffic kind
(``bench/kinds/<kind>.py``).  Runs on
the host only (NumPy): the benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import check, data, harness, load, reference  # noqa: E402


def control(workload: str, seed: int, cache_dir: Path = data.CACHE) -> dict:
    c = harness.cell(workload)
    config, support = c["config"], c["traffic"]["min_support"]
    mat = data.seeded(data.base_dataset(config["name"], config["generator"],
                                        cache_dir), seed,
                      reorder="order_seed" not in c["traffic"])
    results = []
    windows = load("kinds", c["traffic"]["kind"]).control_windows
    for win in windows(c["traffic"], mat, seed):
        want = reference.frequent_itemsets(win, support)
        got = reference.frequent_itemsets(win, support,
                                          rounding=check.bf16_rounding)
        results.append(check.compare(got, want))
    numbers = check.total(results)
    return {"workload": workload, "seed": seed,
            "passed": check.passes(numbers),
            "checks": check.as_checks(numbers, len(results))}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    for seed in args.seeds:
        print(json.dumps(control(args.workload, seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
