"""The count's work, from the algorithm and not from an implementation,
and the chip's peaks.

Counting the k-itemset candidates C_k over N baskets of F frequent items
needs, at least:

- ops = 2 * N * C_k * k: one membership multiply-add per candidate item
  per basket (int8 on the matrix unit);
- bytes = N * ceil(F / 8) + C_k * (k + 1) * 4: the baskets as bits over the
  frequent items, read once, and each candidate's k ids and its int32
  count.

The least time of a level is the larger of ops over the int8 peak and
bytes over the HBM bandwidth; the roofline share is the summed least time
over the device time of the programs that count.
"""

from __future__ import annotations

import json
import math
from pathlib import Path
from typing import Iterable, Tuple

HERE = Path(__file__).resolve().parent


def peaks(device_kind: str) -> dict:
    """One chip's peaks; a device not in ``peaks.json`` is an error."""
    table = json.loads((HERE / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (have {sorted(table)})")
    return table[device_kind]


def count_patterns() -> list:
    return json.loads((HERE / "programs.json").read_text())["count"]


def level_work(n: int, f: int, c: int, k: int) -> Tuple[float, float]:
    """(ops, bytes) of counting ``c`` candidates of size ``k``."""
    return 2.0 * n * c * k, float(n * math.ceil(f / 8) + c * (k + 1) * 4)


def least_time(n: int, f: int, levels: Iterable[Tuple[int, int]],
               peak: dict, chips: int = 1) -> Tuple[float, str]:
    """Least seconds to count ``levels`` ((k, C_k) pairs) on ``chips``
    chips, and which bound dominates (``compute`` or ``memory``)."""
    total = compute = memory = 0.0
    for k, c in levels:
        ops, nbytes = level_work(n, f, c, k)
        t_c = ops / (peak["int8_ops_per_s"] * chips)
        t_m = nbytes / (peak["hbm_bytes_per_s"] * chips)
        total += max(t_c, t_m)
        compute += t_c
        memory += t_m
    return total, ("compute" if compute >= memory else "memory")
