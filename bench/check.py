"""The comparison that decides ``correct``, and the control that must fail it.

Every configuration guarantees exact answers: the frequent itemsets and
each one's support count.  So the numbers compared are counts of
disagreements with the reference, each with the limit 0:

- ``missing``: itemsets the reference finds and the output lacks;
- ``extra``: itemsets in the output that the reference does not find;
- ``wrong_support``: itemsets in both whose supports differ.

The control is the reference with every support count rounded through
bfloat16, the matrix unit's input type: the step that counting on the MXU
in a narrower type would tempt.  It breaks exact supports above 256.
"""

from __future__ import annotations

from typing import Dict, Iterable, Tuple

import ml_dtypes
import numpy as np

LIMITS = {"missing": 0, "extra": 0, "wrong_support": 0}


def compare(got: Dict[Tuple[int, ...], int],
            want: Dict[Tuple[int, ...], int]) -> Dict[str, int]:
    """Disagreements of one output with the reference's answer."""
    both = got.keys() & want.keys()
    return {
        "missing": len(want.keys() - got.keys()),
        "extra": len(got.keys() - want.keys()),
        "wrong_support": sum(got[s] != want[s] for s in both),
    }


def total(results: Iterable[Dict[str, int]]) -> Dict[str, int]:
    """Disagreements summed over every compared output."""
    out = dict.fromkeys(LIMITS, 0)
    for r in results:
        for key in out:
            out[key] += r[key]
    return out


def passes(numbers: Dict[str, int]) -> bool:
    return all(numbers[k] <= limit for k, limit in LIMITS.items())


def as_checks(numbers: Dict[str, int], checked: int) -> Dict[str, dict]:
    """The compared numbers beside their limits, as the result line and
    the last lines of standard error give them."""
    out = {k: {"value": numbers[k], "limit": LIMITS[k]} for k in LIMITS}
    out["outputs_checked"] = {"value": checked, "limit": 1}
    return out


def bf16_rounding(counts: np.ndarray) -> np.ndarray:
    """The control's counts: each exact count rounded to bfloat16."""
    c = np.asarray(counts, np.float32).astype(ml_dtypes.bfloat16)
    return c.astype(np.float32).astype(np.int64)
