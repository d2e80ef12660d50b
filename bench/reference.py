"""Plain NumPy Apriori: the reference that decides ``correct``.

It imports nothing of the program and takes nothing the program made.  It
states the semantics every configuration guarantees: an itemset is
frequent when at least ``ceil(min_support * N)`` baskets hold all of its
items, and each frequent itemset comes with that exact count.

Level 1 is a histogram, level 2 the Gram matrix ``X^T X`` of the 0/1
basket-by-item matrix over the frequent items (float32 sums of 0/1 are
exact below 2**24 baskets), and every deeper level joins the frequent
itemsets of the level below on their common prefix, prunes a candidate
with an infrequent subset, and counts it by ANDing the items' bitsets over
the baskets.  ``rounding`` is applied to every count before the threshold:
the control passes one that rounds through a lower precision.
"""

from __future__ import annotations

import math
from typing import Callable, Dict, Optional, Tuple

import numpy as np

PAD = -1
Itemset = Tuple[int, ...]


def min_count(min_support: float, n: int) -> int:
    """The absolute threshold: ``ceil(min_support * N)``, at least 1."""
    return max(1, math.ceil(min_support * n))


def apriori_gen(level: np.ndarray) -> np.ndarray:
    """Candidates of size k from the (L, k-1) frequent itemsets ``level``,
    rows sorted: join rows that share their first k-2 items, then drop a
    candidate when one of its (k-1)-subsets is not in ``level``."""
    n, k1 = level.shape
    if n < 2:
        return np.zeros((0, k1 + 1), np.int64)
    known = set(map(tuple, level.tolist()))
    head = level[:, :-1]
    new_group = np.ones(n, bool)
    if k1 > 1:
        new_group[1:] = (head[1:] != head[:-1]).any(axis=1)
    starts = np.flatnonzero(new_group).tolist() + [n]
    parts = []
    for s, e in zip(starts[:-1], starts[1:]):
        if e - s < 2:
            continue
        i, j = np.triu_indices(e - s, 1)
        tails = level[s:e, -1]
        part = np.empty((len(i), k1 + 1), np.int64)
        part[:, :-2] = level[s, :-1]
        part[:, -2] = tails[i]
        part[:, -1] = tails[j]
        parts.append(part)
    if not parts:
        return np.zeros((0, k1 + 1), np.int64)
    cand = np.concatenate(parts)
    # Dropping either of the last two items gives a row of ``level`` by
    # construction; check the subsets that drop one of the others.
    keep = np.ones(len(cand), bool)
    for drop in range(k1 - 1):
        sub = np.delete(cand, drop, axis=1)
        keep &= np.fromiter((tuple(r) in known for r in sub.tolist()),
                            bool, count=len(sub))
    return cand[keep]


def _support(bits: np.ndarray, cand: np.ndarray, chunk: int = 2048
             ) -> np.ndarray:
    """Baskets holding every item of each candidate row: popcount of the
    AND of the items' bitsets (``bits``: (F, W) uint64)."""
    out = np.empty(len(cand), np.int64)
    for s in range(0, len(cand), chunk):
        c = cand[s:s + chunk]
        acc = bits[c[:, 0]]
        for j in range(1, c.shape[1]):
            acc = acc & bits[c[:, j]]
        out[s:s + chunk] = np.bitwise_count(acc).sum(axis=1, dtype=np.int64)
    return out


def frequent_itemsets(
    mat: np.ndarray,
    min_support: float,
    rounding: Optional[Callable[[np.ndarray], np.ndarray]] = None,
    max_k: int = 64,
) -> Dict[Itemset, int]:
    """Every frequent itemset of the baskets in ``mat`` ((N, L) ids,
    ``PAD``-filled) with its support count, items ascending."""
    n = mat.shape[0]
    if n >= 1 << 24:
        raise ValueError("the float32 Gram count is exact below 2**24 rows")
    thresh = min_count(min_support, n)
    same = rounding if rounding is not None else (lambda c: c)
    ids = mat[mat != PAD].astype(np.int64)
    hist = same(np.bincount(ids)) if ids.size else np.zeros(0, np.int64)
    items = np.flatnonzero(hist >= thresh)
    result: Dict[Itemset, int] = {(int(i),): int(hist[i]) for i in items}
    f = len(items)
    if f < 2 or max_k < 2:
        return result

    # 0/1 basket-by-item matrix over the frequent items (dense ids 0..F-1).
    col = np.full(len(hist) + 1, -1, np.int64)
    col[items] = np.arange(f)
    dense = col[np.where(mat == PAD, len(hist), mat)]
    rows, cols = np.nonzero(dense >= 0)
    x = np.zeros((n, f), np.float32)
    x[rows, dense[rows, cols]] = 1.0

    iu, ju = np.triu_indices(f, 1)
    counts = same(np.rint((x.T @ x)[iu, ju]).astype(np.int64))
    level = np.stack([iu, ju], axis=1)
    keep = counts >= thresh
    level, counts = level[keep], counts[keep]
    out = [(level, counts)]

    words = (n + 63) // 64
    packed = np.packbits(x.T.astype(bool), axis=1, bitorder="little")
    bits = np.zeros((f, words * 8), np.uint8)
    bits[:, :packed.shape[1]] = packed
    bits = bits.view(np.uint64)
    del x
    k = 3
    while len(level) >= 2 and k <= max_k:
        cand = apriori_gen(level)
        if not len(cand):
            break
        counts = same(_support(bits, cand))
        keep = counts >= thresh
        level, counts = cand[keep], counts[keep]
        out.append((level, counts))
        k += 1
    for lv, cs in out:
        for row, c in zip(items[lv].tolist(), cs.tolist()):
            result[tuple(row)] = int(c)
    return result
