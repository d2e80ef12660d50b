"""Shared encoding for the array-layout candidate stores."""

from __future__ import annotations

import dataclasses
import itertools
from typing import Sequence

import numpy as np

# Sentinel larger than any item id; keeps padded rows sorted for searchsorted.
ITEM_PAD = np.int32(2**30)


WORD_BITS = 32  # items per packed uint32 word


def pack_bitmap(bitmap: np.ndarray) -> np.ndarray:
    """(N, F_pad) uint8 multi-hot -> (N, F_pad/32) uint32, bit b of word w is
    column ``32*w + b``. F_pad is a multiple of 128, so it always divides 32."""
    n, f = bitmap.shape
    assert f % WORD_BITS == 0, f"F_pad={f} must be a multiple of {WORD_BITS}"
    lanes = bitmap.reshape(n, f // WORD_BITS, WORD_BITS).astype(np.uint32)
    weights = np.uint32(1) << np.arange(WORD_BITS, dtype=np.uint32)
    return np.bitwise_or.reduce(lanes * weights, axis=2)


@dataclasses.dataclass
class EncodedDB:
    """Device encoding of a transaction database over F (frequent) items.

    Items are *remapped* to dense ids [0, F). The dense remap is exactly the
    "perfect hash" of the paper's hash-table trie: a candidate item indexes the
    transaction bitmap directly, no probing.

    padded:   (N, L) int32, each row sorted ascending, padded with ITEM_PAD.
    bitmap:   (N, F_pad) uint8 multi-hot; F_pad a multiple of 128 and > F, so
              column F_pad - 1 is guaranteed all-zero (used by candidate pads).
    packed:   (N, F_pad/32) uint32 view of ``bitmap``, 32 item columns per
              word — built lazily and cached (1 bit per column instead of 8).
    n_items:  F, the number of real (frequent) item columns.
    """

    padded: np.ndarray
    bitmap: np.ndarray
    n_items: int
    _packed: np.ndarray = dataclasses.field(default=None, repr=False, compare=False)

    @property
    def n_transactions(self) -> int:
        return self.padded.shape[0]

    @property
    def f_pad(self) -> int:
        return self.bitmap.shape[1]

    @property
    def n_words(self) -> int:
        return self.f_pad // WORD_BITS

    @property
    def packed(self) -> np.ndarray:
        if self._packed is None:
            self._packed = pack_bitmap(self.bitmap)
        return self._packed

    def pad_transactions_to(self, n: int) -> "EncodedDB":
        """Pad N up to ``n`` with empty transactions (match nothing)."""
        if n == self.n_transactions:
            return self
        extra = n - self.n_transactions
        pad_rows = np.full((extra, self.padded.shape[1]), ITEM_PAD, np.int32)
        pad_bits = np.zeros((extra, self.f_pad), np.uint8)
        out = EncodedDB(
            padded=np.concatenate([self.padded, pad_rows]),
            bitmap=np.concatenate([self.bitmap, pad_bits]),
            n_items=self.n_items,
        )
        if self._packed is not None:  # extend the cached packed view in place
            pad_words = np.zeros((extra, self.n_words), np.uint32)
            out._packed = np.concatenate([self._packed, pad_words])
        return out


def pack_transactions(
    transactions: Sequence[Sequence[int]], min_len: int = 8
) -> tuple:
    """``padded_from_transactions`` with its two counts: returns
    ``(padded, n_raw, items, dups)``, where ``items`` is the number of ids
    the rows hold and ``dups`` how many of them repeat an id of their row.

    A fixed number of NumPy passes, none per basket: the rows are flattened
    once, each id keyed ``row * base + (id - lo)`` (``base`` spans the ids,
    so the keys order by row, then by id, and each row keeps its segment),
    the keys sorted once, a repeat of its predecessor dropped (the
    per-basket dedupe), and the rest laid into the first slots of their
    rows.  A row without ``len`` (a generator) is materialised first.
    """
    n = len(transactions)
    try:
        lens = np.fromiter(map(len, transactions), np.int64, count=n)
    except TypeError:
        return pack_transactions([list(t) for t in transactions], min_len)
    items = int(lens.sum())
    flat = np.fromiter(itertools.chain.from_iterable(transactions), np.int64,
                       count=items)
    if not items:
        width = max(min_len, 0 if n else 1)
        return np.full((n, width), ITEM_PAD, np.int32), 0, 0, 0
    lo, hi = int(flat.min()), int(flat.max())
    base = hi - lo + 1
    # int32 keys sort faster; they are exact while every key and id fits.
    fits = n * base < 2**31 and -2**31 <= lo and hi < 2**31
    kdt = np.int32 if fits else np.int64
    row_key = np.repeat(np.arange(n, dtype=kdt) * kdt(base) - kdt(lo), lens)
    key = row_key + flat.astype(kdt, copy=False)
    key.sort()
    first = np.empty(items, bool)
    first[0] = True
    np.not_equal(key[1:], key[:-1], out=first[1:])
    key -= row_key               # each row's ids, sorted
    ulen = lens
    dups = items - int(np.count_nonzero(first))
    if dups:
        at = np.flatnonzero(first)
        key = key[at]
        ulen = np.diff(np.searchsorted(at, np.cumsum(lens)), prepend=0)
    width = max(min_len, int(ulen.max()))
    padded = np.full((n, width), ITEM_PAD, np.int32)
    # A boolean mask fills in row-major order: row r's first ulen[r] slots.
    padded[np.arange(width) < ulen[:, None]] = key
    return padded, hi + 1, items, dups


def padded_from_transactions(
    transactions: Sequence[Sequence[int]], min_len: int = 8
) -> tuple:
    """One host pass over raw transaction lists -> ((N, L) int32 padded
    matrix of unique sorted ids, ITEM_PAD-padded; max item id + 1).

    L is the longest row's count of unique ids, at least ``min_len`` (1 for
    zero rows).  This is the ingestion path's one pass over the baskets
    (``pack_transactions``): Job1 and the dense re-encode both derive from
    the returned matrix with vectorized (or on-device) operations.
    """
    padded, n_raw, _, _ = pack_transactions(transactions, min_len)
    return padded, n_raw


def encode_db_from_padded(
    padded: np.ndarray, n_items: int, align: int = 128
) -> EncodedDB:
    """Vectorized encode from an (N, L) padded matrix of dense ids in
    [0, n_items) — no per-transaction Python loop."""
    n = padded.shape[0]
    f_pad = ((n_items // align) + 1) * align  # strictly greater than n_items
    bitmap = np.zeros((n, f_pad), dtype=np.uint8)
    rows, cols = np.nonzero(padded < ITEM_PAD)
    bitmap[rows, padded[rows, cols]] = 1
    return EncodedDB(padded=np.asarray(padded, dtype=np.int32),
                     bitmap=bitmap, n_items=n_items)


def encode_db(
    transactions: Sequence[Sequence[int]],
    n_items: int,
    min_len: int = 8,
    align: int = 128,
) -> EncodedDB:
    """Encode transactions whose items are already dense ids in [0, n_items)."""
    padded, _ = padded_from_transactions(transactions, min_len=min_len)
    return encode_db_from_padded(padded, n_items=n_items, align=align)


def dense_remap_padded(padded: np.ndarray, item_map: np.ndarray,
                       n_raw: int = None, min_width: int = 8) -> np.ndarray:
    """Remap an (N, L) raw-id padded matrix onto the dense ids of
    ``item_map`` (sorted original ids -> [0, F)); items outside the map —
    infrequent items, pads — become ITEM_PAD and collect at the row ends.

    This is the dense re-encode both the batch path (``JaxRunner._encode``)
    and the serving layer's per-slot delta blocks go through: the remap is
    the "perfect hash" of the paper's hash-table trie, and dropping unmapped
    items is exact (no candidate may contain an infrequent item).  The
    returned width is clamped to a lane-friendly minimum but never past the
    source's column count.
    """
    item_map = np.asarray(item_map, np.int64)
    f = len(item_map)
    if n_raw is None:
        top = int(item_map[-1]) + 1 if f else 0
        real = padded[padded < ITEM_PAD]
        n_raw = max(top, int(real.max()) + 1 if real.size else 0)
    lookup = np.full((n_raw + 1,), ITEM_PAD, np.int32)
    if f:
        lookup[item_map] = np.arange(f, dtype=np.int32)
    dense = lookup[np.minimum(padded, n_raw)]  # unmapped/pad -> ITEM_PAD
    dense = np.sort(dense, axis=1)  # unique-sorted; ITEM_PAD collects at end
    width = int((dense < ITEM_PAD).sum(axis=1).max()) if dense.size else 0
    width = min(dense.shape[1], max(min_width, width))
    return np.ascontiguousarray(dense[:, :max(1, width)])


class DeltaCountMixin:
    """Incremental counting over transaction *blocks* — the serving path.

    Support counts are additive over disjoint transaction sets, so a sliding
    window's counts are maintained exactly by adding the contribution of an
    ingested block and subtracting the contribution of an evicted block:
    ``count(window') = count(window) + count(added) - count(removed)``.
    Both directions reuse the store's own ``count_block`` (same path,
    same integer adds), so delta-maintained counts are bit-identical to a
    full recount at every step.
    """

    @classmethod
    def apply_delta(cls, counts, trans_block: dict, cands: dict, sign: int):
        """counts + sign * the block's contribution (jit-safe, pure).

        The signed form both directions share: ``sign=+1`` folds an ingested
        block in, ``sign=-1`` is the exact inverse — including on a *one-row*
        block, the serving layer's per-basket eviction granularity (evicting
        a single transaction is one signed delta over a (1, L) block).
        """
        import jax.numpy as jnp

        return counts + sign * cls.count_block(trans_block, cands).astype(
            jnp.int64)

    @classmethod
    def count_delta(cls, counts, trans_block: dict, cands: dict):
        """counts + the block's contribution (jit-safe, pure)."""
        return cls.apply_delta(counts, trans_block, cands, +1)

    @classmethod
    def uncount_delta(cls, counts, trans_block: dict, cands: dict):
        """counts - the block's contribution (exact inverse of count_delta)."""
        return cls.apply_delta(counts, trans_block, cands, -1)


def tracked_keep_mask(cand: np.ndarray, prev_freq: np.ndarray) -> np.ndarray:
    """bool[C]: which rows of a tracked (C, k) candidate level survive a
    lattice compaction given the *currently* frequent rows of level k-1.

    A tracked row is worth keeping exactly when the serving walk could still
    generate it — every (k-1)-subset is a row of ``prev_freq`` (the level
    below, filtered at the tracked threshold on current counts).  Rows whose
    support has drained to zero *and* left the generatable closure, and
    negative-border rows no longer adjacent to any frequent itemset, fall
    out; rows that are currently frequent always survive (their subsets are
    frequent by the Apriori property, hence in ``prev_freq``).  Both inputs
    must be lexicographically sorted with dense ids — the tracked lattice's
    native layout.
    """
    from repro.core.itemsets import _rows_member  # lazy: itemsets imports us

    cand = np.asarray(cand)
    if cand.size == 0:
        return np.zeros((cand.shape[0] if cand.ndim == 2 else 0,), bool)
    if prev_freq.size == 0:
        return np.zeros((cand.shape[0],), bool)
    keep = np.ones((cand.shape[0],), bool)
    for drop in range(cand.shape[1]):
        keep &= _rows_member(np.asarray(prev_freq, cand.dtype),
                             np.delete(cand, drop, axis=1))
    return keep


def pad_candidates(cand: np.ndarray, f_pad: int, align: int = 128,
                   shards: int = 1) -> np.ndarray:
    """Pad the candidate count C up to ``align``; pad rows point at the
    always-zero bitmap column so they can never be matched.

    ``shards`` > 1 (candidate-axis sharding) additionally rounds C up to a
    multiple of the shard count so the padded matrix splits evenly over the
    ``cand`` mesh axes; the extra rows are the same unmatchable pads.
    An empty (0, k) matrix keeps its k so downstream shapes stay consistent.
    """
    if cand.ndim == 2 and cand.shape[1]:
        c, k = cand.shape
    else:
        c, k = 0, 1
    c_pad = max(align, ((c + align - 1) // align) * align)
    if shards > 1:
        c_pad = ((c_pad + shards - 1) // shards) * shards
    out = np.full((c_pad, k), f_pad - 1, dtype=np.int32)
    if cand.size:
        out[:c] = cand
    return out
