"""The host ingest (``padded_from_transactions``, ``pack_transactions``)
against the per-basket loop it replaced, kept here as the reference."""

import numpy as np
import pytest

from repro.core.stores import (ITEM_PAD, pack_transactions,
                               padded_from_transactions)


def reference(transactions, min_len=8):
    """The per-basket loop: a sorted set per row, copied into the matrix."""
    n = len(transactions)
    rows = [sorted(set(int(x) for x in t)) for t in transactions]
    lmax = max(min_len, max((len(r) for r in rows), default=1))
    padded = np.full((n, lmax), ITEM_PAD, dtype=np.int32)
    for i, r in enumerate(rows):
        padded[i, : len(r)] = r
    n_raw = max((r[-1] for r in rows if r), default=-1) + 1
    return padded, n_raw


def _random(seed, n, n_items=50, max_len=12):
    """Unsorted rows drawn with replacement (so with duplicates), some
    empty."""
    rng = np.random.default_rng(seed)
    return [rng.integers(0, n_items, size=rng.integers(0, max_len)).tolist()
            for _ in range(n)]


# Each case builds its rows anew, so a generator row is fresh for each side.
CASES = {
    "random_dups_unsorted_empty": (lambda: _random(0, 500), 8),
    "zero_baskets": (lambda: [], 8),
    "zero_baskets_min_len_0": (lambda: [], 0),
    "all_empty": (lambda: [[], [], []], 8),
    "all_empty_min_len_0": (lambda: [[], []], 0),
    "min_len_above_longest": (lambda: [[3, 1], [2]], 16),
    "min_len_below_longest": (lambda: _random(1, 200, max_len=30), 4),
    "dups_narrow_the_width": (lambda: [[5, 5, 5, 5, 5, 5, 5, 5, 5, 5, 1],
                                       [4, 2, 4, 2, 4, 2, 4, 2, 4, 2],
                                       [9, 8, 7]], 2),
    "tuple_set_ndarray_rows": (lambda: [(4, 1, 4), {7, 3, 5},
                                        np.array([6, 2, 6, 0], np.int32),
                                        [np.int64(9), np.int32(9),
                                         np.int16(1)]], 8),
    "ids_up_to_2_30_minus_1": (lambda: [[2**30 - 1, 0, 2**30 - 1],
                                        [2**29, 2**30 - 2], []], 8),
    "generator_row": (lambda: [[3, 1], (x for x in (7, 2, 7)), []], 8),
    "block_256": (lambda: _random(2, 256, n_items=1000, max_len=20), 8),
    "block_256_wide_ids": (lambda: _random(3, 256, n_items=2**30,
                                           max_len=20), 8),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_ingest_equals_the_per_basket_loop(case):
    make, min_len = CASES[case]
    want, want_raw = reference(make(), min_len)
    got, got_raw = padded_from_transactions(make(), min_len)
    assert got.shape == want.shape
    assert got.dtype == want.dtype == np.int32
    np.testing.assert_array_equal(got, want)
    assert got_raw == want_raw
    padded, n_raw, items, dups = pack_transactions(make(), min_len)
    np.testing.assert_array_equal(padded, want)
    assert n_raw == want_raw
    assert items == sum(len(list(t)) for t in make())
    assert dups == items - int(np.count_nonzero(want < ITEM_PAD))
