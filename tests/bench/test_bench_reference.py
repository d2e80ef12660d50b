"""The plain reference against the program's brute-force oracle, and the
control (bfloat16 counts) against the reference."""

import numpy as np
import pytest

from bench import check, data, reference
from repro.core import brute_force_frequent
from repro.core.itemsets import apriori_gen as repro_apriori_gen
from repro.data import get_dataset
from repro.data.datasets import near_duplicate_db


@pytest.mark.parametrize("scale,support,seed", [
    (0.005, 0.014, 42),
    (0.01, 0.02, 3),
    (0.005, 0.02, 7),
])
def test_reference_equals_brute_force(scale, support, seed):
    db = get_dataset("T10I4D100K", scale=scale, seed=seed)
    got = reference.frequent_itemsets(data.padded(db), support)
    want = brute_force_frequent(db, reference.min_count(support, len(db)))
    assert got == want
    assert got


def test_reference_deep_lattice():
    db = near_duplicate_db(400, n_templates=4, n_items=60, template_len=7,
                           flip_prob=0.3, seed=1)
    got = reference.frequent_itemsets(data.padded(db), 0.05)
    want = brute_force_frequent(db, reference.min_count(0.05, len(db)))
    assert got == want
    assert max(map(len, got)) == 7


def test_apriori_gen_matches_the_program_on_a_level():
    rng = np.random.default_rng(0)
    rows = sorted({tuple(sorted(rng.choice(12, 3, replace=False).tolist()))
                   for _ in range(60)})
    got = {tuple(r) for r in reference.apriori_gen(np.array(rows)).tolist()}
    want = set(repro_apriori_gen(rows))
    assert got == want


def test_min_count_is_the_ceiling_of_the_product():
    assert reference.min_count(0.0025, 100_000) == 250
    assert reference.min_count(0.005, 65_536) == 328
    assert reference.min_count(0.5, 0) == 1


def test_control_is_not_correct():
    """The control (every count rounded through bfloat16) fails the
    comparison once supports exceed 256."""
    mat = data.padded(get_dataset("T10I4D100K", scale=0.1, seed=5))
    want = reference.frequent_itemsets(mat, 0.01)
    got = reference.frequent_itemsets(mat, 0.01, rounding=check.bf16_rounding)
    numbers = check.compare(got, want)
    assert numbers["wrong_support"] > 0
    assert not check.passes(numbers)
    assert check.passes(check.compare(want, want))


def test_compare_counts_each_kind_of_disagreement():
    want = {(1,): 5, (2,): 4, (1, 2): 3}
    got = {(1,): 5, (2,): 7, (3,): 4}
    assert check.compare(got, want) == {
        "missing": 1, "extra": 1, "wrong_support": 1}
