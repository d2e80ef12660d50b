"""The benchmark's copies of the generators give what the program's
originals give, and a seed changes the baskets but not the work."""

import numpy as np

from bench import data, reference
from bench.gen.quest import quest_generator
from bench.gen.stream import basket_stream
from repro.data import basket_stream as repro_stream
from repro.data import get_dataset

T10 = dict(kind="quest", n_transactions=1000, avg_transaction_len=10,
           avg_pattern_len=4, n_items=1000, n_patterns=2000,
           corruption_mean=0.5, seed=11)


def test_quest_copy_equals_get_dataset():
    want = get_dataset("T40I10D100K", scale=0.01, seed=11)
    got = quest_generator(n_transactions=1000, avg_transaction_len=40,
                          avg_pattern_len=10, seed=11)
    assert got == want


def test_stream_copy_equals_basket_stream():
    kw = dict(batch_size=50, seed=4, repeat=True, max_batches=50)
    want = list(repro_stream("T10I4D100K", scale=0.01, **kw))
    got = list(basket_stream(get_dataset("T10I4D100K", scale=0.01, seed=4),
                             **kw))
    assert [b.transactions for b in got] == [b.transactions for b in want]
    assert all(np.array_equal(a.t_arrivals, b.t_arrivals)
               for a, b in zip(got, want))
    # Stream positions run on across the epochs.
    assert [b.start for b in got] == list(
        np.cumsum([0] + [len(b) for b in got[:-1]]))


def test_base_dataset_is_cached(tmp_path):
    a = data.base_dataset("t", T10, tmp_path)
    assert len(list(tmp_path.glob("t-*.npy"))) == 1
    b = data.base_dataset("t", T10, tmp_path)
    assert np.array_equal(a, b)
    assert data.to_lists(a) == quest_generator(
        **{k: v for k, v in T10.items() if k != "kind"})


def test_seed_reorders_and_relabels_but_keeps_the_work(tmp_path):
    base = data.base_dataset("t", T10, tmp_path)
    one, again = data.seeded(base, 2**40 + 1), data.seeded(base, 2**40 + 1)
    other = data.seeded(base, 9)
    assert np.array_equal(one, again)
    assert not np.array_equal(one, other)
    for mat in (one, other):
        assert mat.shape == base.shape
        assert sorted((mat >= 0).sum(1)) == sorted((base >= 0).sum(1))
        assert set(mat[mat >= 0].tolist()) == set(base[base >= 0].tolist())
        rows = mat[:, :-1][mat[:, 1:] >= 0]
        assert (rows < mat[:, 1:][mat[:, 1:] >= 0]).all()  # sorted rows

    def sizes(mat):
        return np.bincount([len(s) for s in
                            reference.frequent_itemsets(mat, 0.01)])

    assert np.array_equal(sizes(one), sizes(base))
    assert np.array_equal(sizes(other), sizes(base))


def test_seed_without_reorder_relabels_alone(tmp_path):
    """A fixed arrival order: every row keeps its place and its length."""
    base = data.base_dataset("t", T10, tmp_path)
    mat = data.seeded(base, 2**40 + 3, reorder=False)
    assert not np.array_equal(mat, base)
    assert np.array_equal((mat >= 0).sum(1), (base >= 0).sum(1))
    assert set(mat[mat >= 0].tolist()) == set(base[base >= 0].tolist())
    assert sorted(np.bincount(mat[mat >= 0])) == sorted(
        np.bincount(base[base >= 0]))
    # Row i of the base relabelled is row i: its ids' supports carry over.
    support = np.bincount(base[base >= 0])
    support_after = np.bincount(mat[mat >= 0])
    assert [sorted(support[r[r >= 0]]) for r in base[:50]] == [
        sorted(support_after[r[r >= 0]]) for r in mat[:50]]
