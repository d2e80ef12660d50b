"""Dataset subsystem (repro.data.datasets) + sweep-grid plumbing tests:
Quest-name parsing, registry, seeded determinism, .dat round-trips with the
sidecar dense cache, T/I/D parameter sanity, adversarial generator shapes,
and per-cell JobProfile aggregation / parity-cell divergence detection
(the grid of parity cells itself is ``tests/test_paper_grid.py``)."""

import gzip
import os

import numpy as np
import pytest

from repro.core.runtime import (
    JobProfile,
    aggregate_profiles,
    itemset_digest,
    run_parity_cell,
)
from repro.data import (
    DATASETS,
    dense_to_transactions,
    encode_padded,
    get_dataset,
    list_datasets,
    load_dense,
    long_tail_db,
    near_duplicate_db,
    parse_quest_name,
    quest_from_name,
    read_dat,
    wide_sparse_db,
    write_dat,
)


# -- Quest T/I/D names -------------------------------------------------------

def test_parse_quest_name():
    assert parse_quest_name("T10I4D100K") == {
        "avg_transaction_len": 10, "avg_pattern_len": 4,
        "n_transactions": 100_000}
    assert parse_quest_name("T40I10D100K")["avg_transaction_len"] == 40
    assert parse_quest_name("t5i2d1M")["n_transactions"] == 1_000_000
    assert parse_quest_name("T5I2D800")["n_transactions"] == 800


@pytest.mark.parametrize("bad", ["T10I4", "I4D100K", "T10D100K", "foo",
                                 "T10I4D100G", ""])
def test_parse_quest_name_rejects(bad):
    with pytest.raises(ValueError):
        parse_quest_name(bad)


def test_quest_from_name_tid_sanity():
    # T = mean basket length (within generator tolerance), D = row count.
    db = quest_from_name("T10I4D2K", seed=0)
    assert len(db) == 2000
    lens = [len(t) for t in db]
    assert 7 <= np.mean(lens) <= 13
    # A denser code really shifts the mean length.
    db40 = quest_from_name("T40I10D500", seed=0, n_items=2000)
    assert np.mean([len(t) for t in db40]) > 2 * np.mean(lens)


def test_quest_scale_applies_to_d_only():
    db = quest_from_name("T10I4D100K", scale=0.003, seed=1)
    assert len(db) == 300
    assert 7 <= np.mean([len(t) for t in db]) <= 13


# -- registry ----------------------------------------------------------------

def test_registry_contents_and_determinism():
    names = [s.name for s in list_datasets()]
    for expected in ["T10I4D100K", "T40I10D100K", "BMS_WebView_1",
                     "BMS_WebView_2", "long_tail", "near_duplicate",
                     "wide_sparse"]:
        assert expected in names
    for name in ["T10I4D100K", "long_tail", "near_duplicate"]:
        a = get_dataset(name, scale=0.002, seed=5)
        b = get_dataset(name, scale=0.002, seed=5)
        assert a == b, f"{name} not deterministic under a fixed seed"
        assert a != get_dataset(name, scale=0.002, seed=6)


def test_registry_accepts_adhoc_quest_codes():
    db = get_dataset("T6I3D300", seed=2)   # not registered, still valid
    assert len(db) == 300


def test_registry_unknown_name_raises():
    with pytest.raises(KeyError, match="unknown dataset"):
        get_dataset("no_such_dataset")
    assert "no_such_dataset" not in DATASETS


# -- .dat basket IO + dense cache -------------------------------------------

@pytest.mark.parametrize("fname", ["db.dat", "db.dat.gz"])
def test_dat_round_trip_to_identical_dense(tmp_path, fname):
    db = get_dataset("T10I4D100K", scale=0.001, seed=3)
    path = str(tmp_path / fname)
    write_dat(path, db)
    if fname.endswith(".gz"):   # really gzip, not plain text with a suffix
        with gzip.open(path, "rt") as f:
            assert f.readline().strip()
    assert read_dat(path) == db
    dense = load_dense(path)
    np.testing.assert_array_equal(dense, encode_padded(db))
    assert dense.dtype == np.int32
    assert dense_to_transactions(dense) == db


def test_load_dense_sidecar_cache(tmp_path):
    db = [[1, 2, 3], [2, 7], [5]]
    path = str(tmp_path / "tiny.dat")
    write_dat(path, db)
    first = load_dense(path)
    side = path + ".dense.npz"
    assert os.path.exists(side)
    np.testing.assert_array_equal(load_dense(path), first)  # cache hit
    # Rewriting the source invalidates the sidecar (size/mtime key).
    db2 = [[9, 11], [4]]
    write_dat(path, db2)
    os.utime(path, ns=(1, 1))   # force a distinct mtime even on coarse clocks
    np.testing.assert_array_equal(load_dense(path), encode_padded(db2))
    # cache=False never writes a sidecar.
    path2 = str(tmp_path / "nocache.dat")
    write_dat(path2, db)
    load_dense(path2, cache=False)
    assert not os.path.exists(path2 + ".dense.npz")


def test_read_dat_preserves_empty_transactions_and_dedups(tmp_path):
    # A blank line is an empty transaction: dropping it would change N and
    # therefore every support threshold computed from the reloaded file.
    path = str(tmp_path / "messy.dat")
    with open(path, "w") as f:
        f.write("3 1 2\n\n  \n7 7 5\n")
    assert read_dat(path) == [[1, 2, 3], [], [], [5, 7]]


def test_dat_round_trip_with_empty_baskets(tmp_path):
    db = [[4, 9], [], [2], []]
    path = str(tmp_path / "empty.dat")
    write_dat(path, db)
    assert read_dat(path) == db
    dense = load_dense(path)
    assert dense.shape[0] == 4            # N survives the round trip
    assert dense_to_transactions(dense) == db


# -- adversarial generators --------------------------------------------------

def test_long_tail_head_dominates():
    db = long_tail_db(800, n_items=300, seed=0)
    counts = np.zeros(300)
    for t in db:
        counts[t] += 1
    head = counts[:4].min() / len(db)
    tail_median = np.median(counts[counts > 0]) / len(db)
    assert head > 0.5                      # hot head in most baskets
    assert head > 10 * tail_median         # orders-of-magnitude skew


def test_near_duplicate_tiny_distinct_set():
    db = near_duplicate_db(500, n_templates=8, seed=0)
    distinct = {tuple(t) for t in db}
    assert len(distinct) < len(db) // 5    # overwhelmingly duplicates
    assert len(distinct) >= 8


def test_wide_sparse_density():
    db = wide_sparse_db(400, n_items=20_000, avg_len=3.0, seed=0)
    mean_len = np.mean([len(t) for t in db])
    assert mean_len < 6
    assert max(i for t in db for i in t) > 5_000   # vocabulary really is wide
    assert all(t == sorted(set(t)) for t in db)


# -- sweep plumbing ----------------------------------------------------------

def test_aggregate_profiles_sums_and_models():
    levels = [
        JobProfile(k=1, n_candidates=10, n_frequent=4, seconds=1.0,
                   count_seconds=0.6, reduce_seconds=0.1,
                   mapper_seconds=[0.5, 0.6]),
        JobProfile(k=2, n_candidates=6, n_frequent=2, seconds=2.0,
                   gen_seconds=0.2, build_seconds=0.3, count_seconds=1.0,
                   inflight_depth=3, inflight_retunes=1),
    ]
    agg = aggregate_profiles(levels)
    assert agg["n_jobs"] == 2 and agg["max_k"] == 2
    assert agg["n_candidates"] == 16 and agg["n_frequent"] == 6
    assert agg["seconds"] == pytest.approx(3.0)
    # parallel model: (max(mappers)+reduce) + wall-clock of the profiled job
    assert agg["parallel_seconds"] == pytest.approx(0.6 + 0.1 + 2.0)
    assert agg["gen_seconds"] == pytest.approx(0.2)
    assert agg["inflight_depth"] == 3 and agg["inflight_retunes"] == 1
    empty = aggregate_profiles([])
    assert empty["n_jobs"] == 0 and empty["seconds"] == 0.0


def test_itemset_digest_canonical():
    a = {(1, 2): 5, (3,): 7}
    b = {(3,): 7, (1, 2): 5}
    assert itemset_digest(a) == itemset_digest(b)
    assert itemset_digest(a) != itemset_digest({(1, 2): 6, (3,): 7})
    assert itemset_digest(a) != itemset_digest({(1, 2): 5})


def test_run_parity_cell_detects_divergence():
    from repro.core.runtime import SimRunner

    db = get_dataset("T10I4D100K", scale=0.0015, seed=9)

    class LyingRunner(SimRunner):
        """Mis-reports every count by +1 — the cell must catch it."""

        def count(self, job):
            counts, prof = super().count(job)
            return counts + 1, prof

    with pytest.raises(AssertionError, match="parity violation"):
        run_parity_cell(db, 0.03, {
            "sim": lambda: SimRunner(structure="trie", n_mappers=2),
            "liar": lambda: LyingRunner(structure="trie", n_mappers=2),
        }, max_k=3)


# -- out-of-core chunked reader ----------------------------------------------

from repro.core import FrequentItemsetMiner  # noqa: E402
from repro.core.stores import ARRAY_STORES, padded_from_transactions  # noqa: E402
from repro.data import ChunkedDatasetReader  # noqa: E402


def _chunk_db(seed=11, n=120, n_items=30):
    """A small DB with an empty basket (the reader must preserve N)."""
    db = get_dataset(f"T6I3D{n}", seed=seed, scale=1.0)
    db = [sorted({i % n_items for i in t}) for t in db]
    db[len(db) // 2] = []
    return db


def _write_db(tmp_path, db, gz):
    path = str(tmp_path / ("db.dat.gz" if gz else "db.dat"))
    write_dat(path, db)
    return path


@pytest.mark.parametrize("gz", [False, True])
@pytest.mark.parametrize("chunk", [1, 7, None, "past_end"])
def test_chunked_concat_parity(tmp_path, gz, chunk):
    """Concatenating every chunk reproduces the whole-file padded matrix
    bit for bit — at chunk size 1, a prime, exactly N, and past N."""
    db = _chunk_db()
    path = _write_db(tmp_path, db, gz)
    size = {None: len(db), "past_end": len(db) + 100}.get(chunk, chunk)
    r = ChunkedDatasetReader(path, chunk_transactions=size)
    whole, n_raw = padded_from_transactions(read_dat(path))
    assert len(r) == len(db)
    assert r.n_raw_items == n_raw
    parts = list(r.chunks())
    assert len(parts) == r.n_chunks == -(-len(db) // size)
    assert all(p.shape[1] == r.width for p in parts)
    np.testing.assert_array_equal(np.concatenate(parts, axis=0), whole)


def test_chunked_scan_sidecar_cache(tmp_path):
    db = _chunk_db()
    path = _write_db(tmp_path, db, gz=False)
    r1 = ChunkedDatasetReader(path, chunk_transactions=16)
    assert not r1.scanned_from_cache
    side = path + ".chunkmeta.json"
    assert os.path.exists(side)
    r2 = ChunkedDatasetReader(path, chunk_transactions=16)
    assert r2.scanned_from_cache
    assert (len(r2), r2.width, r2.n_raw_items) == (len(r1), r1.width,
                                                   r1.n_raw_items)
    # Rewriting the source invalidates the sidecar (size/mtime key).
    write_dat(path, [[1, 2], [3]])
    os.utime(path, ns=(1, 1))
    r3 = ChunkedDatasetReader(path)
    assert not r3.scanned_from_cache
    assert len(r3) == 2 and r3.n_raw_items == 4
    # cache=False never writes a sidecar.
    path2 = _write_db(tmp_path, db, gz=True)
    ChunkedDatasetReader(path2, cache=False)
    assert not os.path.exists(path2 + ".chunkmeta.json")


def test_chunked_memory_budget_bounds_chunk(tmp_path):
    db = _chunk_db()
    path = _write_db(tmp_path, db, gz=False)
    probe = ChunkedDatasetReader(path)
    # A budget of a quarter of the padded matrix forces >= 4 chunks.
    budget = (len(db) * probe.width * 4) // 4
    r = ChunkedDatasetReader(path, memory_budget_bytes=budget)
    assert r.chunk_transactions == budget // (r.width * 4)
    assert r.n_chunks >= 4
    for p in r.chunks():
        assert p.nbytes <= budget
    with pytest.raises(ValueError, match="not both"):
        ChunkedDatasetReader(path, chunk_transactions=8,
                             memory_budget_bytes=1024)
    with pytest.raises(ValueError, match=">= 1"):
        ChunkedDatasetReader(path, chunk_transactions=0)


MIN_WIDTH_EXPECTED = 8  # padded_from_transactions(min_len=8) lane minimum


def test_chunked_empty_file(tmp_path):
    path = str(tmp_path / "empty.dat")
    write_dat(path, [])
    r = ChunkedDatasetReader(path)
    assert len(r) == 0 and r.n_chunks == 0
    assert list(r.chunks()) == []
    assert r.width == MIN_WIDTH_EXPECTED


@pytest.mark.parametrize("store", list(ARRAY_STORES))
@pytest.mark.parametrize("backend", ["jax", "sharded"])
def test_chunked_mine_matches_in_memory(tmp_path, store, backend):
    """Streaming the DB in >= 4 chunks mines bit-identical itemsets AND
    supports to the fully-resident path, on every store and both engine
    backends — the tentpole's additivity claim, end to end."""
    from repro.core.runtime import ShardedRunner
    from repro.launch.mesh import compat_make_mesh

    db = _chunk_db()
    path = _write_db(tmp_path, db, gz=False)
    reader = ChunkedDatasetReader(path, chunk_transactions=len(db) // 5)
    assert reader.n_chunks >= 4

    def miner():
        if backend == "sharded":
            runner = ShardedRunner(store=store,
                                   mesh=compat_make_mesh((1,), ("data",)))
            return FrequentItemsetMiner(min_support=0.05, runner=runner,
                                        max_k=4)
        return FrequentItemsetMiner(min_support=0.05, store=store, max_k=4)

    res_mem = miner().mine(db)
    res_chunked = miner().mine(reader)
    assert res_chunked.itemsets == res_mem.itemsets
    assert res_chunked.n_transactions == res_mem.n_transactions == len(db)
    assert res_chunked.min_count == res_mem.min_count
    assert all(p.chunks == reader.n_chunks for p in res_chunked.levels)
    assert all(p.chunks == 0 for p in res_mem.levels)


def test_chunked_mine_matches_device_loop_reference(tmp_path):
    """The chunked stream agrees with the fused device ladder too (the
    ladder needs a resident DB, so it is the in-memory reference here)."""
    db = _chunk_db()
    path = _write_db(tmp_path, db, gz=False)
    reader = ChunkedDatasetReader(path, chunk_transactions=len(db) // 4)
    ladder = FrequentItemsetMiner(min_support=0.05, store="perfect_hash",
                                  max_k=4, device_loop=True).mine(db)
    chunked = FrequentItemsetMiner(min_support=0.05, store="perfect_hash",
                                   max_k=4).mine(reader)
    assert chunked.itemsets == ladder.itemsets


def test_chunked_device_loop_rejected(tmp_path):
    db = _chunk_db()
    reader = ChunkedDatasetReader(_write_db(tmp_path, db, gz=False),
                                  chunk_transactions=32)
    miner = FrequentItemsetMiner(min_support=0.05, store="perfect_hash",
                                 device_loop=True)
    with pytest.raises(ValueError, match="device_loop=False"):
        miner.mine(reader)


def test_chunked_sim_runner_rejected(tmp_path):
    from repro.core.runtime import SimRunner

    db = _chunk_db()
    reader = ChunkedDatasetReader(_write_db(tmp_path, db, gz=False))
    with pytest.raises(TypeError, match="engine-backed"):
        SimRunner(structure="hash_tree").ingest(reader)


def test_chunked_reader_describe(tmp_path):
    db = _chunk_db()
    reader = ChunkedDatasetReader(_write_db(tmp_path, db, gz=False),
                                  chunk_transactions=30)
    d = reader.describe()
    assert "chunks" in d and str(len(db)) in d and "30" in d
