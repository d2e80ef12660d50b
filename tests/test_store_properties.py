"""Store-parity property suite (PR 4 satellite).

Every array-layout store's device path — ``encode_candidates`` (shard-local
under candidate-axis sharding) + ``count_block`` through the engine — must
reproduce the support counts of the paper's three sequential reference
structures (hash tree, trie, hash-table trie) *exactly*, for k = 1..4, on
adversarial databases: varying n_items and density, duplicate transactions,
duplicate items inside a transaction, empty transactions, empty databases.

The suite is layered so the same parity helper runs everywhere:

- fixed-seed random DBs + hand-picked edge DBs run on any box (no optional
  deps) — the regression floor;
- the hypothesis wrapper feeds generated DBs through the identical helper
  when hypothesis is installed (CI always has it; the local toolchain may
  not, hence no module-level importorskip);
- the cand-sharded variant builds a ``(1, device_count)`` data x cand mesh,
  so the very same test that runs trivially at one device exercises real
  8-way shard-local encodes in the CI ``mesh-2d`` job.
"""

import itertools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.itemsets import level_to_matrix
from repro.core.runtime.engine import MapReduceEngine
from repro.core.sequential import SEQUENTIAL_STORES
from repro.core.stores import ARRAY_STORES, encode_db, pad_candidates
from repro.core.stores.perfect_hash import (
    GRAM_RATIO, PerfectHashStore, gathered_counts, gram_pair_counts)
from repro.launch.mesh import compat_make_mesh

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # optional dep: the fixed-seed layer still runs
    HAVE_HYPOTHESIS = False

MAX_K = 4
MAX_CANDS = 40  # candidate pool cap per level (keeps jit shapes small)


def _candidates(db, k):
    """Deterministic candidate pool: the first MAX_CANDS k-combinations of
    the observed items, lexicographic (the canonical level-matrix order)."""
    items = sorted({int(i) for t in db for i in t})
    return list(itertools.islice(itertools.combinations(items, k), MAX_CANDS))


def _sequential_counts(db, cands, structure):
    store = SEQUENTIAL_STORES[structure](cands)
    for t in db:
        store.count_transaction(t)
    got = store.counts()
    return np.array([got.get(c, 0) for c in cands], np.int64)


def _assert_store_parity(db, n_items, store, mesh=None, cand_axes=()):
    """One DB through one array store (k=1..4) vs all three references."""
    engine = MapReduceEngine(store=store, mesh=mesh, cand_axes=cand_axes,
                             block_n=16, cand_block=64)
    engine.place(encode_db(db, n_items=n_items))
    for k in range(1, MAX_K + 1):
        cands = _candidates(db, k)
        if not cands:
            continue
        got = engine.count_candidates(level_to_matrix(cands))
        for structure in SEQUENTIAL_STORES:
            want = _sequential_counts(db, cands, structure)
            np.testing.assert_array_equal(
                got, want, err_msg=f"{store} vs {structure} at k={k}")


# -- fixed-seed layer (runs without hypothesis) ------------------------------
def _random_db(seed):
    rng = np.random.default_rng(seed)
    n_items = int(rng.integers(2, 20))
    density = float(rng.uniform(0.1, 0.6))
    db = [list(map(int, np.nonzero(rng.random(n_items) < density)[0]))
          for _ in range(int(rng.integers(1, 30)))]
    db.append(list(db[0]))  # duplicate transaction: supports must add up
    db.append([])           # empty transaction: matches nothing
    if db[0]:
        db.append([db[0][0]] * 3)  # duplicate items inside one transaction
    return n_items, db


def _wide_db(seed, n=225, n_items=270, width=6):
    """Baskets over a wide vocabulary (F_pad 384 once most items are
    frequent), half of them holding one of eight planted triples."""
    rng = np.random.default_rng(seed)
    patterns = [rng.choice(n_items, 3, replace=False) for _ in range(8)]
    db = []
    for _ in range(n):
        row = set(rng.choice(n_items, width, replace=False).tolist())
        if rng.random() < 0.5:
            row |= set(patterns[rng.integers(8)].tolist())
        db.append(sorted(row))
    return db


EDGE_DBS = [
    (1, []),                             # empty database
    (1, [[]]),                           # single empty transaction
    (1, [[0], [0], [0]]),                # one item in the whole universe
    (3, [[0, 1, 2]] * 5),                # identical dense transactions
    (5, [[4], [0, 4], [], [4, 4, 0]]),   # dup items + empty + sparse ids
    # F_pad 384 over chunks of at most 128 candidates: perfect_hash counts
    # k = 2 by its gathers (every smaller DB here takes its Gram path);
    # 37 rows end in a ragged block of 5 under block_n 16.
    (270, _wide_db(3, n=37)),
]


@pytest.mark.parametrize("store", list(ARRAY_STORES))
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_store_parity_fixed_seeds(store, seed):
    n_items, db = _random_db(seed)
    _assert_store_parity(db, n_items, store)


@pytest.mark.parametrize("store", list(ARRAY_STORES))
@pytest.mark.parametrize("case", range(len(EDGE_DBS)))
def test_store_parity_edge_dbs(store, case):
    n_items, db = EDGE_DBS[case]
    _assert_store_parity(db, n_items, store)


@pytest.mark.parametrize("store", list(ARRAY_STORES))
def test_store_parity_cand_sharded(store):
    """The shard-local encode path (encode_candidates inside shard_map) on a
    (1, device_count) data x cand mesh: trivial at one device, 8-way
    partitioned in the CI mesh-2d job — same counts either way."""
    n_items, db = _random_db(7)
    mesh = compat_make_mesh((1, jax.device_count()), ("data", "cand"))
    _assert_store_parity(db, n_items, store, mesh=mesh, cand_axes=("cand",))


# -- the shard-axes layout contract ------------------------------------------
@pytest.mark.parametrize("store", list(ARRAY_STORES))
def test_candidate_shard_axes_cover_encode_outputs(store):
    """candidate_shard_axes() doubles as the shard-local encode's out_specs:
    it must name every tensor encode_candidates returns, each with a valid
    axis that really carries C (rows in == rows out along that axis)."""
    cls = ARRAY_STORES[store]
    cand = jnp.asarray(np.array([[0, 1], [1, 2], [2, 3]], np.int32))
    out = cls.encode_candidates(cand, f_pad=128)
    axes = cls.candidate_shard_axes()
    assert set(out) == set(axes)
    for name, axis in axes.items():
        assert 0 <= axis < out[name].ndim
        assert out[name].shape[axis] == cand.shape[0]


# -- perfect_hash's two k = 2 paths ------------------------------------------
# ``pair_by_gram`` picks the Gram matrix BᵀB or the two gathers from static
# shapes; both must give the brute-force counts on every block the engine
# and the service hand them: a ragged tail, padded candidate rows, and the
# one-row signed deltas of basket eviction.

def _block_and_pairs(rows, f_pad, seed=0):
    """A dense block (600 rows give pair counts past 255, so a sum kept
    in 8 bits would show) and 100 pairs padded to 128 rows."""
    rng = np.random.default_rng(seed)
    bits = (rng.random((rows, f_pad)) < 0.7).astype(np.uint8)
    bits[:, -1] = 0                       # the pad candidates' column
    pairs = np.array(list(itertools.combinations(range(f_pad - 1), 2)))
    pairs = pairs[rng.choice(len(pairs), 100, replace=False)]
    cand = pad_candidates(pairs[np.lexsort(pairs.T[::-1])], f_pad)
    want = (bits[:, cand[:, 0]] & bits[:, cand[:, 1]]).sum(axis=0)
    return bits, cand, want


@pytest.mark.parametrize("f_pad, by_gram", [(128, True), (384, False)])
@pytest.mark.parametrize("rows", [1, 5, 37, 600])
def test_pair_count_paths_agree_on_edge_blocks(rows, f_pad, by_gram):
    bits, cand, want = _block_and_pairs(rows, f_pad)
    assert cand.shape == (128, 2) and (cand[100:] == f_pad - 1).all()
    assert PerfectHashStore.pair_by_gram(2, f_pad, len(cand)) is by_gram
    block, cands = {"bitmap": jnp.asarray(bits)}, {"cand": jnp.asarray(cand)}
    for path in (gram_pair_counts, gathered_counts):
        got = np.asarray(path(block["bitmap"], cands["cand"]))
        np.testing.assert_array_equal(got, want, err_msg=path.__name__)
    np.testing.assert_array_equal(
        PerfectHashStore.count_block(block, cands), want)
    # One-row signed deltas, the service's eviction granularity: +1 then -1
    # of every row leaves the counts as they were, through the same path.
    base = jnp.asarray(want.astype(np.int32))
    counts = base
    for r in range(min(rows, 5)):
        row = {"bitmap": jnp.asarray(bits[r:r + 1])}
        added = PerfectHashStore.apply_delta(counts, row, cands, +1)
        np.testing.assert_array_equal(
            added - counts, bits[r, cand[:, 0]] & bits[r, cand[:, 1]])
        counts = PerfectHashStore.apply_delta(added, row, cands, -1)
    np.testing.assert_array_equal(counts, base)


def test_gram_ratio_engages_at_the_batch_cells_chunk_shapes():
    """F_pad 1024 with full 32,768-pair chunks and T10's, four-chip T10's
    and T40's padded tail chunks take the Gram path; k = 3 never does, nor
    a wide vocabulary with small chunks."""
    for c in (32_768, 16_384, 12_544, 8_064):
        assert PerfectHashStore.pair_by_gram(2, 1024, c), c
    assert not PerfectHashStore.pair_by_gram(3, 1024, 32_768)
    assert not PerfectHashStore.pair_by_gram(1, 128, 32_768)
    assert not PerfectHashStore.pair_by_gram(2, 16_384, 32_768)
    assert 131 <= GRAM_RATIO


@pytest.mark.parametrize("cand_block, by_gram", [(32_768, True), (128, False)])
def test_pair_count_paths_mine_exactly(cand_block, by_gram):
    """Whole mines on each side of the predicate equal brute force, and
    the level-2 job counted every chunk by the path the predicate names."""
    from repro.core import FrequentItemsetMiner, brute_force_frequent

    db = _wide_db(0)
    res = FrequentItemsetMiner(min_support=0.008, cand_block=cand_block,
                               block_n=64).mine(db)
    assert res.itemsets == brute_force_frequent(db, 2)
    job1, c2, *deeper = res.levels
    f_pad = ((job1.n_frequent // 128) + 1) * 128
    assert f_pad == 384, job1
    chunks = -(-c2.n_candidates // cand_block)
    assert c2.gram_chunks == (chunks if by_gram else 0), c2
    assert deeper and all(lv.gram_chunks == 0 for lv in deeper)


# -- fused-ladder layer -------------------------------------------------------
# The same adversarial DBs through the device-resident level ladder
# (gen -> encode -> count -> prune fused in one dispatch per level, with
# on-device trimming): every store's fused path must reproduce brute force
# exactly — supports included — like its per-wave path above.

def _assert_ladder_parity(db, n_items, store, trim):
    from repro.core import FrequentItemsetMiner, brute_force_frequent

    min_support = 0.2
    res = FrequentItemsetMiner(min_support=min_support, store=store,
                               device_loop=True, trim=trim).mine(db)
    want = brute_force_frequent(
        db, max(1, int(np.ceil(min_support * len(db)))))
    assert res.itemsets == want


@pytest.mark.parametrize("store", list(ARRAY_STORES))
@pytest.mark.parametrize("trim", [False, True])
@pytest.mark.parametrize("seed", [0, 2])
def test_ladder_parity_fixed_seeds(store, trim, seed):
    n_items, db = _random_db(seed)
    _assert_ladder_parity(db, n_items, store, trim)


@pytest.mark.parametrize("store", list(ARRAY_STORES))
@pytest.mark.parametrize("case", range(len(EDGE_DBS)))
def test_ladder_parity_edge_dbs(store, case):
    n_items, db = EDGE_DBS[case]
    _assert_ladder_parity(db, n_items, store, trim=True)


# -- hypothesis layer --------------------------------------------------------
if HAVE_HYPOTHESIS:

    @st.composite
    def _databases(draw):
        n_items = draw(st.integers(1, 16))
        base = draw(st.lists(
            st.lists(st.integers(0, n_items - 1), min_size=0, max_size=12),
            min_size=0, max_size=24))
        if base:  # duplicate whole transactions (support counts accumulate)
            dup_idx = draw(st.lists(st.integers(0, len(base) - 1),
                                    max_size=8))
            base = base + [list(base[i]) for i in dup_idx]
        return n_items, base

    @pytest.mark.parametrize("store", list(ARRAY_STORES))
    @given(db=_databases())
    @settings(max_examples=10, deadline=None)
    def test_property_store_parity(store, db):
        n_items, transactions = db
        _assert_store_parity(transactions, n_items, store)
