"""Unified job runtime: runner parity across backends, async double-buffered
wave determinism, candidate-axis sharding, executor-pooled SimRunner,
device-side Job1, degenerate DBs, checkpoint config stamp."""

import os

import jax
import numpy as np
import pytest

from repro.core import (
    CountJob,
    FrequentItemsetMiner,
    JobProfile,
    MapReduceEngine,
    brute_force_frequent,
    run_mapreduce_apriori,
)
from repro.core.itemsets import level_to_matrix
from repro.core.runtime import JaxRunner, ShardedRunner, SimRunner
from repro.core.runtime.runners import _chunks
from repro.core.sequential import SEQUENTIAL_STORES
from repro.core.stores import ARRAY_STORES, encode_db, pad_candidates
from repro.data import quest_generator
from repro.launch.mesh import compat_make_mesh

MIN_SUPPORT = 0.05


@pytest.fixture(scope="module")
def t10_db():
    """Small T10-style (Quest) twin: enough levels to exercise the loop."""
    return quest_generator(n_transactions=300, avg_transaction_len=8,
                           n_items=50, n_patterns=30, seed=3)


@pytest.fixture(scope="module")
def oracle(t10_db):
    return brute_force_frequent(t10_db, int(np.ceil(MIN_SUPPORT * len(t10_db))))


def _mesh():
    return compat_make_mesh((1,), ("data",))


# -- runner parity matrix --------------------------------------------------
@pytest.mark.parametrize("structure", list(SEQUENTIAL_STORES))
def test_parity_sim_runner(t10_db, oracle, structure):
    runner = SimRunner(structure=structure, n_mappers=3)
    res = FrequentItemsetMiner(min_support=MIN_SUPPORT, runner=runner).mine(t10_db)
    assert res.itemsets == oracle  # itemsets AND counts


@pytest.mark.parametrize("store", list(ARRAY_STORES))
@pytest.mark.parametrize("strategy", ["spc", "fpc", "dpc"])
def test_parity_jax_runner(t10_db, oracle, store, strategy):
    runner = JaxRunner(store=store)
    res = FrequentItemsetMiner(min_support=MIN_SUPPORT, strategy=strategy,
                               runner=runner).mine(t10_db)
    assert res.itemsets == oracle


@pytest.mark.parametrize("store", list(ARRAY_STORES))
def test_parity_sharded_runner(t10_db, oracle, store):
    runner = ShardedRunner(store=store, mesh=_mesh())
    res = FrequentItemsetMiner(min_support=MIN_SUPPORT, runner=runner).mine(t10_db)
    assert res.itemsets == oracle


RUNNER_MATRIX = ["sim-thread", "sim-process", "jax", "sharded-1d",
                 "sharded-2x4"]


@pytest.mark.parametrize("inflight", [0, 1, None])
@pytest.mark.parametrize("spec", RUNNER_MATRIX)
def test_runner_cross_product_parity(t10_db, oracle, spec, inflight):
    """The same seeded DB on every backend x inflight depth yields identical
    frequent-itemset sets AND supports — pins the shard-local encode +
    double-buffered encode/count pipeline as bit-identical end to end
    (cand_block=64 forces multi-chunk waves so the queues actually engage)."""
    if spec.startswith("sim"):
        if inflight != 1:
            pytest.skip("inflight applies to the engine-backed runners only")
        runner = SimRunner(structure="hash_table_trie", n_mappers=3,
                           executor=spec.split("-", 1)[1])
    elif spec == "jax":
        runner = JaxRunner(store="perfect_hash", cand_block=64,
                           inflight=inflight)
    elif spec == "sharded-1d":
        runner = ShardedRunner(store="packed_bitmap", mesh=_mesh(),
                               cand_block=64, inflight=inflight)
    else:  # sharded-2x4: candidate-axis sharding on the full 2-D grid
        if jax.device_count() < 8:
            pytest.skip(
                "needs XLA_FLAGS=--xla_force_host_platform_device_count=8")
        runner = ShardedRunner(store="packed_bitmap",
                               mesh=_mesh_2d(2, 4), cand_axes=("cand",),
                               cand_block=64, inflight=inflight)
    try:
        res = FrequentItemsetMiner(min_support=MIN_SUPPORT,
                                   runner=runner).mine(t10_db)
    finally:
        if isinstance(runner, SimRunner):
            runner.close()
    assert res.itemsets == oracle


def test_both_drivers_emit_job_profiles(t10_db):
    sim = run_mapreduce_apriori(t10_db, MIN_SUPPORT, structure="trie", n_mappers=3)
    jax_res = FrequentItemsetMiner(min_support=MIN_SUPPORT).mine(t10_db)
    assert all(isinstance(it, JobProfile) for it in sim.iterations)
    assert all(isinstance(lv, JobProfile) for lv in jax_res.levels)
    # The sim track keeps the max-mapper parallel-time model ...
    assert all(len(it.mapper_seconds) == 3 for it in sim.iterations)
    assert sim.parallel_seconds <= sim.sequential_seconds + 1e-9
    # ... and both report through the same per-phase schema.
    assert any(it.count_seconds > 0 for it in sim.iterations)
    assert any(lv.count_seconds > 0 for lv in jax_res.levels)


# -- mapper input splits ----------------------------------------------------
@pytest.mark.parametrize("n,m", [(5, 4), (2, 5), (7, 3), (0, 3), (12, 5),
                                 (1, 4), (8, 4)])
def test_chunks_fills_every_mapper_slot(n, m):
    """np.array_split semantics: exactly m splits, sizes differing by at most
    one, order preserved — the old ceil-size slicing dropped slots (5/4 -> 3
    chunks), skewing the max-mapper parallel model."""
    chunks = _chunks(list(range(n)), m)
    assert len(chunks) == m
    assert [x for c in chunks for x in c] == list(range(n))
    sizes = [len(c) for c in chunks]
    assert max(sizes) - min(sizes) <= 1


def test_sim_profiles_cover_every_mapper_slot():
    """The uneven-split regression end-to-end: 5 transactions over 4 mappers
    must still time 4 mapper slots in every job profile."""
    db = [[0, 1], [0, 1], [0, 2], [1, 2], [0, 1, 2]]
    runner = SimRunner(structure="trie", n_mappers=4)
    res = FrequentItemsetMiner(min_support=0.2, runner=runner).mine(db)
    assert res.itemsets == brute_force_frequent(db, 1)
    assert all(len(p.mapper_seconds) == 4 for p in res.levels)


# -- executor-pooled SimRunner ----------------------------------------------
@pytest.mark.parametrize("executor", ["thread", "process"])
def test_sim_runner_pool_matches_sequential(t10_db, oracle, executor):
    """Pooled mappers reproduce the sequential counts exactly (itemsets AND
    counts) and still report one wall clock per mapper slot."""
    runner = SimRunner(structure="trie", n_mappers=3, executor=executor)
    try:
        res = FrequentItemsetMiner(min_support=MIN_SUPPORT,
                                   runner=runner).mine(t10_db)
    finally:
        runner.close()
    assert res.itemsets == oracle
    assert all(len(p.mapper_seconds) == 3 for p in res.levels)
    assert "+" + executor in runner.describe()


def test_sim_runner_rejects_unknown_executor():
    with pytest.raises(ValueError, match="executor"):
        SimRunner(structure="trie", executor="celery")


def test_hadoop_sim_executor_passthrough(t10_db, oracle):
    res = run_mapreduce_apriori(t10_db, MIN_SUPPORT, structure="trie",
                                n_mappers=3, executor="thread")
    assert res.itemsets == oracle


# -- async double-buffered wave dispatch -----------------------------------
def _c2_wave(db, min_count: int = 5):
    """One C2 counting wave over ``db``: dense-remap the items, keep those
    with at least ``min_count`` baskets, and join them into candidate pairs.

    Returns ``(dense db, n_items, (C, 2) candidate matrix)``.
    """
    from collections import Counter

    from repro.core.itemsets import apriori_gen, sort_level

    items = sorted({i for t in db for i in t})
    remap = {it: i for i, it in enumerate(items)}
    db_dense = [[remap[i] for i in t] for t in db]
    c1 = Counter(i for t in db_dense for i in t)
    l1 = sort_level((i,) for i, c in c1.items() if c >= min_count)
    return db_dense, len(items), level_to_matrix(apriori_gen(l1))


def test_pipeline_determinism_engine(t10_db):
    """Counts are bit-identical at every inflight depth (0 == sync)."""
    dbd, n_items, mat = _c2_wave(t10_db)
    assert mat.shape[0] > 8
    enc = encode_db(dbd, n_items=n_items)
    ref = None
    for inflight in [0, 1, 2, 4]:
        engine = MapReduceEngine(store="packed_bitmap", cand_block=64,
                                 inflight=inflight)
        engine.place(enc)
        got = engine.count_candidates(mat)
        if ref is None:
            ref = got
        np.testing.assert_array_equal(got, ref)


@pytest.mark.parametrize("strategy", ["spc", "fpc"])
def test_pipeline_determinism_miner(t10_db, oracle, strategy):
    """Full mining results independent of the pipeline depth."""
    for inflight in [0, 1, 4]:
        runner = JaxRunner(store="perfect_hash", cand_block=64,
                           inflight=inflight)
        res = FrequentItemsetMiner(min_support=MIN_SUPPORT, strategy=strategy,
                                   runner=runner).mine(t10_db)
        assert res.itemsets == oracle


def test_pending_handles_survive_interleaving(t10_db):
    """Two waves dispatched before either is resolved still return correct
    counts (the FIFO resolves strictly in dispatch order)."""
    dbd, n_items, mat = _c2_wave(t10_db)
    engine = MapReduceEngine(store="perfect_hash", cand_block=32, inflight=4)
    engine.place(encode_db(dbd, n_items=n_items))
    sync = MapReduceEngine(store="perfect_hash")
    sync.place(encode_db(dbd, n_items=n_items))
    half = mat.shape[0] // 2
    p1 = engine.count_candidates_async(mat[:half])
    p2 = engine.count_candidates_async(mat[half:])
    # Resolve out of dispatch order on purpose.
    np.testing.assert_array_equal(p2.result(), sync.count_candidates(mat[half:]))
    np.testing.assert_array_equal(p1.result(), sync.count_candidates(mat[:half]))


def test_place_cancels_outstanding_pendings(t10_db):
    """Re-placing the DB voids in-flight handles loudly, not via IndexError."""
    dbd, n_items, mat = _c2_wave(t10_db)
    engine = MapReduceEngine(store="perfect_hash", cand_block=32, inflight=8)
    engine.place(encode_db(dbd, n_items=n_items))
    pending = engine.count_candidates_async(mat)
    engine.place(encode_db(dbd[: len(dbd) // 2], n_items=n_items))
    with pytest.raises(RuntimeError, match="cancelled"):
        pending.result()


@pytest.mark.parametrize("device_loop", [False, True])
def test_mine_frees_its_device_db_without_the_cyclic_collector(
        t10_db, oracle, device_loop):
    """A runner the miner builds is closed with the mine, so its placed DB
    leaves the device at once; a runner passed in stays open."""
    import gc

    FrequentItemsetMiner(min_support=MIN_SUPPORT,
                         device_loop=device_loop).mine(t10_db)  # warm-up
    gc.collect()
    before = len(jax.live_arrays())
    gc.disable()
    try:
        for _ in range(2):
            res = FrequentItemsetMiner(min_support=MIN_SUPPORT,
                                       device_loop=device_loop).mine(t10_db)
            assert res.itemsets == oracle
            assert len(jax.live_arrays()) == before
    finally:
        gc.enable()
    runner = JaxRunner()
    FrequentItemsetMiner(min_support=MIN_SUPPORT, runner=runner).mine(t10_db)
    res = FrequentItemsetMiner(min_support=MIN_SUPPORT,
                               runner=runner).mine(t10_db)
    assert res.itemsets == oracle and runner.engine._enc is not None


def test_miner_rejects_runner_plus_backend_config():
    with pytest.raises(ValueError, match="not both"):
        FrequentItemsetMiner(store="bitmap", runner=JaxRunner())
    with pytest.raises(ValueError, match="not both"):
        FrequentItemsetMiner(inflight=4, runner=SimRunner())


# -- device-side Job1 ------------------------------------------------------
def test_job1_device_matches_host(t10_db):
    runner = JaxRunner(store="perfect_hash")
    runner.ingest(t10_db)
    hist, prof = runner.job1()
    np.testing.assert_array_equal(
        hist, MapReduceEngine.count_items(t10_db, runner.n_raw_items))
    assert prof.k == 1 and prof.seconds >= 0


def test_job1_device_sharded(t10_db):
    runner = ShardedRunner(store="perfect_hash", mesh=_mesh())
    runner.ingest(t10_db)
    hist, _ = runner.job1()
    np.testing.assert_array_equal(
        hist, MapReduceEngine.count_items(t10_db, runner.n_raw_items))


# -- place() width clamp ----------------------------------------------------
def test_place_width_clamp_narrow_matrix():
    """place() on a dense matrix narrower than the 8-column lane clamp must
    slice only what exists — max(8, width) alone announced 8 columns while
    the slice silently delivered fewer."""
    runner = JaxRunner(store="perfect_hash")
    runner.ingest([[0], [0], [1]])
    runner._padded_raw = runner._padded_raw[:, :2]  # force the narrow edge
    runner.place(np.array([0, 1]))
    assert runner.engine._enc.padded.shape[1] == 2
    counts, _ = runner.count(CountJob(k=1, cand=np.array([[0], [1]], np.int32)))
    np.testing.assert_array_equal(counts, [2, 1])


@pytest.mark.parametrize("runner_idx", range(3))
def test_mine_single_item_db(runner_idx):
    """One distinct item total: the dense matrix is as narrow as it gets."""
    runner = _all_runners()[runner_idx]
    db = [[5]] * 4
    res = FrequentItemsetMiner(min_support=0.5, runner=runner).mine(db)
    assert res.itemsets == {(5,): 4}


# -- auto-sized inflight ----------------------------------------------------
def test_auto_inflight_tunes_and_records(t10_db, oracle):
    """inflight=None: the engine self-sizes the queue depth from the first
    steady-state chunk, results stay exact, and the chosen depth lands in
    the JobProfile rows."""
    runner = JaxRunner(store="packed_bitmap", cand_block=32, inflight=None)
    assert runner.engine.inflight_auto and runner.engine.inflight == 1
    res = FrequentItemsetMiner(min_support=MIN_SUPPORT,
                               runner=runner).mine(t10_db)
    assert res.itemsets == oracle
    # Tuned at least once (later waves may drift >2x and leave a re-tune
    # pending that never finds a clean sample chunk — that's fine).
    assert runner.engine._tuned_work is not None
    assert 1 <= runner.engine.inflight <= 8
    assert any(p.inflight_depth == runner.engine.inflight
               for p in res.levels if p.k > 1)


def test_auto_inflight_single_chunk_waves_stay_default(t10_db, oracle):
    """Waves that fit one cand_block never produce a clean sample; auto mode
    must behave exactly like the default depth (not degrade to sync)."""
    runner = JaxRunner(store="packed_bitmap", inflight=None)  # cand_block 32k
    res = FrequentItemsetMiner(min_support=MIN_SUPPORT,
                               runner=runner).mine(t10_db)
    assert res.itemsets == oracle
    assert not runner.engine._inflight_tuned
    assert runner.engine.inflight == 1  # classic double buffering throughout


def test_miner_inflight_none_means_auto():
    """inflight=None through the miner reaches the engine as auto-sizing —
    the same sentinel must not silently mean a fixed depth of 1."""
    auto = FrequentItemsetMiner(min_support=0.05, store="packed_bitmap",
                                inflight=None)._make_runner()
    assert auto.engine.inflight_auto
    fixed = FrequentItemsetMiner(min_support=0.05,
                                 store="packed_bitmap")._make_runner()
    assert not fixed.engine.inflight_auto and fixed.engine.inflight == 1


# -- mid-run depth re-tuning -------------------------------------------------
def test_inflight_retune_on_wave_shape_drift(t10_db):
    """inflight=None re-tunes the queue depth when a wave's *per-chunk*
    (C, k) work drifts more than 2x from the tuned shape, counts the
    re-tune in ``inflight_retunes``, and stays bit-identical through it.
    A wave whose C shrinks but still fills cand_block-sized chunks has
    identical chunk latency and must NOT pay a pipeline-draining re-tune."""
    import itertools

    dbd, n_items, mat = _c2_wave(t10_db)
    engine = MapReduceEngine(store="perfect_hash", cand_block=32,
                             inflight=None)
    engine.place(encode_db(dbd, n_items=n_items))
    sync = MapReduceEngine(store="perfect_hash")
    sync.place(encode_db(dbd, n_items=n_items))
    engine.count_candidates(mat)  # first clean sample tunes (k=2 chunks)
    assert engine._inflight_tuned and engine.inflight_retunes == 0
    engine.count_candidates(mat)  # same shape: no re-tune
    assert engine.inflight_retunes == 0
    fewer = mat[:96]  # C shrinks 2x+ but chunks stay full cand_block x k=2
    np.testing.assert_array_equal(engine.count_candidates(fewer),
                                  sync.count_candidates(fewer))
    assert engine.inflight_retunes == 0  # same chunk latency: no stall
    # k jump 2 -> 5: per-chunk work * 2.5, the depth model is stale.
    wide = level_to_matrix(list(itertools.islice(
        itertools.combinations(range(n_items), 5), 80)))
    np.testing.assert_array_equal(engine.count_candidates(wide),
                                  sync.count_candidates(wide))
    assert engine.inflight_retunes == 1
    small = mat[:16]  # drift back down; single chunk => no clean sample
    np.testing.assert_array_equal(engine.count_candidates(small),
                                  sync.count_candidates(small))
    # No clean second chunk in a single-chunk wave: the re-tune stays
    # pending, the counter must not advance.
    assert engine.inflight_retunes == 1
    assert 1 <= engine.inflight <= 8


def test_encode_ahead_determinism(t10_db):
    """Counts are bit-identical at every (inflight, encode_ahead) pairing —
    the encode-slot queue only reorders waiting, never arithmetic."""
    dbd, n_items, mat = _c2_wave(t10_db)
    enc = encode_db(dbd, n_items=n_items)
    ref = None
    for inflight in [0, 1, 3]:
        for ahead in [0, 1, 2, 4]:
            engine = MapReduceEngine(store="packed_bitmap", cand_block=64,
                                     inflight=inflight, encode_ahead=ahead)
            engine.place(enc)
            got = engine.count_candidates(mat)
            if ref is None:
                ref = got
            np.testing.assert_array_equal(got, ref)


# -- fuzzed PR 3 edge cases (explicit seeds) ---------------------------------
@pytest.mark.parametrize("seed", [11, 23, 47])
def test_chunks_fuzz_mappers_exceed_transactions(seed):
    """More mapper slots than transactions: every slot still represented,
    order preserved, and exactly m - n of them empty."""
    rng = np.random.default_rng(seed)
    for _ in range(25):
        n = int(rng.integers(0, 6))
        m = int(rng.integers(n + 1, 12))
        chunks = _chunks(list(range(n)), m)
        assert len(chunks) == m
        assert [x for c in chunks for x in c] == list(range(n))
        assert sum(len(c) == 0 for c in chunks) == m - n


@pytest.mark.parametrize("c,shards", [(0, 8), (1, 8), (3, 8), (5, 256),
                                      (127, 130)])
def test_pad_candidates_fewer_rows_than_shards(c, shards):
    """C < shards (and shards > the 128 alignment): the padded matrix still
    splits evenly over the cand axis and pads stay unmatchable."""
    cand = np.arange(c * 2, dtype=np.int32).reshape(c, 2)
    out = pad_candidates(cand, f_pad=512, shards=shards)
    assert out.shape[0] % shards == 0
    assert out.shape[0] >= c
    np.testing.assert_array_equal(out[:c], cand)
    assert (out[c:] == 511).all()


@pytest.mark.parametrize("seed", [5, 17])
def test_place_single_item_db_fuzz(seed):
    """Seeded single-item DBs through JaxRunner.place(): the dense matrix
    collapses to the minimum width and counting still works."""
    rng = np.random.default_rng(seed)
    item = int(rng.integers(0, 50))
    db = [[item] for _ in range(int(rng.integers(1, 20)))]
    runner = JaxRunner(store="perfect_hash")
    runner.ingest(db)
    runner.place(np.array([item], np.int64))
    counts, _ = runner.count(CountJob(k=1, cand=np.array([[0]], np.int32)))
    np.testing.assert_array_equal(counts, [len(db)])


@pytest.mark.parametrize("seed", [29, 31])
def test_place_all_infrequent_empty_item_map(seed):
    """All items infrequent: place() with an empty item_map must leave a
    countable (zero-item) DB instead of tripping on the width clamp."""
    rng = np.random.default_rng(seed)
    db = [[int(i)] for i in rng.permutation(30)]
    runner = JaxRunner(store="perfect_hash")
    runner.ingest(db)
    runner.place(np.array([], np.int64))
    counts, _ = runner.count(
        CountJob(k=1, cand=np.zeros((0, 1), np.int32)))
    assert counts.shape == (0,)


# -- candidate-axis sharding ------------------------------------------------
def _mesh_2d(n_data, n_cand):
    return compat_make_mesh((n_data, n_cand), ("data", "cand"))


def test_pad_candidates_shard_divisible():
    cand = np.arange(130 * 2, dtype=np.int32).reshape(130, 2)
    for shards in [1, 2, 3, 8]:
        out = pad_candidates(cand, f_pad=512, shards=shards)
        assert out.shape[0] % shards == 0
        np.testing.assert_array_equal(out[:130], cand)
        assert (out[130:] == 511).all()  # unmatchable pad rows


def test_cand_axes_requires_mesh():
    with pytest.raises(ValueError, match="cand_axes"):
        MapReduceEngine(store="perfect_hash", cand_axes=("cand",))


def test_engine_rejects_axes_missing_from_mesh():
    """Misconfiguration (cand_axes on a data-only mesh) fails at
    construction, not as a KeyError inside the first count."""
    mesh = compat_make_mesh((1,), ("data",))
    with pytest.raises(ValueError, match="missing"):
        MapReduceEngine(store="perfect_hash", mesh=mesh, cand_axes=("cand",))


@pytest.mark.parametrize("store", list(ARRAY_STORES))
def test_cand_sharding_trivial_mesh_bit_identical(t10_db, store):
    """The cand-sharded code path (specs, padding, out_specs stitching) on a
    1x1 mesh reproduces the single-device counts bit-for-bit."""
    dbd, n_items, mat = _c2_wave(t10_db)
    ref = MapReduceEngine(store=store)
    ref.place(encode_db(dbd, n_items=n_items))
    eng = MapReduceEngine(store=store, mesh=_mesh_2d(1, 1),
                          data_axes=("data",), cand_axes=("cand",))
    eng.place(encode_db(dbd, n_items=n_items))
    np.testing.assert_array_equal(eng.count_candidates(mat),
                                  ref.count_candidates(mat))


needs_8_devices = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8",
)


@needs_8_devices
@pytest.mark.parametrize("store", list(ARRAY_STORES))
def test_cand_sharding_2x4_bit_identical(t10_db, store):
    """Acceptance: candidate-axis sharded counts on a 2x4 data x cand mesh
    are bit-identical to the replicated path, for every store layout
    (row-major, word-major transposed, k-hot)."""
    dbd, n_items, mat = _c2_wave(t10_db)
    enc = encode_db(dbd, n_items=n_items)
    rep = MapReduceEngine(store=store, mesh=_mesh_2d(8, 1),
                          data_axes=("data",))
    rep.place(enc)
    shd = MapReduceEngine(store=store, mesh=_mesh_2d(2, 4),
                          data_axes=("data",), cand_axes=("cand",),
                          cand_block=64, inflight=2)
    shd.place(enc)
    single = MapReduceEngine(store=store)
    single.place(enc)
    expect = single.count_candidates(mat)
    np.testing.assert_array_equal(rep.count_candidates(mat), expect)
    np.testing.assert_array_equal(shd.count_candidates(mat), expect)


@needs_8_devices
def test_shard_local_encode_partitions_candidates(t10_db):
    """The encoded candidate tensors of a cand-sharded engine come out of
    the encode shard_map *partitioned* over cand (each device encoded only
    its own C/4 rows) — not replicated then resharded."""
    dbd, n_items, mat = _c2_wave(t10_db)
    eng = MapReduceEngine(store="bitmap", mesh=_mesh_2d(2, 4),
                          data_axes=("data",), cand_axes=("cand",))
    eng.place(encode_db(dbd, n_items=n_items))
    cands = eng._dispatch_encode(mat[:64])
    khot = cands["khot"]
    assert not khot.sharding.is_fully_replicated
    assert {s.data.shape[0] for s in khot.addressable_shards} \
        == {khot.shape[0] // 4}
    kvec = cands["kvec"]
    assert {s.data.shape[0] for s in kvec.addressable_shards} \
        == {kvec.shape[0] // 4}


def test_make_data_cand_mesh_rejects_oversubscription():
    from repro.launch.mesh import make_data_cand_mesh

    with pytest.raises(ValueError, match="devices"):
        make_data_cand_mesh(jax.device_count() * 2, 2)


@needs_8_devices
@pytest.mark.parametrize("store", ["packed_bitmap", "perfect_hash"])
def test_cand_sharding_2x4_miner_parity(t10_db, oracle, store):
    runner = ShardedRunner(store=store, mesh=_mesh_2d(2, 4),
                           cand_axes=("cand",))
    assert "c4" in runner.describe()
    res = FrequentItemsetMiner(min_support=MIN_SUPPORT,
                               runner=runner).mine(t10_db)
    assert res.itemsets == oracle


# -- degenerate databases --------------------------------------------------
def _all_runners():
    return [
        SimRunner(structure="trie", n_mappers=4),
        JaxRunner(store="bitmap"),
        ShardedRunner(store="perfect_hash", mesh=_mesh()),
    ]


@pytest.mark.parametrize("runner_idx", range(3))
def test_mine_empty_db(runner_idx):
    runner = _all_runners()[runner_idx]
    res = FrequentItemsetMiner(min_support=0.1, runner=runner).mine([])
    assert res.itemsets == {}
    assert res.n_transactions == 0


@pytest.mark.parametrize("runner_idx", range(3))
def test_mine_all_infrequent(runner_idx):
    """Every item unique: nothing survives Job1, the level loop is a no-op."""
    db = [[i] for i in range(40)]
    runner = _all_runners()[runner_idx]
    res = FrequentItemsetMiner(min_support=0.5, runner=runner).mine(db)
    assert res.itemsets == {}


def test_engine_empty_db_zero_counts():
    """A placed DB with no transactions counts everything as zero (the old
    code divided by a zero block_n here)."""
    engine = MapReduceEngine(store="bitmap")
    engine.place(encode_db([], n_items=4))
    got = engine.count_candidates(level_to_matrix([(0, 1), (2, 3)]))
    np.testing.assert_array_equal(got, [0, 0])


def test_hadoop_sim_empty_db():
    res = run_mapreduce_apriori([], 0.1, structure="trie", n_mappers=4)
    assert res.itemsets == {}


@pytest.mark.parametrize("strategy", ["fpc", "dpc"])
def test_checkpoint_restore_combined_strategy(tmp_path, t10_db, oracle, strategy):
    """Combined waves yield mixed-k itemsets; the checkpointed level must
    stay a rectangular top-k matrix so a same-config restart restores."""
    d = str(tmp_path)
    m = FrequentItemsetMiner(min_support=MIN_SUPPORT, strategy=strategy,
                             checkpoint_dir=d)
    assert m.mine(t10_db).itemsets == oracle
    m2 = FrequentItemsetMiner(min_support=MIN_SUPPORT, strategy=strategy,
                              checkpoint_dir=d)
    min_count = max(1, int(np.ceil(MIN_SUPPORT * len(t10_db))))
    assert m2._try_restore(len(t10_db), min_count,
                           m2._config(m2._make_runner())) is not None
    assert m2.mine(t10_db).itemsets == oracle  # restores, does not crash


# -- checkpoint config aliasing --------------------------------------------
def test_checkpoint_rejects_mismatched_config(tmp_path, t10_db, oracle):
    d = str(tmp_path)
    m = FrequentItemsetMiner(min_support=MIN_SUPPORT, store="perfect_hash",
                             checkpoint_dir=d)
    r1 = m.mine(t10_db)
    assert r1.itemsets == oracle
    n, mc = len(t10_db), r1.min_count

    # Same config restores ...
    same = FrequentItemsetMiner(min_support=MIN_SUPPORT, store="perfect_hash",
                                checkpoint_dir=d)
    assert same._try_restore(n, mc, same._config(same._make_runner())) is not None

    # ... different store / strategy / max_k / runner kind must NOT resume.
    for other in [
        FrequentItemsetMiner(min_support=MIN_SUPPORT, store="bitmap",
                             checkpoint_dir=d),
        FrequentItemsetMiner(min_support=MIN_SUPPORT, strategy="fpc",
                             checkpoint_dir=d),
        FrequentItemsetMiner(min_support=MIN_SUPPORT, max_k=3,
                             checkpoint_dir=d),
        FrequentItemsetMiner(min_support=MIN_SUPPORT, checkpoint_dir=d,
                             runner=SimRunner(structure="trie")),
        FrequentItemsetMiner(min_support=MIN_SUPPORT, checkpoint_dir=d,
                             device_loop=True),  # fused loop != host loop
    ]:
        assert other._try_restore(n, mc, other._config(other._make_runner())) \
            is None
        res = other.mine(t10_db)  # recomputes from scratch, still correct
        if other.max_k >= r1.max_k:
            assert res.itemsets == oracle


# -- device-resident level ladder (fused gen->encode->count->prune) ----------
def _deep_db():
    """Correlated DB with n_items > 128 so trimming can shrink every padded
    dimension (N_pad, F_pad, row width) and the ladder runs to k >= 4."""
    rng = np.random.default_rng(11)
    n_items = 300
    pats = [sorted(rng.choice(n_items, size=5, replace=False))
            for _ in range(4)]
    db = []
    for _ in range(400):
        t = set()
        if rng.random() < 0.5:
            t |= set(pats[rng.integers(4)])
        t |= set(rng.choice(n_items, size=rng.integers(2, 8)).tolist())
        db.append(sorted(t))
    return db


@pytest.fixture(scope="module")
def deep_db():
    return _deep_db()


@pytest.fixture(scope="module")
def deep_oracle(deep_db):
    return brute_force_frequent(deep_db, int(np.ceil(0.08 * len(deep_db))))


def _mined_levels(t10_db):
    """Real level matrices (dense ids) from a host-loop mine, per k."""
    res = FrequentItemsetMiner(min_support=MIN_SUPPORT).mine(t10_db)
    remap = {int(orig): dense for dense, orig in enumerate(res.item_map)}
    out = []
    for k in sorted({len(s) for s in res.itemsets}):
        out.append(level_to_matrix(sorted(
            tuple(remap[i] for i in s)
            for s in res.itemsets if len(s) == k)))
    return out


def test_device_gen_matches_host_on_mined_levels(t10_db):
    """jit-able join+prune == apriori_gen_matrix row-for-row (same lex
    order, same dtype) on every level a real mine produces, plus edges."""
    from repro.core.itemsets import apriori_gen_matrix
    from repro.core.runtime import apriori_gen_device

    levels = _mined_levels(t10_db)
    assert levels, "fixture mined nothing"
    cases = levels + [np.zeros((0, 2), np.int32), levels[0][:1]]
    for lvl in cases:
        want = apriori_gen_matrix(lvl)
        got = apriori_gen_device(lvl)
        assert got.dtype == np.int32 and got.shape == want.shape
        np.testing.assert_array_equal(got, want)


def test_device_filter_matches_host(t10_db):
    """filter_candidates_device == filter_candidates_matrix (same rows, same
    order) on speculative SPC waves, including keep-none and keep-all."""
    from repro.core.itemsets import apriori_gen_matrix, \
        filter_candidates_matrix
    from repro.core.runtime import filter_candidates_device

    for lvl in _mined_levels(t10_db):
        cand = apriori_gen_matrix(lvl)
        if not cand.size:
            continue
        spec = apriori_gen_matrix(cand)
        for freq in [cand, cand[::2], cand[:0]]:
            want = filter_candidates_matrix(spec, freq)
            got = filter_candidates_device(spec, freq)
            assert got.shape == want.shape
            np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("budget", [0, 1 << 28])
def test_spc_speculation_bound_keeps_results(t10_db, oracle, monkeypatch,
                                             budget):
    """A level whose cut-back could outgrow SPECULATION_BYTES is generated
    after its count instead of speculated during it; either way the mine is
    exact."""
    from repro.core.itemsets import filter_candidates_matrix
    from repro.core.runtime import strategies

    monkeypatch.setattr(strategies, "SPECULATION_BYTES", budget)
    calls = []
    monkeypatch.setattr(JaxRunner, "filter_candidates",
                        lambda self, spec, lvl: calls.append(1) or
                        filter_candidates_matrix(spec, lvl))
    res = FrequentItemsetMiner(min_support=MIN_SUPPORT,
                               runner=JaxRunner()).mine(t10_db)
    assert res.itemsets == oracle
    assert bool(calls) == (budget > 0)


@pytest.mark.parametrize("store", list(ARRAY_STORES))
@pytest.mark.parametrize("trim", [False, True])
def test_ladder_parity_all_stores(t10_db, oracle, store, trim):
    """Fused ladder == host loop == brute force: itemsets AND supports, for
    every array store, trim on and off."""
    res = FrequentItemsetMiner(min_support=MIN_SUPPORT, store=store,
                               device_loop=True, trim=trim).mine(t10_db)
    assert res.itemsets == oracle


@pytest.mark.parametrize("trim", [False, True])
def test_ladder_parity_sharded_1d(deep_db, deep_oracle, trim):
    runner = ShardedRunner(store="perfect_hash", mesh=_mesh())
    res = FrequentItemsetMiner(min_support=0.08, runner=runner,
                               device_loop=True, trim=trim).mine(deep_db)
    assert res.itemsets == deep_oracle


@needs_8_devices
@pytest.mark.parametrize("store", ["packed_bitmap", "perfect_hash"])
def test_ladder_parity_sharded_2x4(deep_db, deep_oracle, store):
    """Fused + trimmed ladder on the full 2-D data x cand grid: the trim
    re-compaction must stay bit-identical under candidate-axis sharding."""
    runner = ShardedRunner(store=store, mesh=_mesh_2d(2, 4),
                           cand_axes=("cand",))
    res = FrequentItemsetMiner(min_support=0.08, runner=runner,
                               device_loop=True, trim=True).mine(deep_db)
    assert res.itemsets == deep_oracle


def test_ladder_trim_shrinks_monotonically(deep_db, deep_oracle):
    """Trimming must shrink: N_pad and F_pad non-increasing with k, with an
    actual strict shrink somewhere (the DB is built to die off), while
    results stay bit-identical to the untrimmed ladder and the oracle."""
    mined = FrequentItemsetMiner(min_support=0.08, store="packed_bitmap",
                                 device_loop=True, trim=True).mine(deep_db)
    assert mined.itemsets == deep_oracle
    pads = [(p.n_pad, p.f_pad) for p in mined.levels if p.n_pad]
    assert len(pads) >= 3
    assert all(a >= b for (a, _), (b, _) in zip(pads, pads[1:]))
    assert all(a >= b for (_, a), (_, b) in zip(pads, pads[1:]))
    assert pads[-1][0] < pads[0][0]  # transactions really died off
    untrimmed = FrequentItemsetMiner(min_support=0.08, store="packed_bitmap",
                                     device_loop=True, trim=False
                                     ).mine(deep_db)
    assert untrimmed.itemsets == mined.itemsets
    upads = [(p.n_pad, p.f_pad) for p in untrimmed.levels if p.n_pad]
    assert len(set(upads)) == 1  # untrimmed: dims never move


def test_ladder_rejects_sim_runner():
    from repro.core.runtime import ladder

    with pytest.raises(ValueError, match="oracle"):
        next(ladder(SimRunner(structure="trie"),
                    np.zeros((2, 1), np.int32), 1, start_k=2, max_k=4))


def test_miner_rejects_device_loop_with_combined_strategy():
    with pytest.raises(ValueError, match="device_loop"):
        FrequentItemsetMiner(strategy="fpc", device_loop=True)


def test_ladder_mid_run_restore_parity(tmp_path, deep_db, deep_oracle):
    """Crash-and-resume mid-ladder: delete the newest snapshots, resume from
    an earlier level, and the resumed run must reproduce the uninterrupted
    run bit-identically — itemsets, supports, AND the per-level trimmed
    (n_pad, f_pad) dims of the re-run levels (the one-shot entry trim from
    the restored level equals the cumulative trims it replaces)."""
    import shutil

    d = str(tmp_path)
    full = FrequentItemsetMiner(min_support=0.08, store="perfect_hash",
                                device_loop=True, trim=True,
                                checkpoint_dir=d).mine(deep_db)
    assert full.itemsets == deep_oracle
    pads_full = {p.k: (p.n_pad, p.f_pad) for p in full.levels if p.n_pad}
    steps = sorted(int(n.split("_")[1]) for n in os.listdir(d)
                   if n.startswith("step_") and "." not in n)
    assert len(steps) >= 2
    resume_step = steps[0]  # keep only the oldest surviving snapshot
    for s in steps[1:]:
        shutil.rmtree(os.path.join(d, f"step_{s:08d}"))
    os.remove(os.path.join(d, "LATEST"))
    m2 = FrequentItemsetMiner(min_support=0.08, store="perfect_hash",
                              device_loop=True, trim=True, checkpoint_dir=d)
    min_count = max(1, int(np.ceil(0.08 * len(deep_db))))
    state = m2._try_restore(len(deep_db), min_count,
                            m2._config(m2._make_runner()))
    assert state is not None and state[3] == resume_step  # resumes mid-run
    resumed = m2.mine(deep_db)
    assert resumed.itemsets == full.itemsets  # itemsets AND supports
    pads_resumed = {p.k: (p.n_pad, p.f_pad)
                    for p in resumed.levels if p.n_pad}
    for k in pads_resumed:  # re-run levels: identical trimmed dims
        assert pads_resumed[k] == pads_full[k], k


# -- encoded-dataset cache ---------------------------------------------------
def test_dataset_cache_hit_across_runners(t10_db, oracle):
    """Two runners over the same (DB, store, item_map) share one encode."""
    from repro.core.runtime import DATASET_CACHE

    DATASET_CACHE.clear()
    r1 = FrequentItemsetMiner(min_support=MIN_SUPPORT,
                              runner=JaxRunner(store="perfect_hash"))
    assert r1.mine(t10_db).itemsets == oracle
    assert DATASET_CACHE.stats()["misses"] == 1
    r2 = FrequentItemsetMiner(min_support=MIN_SUPPORT,
                              runner=JaxRunner(store="perfect_hash"))
    assert r2.mine(t10_db).itemsets == oracle
    stats = DATASET_CACHE.stats()
    assert stats["hits"] == 1 and stats["misses"] == 1
    DATASET_CACHE.clear()


def test_dataset_cache_key_sensitivity(t10_db):
    """A different store, DB, or item_map must miss, never alias."""
    from repro.core.runtime import DATASET_CACHE

    DATASET_CACHE.clear()
    FrequentItemsetMiner(min_support=MIN_SUPPORT,
                         store="perfect_hash").mine(t10_db)
    FrequentItemsetMiner(min_support=MIN_SUPPORT,
                         store="sorted_prefix").mine(t10_db)  # new store
    FrequentItemsetMiner(min_support=0.2,
                         store="perfect_hash").mine(t10_db)  # new item_map
    FrequentItemsetMiner(min_support=MIN_SUPPORT,
                         store="perfect_hash").mine(t10_db[:200])  # new DB
    assert DATASET_CACHE.stats()["misses"] == 4
    DATASET_CACHE.clear()


def test_dataset_cache_lru_eviction():
    from repro.core.runtime import EncodedDatasetCache

    cache = EncodedDatasetCache(max_entries=2)
    assert cache.get_or_build("a", lambda: 1) == 1
    assert cache.get_or_build("b", lambda: 2) == 2
    assert cache.get_or_build("a", lambda: -1) == 1   # hit, refreshes a
    assert cache.get_or_build("c", lambda: 3) == 3    # evicts b
    assert cache.get_or_build("b", lambda: 9) == 9    # rebuilt: was evicted
    assert cache.stats() == {"hits": 1, "misses": 4, "entries": 2}


def test_dataset_cache_concurrent_same_key():
    """Two runners placing the same digest simultaneously: both builders may
    race (they run outside the lock by design), last insert wins, and every
    caller gets a usable, equal value — never an error or a partial entry."""
    import threading

    from repro.core.runtime import EncodedDatasetCache

    cache = EncodedDatasetCache(max_entries=4)
    barrier = threading.Barrier(2)
    built = []
    results = [None, None]

    def builder():
        barrier.wait()          # force both misses into the build phase
        built.append(threading.get_ident())
        return ("encoded", 42)  # equal values, as real encodes are

    def worker(i):
        results[i] = cache.get_or_build("digest", builder)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert results[0] == results[1] == ("encoded", 42)
    assert len(built) == 2                      # both raced, by design
    stats = cache.stats()
    assert stats["misses"] == 2 and stats["entries"] == 1  # last insert wins
    # The surviving entry serves subsequent lookups.
    assert cache.get_or_build("digest", lambda: "nope") == ("encoded", 42)


def test_dataset_cache_eviction_while_in_use():
    """LRU eviction only drops the cache's reference: a runner still holding
    an evicted entry keeps using it safely, and a re-request rebuilds a
    fresh, equal entry instead of resurrecting the evicted object."""
    import numpy as np

    from repro.core.runtime import EncodedDatasetCache

    cache = EncodedDatasetCache(max_entries=1)
    build = lambda: np.arange(8)
    held = cache.get_or_build("a", build)       # runner A holds this
    cache.get_or_build("b", lambda: "other")    # evicts "a" while A mines
    assert cache.stats()["entries"] == 1
    assert np.array_equal(held, np.arange(8))   # A's reference is unharmed
    rebuilt = cache.get_or_build("a", build)    # B re-places the same digest
    assert rebuilt is not held                  # fresh build, not the old ref
    assert np.array_equal(rebuilt, held)        # ... but identical content


def test_dataset_cache_stats_coherent_under_hammer():
    """Stats stay coherent under a concurrent hit/miss storm: every call is
    classified exactly once (hits + misses == calls) and LRU eviction keeps
    the entry count bounded, with more keys in play than cache slots so
    evict/rebuild churn runs the whole time."""
    import threading

    from repro.core.runtime import EncodedDatasetCache

    n_threads, n_iter, n_keys = 8, 200, 6
    cache = EncodedDatasetCache(max_entries=4)
    barrier = threading.Barrier(n_threads)
    errors = []

    def worker(tid):
        try:
            barrier.wait()
            for i in range(n_iter):
                key = (tid + i) % n_keys
                value = cache.get_or_build(key, lambda k=key: ("enc", k))
                assert value == ("enc", key)
        except Exception as exc:  # pragma: no cover - surfaced below
            errors.append(exc)

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    stats = cache.stats()
    assert stats["hits"] + stats["misses"] == n_threads * n_iter
    assert stats["hits"] > 0 and stats["misses"] > 0
    assert stats["entries"] <= 4
