"""Streaming mining service: slot lifecycle, delta-count exactness, and the
mid-stream parity anchor — any seeded ingest/evict sequence must serve
queries bit-identical (itemsets AND supports) to a fresh batch mine over
the exact current window, across stores and backends."""

import time

import numpy as np
import pytest

from repro.core import FrequentItemsetMiner
from repro.core.runtime import JaxRunner, ShardedRunner, SimRunner
from repro.core.stores import ARRAY_STORES
from repro.data import ArrivalBatch, basket_stream
from repro.launch.mesh import compat_make_mesh
from repro.serve import (
    ErrorCertificate,
    IngestReport,
    MiningService,
    ServeResult,
)


def _batches(rng, n_batches, size, n_items=36, max_len=7):
    """Seeded arrival batches of unique-sorted baskets."""
    out = []
    for _ in range(n_batches):
        out.append([
            sorted(set(rng.integers(0, n_items,
                                    size=rng.integers(2, max_len)).tolist()))
            for _ in range(size)])
    return out


def _oracle(window, min_support, max_k):
    return FrequentItemsetMiner(min_support=min_support, store="perfect_hash",
                                max_k=max_k).mine(window).itemsets


def _support(window, itemset):
    """Exact support of one itemset over the window (ground truth)."""
    s = set(itemset)
    return sum(1 for t in window if s <= set(t))


def _validate_certificate(window, ms, max_k, res):
    """Pin a (possibly stale) answer against the exact recount: every
    reported support within max_drift of truth, every missed frequent
    itemset below miss_bound, and exactness whenever the bound says so."""
    cert = res.certificate
    assert isinstance(cert, ErrorCertificate)
    oracle = _oracle(window, ms, max_k)
    for itemset, c in res.itemsets.items():
        drift = abs(c - _support(window, itemset))
        assert drift <= cert.max_drift, (itemset, drift, cert)
    for itemset, exact in oracle.items():
        if itemset not in res.itemsets:
            assert exact < cert.miss_bound, (itemset, exact, cert)
    if cert.is_exact(res.min_count):
        assert res.itemsets == oracle


# -- parity anchor -----------------------------------------------------------
@pytest.mark.parametrize("store", list(ARRAY_STORES))
def test_midstream_parity_across_stores(store):
    """Every query along a seeded ingest/evict stream equals a fresh batch
    mine over the exact current window — per store."""
    rng = np.random.default_rng(hash(store) % (2**32))
    svc = MiningService(min_support=0.06, store=store, n_slots=5,
                        slot_size=40, staleness=0.5, max_k=6)
    for batch in _batches(rng, 6, 60):
        svc.ingest(batch)
        res = svc.query()
        assert res.itemsets == _oracle(svc.window(), 0.06, 6), store
    svc.close()


def test_midstream_parity_sharded():
    runner = ShardedRunner(store="packed_bitmap",
                           mesh=compat_make_mesh((1,), ("data",)))
    rng = np.random.default_rng(5)
    svc = MiningService(min_support=0.06, runner=runner, n_slots=4,
                        slot_size=32, staleness=0.5, max_k=6)
    for batch in _batches(rng, 5, 48):
        svc.ingest(batch)
        res = svc.query()
        assert res.itemsets == _oracle(svc.window(), 0.06, 6)
    svc.close()


@pytest.mark.parametrize("device_loop,trim", [(True, True), (True, False)])
def test_midstream_parity_ladder_refresh(device_loop, trim):
    """Ladder-mode refresh (fused level loop + negative-border waves) serves
    the same answers as the host-SPC refresh and the batch miner."""
    rng = np.random.default_rng(9)
    svc = MiningService(min_support=0.08, store="sorted_prefix", n_slots=6,
                        slot_size=32, staleness=0.5, max_k=6,
                        device_loop=device_loop, trim=trim)
    for batch in _batches(rng, 6, 40, n_items=28, max_len=6):
        svc.ingest(batch)
        res = svc.query()
        assert res.itemsets == _oracle(svc.window(), 0.08, 6)
    svc.close()


def test_delta_served_queries_are_exact():
    """With churn below the staleness threshold, queries are served from the
    delta-maintained lattice (no refresh) and still match the batch miner —
    the tentpole's correctness anchor."""
    rng = np.random.default_rng(3)
    svc = MiningService(min_support=0.08, store="perfect_hash", n_slots=12,
                        slot_size=32, staleness=0.6, max_k=6)
    svc.ingest([t for b in _batches(rng, 12, 32, n_items=24) for t in b])
    svc.query()                      # cold refresh builds the lattice
    delta_served = 0
    for batch in _batches(rng, 8, 32, n_items=24):
        svc.ingest(batch)            # one slot churn per step
        res = svc.query()
        assert res.itemsets == _oracle(svc.window(), 0.08, 6)
        delta_served += 0 if res.refreshed else 1
    assert delta_served > 0, "staleness policy never exercised the delta path"
    svc.close()


def test_query_at_other_thresholds_is_exact():
    """Exact counts + the standard gen closure make any query threshold
    exact — including thresholds looser or tighter than the service's."""
    rng = np.random.default_rng(17)
    svc = MiningService(min_support=0.08, store="perfect_hash", n_slots=8,
                        slot_size=32, staleness=0.6, max_k=6)
    svc.ingest([t for b in _batches(rng, 8, 32, n_items=24) for t in b])
    svc.query()
    svc.ingest(_batches(rng, 1, 32, n_items=24)[0])
    for ms in (0.12, 0.08, 0.06):
        res = svc.query(min_support=ms)
        assert res.itemsets == _oracle(svc.window(), ms, 6), ms
    svc.close()


# -- slot lifecycle ----------------------------------------------------------
def test_slot_ring_eviction_and_window():
    svc = MiningService(min_support=0.5, store="perfect_hash", n_slots=3,
                        slot_size=4)
    first = [[1, 2], [2, 3], [1, 3], [1, 2, 3]]
    rep = svc.ingest(first)
    assert isinstance(rep, IngestReport)
    assert (rep.n_ingested, rep.n_evicted, rep.n_slots) == (4, 0, 1)
    svc.ingest([[4, 5]] * 4)
    svc.ingest([[6, 7]] * 4)
    assert svc.window_size == 12 and svc.window()[:4] == first
    rep = svc.ingest([[8, 9]] * 4)   # ring full: oldest slot leaves whole
    assert (rep.n_evicted, rep.n_slots) == (4, 3)
    assert svc.window_size == 12
    assert svc.window()[0] == [4, 5] and svc.window()[-1] == [8, 9]
    svc.close()


def test_oversized_batch_splits_into_slots():
    svc = MiningService(min_support=0.5, store="perfect_hash", n_slots=4,
                        slot_size=8)
    rep = svc.ingest([[1, 2]] * 20)  # 2.5 slots in one call
    assert rep.n_slots == 3 and svc.window_size == 20
    rep = svc.ingest([[3, 4]] * 20)  # wraps: evicts 2 full + 1 partial slot
    assert rep.n_slots == 4 and svc.window_size <= 4 * 8
    res = svc.query()
    assert res.itemsets == _oracle(svc.window(), 0.5, 16)
    svc.close()


def test_empty_window_query():
    svc = MiningService(min_support=0.1, store="perfect_hash")
    res = svc.query()
    assert isinstance(res, ServeResult)
    assert res.itemsets == {} and res.n_transactions == 0
    svc.close()


def test_stats_and_result_fields():
    rng = np.random.default_rng(0)
    svc = MiningService(min_support=0.1, store="perfect_hash", n_slots=4,
                        slot_size=16)
    svc.ingest(_batches(rng, 1, 24)[0])
    res = svc.query()
    assert res.refreshed and res.stale_reason == "cold"
    assert res.frequent_at(1) and all(
        len(s) == 1 for s in res.frequent_at(1))
    st = svc.stats()
    assert st["window"] == 24 and st["refreshes"] == 1
    assert st["tracked_candidates"] >= 0
    svc.close()


# -- backend gating ----------------------------------------------------------
def test_sim_runner_rejected():
    """The cost-model backend has no resident device state: loud error, not
    a silent fallback."""
    with pytest.raises(ValueError, match="engine-backed"):
        MiningService(runner=SimRunner(structure="trie"))
    with pytest.raises(NotImplementedError, match="resident-session"):
        SimRunner(structure="trie").count_block_async(None, np.zeros((1, 1)))


def test_runner_and_store_args_conflict():
    with pytest.raises(ValueError, match="not both"):
        MiningService(runner=JaxRunner(store="perfect_hash"),
                      store="perfect_hash")


# -- per-basket eviction -----------------------------------------------------
@pytest.mark.parametrize("store", list(ARRAY_STORES))
def test_per_basket_eviction_parity_across_stores(store):
    """Basket-granular eviction (overflow + explicit evict) keeps every
    query bit-identical to a batch mine of the exact current window."""
    rng = np.random.default_rng((hash(store) + 1) % (2**32))
    svc = MiningService(min_support=0.08, store=store, n_slots=4,
                        slot_size=24, eviction="basket", staleness=0.5,
                        max_k=5)
    for batch in _batches(rng, 5, 30, n_items=20, max_len=6):
        svc.ingest(batch)            # overflow leaves per basket
        svc.evict(3)                 # plus explicit sub-slot evictions
        res = svc.query()
        assert res.itemsets == _oracle(svc.window(), 0.08, 5), store
    assert svc.window_size <= 4 * 24
    svc.close()


def test_per_basket_eviction_sharded():
    runner = ShardedRunner(store="bitmap",
                           mesh=compat_make_mesh((1,), ("data",)))
    rng = np.random.default_rng(21)
    svc = MiningService(min_support=0.08, runner=runner, n_slots=3,
                        slot_size=24, eviction="basket", max_k=5)
    for batch in _batches(rng, 4, 28, n_items=20, max_len=6):
        svc.ingest(batch)
        svc.evict(2)
        res = svc.query()
        assert res.itemsets == _oracle(svc.window(), 0.08, 5)
    svc.close()


def test_per_basket_eviction_ladder_refresh():
    svc = MiningService(min_support=0.08, store="sorted_prefix", n_slots=3,
                        slot_size=24, eviction="basket", max_k=5,
                        device_loop=True, trim=True)
    rng = np.random.default_rng(22)
    for batch in _batches(rng, 4, 28, n_items=20, max_len=6):
        svc.ingest(batch)
        svc.evict(2)
        res = svc.query()
        assert res.itemsets == _oracle(svc.window(), 0.08, 5)
    svc.close()


def test_evict_single_basket_is_one_row_delta():
    """evict(1) uncounts a one-row block — the finest delta granularity —
    and the delta-served answer still matches the batch miner."""
    svc = MiningService(min_support=0.25, store="perfect_hash", n_slots=2,
                        slot_size=8, eviction="basket")
    svc.ingest([[0, 1], [1, 2], [0, 2], [0, 1, 2]] * 2)
    svc.query()
    jobs0 = svc.delta_jobs
    delta_served = 0
    for _ in range(3):
        rep = svc.evict(1)
        assert rep.n_evicted == 1 and rep.n_ingested == 0
        res = svc.query()
        assert res.itemsets == _oracle(svc.window(), 0.25, 16)
        delta_served += 0 if res.refreshed else 1
    assert svc.delta_jobs > jobs0, "evictions dispatched no signed deltas"
    assert delta_served > 0, "every post-evict query escaped to a refresh"
    svc.close()


def test_evict_to_empty_window_then_refill():
    """Evicting the only slot empties the window exactly; refilling recovers
    full parity."""
    svc = MiningService(min_support=0.3, store="packed_bitmap", n_slots=3,
                        slot_size=4, eviction="basket")
    svc.ingest([[1, 2], [2, 3], [1, 3], [1, 2, 3]])
    svc.query()
    rep = svc.evict(4)
    assert rep.n_evicted == 4 and svc.window_size == 0
    res = svc.query()
    assert res.itemsets == {} and res.n_transactions == 0
    svc.ingest([[4, 5], [4, 5], [5, 6], [4, 5, 6]])
    res = svc.query()
    assert res.itemsets == _oracle(svc.window(), 0.3, 16)
    svc.close()


# -- delta-path edge cases ---------------------------------------------------
def test_all_empty_transaction_blocks():
    """A whole slot of empty baskets is an exact no-op on every count."""
    svc = MiningService(min_support=0.3, store="bitmap", n_slots=4,
                        slot_size=8)
    svc.ingest([[1, 2], [2, 3], [1, 2, 3], [1, 3]] * 2)
    svc.query()
    svc.ingest([[]] * 8)
    res = svc.query()
    assert res.itemsets == _oracle(svc.window(), 0.3, 16)
    assert svc.window_size == 16
    svc.close()


def test_block_of_entirely_new_items():
    """A block whose items all fall outside the tracked item map grows the
    raw histogram mid-stream; the stale path certifies around it and the
    exact path escapes and refreshes."""
    svc = MiningService(min_support=0.25, store="perfect_hash", n_slots=4,
                        slot_size=8, staleness=1.0)
    svc.ingest([[0, 1], [1, 2], [0, 2], [0, 1, 2]] * 2)
    svc.query()
    svc.ingest([[100, 101], [101, 102], [100, 102], [100, 101, 102]] * 2)
    stale = svc.query(staleness=2.0)
    assert not stale.refreshed
    _validate_certificate(svc.window(), 0.25, 16, stale)
    res = svc.query()
    assert res.refreshed and res.stale_reason == "untracked"
    assert res.itemsets == _oracle(svc.window(), 0.25, 16)
    svc.close()


# -- bounded-staleness serving ----------------------------------------------
def test_stale_serving_certificates_validate_against_recount():
    """Every staleness-budget answer's certificate holds against the exact
    ground-truth recount of the window it was served over."""
    rng = np.random.default_rng(11)
    svc = MiningService(min_support=0.08, store="perfect_hash", n_slots=8,
                        slot_size=32, staleness=0.3, max_k=6)
    svc.ingest([t for b in _batches(rng, 8, 32, n_items=24) for t in b])
    svc.query()                      # cold refresh builds the lattice
    r0 = svc.refreshes
    saw_inflight = saw_stale = False
    for batch in _batches(rng, 6, 32, n_items=24):
        svc.ingest(batch)
        res = svc.query(staleness=4.0)
        assert not res.refreshed, "staleness budget still blocked a query"
        _validate_certificate(svc.window(), 0.08, 6, res)
        saw_inflight = saw_inflight or res.refresh_in_flight
        saw_stale = saw_stale or res.stale_reason == "stale"
    assert saw_inflight, "drift never kicked a background refresh"
    # Drive the in-flight refresh to its handoff without blocking queries.
    for _ in range(2000):
        if not svc.stats()["refresh_in_flight"]:
            break
        svc.refresh_async()
        time.sleep(0.001)
    assert not svc.stats()["refresh_in_flight"]
    assert svc.refreshes > r0, "background refresh never handed off"
    res = svc.query()                # exact after the background handoff
    assert res.itemsets == _oracle(svc.window(), 0.08, 6)
    svc.close()


def test_stale_query_exact_when_bound_is_zero():
    """With zero churn since refresh the certificate certifies exactness —
    and the answer really is the oracle's."""
    rng = np.random.default_rng(13)
    svc = MiningService(min_support=0.08, store="perfect_hash", n_slots=6,
                        slot_size=32, max_k=6)
    svc.ingest([t for b in _batches(rng, 6, 32, n_items=24) for t in b])
    svc.query()
    res = svc.query(staleness=1.0)
    cert = res.certificate
    assert cert.max_drift == 0 and cert.miss_bound == res.min_count
    assert cert.is_exact(res.min_count)
    assert res.stale_reason is None and not res.refreshed
    assert res.itemsets == _oracle(svc.window(), 0.08, 6)
    svc.close()


def test_below_track_threshold_refreshes_at_queried_threshold():
    """A query below the margin-lowered track threshold must never walk (or
    approximately serve) the provably incomplete lattice — it refreshes at
    the queried threshold, on the exact AND the stale path."""
    rng = np.random.default_rng(15)
    svc = MiningService(min_support=0.08, store="perfect_hash", n_slots=8,
                        slot_size=32, margin=0.8, max_k=6)
    svc.ingest([t for b in _batches(rng, 8, 32, n_items=24) for t in b])
    svc.query()                      # lattice tracked at 0.8 * ceil(.08 * n)
    res = svc.query(min_support=0.04)
    assert res.refreshed and res.stale_reason == "below_track"
    assert res.itemsets == _oracle(svc.window(), 0.04, 6)
    # The refresh above re-tracked at the lower threshold; go lower still so
    # the stale path hits the same guard.
    res = svc.query(min_support=0.02, staleness=10.0)
    assert res.refreshed and res.stale_reason == "below_track"
    assert res.itemsets == _oracle(svc.window(), 0.02, 6)
    svc.close()


# -- tracked-lattice compaction ----------------------------------------------
def test_compaction_prunes_drained_rows_and_preserves_parity():
    """After item churn drains tracked rows to zero support, compaction
    removes them (and their orphaned border) without changing any answer."""
    tails = [[3, 4, 5], [4, 5, 6], [3, 5, 6], [3, 4, 6]]
    first = [[0, 1, 2] + tails[i % 4] for i in range(16)]
    # Window cap == 16 baskets, so the second ingest evicts the first whole.
    svc = MiningService(min_support=0.2, store="perfect_hash", n_slots=1,
                        slot_size=16, eviction="basket", staleness=2.1,
                        max_k=5, compact_churn=0.1)
    svc.ingest(first)
    svc.query()
    pre = svc.stats()["tracked_candidates"]
    assert pre > 0
    # Replace every {0,1,2}-carrying basket with its tail: supports of all
    # other itemsets are unchanged, so no new itemset can cross the track
    # threshold — the only lattice change is {0,1,2} draining to zero.
    svc.ingest([tails[i % 4] for i in range(16)])
    res = svc.query()                # drains -> compacts -> serves
    assert res.itemsets == _oracle(svc.window(), 0.2, 5)
    st = svc.stats()
    assert st["compactions"] >= 1, "drain threshold never compacted"
    assert st["compacted_rows"] > 0
    assert st["tracked_candidates"] < pre
    res = svc.query()                # parity again on the compacted lattice
    assert res.itemsets == _oracle(svc.window(), 0.2, 5)
    svc.close()


# -- tracked-level lookup ----------------------------------------------------
def _dict_rows_of(cand, queries):
    """The lookup ``_TrackedLevel.rows_of`` once made, kept as its
    reference: a bytes-keyed dict over the level's rows, probed once per
    query row."""
    index = {row.tobytes(): i
             for i, row in enumerate(np.ascontiguousarray(cand, np.int32))}
    q = np.ascontiguousarray(queries, np.int32)
    return np.fromiter((index.get(row.tobytes(), -1) for row in q),
                       dtype=np.int64, count=q.shape[0])


def _random_level(rng, k, n_rows=400, n_items=40):
    rows = np.array([np.sort(rng.choice(n_items, k, replace=False))
                     for _ in range(n_rows)], np.int32)
    return np.unique(rows, axis=0)       # lex-sorted and duplicate-free


def _compacted_level():
    """A tracked level as compaction rebuilt it, and the level it was cut
    from (the compaction scenario above)."""
    tails = [[3, 4, 5], [4, 5, 6], [3, 5, 6], [3, 4, 6]]
    svc = MiningService(min_support=0.2, store="perfect_hash", n_slots=1,
                        slot_size=16, eviction="basket", staleness=2.1,
                        max_k=5, compact_churn=0.1)
    svc.ingest([[0, 1, 2] + tails[i % 4] for i in range(16)])
    svc.query()
    before = svc._levels[2].cand.copy()
    svc.ingest([tails[i % 4] for i in range(16)])
    svc.query()
    assert svc.compactions >= 1
    tl = svc._levels[2]
    svc.close()
    assert 0 < tl.cand.shape[0] < before.shape[0]
    return tl, before


@pytest.mark.parametrize("case", ["k2", "k3", "k4", "k5", "empty_level",
                                  "compacted"])
def test_rows_of_matches_dict_lookup(case):
    """The sorted-prefix index answers every query row as the dict did:
    tracked rows in any order, untracked rows, ids at or above the index
    base (including one built to alias another prefix's key), an empty
    query, an empty level and a level rebuilt by compaction."""
    from repro.serve.engine import _TrackedLevel

    rng = np.random.default_rng(sum(map(ord, case)))
    if case == "compacted":
        tl, other = _compacted_level()
        cand = tl.cand
    else:
        k = 3 if case == "empty_level" else int(case[1:])
        other = _random_level(rng, k)
        cand = (other[:0] if case == "empty_level"
                else other[rng.random(other.shape[0]) < 0.6])
        tl = _TrackedLevel(cand, np.arange(cand.shape[0]))
    k = cand.shape[1]
    base = int(cand.max()) + 1 if cand.size else 1
    parts = [other, cand[rng.permutation(cand.shape[0])]]
    if cand.shape[0]:
        high = cand[rng.integers(0, cand.shape[0], 8)].copy()
        high[:, -1] += base
        parts.append(high)
        firsts = np.unique(cand[:, 0])
        if len(firsts) > 1:
            # (rank(a) - 1) * base + (b + base) == rank(a) * base + b
            row = cand[np.flatnonzero(cand[:, 0] == firsts[1])[0]].copy()
            alias = row.copy()
            alias[0], alias[1] = firsts[0], row[1] + base
            parts.append(alias[None, :])
    queries = np.concatenate(parts).astype(np.int32)
    for q in (queries, queries[:0]):
        got = tl.rows_of(q)
        assert got.dtype == np.int64 and got.shape == (q.shape[0],)
        np.testing.assert_array_equal(got, _dict_rows_of(cand, q))
    if cand.shape[0]:
        np.testing.assert_array_equal(tl.rows_of(cand),
                                      np.arange(cand.shape[0]))
        assert (tl.rows_of(queries[-1:]) == -1).all()


def test_rows_of_refuses_an_unsorted_level():
    from repro.serve.engine import _TrackedLevel

    tl = _TrackedLevel(np.array([[1, 2], [0, 3]], np.int32), np.zeros(2))
    with pytest.raises(ValueError):
        tl.rows_of(np.array([[0, 3]], np.int32))


# -- basket stream -----------------------------------------------------------
def test_basket_stream_seeded_and_reproducible():
    a = list(basket_stream("T10I4D100K", batch_size=32, scale=0.002, seed=4))
    b = list(basket_stream("T10I4D100K", batch_size=32, scale=0.002, seed=4))
    assert [ab.transactions for ab in a] == [ab.transactions for ab in b]
    assert [ab.seq for ab in a] == list(range(len(a)))
    t = [ab.t_arrival for ab in a]
    assert all(x < y for x, y in zip(t, t[1:]))  # clock advances
    assert isinstance(a[0], ArrivalBatch) and len(a[0]) > 0
    c = list(basket_stream("T10I4D100K", batch_size=32, scale=0.002, seed=5))
    assert [ab.transactions for ab in a] != [ab.transactions for ab in c]


def test_basket_stream_repeat_and_cap():
    n_one_epoch = len(list(
        basket_stream("T10I4D100K", batch_size=32, scale=0.002, seed=0)))
    capped = list(basket_stream("T10I4D100K", batch_size=32, scale=0.002,
                                seed=0, repeat=True,
                                max_batches=n_one_epoch + 3))
    assert len(capped) == n_one_epoch + 3


def test_stream_replay_invariant_across_batch_sizes():
    """Same seed => same basket order AND same per-basket timestamps no
    matter how the stream is cut into batches — including past the first
    epoch (the old shared-RNG draws made epoch 2's shuffle depend on how
    many batch-size draws epoch 1 consumed)."""
    n_epoch = sum(len(ab) for ab in
                  basket_stream("T10I4D100K", batch_size=32, scale=0.002,
                                seed=7))

    def flat(bs, n_batches):
        txs, ts = [], []
        for ab in basket_stream("T10I4D100K", batch_size=bs, scale=0.002,
                                seed=7, repeat=True, max_batches=n_batches):
            assert ab.t_arrivals is not None
            assert len(ab.t_arrivals) == len(ab.transactions)
            assert ab.t_arrival == ab.t_arrivals[-1]
            txs.extend(ab.transactions)
            ts.extend(float(t) for t in ab.t_arrivals)
        return txs, ts

    txs_a, ts_a = flat(16, 40)
    txs_b, ts_b = flat(48, 14)
    k = min(len(txs_a), len(txs_b))
    assert k > n_epoch + 10, "comparison must reach into epoch 2"
    assert txs_a[:k] == txs_b[:k]
    assert ts_a[:k] == ts_b[:k]      # bit-identical, not just close
    assert all(x < y for x, y in zip(ts_a, ts_a[1:]))


def test_stream_feeds_service():
    """End-to-end: the seeded stream through the service, parity on the way."""
    svc = MiningService(min_support=0.05, store="hash_bucket", n_slots=4,
                        slot_size=48, max_k=5)
    for ab in basket_stream("T10I4D100K", batch_size=48, scale=0.003, seed=2,
                            repeat=True, max_batches=5):
        svc.ingest(ab.transactions)
    res = svc.query()
    oracle = FrequentItemsetMiner(min_support=0.05, store="hash_bucket",
                                  max_k=5).mine(svc.window()).itemsets
    assert res.itemsets == oracle
    svc.close()
