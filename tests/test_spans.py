"""The span helper (``repro.core.runtime.spans``) and the phase times it
fills: every hand-timed boundary of a mine and of the service now takes its
seconds from its span."""

import time

import numpy as np
import pytest

from repro.core import FrequentItemsetMiner
from repro.core.runtime import spans
from repro.serve import MiningService


def _baskets(seed, n, n_items=24, max_len=7):
    rng = np.random.default_rng(seed)
    return [sorted(set(rng.integers(0, n_items,
                                    size=rng.integers(2, max_len)).tolist()))
            for _ in range(n)]


def test_span_times_its_block_with_the_profiler_off():
    with spans.span("repro.test", rows=3, k=2) as s:
        time.sleep(0.01)
    assert 0.01 <= s.seconds < 1.0


def test_span_lets_an_exception_through():
    with pytest.raises(KeyError):
        with spans.span("repro.test") as s:
            raise KeyError("x")
    assert s.seconds >= 0.0


FIXED = 7.0  # what every span reports under ``fixed_spans``


@pytest.fixture
def fixed_spans(monkeypatch):
    exit_ = spans.span.__exit__

    def fixed_exit(self, *exc):
        exit_(self, *exc)
        self.seconds = FIXED

    monkeypatch.setattr(spans.span, "__exit__", fixed_exit)


def test_job_profiles_take_their_phase_times_from_spans(fixed_spans):
    res = FrequentItemsetMiner(min_support=0.1, store="perfect_hash").mine(
        _baskets(0, 300))
    job1, *levels = res.levels
    assert job1.seconds == job1.count_seconds == FIXED
    assert levels
    for lv in levels:
        assert lv.encode_seconds == FIXED      # one repro.dispatch
        assert lv.count_seconds == FIXED       # one repro.wait
        # repro.gen: the level's generation, or speculation and cut-back.
        assert lv.gen_seconds in (FIXED, 2 * FIXED)


def test_service_reports_take_their_seconds_from_spans(fixed_spans):
    svc = MiningService(min_support=0.1, store="perfect_hash", n_slots=4,
                        slot_size=32, eviction="basket")
    assert svc.ingest(_baskets(1, 128)).seconds == FIXED
    assert svc.query().seconds == FIXED
    assert svc.evict(3).seconds == FIXED
    svc.close()


def test_lookup_index_is_built_once_per_tracked_level_per_refresh(
        monkeypatch):
    """``repro.svc.lookup.index`` fires once for each tracked level a walk
    reaches after a refresh, with the level's row count, and never on the
    queries that follow without one; ``repro.svc.lookup`` keeps its
    ``rows`` argument."""
    seen = []
    init = spans.span.__init__

    def recording_init(self, name, **counts):
        seen.append((name, counts))
        init(self, name, **counts)

    monkeypatch.setattr(spans.span, "__init__", recording_init)
    svc = MiningService(min_support=0.03, store="perfect_hash", n_slots=8,
                        slot_size=32, staleness=0.5, margin=0.5, max_k=6)
    svc.ingest(_baskets(2, 224))

    def query(**kw):
        seen.clear()
        res = svc.query(**kw)
        return (res, [c for n, c in seen if n == "repro.svc.lookup.index"],
                [c for n, c in seen if n == "repro.svc.lookup"])

    def level_rows(n_lookups):
        return sorted(svc._levels[k].cand.shape[0]
                      for k in range(2, 2 + n_lookups))

    res, index, lookup = query()
    assert res.refreshed and len(lookup) >= 2
    assert all(set(c) == {"rows"} and c["rows"] > 0 for c in lookup)
    assert sorted(c["rows"] for c in index) == level_rows(len(lookup))

    res, index, lookup_again = query()
    assert not res.refreshed and index == [] and lookup_again == lookup

    svc.ingest(_baskets(2, 32))    # a slot of baskets already seen: delta path
    res, index, lookup = query()
    assert not res.refreshed and index == [] and lookup

    res, index, lookup = query(min_support=0.01)  # below track: refresh
    assert res.refreshed and res.stale_reason == "below_track"
    assert sorted(c["rows"] for c in index) == level_rows(len(lookup))
    svc.close()


def test_ingest_span_counts_rows_items_and_dups(tmp_path):
    """Traced on the CPU, a mine's ``repro.ingest`` carries ``rows`` and its
    ``repro.ingest.pack`` the ids flattened (``items``) and the repeats of
    an id within a basket dropped (``dups``)."""
    import jax
    from jax.profiler import ProfileData

    db = [row + row[:2] for row in _baskets(3, 200)]  # two ids repeated
    items = sum(len(row) for row in db)
    dups = sum(len(row) - len(set(row)) for row in db)
    assert dups > 0
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        FrequentItemsetMiner(min_support=0.1, store="perfect_hash").mine(db)
    finally:
        jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    args = {ev.name: dict(ev.stats)
            for plane in ProfileData.from_file(str(path)).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith("repro.ingest")}
    assert args["repro.ingest"] == {"rows": 200}
    assert args["repro.ingest.pack"] == {"items": items, "dups": dups}


def test_dispatch_span_and_job_profile_count_gram_chunks(tmp_path):
    """A T10-shaped wave in small: full chunks of pairs, a tail chunk, then
    a k = 3 wave.  The ``gram`` argument of each ``repro.dispatch`` and the
    job's ``gram_chunks`` count the chunks ``pair_by_gram`` sends to the
    Gram path: over F_pad 384, chunks of 512 pairs do (384² <= 512 x 512
    at ``GRAM_RATIO`` 512), the tail padded to 128 does not, and k = 3
    never does."""
    import itertools

    import jax
    from jax.profiler import ProfileData

    from repro.core.itemsets import brute_force_counts
    from repro.core.runtime import JaxRunner
    from repro.core.runtime.job import CountJob
    from repro.core.stores.perfect_hash import PerfectHashStore

    f_pad = 384
    assert PerfectHashStore.pair_by_gram(2, f_pad, 512)
    assert not PerfectHashStore.pair_by_gram(2, f_pad, 128)
    db = _baskets(4, 150, n_items=300, max_len=12)
    runner = JaxRunner(cand_block=512, block_n=64)
    runner.ingest(db)
    runner.place(np.arange(300))                # F = 300: F_pad 384
    pairs = np.array(
        list(itertools.combinations(range(300), 2))[:3 * 512 + 100], np.int32)
    triples = np.array(list(itertools.combinations(range(40), 3))[:600],
                       np.int32)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        got = [runner.count_async(CountJob(k=c.shape[1], cand=c)).result()
               for c in (pairs, triples)]
    finally:
        jax.profiler.stop_trace()
    (c2, p2), (c3, p3) = got
    assert (p2.gram_chunks, p3.gram_chunks) == (3, 0)
    for counts, cand in ((c2, pairs), (c3, triples)):
        want = brute_force_counts(db, [tuple(r) for r in cand.tolist()])
        np.testing.assert_array_equal(counts, list(want.values()))
    path = next(tmp_path.rglob("*.xplane.pb"))
    events = sorted((ev.start_ns, dict(ev.stats))
                    for plane in ProfileData.from_file(str(path)).planes
                    if plane.name.startswith("/host:")
                    for line in plane.lines for ev in line.events
                    if ev.name == "repro.dispatch")
    assert [args for _, args in events] == [
        {"k": 2, "rows": 1636, "gram": 3}, {"k": 3, "rows": 600, "gram": 0}]
    runner.close()

    # A whole small mine: every k = 2 chunk by the Gram path (F_pad 128),
    # none deeper.
    res = FrequentItemsetMiner(min_support=0.02, store="perfect_hash",
                               cand_block=128).mine(_baskets(0, 300))
    job1, c2, *deeper = res.levels
    assert c2.gram_chunks == -(-c2.n_candidates // 128) > 1
    assert deeper and all(lv.gram_chunks == 0 for lv in deeper)
