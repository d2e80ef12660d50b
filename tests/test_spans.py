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
