"""The span reduction (``bench/spans.py``): on a small trace recorded on a
TPU v5e, and on a CPU trace of the program's own spans through a mine and a
few service cycles.

``data/tiny_spans.xplane.pb`` was written by
``python bench/tools/record_spans.py`` on one chip: a jitted ``tiny_step``
run three times in each of three ``bench.step`` spans, once in the step,
once in ``repro.outer`` (``rows=512``) and once in ``repro.inner`` nested
in it, each after a host sleep of 10, 20 and 30 ms.
"""

from pathlib import Path

import pytest

from bench import spans, trace

DATA = Path(__file__).parent / "data"
TINY = DATA / "tiny_spans.xplane.pb"


@pytest.fixture(scope="module")
def tiny():
    return spans.table(str(TINY))


def test_recorded_self_time_is_total_less_children(tiny):
    step, outer, inner = (tiny["bench.step"], tiny["repro.outer"],
                          tiny["repro.inner"])
    assert step.count == outer.count == inner.count == 3
    assert inner.self_s == inner.seconds
    assert outer.self_s == pytest.approx(outer.seconds - inner.seconds,
                                         abs=1e-9)
    assert step.self_s == pytest.approx(step.seconds - outer.seconds,
                                        abs=1e-9)
    # Each holds its own sleeps: 3 x 30, 3 x 20 and 3 x 10 ms.
    assert inner.self_s >= 0.09
    assert outer.self_s >= 0.06
    assert step.self_s >= 0.03
    assert tiny["bench.window"].seconds >= step.seconds


def test_recorded_arguments_are_summed(tiny):
    assert tiny["repro.outer"].args == {"rows": 3 * 512}
    assert tiny["repro.inner"].args == {}


def test_recorded_gaps_carry_the_innermost_span():
    gaps = spans.idle_gaps(str(TINY))
    labels = [label for label, _ in gaps]
    # A program span keeps its whole name; a benchmark span loses "bench.".
    # Longest first: the 30, 20 and 10 ms sleeps; the tenth is the window's
    # tail after the last run, still inside repro.inner.
    assert labels == (["repro.inner"] * 3 + ["repro.outer"] * 3
                      + ["step"] * 3 + ["repro.inner"])
    # The same stretches as the benchmark's own reduction finds.
    summary = trace.reduce(str(TINY))
    assert [s for _, s in gaps] == [s for _, s in summary.idle_gaps]


def test_gaps_without_program_spans_read_as_before():
    old = DATA / "tiny_step.xplane.pb"
    assert spans.idle_gaps(str(old)) == trace.reduce(str(old)).idle_gaps


def test_label():
    assert spans.label("bench.mine") == "mine"
    assert spans.label("repro.mine") == "repro.mine"
    assert spans.label(None) == "other"


# -- the program's spans, traced on the CPU ----------------------------------

ROOTS = ("repro.mine", "repro.svc.ingest", "repro.svc.query")
BATCH = ("repro.mine", "repro.build", "repro.ingest", "repro.job1",
         "repro.place", "repro.place.digest", "repro.place.encode",
         "repro.place.put", "repro.gen", "repro.dispatch", "repro.fetch",
         "repro.wait", "repro.collect", "repro.checkpoint",
         "repro.ladder.level")
SERVICE = ("repro.svc.ingest", "repro.svc.slot", "repro.svc.delta",
           "repro.svc.evict", "repro.svc.query", "repro.svc.drain",
           "repro.svc.walk", "repro.svc.walk.gen", "repro.svc.lookup",
           "repro.svc.refresh", "repro.svc.compact")


@pytest.fixture(scope="module")
def program(tmp_path_factory):
    """A host-loop mine with a checkpoint directory, a ladder mine and four
    service cycles, traced; returns the host-loop mine's result and the
    trace."""
    import jax

    from bench.gen.quest import quest_generator
    from repro.core import FrequentItemsetMiner
    from repro.serve import MiningService

    db = quest_generator(n_transactions=400, avg_transaction_len=5,
                         avg_pattern_len=3, n_items=40, n_patterns=20, seed=1)
    svc = MiningService(min_support=0.08, n_slots=4, slot_size=64,
                        eviction="basket", staleness=0.3, compact_churn=0.2)
    svc.ingest(db[:256])
    svc.query()
    FrequentItemsetMiner(min_support=0.08).mine(db)
    out = tmp_path_factory.mktemp("trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(out), profiler_options=opts)
    try:
        res = FrequentItemsetMiner(
            min_support=0.08,
            checkpoint_dir=str(tmp_path_factory.mktemp("ckpt"))).mine(db)
        FrequentItemsetMiner(min_support=0.08, device_loop=True,
                             max_k=2).mine(db)
        for i in range(4):
            svc.ingest(db[256 + 32 * i: 288 + 32 * i])
            svc.query()
    finally:
        jax.profiler.stop_trace()
        svc.close()
    return res, str(next(out.rglob("*.xplane.pb")))


def test_every_span_appears(program):
    _, path = program
    tab = spans.table(path)
    assert set(BATCH + SERVICE) <= set(tab)
    assert tab["repro.mine"].count == 2
    assert tab["repro.svc.query"].count == tab["repro.svc.ingest"].count == 4
    assert tab["repro.svc.lookup"].args["rows"] > 0
    assert tab["repro.dispatch"].args["rows"] > 0
    # Two mines of 400 baskets; each refresh of the service ingests its
    # window of 256 again.
    refreshes = tab["repro.ingest"].count - 2
    assert refreshes >= 1
    assert tab["repro.ingest"].args == {"rows": 2 * 400 + 256 * refreshes}


def test_every_span_nests_under_a_root(program):
    _, path = program
    for line in spans.host_lines(path):
        roots = [s for s in line if s.name in ROOTS]
        for s in line:
            if s.name.startswith("repro.") and s.name not in ROOTS:
                assert any(r.start <= s.start and s.end <= r.end
                           for r in roots), s.name


def test_spans_equal_the_profiles_phase_times(program):
    res, path = program
    line = next(ln for ln in spans.host_lines(path)
                if any(s.name == "repro.mine" for s in ln))
    mine = next(s for s in line if s.name == "repro.mine")  # the first
    inside = [s for s in line if mine.start <= s.start and s.end <= mine.end]
    top = max(lv.k for lv in res.levels)
    # A level's generation is timed by repro.gen spans of its k; the last
    # generation, which found no candidates, belongs to no level.
    gen = sum(s.end - s.start for s in inside
              if s.name == "repro.gen" and s.args["k"] <= top) * 1e-9
    dispatch = sum(s.end - s.start for s in inside
                   if s.name == "repro.dispatch") * 1e-9
    assert gen == pytest.approx(sum(lv.gen_seconds for lv in res.levels),
                                abs=1e-3)
    assert dispatch == pytest.approx(
        sum(lv.encode_seconds for lv in res.levels), abs=1e-3)
    assert not any(s.name == "repro.ladder.level" for s in inside)


def test_table_reads_a_trace_without_a_device_plane(program):
    import jax

    _, path = program
    if jax.default_backend() == "cpu":
        with pytest.raises(ValueError, match="no device plane"):
            trace.reduce(path)
    mine = spans.table(path)["repro.mine"]
    assert 0 < mine.self_s < mine.seconds
