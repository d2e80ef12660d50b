"""A run with the timed path broken underneath reports ``correct`` false:
once for each fault a cell can have.  The runs are CPU rehearsals at a
tiny size (``test_bench_rehearsal.SHRINK``), so the look for a chip is
skipped and the rest of the run is the benchmark's own."""

import io

import pytest

from bench import harness
from repro.core.runtime.runners import JaxRunner
from repro.core.stores.perfect_hash import PerfectHashStore
from repro.serve import MiningService

from test_bench_rehearsal import SHRINK


def broken_run(workload, tmp_path):
    out, err = io.StringIO(), io.StringIO()
    return harness.run(workload, 11, 0.5, False, require_tpu=False,
                       shrink=SHRINK[workload], cache_dir=tmp_path,
                       compile_cache=False, out=out, err=err)


def altered_count(monkeypatch):
    count = PerfectHashStore.count_block
    monkeypatch.setattr(PerfectHashStore, "count_block", staticmethod(
        lambda trans, cands: count(trans, cands) + 1))


def half_ingested(monkeypatch):
    ingest = JaxRunner.ingest
    monkeypatch.setattr(JaxRunner, "ingest", lambda self, t: ingest(
        self, t[: len(t) // 2]))


def state_unchanged(monkeypatch):
    monkeypatch.setattr(MiningService, "_dispatch_deltas",
                        lambda self, padded, sign: None)


def answer_altered(monkeypatch):
    query = MiningService.query

    def dropped(self, *a, **k):
        res = query(self, *a, **k)
        res.itemsets.pop(max(res.itemsets, key=len), None)
        return res

    monkeypatch.setattr(MiningService, "query", dropped)


def half_batch_ingested(monkeypatch):
    ingest = MiningService.ingest
    monkeypatch.setattr(MiningService, "ingest", lambda self, t: ingest(
        self, t[: len(t) // 2]))


@pytest.mark.parametrize("workload,fault", [
    ("t10.batch", altered_count),
    ("t10.batch", half_ingested),
    ("t40.batch", altered_count),
    ("t10.stream", state_unchanged),
    ("t10.stream", answer_altered),
    ("t10.stream", half_batch_ingested),
], ids=lambda x: getattr(x, "__name__", x))
def test_fault_makes_the_run_incorrect(workload, fault, monkeypatch,
                                       tmp_path):
    fault(monkeypatch)
    res = broken_run(workload, tmp_path)
    assert res["correct"] is False
    c = res["checks"]
    assert (c["missing"]["value"] + c["extra"]["value"]
            + c["wrong_support"]["value"] > 0) or res["failed"] > 0
