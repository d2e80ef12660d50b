"""Traffic kind ``batch``: whole mines back to back.

Set-up mines the run's dataset once through the same call the window
makes, so every program the window runs is compiled (or in the
persistent cache) before it starts.  The window then runs mines back to
back and closes when the mine running at ``--seconds`` ends.  Each mine
builds a new ``FrequentItemsetMiner``, as a job does, and clears the
program's encoded-dataset cache first, so that it pays its own encode.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

from bench import check, reference
from bench.counters import Counts


@dataclasses.dataclass
class Mine:
    seconds: float
    levels: List[dict]             # one JobProfile per counting job, as dicts
    compiles: Counts               # compile events during the mine
    cache_hits: int                # encoded-dataset cache hits (must be 0)
    itemsets: Optional[Dict] = None
    error: Optional[str] = None


class Kind:
    def __init__(self, run, config: dict, traffic: dict, data) -> None:
        from repro.core import FrequentItemsetMiner
        from repro.core.runtime.cache import DATASET_CACHE

        from bench.data import to_lists

        self.run = run
        self.min_support = traffic["min_support"]
        self.trace_mines = traffic["trace_mines"]
        self.data = data
        self.transactions = to_lists(data)
        self._miner = FrequentItemsetMiner
        self._cache = DATASET_CACHE
        self.mines: List[Mine] = []
        warm = self._mine()
        if warm.error:
            raise RuntimeError(f"warm-up mine failed: {warm.error}")
        run.log(f"warm-up mine: {warm.seconds} s, "
                f"{len(warm.itemsets)} itemsets, "
                f"levels {[(lv['k'], lv['n_candidates']) for lv in warm.levels]}")
        self.n_frequent_items = warm.levels[0]["n_frequent"]

    def _mine(self) -> Mine:
        import jax

        self._cache.clear()
        c0 = self.run.counter.now()
        t0 = time.perf_counter()
        itemsets, levels, error = None, [], None
        with jax.profiler.TraceAnnotation("bench.mine"):
            try:
                miner = self._miner(min_support=self.min_support)
                res = miner.mine(self.transactions)
                itemsets = res.itemsets
                levels = [dataclasses.asdict(lv) for lv in res.levels]
            except Exception as e:  # reported as a failed mine
                error = f"{type(e).__name__}: {e}"
        seconds = time.perf_counter() - t0
        return Mine(seconds=seconds, levels=levels,
                    compiles=self.run.counter.now() - c0,
                    cache_hits=self._cache.stats()["hits"],
                    itemsets=itemsets, error=error)

    def window(self, seconds: float) -> float:
        t0 = time.perf_counter()
        self.run.tracer.start()
        while True:
            mine = self._mine()
            self.mines.append(mine)
            if len(self.mines) == self.trace_mines:
                self.run.tracer.stop()
            elapsed = time.perf_counter() - t0
            if mine.error or elapsed >= seconds:
                break
        self.run.tracer.stop()
        return elapsed

    def summary(self) -> List[str]:
        hits = sum(m.cache_hits for m in self.mines)
        c = sum((m.compiles for m in self.mines), Counts())
        return [
            f"mines: {len(self.mines)}, seconds each "
            f"{[m.seconds for m in self.mines]}",
            f"encoded-dataset cache hits in the window: {hits}",
            f"compile events in the window: programs={c.programs} "
            f"persistent_cache_hits={c.cache_hits} compiled={c.compiled} "
            f"traces={c.traces} compile_s={c.compile_s}",
        ]

    def end_to_end(self, window_s: float) -> Dict[str, float]:
        return {"mine_s": window_s / len(self.mines)}

    @property
    def attempted(self) -> int:
        return len(self.mines)

    def free(self) -> None:
        self._cache.clear()

    def check(self):
        """Every mine of the window against one reference mine of the
        run's dataset (every mine mined the same baskets)."""
        want = reference.frequent_itemsets(self.data, self.min_support)
        results, failed = [], 0
        for m in self.mines:
            if m.itemsets is None:
                failed += 1
                continue
            r = check.compare(m.itemsets, want)
            failed += not check.passes(r)
            results.append(r)
        return check.total(results), len(results), failed


def control_windows(traffic: dict, data, seed: int):
    """The baskets each output a run compares is mined over, for
    ``bench/control.py``: the run's dataset."""
    yield data
