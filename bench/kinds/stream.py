"""Traffic kind ``stream``: the mining service over a sliding window.

Set-up fills the window in one bulk ingest of the stream's first
``n_slots * slot_size`` baskets and answers one exact query (the cold
refresh that mines the window), then runs ``warmup_cycles`` cycles.  The
window runs closed-loop cycles, each one arrival batch ingested (evicting
as many baskets) and then one exact ``query()``, and closes when the
cycle running at ``--seconds`` ends.

The arrival order is drawn from the mix's ``order_seed`` where it has one,
and ``--seed`` then only relabels the items: every seed sends the same
windows, so the refreshes come at the same cycles with the same shapes.
A run compiles the programs of each refresh it is the first to reach;
once runs in a checkout have reached every refresh a window reaches, the
persistent cache holds every program later runs need.

Once the window has closed, a sample of its queries drawn from the seed
(every query that refreshed, then others up to ``check_queries``) is
compared with the reference mined over that query's window, which the
benchmark rebuilds from the baskets it sent.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import numpy as np

from bench import check, reference
from bench.counters import Counts
from bench.data import padded, to_lists
from bench.gen.stream import basket_stream


@dataclasses.dataclass
class Cycle:
    n: int                 # baskets ingested
    end: int               # baskets sent so far, this cycle's included
    ingest_s: float        # host clock around ingest()
    report_s: float        # IngestReport.seconds
    query_s: float         # host clock around query()
    refreshed: bool        # ServeResult.refreshed
    compiles: Counts
    itemsets: Optional[Dict] = None
    error: Optional[str] = None


class Kind:
    def __init__(self, run, config: dict, traffic: dict, data) -> None:
        from repro.serve import MiningService

        self.run = run
        self.t = traffic
        self.cap = traffic["n_slots"] * traffic["slot_size"]
        self.sent: List[List[int]] = []
        self.cycles: List[Cycle] = []
        self._stream = basket_stream(
            to_lists(data), batch_size=traffic["batch_size"],
            seed=traffic.get("order_seed", run.seed),
            jitter=traffic["jitter"], repeat=True)
        self.svc = MiningService(
            min_support=traffic["min_support"], n_slots=traffic["n_slots"],
            slot_size=traffic["slot_size"], eviction=traffic["eviction"])
        while len(self.sent) < self.cap:
            self.sent += next(self._stream).transactions
        fill, extra = self.sent[:self.cap], self.sent[self.cap:]
        self.sent = fill
        t0 = time.perf_counter()
        self.svc.ingest(fill)
        res = self.svc.query()
        run.log(f"bulk fill of {len(fill)} baskets and cold query: "
                f"{time.perf_counter() - t0} s, {len(res.itemsets)} itemsets")
        if extra:
            self._cycle(extra)
        for _ in range(traffic["warmup_cycles"]):
            self._cycle(next(self._stream).transactions)
        self.cycles.clear()

    def _cycle(self, batch) -> Cycle:
        import jax

        c0 = self.run.counter.now()
        self.sent += batch
        itemsets, error = None, None
        t0 = t1 = time.perf_counter()
        report_s, refreshed = 0.0, False
        try:
            with jax.profiler.TraceAnnotation("bench.ingest"):
                rep = self.svc.ingest(batch)
            t1 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.query"):
                res = self.svc.query()
            report_s, refreshed, itemsets = (rep.seconds, res.refreshed,
                                             res.itemsets)
        except Exception as e:  # reported as a failed cycle
            error = f"{type(e).__name__}: {e}"
        t2 = time.perf_counter()
        cyc = Cycle(n=len(batch), end=len(self.sent), ingest_s=t1 - t0,
                    report_s=report_s, query_s=t2 - t1, refreshed=refreshed,
                    compiles=self.run.counter.now() - c0,
                    itemsets=itemsets, error=error)
        self.cycles.append(cyc)
        return cyc

    def window(self, seconds: float) -> float:
        t0 = time.perf_counter()
        self.run.tracer.start()
        while True:
            cyc = self._cycle(next(self._stream).transactions)
            if len(self.cycles) == self.t["trace_cycles"]:
                self.run.tracer.stop()
            elapsed = time.perf_counter() - t0
            if cyc.error or elapsed >= seconds:
                break
        self.run.tracer.stop()
        return elapsed

    def summary(self) -> List[str]:
        c = sum((y.compiles for y in self.cycles), Counts())
        q = [y.query_s for y in self.cycles]
        return [
            f"cycles: {len(self.cycles)}, refreshes "
            f"{sum(y.refreshed for y in self.cycles)}, query s median "
            f"{float(np.median(q))} max {max(q)}",
            f"compile events in the window: programs={c.programs} "
            f"persistent_cache_hits={c.cache_hits} compiled={c.compiled} "
            f"traces={c.traces} compile_s={c.compile_s}",
            f"service: {self.svc.stats()}",
        ]

    def end_to_end(self, window_s: float) -> Dict[str, float]:
        q = [y.query_s for y in self.cycles if y.error is None]
        return {
            "stream_baskets_per_s": sum(y.n for y in self.cycles) / window_s,
            "query_p90_s": float(np.percentile(q, 90)) if q else float("nan"),
        }

    @property
    def attempted(self) -> int:
        return len(self.cycles)

    def free(self) -> None:
        self.svc.close()
        self.svc = None

    def sample(self) -> List[int]:
        """Indices of the queries compared: every one that refreshed, then
        a seeded draw of the others, ``check_queries`` in all."""
        done = [i for i, y in enumerate(self.cycles) if y.error is None]
        pick = [i for i in done if self.cycles[i].refreshed]
        rest = [i for i in done if i not in pick]
        n = max(0, self.t["check_queries"] - len(pick))
        rng = np.random.default_rng([self.run.seed, 0xC4EC])
        pick += rng.choice(rest, size=min(n, len(rest)),
                           replace=False).tolist() if rest else []
        return sorted(pick)

    def check(self):
        results = []
        failed = sum(y.error is not None for y in self.cycles)
        for i in self.sample():
            y = self.cycles[i]
            win = padded(self.sent[y.end - self.cap:y.end])
            want = reference.frequent_itemsets(win, self.t["min_support"])
            r = check.compare(y.itemsets, want)
            failed += not check.passes(r)
            results.append(r)
        return check.total(results), len(results), failed


def control_windows(traffic: dict, data, seed: int):
    """The windows ``bench/control.py`` compares: after the bulk fill and
    after 32 and 64 arrival batches."""
    cap = traffic["n_slots"] * traffic["slot_size"]
    ends = [cap + traffic["batch_size"] * j for j in (0, 32, 64)]
    sent: List[List[int]] = []
    for batch in basket_stream(to_lists(data),
                               batch_size=traffic["batch_size"],
                               seed=traffic.get("order_seed", seed),
                               jitter=traffic["jitter"], repeat=True):
        sent += batch.transactions
        if len(sent) >= ends[-1]:
            break
    for end in ends:
        yield padded(sent[end - cap:end])
