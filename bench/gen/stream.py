"""Seeded basket streams, as the benchmark's own copy.

A copy of ``repro.data.stream.basket_stream`` that takes the baskets to
replay instead of a dataset name, so the stream cell can replay the
benchmark's own data.  The arrival order, the per-basket timestamps and the
batch sizes are drawn exactly as the original draws them:
``basket_stream(get_dataset(name, scale, seed), seed=seed, ...)`` gives the
batches of ``repro.data.basket_stream(name, scale=scale, seed=seed, ...)``
(``tests/bench/test_bench_gen.py`` checks one seed).
"""

from __future__ import annotations

import dataclasses
from typing import Iterator, List, Optional, Sequence

import numpy as np


@dataclasses.dataclass
class ArrivalBatch:
    """One tick of the stream: the baskets that arrived by ``t_arrival``."""

    transactions: List[List[int]]
    t_arrival: float               # seconds since stream start (synthetic)
    seq: int                       # batch index, 0-based
    t_arrivals: Optional[np.ndarray] = None
    # Stream positions (0-based, across epochs) of this batch's baskets.
    start: int = 0

    def __len__(self) -> int:
        return len(self.transactions)


def epoch_order(n: int, seed: int, epoch: int, shuffle: bool = True
                ) -> np.ndarray:
    """The order in which epoch ``epoch`` replays the ``n`` baskets."""
    if not shuffle:
        return np.arange(n)
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0x5EED, epoch, 1]))
    return rng.permutation(n)


def basket_stream(
    base: Sequence[Sequence[int]],
    batch_size: int = 256,
    seed: int = 0,
    shuffle: bool = True,
    jitter: float = 0.25,
    rate: float = 10_000.0,
    repeat: bool = False,
    max_batches: Optional[int] = None,
) -> Iterator[ArrivalBatch]:
    """Replay ``base`` as a seeded stream of timestamped arrival batches.

    ``batch_size`` is the mean arrivals per tick; sizes jitter uniformly
    within ``±jitter`` of it (``jitter=0`` gives fixed sizes).  ``rate``
    (baskets/s) sets the synthetic arrival clock.  ``repeat`` loops the
    baskets forever, reshuffled per epoch; cap with ``max_batches``.
    """
    if batch_size < 1:
        raise ValueError("batch_size must be >= 1")
    if not 0.0 <= jitter < 1.0:
        raise ValueError("jitter must be in [0, 1) — gaps must stay positive")
    if len(base) == 0:
        return
    lo = max(1, int(round(batch_size * (1.0 - jitter))))
    hi = max(lo, int(round(batch_size * (1.0 + jitter))))
    t0 = 0.0
    seq = 0
    epoch = 0
    pos = 0
    while True:
        def erng(stream: int) -> np.random.Generator:
            return np.random.default_rng(
                np.random.SeedSequence([seed, 0x5EED, epoch, stream]))

        order = epoch_order(len(base), seed, epoch, shuffle)
        gaps = (1.0 + jitter * (2.0 * erng(2).random(len(base)) - 1.0)) / rate
        times = t0 + np.cumsum(gaps)
        size_rng = erng(3)
        i = 0
        while i < len(base):
            n = int(size_rng.integers(lo, hi + 1))
            block = [list(base[j]) for j in order[i : i + n]]
            ts = times[i : i + len(block)].copy()
            yield ArrivalBatch(transactions=block, t_arrival=float(ts[-1]),
                               seq=seq, t_arrivals=ts, start=pos + i)
            i += len(block)
            seq += 1
            if max_batches is not None and seq >= max_batches:
                return
        if not repeat:
            return
        t0 = float(times[-1])
        pos += len(base)
        epoch += 1
