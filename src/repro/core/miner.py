"""User-facing frequent-itemset miner: the paper's Driver (Algorithm 1).

``FrequentItemsetMiner`` is a *thin* driver over the MapReduce job runtime
(``core.runtime``): it ingests the database into a runner, submits Job1 (the
1-itemset histogram job), dense-remaps over the frequent items, and iterates
a pass-combining strategy — which owns the per-level jobs — checkpointing
after every counting job so a preempted mining run resumes at the last
completed level (the Hadoop analogue: completed jobs are never re-run).

Per-level checkpoints ride the hardened snapshot store
(``distributed.checkpoint``): one atomic, digest-stamped snapshot per
completed level, so torn writes are ignored, bit rot is detected and
quarantined, and a corrupt newest level falls back to the previous one
(one re-counted level, identical results).  On ``DeviceLostError`` —
simulated device loss injected through a ``FaultPlan`` — the driver
rebuilds the largest valid mesh on the surviving devices
(``distributed.elastic``), restores the level checkpoint, and resumes;
itemsets AND supports stay bit-identical to a fault-free run because
counts are mesh-shape-independent.

Any runner works: ``JaxRunner``/``ShardedRunner`` (array-layout stores, the
TPU-native track) or ``SimRunner`` (the paper's Hadoop cost model over the
Java-equivalent stores). All of them report per-job ``JobProfile`` rows
through the same schema, so ``MiningResult.levels`` is directly comparable
across backends.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.itemsets import Itemset, level_to_matrix, sort_level
from repro.core.runtime import BaseRunner, JobProfile, make_runner
from repro.core.runtime import strategies
from repro.core.runtime.faults import DeviceLostError
from repro.core.runtime.spans import span

# Back-compat alias: the old per-level stats type is the unified JobProfile.
LevelStats = JobProfile

# Distinguishes "inflight not configured" (default depth 1) from an explicit
# inflight=None, which means auto-size the queue depth (engine semantics).
_UNSET = object()


@dataclasses.dataclass
class MiningResult:
    itemsets: Dict[Itemset, int]          # frequent itemset -> global support count
    min_count: int
    n_transactions: int
    levels: List[JobProfile]
    item_map: np.ndarray                  # dense id -> original item id

    def frequent_at(self, k: int) -> Dict[Itemset, int]:
        return {s: c for s, c in self.itemsets.items() if len(s) == k}

    @property
    def max_k(self) -> int:
        return max((len(s) for s in self.itemsets), default=0)


class FrequentItemsetMiner:
    def __init__(
        self,
        min_support: float = 0.01,
        store: Optional[str] = None,
        strategy: str = "spc",
        mesh=None,
        data_axes: Optional[Tuple[str, ...]] = None,
        cand_axes: Optional[Tuple[str, ...]] = None,
        max_k: int = 16,
        block_n: Optional[int] = None,
        cand_block: Optional[int] = None,
        inflight=_UNSET,
        encode_ahead: Optional[int] = None,
        checkpoint_dir: Optional[str] = None,
        runner: Optional[BaseRunner] = None,
        elastic_restarts: int = 2,
        device_loop: bool = False,
        trim: bool = True,
    ) -> None:
        if runner is not None and (
            any(v is not None
                for v in (store, mesh, data_axes, cand_axes, block_n,
                          cand_block, encode_ahead))
            or inflight is not _UNSET
        ):
            # An explicit runner owns its backend config; silently ignoring
            # these would mine with a different setup than requested.
            raise ValueError(
                "pass backend config either through runner= or through "
                "store/mesh/data_axes/cand_axes/block_n/cand_block/"
                "inflight/encode_ahead — not both"
            )
        self.min_support = min_support
        self.store = store if store is not None else "perfect_hash"
        self.strategy = strategy
        self.mesh = mesh
        self.data_axes = data_axes if data_axes is not None else ("data",)
        self.cand_axes = cand_axes if cand_axes is not None else ()
        self.max_k = max_k
        self.block_n = block_n if block_n is not None else 2048
        self.cand_block = cand_block if cand_block is not None else 32_768
        # inflight=None passes through to the engine as "auto-size the
        # async queue depth"; unset means the fixed default of 1.
        self.inflight = 1 if inflight is _UNSET else inflight
        # Encode-stage lookahead (chunks encoded on device ahead of their
        # count dispatch); None keeps the engine's double-buffered default.
        self.encode_ahead = encode_ahead if encode_ahead is not None else 2
        if device_loop and strategy != "spc":
            # The ladder *is* the SPC schedule fused on device — FPC/DPC's
            # speculative combined waves have no fused counterpart.
            raise ValueError(
                "device_loop=True fuses the SPC level loop on device; "
                f"it cannot run the {strategy!r} strategy"
            )
        self.device_loop = device_loop
        self.trim = trim
        self.checkpoint_dir = checkpoint_dir
        self.runner = runner
        # How many simulated device losses a single mine() survives before
        # giving up (each one rebuilds a smaller mesh and resumes).
        self.elastic_restarts = elastic_restarts

    def _make_runner(self) -> BaseRunner:
        if self.runner is not None:
            return self.runner
        with span("repro.build"):
            return make_runner(store=self.store, mesh=self.mesh,
                               data_axes=self.data_axes,
                               cand_axes=self.cand_axes,
                               block_n=self.block_n,
                               cand_block=self.cand_block,
                               inflight=self.inflight,
                               encode_ahead=self.encode_ahead)

    def _config(self, runner: BaseRunner) -> dict:
        """The run configuration stamped into checkpoints; a checkpoint from
        a different config must never silently resume this run.  The stamp
        uses ``config_signature()`` (not ``describe()``) so an *elastic*
        restart — same backend kind and store, shrunk mesh — still resumes."""
        return {"runner": runner.config_signature(),
                "strategy": self.strategy, "max_k": self.max_k,
                "device_loop": bool(self.device_loop),
                "trim": bool(self.device_loop and self.trim)}

    # ------------------------------------------------------------------
    def mine(self, transactions: Sequence[Sequence[int]]) -> MiningResult:
        """Mine frequent itemsets; survives simulated device loss.

        On ``DeviceLostError`` (injected via a runner ``fault_plan``) the
        driver closes the dead runner, rebuilds the largest valid mesh on
        the surviving devices, and re-enters the mining loop — which
        restores from the per-level checkpoint when ``checkpoint_dir`` is
        set, or deterministically recomputes from scratch otherwise.
        Either way the result is bit-identical to a fault-free run.
        """
        with span("repro.mine"):
            n = len(transactions)
            min_count = max(1, int(np.ceil(self.min_support * n)))
            runner = self._make_runner()
            restarts = 0
            done = False
            try:
                while True:
                    # introspection: tests/benchmarks
                    self.active_runner = runner
                    try:
                        res = self._mine_once(runner, transactions, n,
                                              min_count)
                        done = True
                        return res
                    except DeviceLostError as err:
                        restarts += 1
                        runner.close(wait=False)
                        if restarts > self.elastic_restarts:
                            raise
                        runner = self._elastic_rebuild(runner, err)
            finally:
                # A runner built for this mine is closed with it: its device
                # DB is freed now, not at the next cyclic collection.
                if runner is not self.runner:
                    runner.close(wait=done)

    def _elastic_rebuild(self, runner: BaseRunner,
                         err: DeviceLostError) -> BaseRunner:
        """A replacement runner on the largest mesh the survivors support."""
        from repro.core.runtime import ShardedRunner
        from repro.distributed import elastic

        engine = getattr(runner, "engine", None)
        if engine is None or engine.mesh is None:
            raise err  # nothing to shrink: single-device or simulated runner
        survivors = elastic.surviving_devices(engine.mesh, err.lost)
        if not survivors:
            raise err
        mesh = elastic.elastic_data_cand_mesh(
            survivors, want_cand=bool(engine.cand_axes))
        return ShardedRunner(
            store=engine.store_name, mesh=mesh, data_axes=("data",),
            cand_axes=("cand",) if engine.cand_axes else (),
            block_n=engine.block_n, cand_block=engine.cand_block,
            inflight=None if engine.inflight_auto else engine.inflight,
            encode_ahead=engine.encode_ahead,
            fault_plan=getattr(runner, "fault_plan", None),
        )

    def _mine_once(self, runner: BaseRunner, transactions, n: int,
                   min_count: int) -> MiningResult:
        runner.ingest(transactions)

        state = self._try_restore(n, min_count, self._config(runner))
        if state is None:
            # Job1: frequent 1-itemsets over the raw item universe — a
            # histogram job on the runner (device-side for the JAX runners).
            hist, prof1 = runner.job1()
            frequent_items = np.nonzero(hist >= min_count)[0]
            item_map = frequent_items.astype(np.int64)  # dense id -> original id
            itemsets: Dict[Itemset, int] = {
                (int(it),): int(hist[it]) for it in frequent_items
            }
            prof1.n_frequent = len(frequent_items)
            levels = [prof1]
            # L1 in dense ids is simply 0..F-1, one item per row.
            level_mat = np.arange(len(item_map), dtype=np.int32).reshape(-1, 1)
            k = 2
        else:
            itemsets, levels, level, k, item_map = state
            level_mat = level_to_matrix(level)

        # Dense re-encode over frequent items only (Apriori property: no
        # candidate may contain an infrequent item) and make the DB resident.
        runner.place(item_map)

        if self.device_loop:
            # Fused device-resident level loop: one compiled dispatch per
            # level, per-level state never leaving the device.  Yields the
            # same (JobProfile, {itemset: count}) stream as the strategies,
            # so checkpointing and restore below are untouched.
            from repro.core.runtime import device_loop as _dl

            combiner = functools.partial(_dl.ladder, trim=self.trim)
        else:
            combiner = strategies.get(self.strategy)
        # Levels enter (and stay in) matrix form inside the strategy loop;
        # tuples only reappear in the yielded result dicts.
        for stats, freq_dense in combiner(
            runner, level_mat, min_count, start_k=k, max_k=self.max_k
        ):
            levels.append(stats)
            with span("repro.collect", rows=len(freq_dense)):
                for s, c in freq_dense.items():
                    orig = tuple(int(item_map[i]) for i in s)
                    itemsets[orig] = int(c)
                # A combined (FPC/DPC) wave yields mixed itemset sizes; the
                # next level the strategy continues from — and the only
                # thing a restore may rebuild into a (C, k) matrix — is the
                # top-k slice.
                top_k = max((len(s) for s in freq_dense), default=0)
                level = sort_level(s for s in freq_dense if len(s) == top_k)
            self._checkpoint(itemsets, levels, level, stats.k + 1, item_map,
                             n, min_count, self._config(runner),
                             fault_plan=getattr(runner, "fault_plan", None))

        return MiningResult(
            itemsets=itemsets, min_count=min_count, n_transactions=n,
            levels=levels, item_map=item_map,
        )

    # -- fault tolerance ------------------------------------------------
    # Per-level state rides the hardened snapshot store
    # (``distributed.checkpoint``): one digest-stamped snapshot per
    # completed level keyed by ``step=next_k``, the item_map as the tensor
    # tree and everything else JSON-packed in the manifest's ``extra``.
    # Torn writes never commit, bit rot quarantines, and a corrupt newest
    # level falls back to the previous valid one.

    def _checkpoint(self, itemsets, levels, level, next_k, item_map, n,
                    min_count, config, fault_plan=None):
        if self.checkpoint_dir is None:
            return
        from repro.distributed import checkpoint as ckpt

        with span("repro.checkpoint", k=next_k - 1):
            # ``level`` arrives in dense ids; persist original ids so a
            # restart (which recomputes the dense remap) stays consistent.
            orig_level = [[int(item_map[i]) for i in s] for s in level]
            extra = {
                "itemsets": [[list(s), c] for s, c in itemsets.items()],
                "levels": [dataclasses.asdict(s) for s in levels],
                "level": orig_level,
                "next_k": next_k,
                "n": n,
                "min_count": min_count,
                "config": json.dumps(config, sort_keys=True),
            }
            ckpt.save(self.checkpoint_dir, step=next_k,
                      tree={"item_map": np.asarray(item_map)}, extra=extra,
                      fault_plan=fault_plan)

    def _try_restore(self, n: int, min_count: int, config: dict):
        if self.checkpoint_dir is None or \
                not os.path.isdir(self.checkpoint_dir):
            return None
        from repro.distributed import checkpoint as ckpt

        out = ckpt.load(self.checkpoint_dir)
        if out is None:
            return None
        tensors, _step, extra = out
        if int(extra.get("n", -1)) != n or \
                int(extra.get("min_count", -1)) != min_count:
            return None  # stale checkpoint from a different run
        if extra.get("config") != json.dumps(config, sort_keys=True):
            # Written under a different runner/store/strategy/max_k (or by a
            # pre-runtime version): resuming would silently mix configs.
            return None
        itemsets = {tuple(s): int(c) for s, c in extra["itemsets"]}
        levels = [JobProfile(**d) for d in extra["levels"]]
        level = [tuple(s) for s in extra["level"]]
        next_k = int(extra["next_k"])
        item_map = np.asarray(tensors["item_map"])
        # Stored levels are in original ids; the loop needs dense ids.
        remap = {int(orig): dense for dense, orig in enumerate(item_map)}
        dense_level = [tuple(remap[i] for i in s) for s in level]
        return itemsets, levels, dense_level, next_k, item_map
