#!/usr/bin/env python3
"""End-to-end smoke of the Apriori miner on a TPU.

    python chip_smoke.py             # one chip: batch mine, service, kernels
    python chip_smoke.py --chips 4   # four chips: the two sharded meshes only

Drives the main path through the entry points a user calls, at the size of
the paper's workload: T10I4D100K (100,000 baskets) mined at support 0.0025.
Every result must equal the ``hash_table_trie`` oracle, itemsets and
supports; each phase prints the device and its own wall and compile seconds.
A failed phase is reported and the next one runs.  The last line of standard
output is the JSON verdict ``{"ok": true, "device": {...}}``.  The script
exits non-zero and prints no verdict when JAX finds no TPU or any phase
fails.

``run_one_chip`` and ``run_four_chips`` take a :class:`Config` and a
:class:`Run`; the repo's tests call them with ``REHEARSAL`` on the CPU.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import tempfile
import time
import traceback
from pathlib import Path
from typing import Dict, List, Optional, Sequence

SRC = Path(__file__).resolve().parent / "src"

# JAX's compile-duration events: tracing, lowering, and the backend compile
# (which includes a read from the persistent cache on a hit).
_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


@dataclasses.dataclass(frozen=True)
class Config:
    """What the smoke runs; ``FULL`` is the chip run."""

    dataset: str = "T10I4D100K"
    scale: float = 1.0
    seed: int = 42
    min_support: float = 0.0025
    # Frequent itemsets the oracle must find (None: not pinned).
    expect_itemsets: Optional[int] = 1925
    min_chunks: int = 4
    # Service: a basket-evicted window over a seeded stream.
    serve_support: float = 0.005
    serve_slots: int = 16
    serve_slot_size: int = 4096
    serve_batch: int = 1024
    serve_scale: float = 1.0
    serve_batches: int = 40
    query_every: int = 4
    # Kernels: one C2 chunk of this many rows over the whole database.
    kernel_cands: int = 32_768
    interpret: Optional[bool] = False


FULL = Config()

# The CPU rehearsal the tests run: the same phases at a size that reaches
# k = 3 in seconds, with the kernels in interpret mode.
REHEARSAL = Config(scale=0.005, min_support=0.01, expect_itemsets=579,
                   serve_support=0.05, serve_slots=4,
                   serve_slot_size=128, serve_batch=64, serve_scale=0.02,
                   serve_batches=12, kernel_cands=256, interpret=None)


class Run:
    """Phase bookkeeping: the device, wall seconds and compile seconds of
    every phase, summed from JAX's monitoring events, and the phases that
    failed.

    With ``keep_going`` a failed phase is logged and recorded and the next
    one starts, so one chip run reports every failure; without it the first
    failure raises, as the rehearsal tests want.
    """

    def __init__(self, log=print, keep_going: bool = False) -> None:
        import jax

        self.log = log
        self.keep_going = keep_going
        self.failures: List[str] = []
        self.compile_s = 0.0
        self.programs = 0  # executables built or read from the cache
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event in _COMPILE_EVENTS:
            self.compile_s += duration
        if event == _COMPILE_EVENTS[-1]:
            self.programs += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def totals(self) -> str:
        return (f"compile_s={self.compile_s} programs={self.programs} "
                f"cache_hits={self.cache_hits}")

    @contextlib.contextmanager
    def phase(self, name: str, fatal: bool = False):
        """Time one phase; ``fatal`` phases raise even with ``keep_going``
        (later phases need what they build)."""
        dev = device_info()
        where = (f"platform={dev['platform']} device_kind={dev['kind']} "
                 f"devices={dev['count']}")
        c0, p0, h0 = self.compile_s, self.programs, self.cache_hits
        t0 = time.perf_counter()
        self.log(f"[{name}] start {where}")
        try:
            yield
        except Exception as e:
            if fatal or not self.keep_going:
                raise
            traceback.print_exc()
            self.failures.append(f"{name}: {type(e).__name__}: {e}")
            self.log(f"[{name}] FAILED {where} {type(e).__name__}: {e}")
            return
        self.log(f"[{name}] done {where} "
                 f"wall_s={time.perf_counter() - t0} "
                 f"compile_s={self.compile_s - c0} "
                 f"programs={self.programs - p0} "
                 f"cache_hits={self.cache_hits - h0}")


def device_info() -> Dict[str, object]:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def check_equal(name: str, got: Dict, want: Dict) -> None:
    """Itemsets AND supports must match the oracle exactly."""
    if got == want:
        return
    missing = sorted(set(want) - set(got))[:5]
    extra = sorted(set(got) - set(want))[:5]
    wrong = sorted(s for s in set(got) & set(want) if got[s] != want[s])[:5]
    raise AssertionError(
        f"{name}: {len(got)} itemsets vs oracle {len(want)}; "
        f"missing {missing} extra {extra} wrong supports "
        f"{[(s, got[s], want[s]) for s in wrong]}")


def oracle_mine(db, min_support: float) -> Dict:
    from repro.core import FrequentItemsetMiner, SimRunner

    runner = SimRunner(structure="hash_table_trie")
    return FrequentItemsetMiner(min_support=min_support,
                                runner=runner).mine(db).itemsets


def phase_batch(cfg: Config, run: Run, db, oracle: Dict) -> None:
    """Host SPC loop and fused ladder on every store, and one out-of-core
    mine from a chunked ``.dat`` reader."""
    from repro.core import FrequentItemsetMiner
    from repro.core.stores import ARRAY_STORES
    from repro.data import ChunkedDatasetReader, write_dat

    for store in ARRAY_STORES:
        with run.phase(f"batch/host_loop/{store}"):
            res = FrequentItemsetMiner(min_support=cfg.min_support,
                                       store=store).mine(db)
            check_equal(f"host loop {store}", res.itemsets, oracle)
    # Every store's fused k=2 step fits one v5e at this size
    # (tests/test_tpu_compile.py pins the largest, sorted_prefix's).
    for store in ARRAY_STORES:
        with run.phase(f"batch/ladder/{store}"):
            res = FrequentItemsetMiner(min_support=cfg.min_support,
                                       store=store, device_loop=True,
                                       trim=True).mine(db)
            check_equal(f"ladder {store}", res.itemsets, oracle)
    with run.phase("batch/chunked"), tempfile.TemporaryDirectory() as d:
        path = write_dat(os.path.join(d, "db.dat"), db)
        width = ChunkedDatasetReader(path).width
        budget = len(db) * width * 4 // (cfg.min_chunks + 1)
        reader = ChunkedDatasetReader(path, memory_budget_bytes=budget)
        if reader.n_chunks < cfg.min_chunks:
            raise AssertionError(f"only {reader.n_chunks} chunks: "
                                 f"{reader.describe()}")
        res = FrequentItemsetMiner(min_support=cfg.min_support).mine(reader)
        check_equal("chunked", res.itemsets, oracle)
        run.log(f"[batch/chunked] {reader.describe()}")


def phase_service(cfg: Config, run: Run) -> None:
    """A basket-evicted window over a seeded stream; every exact query
    equals the oracle over the window it was served on."""
    from repro.data import basket_stream
    from repro.serve import MiningService

    with run.phase("service"):
        svc = MiningService(min_support=cfg.serve_support,
                            n_slots=cfg.serve_slots,
                            slot_size=cfg.serve_slot_size, eviction="basket")
        try:
            queries = 0
            for ab in basket_stream(cfg.dataset, batch_size=cfg.serve_batch,
                                    scale=cfg.serve_scale, seed=0,
                                    repeat=True,
                                    max_batches=cfg.serve_batches):
                svc.ingest(ab.transactions)
                if (ab.seq + 1) % cfg.query_every:
                    continue
                res = svc.query()
                want = oracle_mine(svc.window(), cfg.serve_support)
                check_equal(f"service query after batch {ab.seq + 1}",
                            res.itemsets, want)
                queries += 1
                run.log(f"[service] batch {ab.seq + 1}: window "
                        f"{res.n_transactions}, {len(res.itemsets)} "
                        f"itemsets, refreshed={res.refreshed}, "
                        f"query_s={res.seconds}")
            if queries == 0:
                raise AssertionError("the service answered no query")
        finally:
            svc.close()


def phase_kernels(cfg: Config, run: Run, db, min_count: int,
                  oracle: Dict) -> None:
    """Both Pallas kernels over the whole encoded database and one C2
    chunk, against the same store's jnp count."""
    import jax
    import numpy as np

    from repro.core.engine import MapReduceEngine
    from repro.core.itemsets import apriori_gen_matrix
    from repro.core.stores import (
        BitmapMXUStore, dense_remap_padded, encode_db_from_padded,
        padded_from_transactions,
    )
    from repro.core.stores.packed_bitmap import pack_candidates_device
    from repro.kernels.support_count import (
        packed_support_count, support_count,
    )
    from repro.kernels.support_count.ops import resolve_interpret

    padded, n_raw = padded_from_transactions(db)
    hist = np.bincount(padded[padded < n_raw].ravel(), minlength=n_raw)
    item_map = np.nonzero(hist >= min_count)[0]
    enc = encode_db_from_padded(dense_remap_padded(padded, item_map, n_raw),
                                n_items=len(item_map))
    level1 = np.arange(len(item_map), dtype=np.int32).reshape(-1, 1)
    cand = apriori_gen_matrix(level1)[: cfg.kernel_cands]
    pairs = {(int(item_map[a]), int(item_map[b])) for a, b in cand}
    want_freq = {s: c for s, c in oracle.items() if s in pairs}
    interpreted = resolve_interpret(cfg.interpret)
    run.log(f"[kernels] N={enc.n_transactions} F_pad={enc.f_pad} "
            f"W={enc.n_words} C={cand.shape[0]} "
            f"mode={'interpret' if interpreted else 'mosaic'}")

    def check(name, fn, store, args):
        with run.phase(f"kernels/{name}"):
            text = jax.jit(fn).lower(*args).as_text()
            if not interpreted and "tpu_custom_call" not in text:
                raise AssertionError(f"{name}: no tpu_custom_call in HLO")
            got = np.asarray(jax.device_get(jax.jit(fn)(*args)))
            eng = MapReduceEngine(store=store)
            eng.place(enc)
            ref = eng.count_candidates(cand)
            if not np.array_equal(got.astype(np.int64), ref):
                bad = np.flatnonzero(got != ref)[:5]
                raise AssertionError(f"{name}: kernel != jnp count at rows "
                                     f"{bad}: {got[bad]} vs {ref[bad]}")
            freq = {(int(item_map[a]), int(item_map[b])): int(c)
                    for (a, b), c in zip(cand, got) if c >= min_count}
            check_equal(f"{name} frequent pairs", freq, want_freq)

    khot = BitmapMXUStore.encode_candidates(jax.numpy.asarray(cand),
                                            f_pad=enc.f_pad)
    check("support_count", lambda b, kh, kv: support_count(
        b, kh, kv, interpret=cfg.interpret), "bitmap",
        (enc.bitmap, khot["khot"], khot["kvec"]))
    cpacked = pack_candidates_device(jax.numpy.asarray(cand), enc.n_words)
    check("packed_support_count", lambda p, cp, kv: packed_support_count(
        p, cp, kv, interpret=cfg.interpret), "packed_bitmap",
        (enc.packed, cpacked, khot["kvec"]))


def load_with_oracle(cfg: Config, run: Run, name: str):
    """The batch dataset and its ``hash_table_trie`` mine."""
    from repro.data import get_dataset

    with run.phase(name, fatal=True):
        db = get_dataset(cfg.dataset, scale=cfg.scale, seed=cfg.seed)
        oracle = oracle_mine(db, cfg.min_support)
        run.log(f"[{name}] {len(db)} baskets, {len(oracle)} itemsets, "
                f"max k {max(map(len, oracle), default=0)}")
        if cfg.expect_itemsets is not None and \
                len(oracle) != cfg.expect_itemsets:
            raise AssertionError(f"oracle found {len(oracle)} itemsets, "
                                 f"expected {cfg.expect_itemsets}")
    return db, oracle


def run_one_chip(cfg: Config, run: Run) -> None:
    """Phases 1-3: batch mine, service, kernels."""
    import numpy as np

    db, oracle = load_with_oracle(cfg, run, "batch/oracle")
    phase_batch(cfg, run, db, oracle)
    phase_service(cfg, run)
    min_count = max(1, int(np.ceil(cfg.min_support * len(db))))
    phase_kernels(cfg, run, db, min_count, oracle)


def run_four_chips(cfg: Config, run: Run) -> None:
    """Phase 1's dataset on two meshes over all devices (1-D data, then
    2x2 data x cand), each through the host loop and the ladder, against
    the one-chip result and the oracle."""
    from repro.core import FrequentItemsetMiner, ShardedRunner
    from repro.launch.mesh import make_data_cand_mesh, make_data_mesh

    db, oracle = load_with_oracle(cfg, run, "mesh/oracle")
    with run.phase("mesh/one_chip", fatal=True):
        one = FrequentItemsetMiner(min_support=cfg.min_support).mine(db)
        check_equal("one chip", one.itemsets, oracle)
    meshes = [("data", make_data_mesh, ()),
              ("data_x_cand", lambda: make_data_cand_mesh(2, 2), ("cand",))]
    for label, make, cand_axes in meshes:
        for device_loop in (False, True):
            name = f"mesh/{label}/{'ladder' if device_loop else 'host_loop'}"
            with run.phase(name):
                mesh = make()
                runner = ShardedRunner(mesh=mesh, cand_axes=cand_axes)
                # The ladder runs untrimmed here: these phases check the
                # sharded join and count, and the four trim programs would
                # add about half again to each mesh's compile time.
                res = FrequentItemsetMiner(
                    min_support=cfg.min_support, runner=runner,
                    device_loop=device_loop, trim=False).mine(db)
                run.log(f"[{name}] mesh {dict(mesh.shape)}")
                check_equal(name, res.itemsets, one.itemsets)
                check_equal(name, res.itemsets, oracle)


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded meshes over four chips")
    args = ap.parse_args(argv)

    import jax

    dev = device_info()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev['platform']})",
              file=sys.stderr)
        return 2
    if dev["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{dev['count']} device(s)", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    from repro.launch.compile_cache import enable_compile_cache

    def log(msg: str) -> None:
        print(msg, flush=True)

    log(f"compile cache: {enable_compile_cache()}; jax {jax.__version__}; "
        f"memory_stats {jax.devices()[0].memory_stats()}")
    run = Run(log, keep_going=True)
    t0 = time.perf_counter()
    (run_four_chips if args.chips == 4 else run_one_chip)(FULL, run)
    log(f"total wall_s={time.perf_counter() - t0} {run.totals()}")
    if run.failures:
        print("chip_smoke: failed phases:\n  " + "\n  ".join(run.failures),
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
