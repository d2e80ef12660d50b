#!/usr/bin/env bash
# Single entry point for a local check: tier-1 tests, the markdown link
# check, and cross-backend parity smokes (runner, ladder, 2-D mesh, chaos,
# elastic, serving, out-of-core). Speed is measured on the chip only, by
# bench/run.py.
#
#   ./scripts/verify.sh
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "== tier-1: pytest =="
python -m pytest -x -q

echo "== docs: markdown link check =="
python scripts/check_links.py README.md docs/ARCHITECTURE.md ROADMAP.md

echo "== smoke: runner parity (sim vs jax vs sharded) =="
# Independent of the pytest fixtures above (different seed/params), and far
# cheaper than re-running the full parity matrix the suite just covered.
python - <<'PY'
import numpy as np
from repro.core import (FrequentItemsetMiner, JaxRunner, ShardedRunner,
                        SimRunner, brute_force_frequent)
from repro.data import quest_generator
from repro.launch.mesh import compat_make_mesh

db = quest_generator(n_transactions=150, avg_transaction_len=6, n_items=40,
                     n_patterns=25, seed=11)
oracle = brute_force_frequent(db, int(np.ceil(0.06 * len(db))))
for runner in [
    SimRunner(structure="hash_tree", n_mappers=4),
    JaxRunner(store="packed_bitmap"),
    ShardedRunner(store="perfect_hash", mesh=compat_make_mesh((1,), ("data",))),
]:
    res = FrequentItemsetMiner(min_support=0.06, runner=runner).mine(db)
    assert res.itemsets == oracle, runner.describe()
print("runner parity smoke OK (sim == jax == sharded == brute force)")
PY

echo "== smoke: device ladder (fused gen->count->prune + on-device trim) =="
python - <<'PY'
import numpy as np
from repro.core import FrequentItemsetMiner, brute_force_frequent
from repro.data import quest_generator

db = quest_generator(n_transactions=150, avg_transaction_len=6, n_items=40,
                     n_patterns=25, seed=11)
oracle = brute_force_frequent(db, int(np.ceil(0.06 * len(db))))
for trim in (False, True):
    res = FrequentItemsetMiner(min_support=0.06, store="packed_bitmap",
                               device_loop=True, trim=trim).mine(db)
    assert res.itemsets == oracle, f"device_loop trim={trim} diverged"
pads = [(p.n_pad, p.f_pad) for p in res.levels if p.n_pad]
assert all(a[0] >= b[0] and a[1] >= b[1] for a, b in zip(pads, pads[1:])), pads
print("device-ladder smoke OK (fused == fused+trim == brute force), "
      "Npad/Fpad per level:", pads)
PY

echo "== smoke: 2-D data x cand mesh parity (forced 8 host devices) =="
# Candidate-axis sharding must be bit-identical to the replicated path; run
# in a subprocess so XLA_FLAGS takes effect before jax initializes.
XLA_FLAGS="--xla_force_host_platform_device_count=8" python - <<'PY'
import numpy as np
from repro.core import FrequentItemsetMiner, MapReduceEngine, ShardedRunner, \
    brute_force_frequent
from repro.data import quest_generator
from repro.launch.mesh import make_data_cand_mesh

db = quest_generator(n_transactions=150, avg_transaction_len=6, n_items=40,
                     n_patterns=25, seed=11)
oracle = brute_force_frequent(db, int(np.ceil(0.06 * len(db))))
mesh = make_data_cand_mesh(2, 4)
runner = ShardedRunner(store="packed_bitmap", mesh=mesh, cand_axes=("cand",))
res = FrequentItemsetMiner(min_support=0.06, runner=runner).mine(db)
assert res.itemsets == oracle, runner.describe()
print("2-D mesh smoke OK (cand-sharded == brute force) on", runner.describe())
PY

echo "== smoke: chaos (fault injection + retry/speculation parity) =="
python - <<'PY'
import numpy as np
from repro.core import FrequentItemsetMiner, SimRunner
from repro.core.runtime import FaultPlan, RetryPolicy
from repro.core.runtime import faults as F
from repro.data import quest_generator

db = quest_generator(n_transactions=150, avg_transaction_len=6, n_items=40,
                     n_patterns=25, seed=11)
clean = FrequentItemsetMiner(min_support=0.06,
                             runner=SimRunner(structure="trie")).mine(db)
plan = FaultPlan(F.crash(k=2, slot=0), F.corrupt(k=2, slot=1),
                 F.hang(delay=2.0, k=2, slot=2))
with SimRunner(structure="trie", executor="thread", fault_plan=plan,
               retry=RetryPolicy(backoff=0.001, timeout=0.1)) as runner:
    res = FrequentItemsetMiner(min_support=0.06, runner=runner).mine(db)
assert res.itemsets == clean.itemsets, "recovery changed results"
assert len(plan.injected) == 3, plan.injected
print("chaos smoke OK: crash+corrupt+straggler recovered, "
      f"retries={sum(p.retries for p in res.levels)}, "
      f"spec_wins={sum(p.speculative_wins for p in res.levels)}, "
      "counts bit-identical")
PY

echo "== smoke: elastic device-loss recovery (forced 8 host devices) =="
XLA_FLAGS="--xla_force_host_platform_device_count=8" python - <<'PY'
import tempfile
import numpy as np
from repro.core import FrequentItemsetMiner, ShardedRunner, SimRunner
from repro.core.runtime import FaultPlan
from repro.core.runtime import faults as F
from repro.data import quest_generator
from repro.launch.mesh import make_data_cand_mesh

db = quest_generator(n_transactions=150, avg_transaction_len=6, n_items=40,
                     n_patterns=25, seed=11)
clean = FrequentItemsetMiner(min_support=0.06,
                             runner=SimRunner(structure="trie")).mine(db)
with tempfile.TemporaryDirectory() as d:
    plan = FaultPlan(F.device_loss(k=3, lost=4))
    runner = ShardedRunner(store="perfect_hash", mesh=make_data_cand_mesh(),
                           cand_axes=("cand",), fault_plan=plan)
    miner = FrequentItemsetMiner(min_support=0.06, runner=runner,
                                 checkpoint_dir=d)
    res = miner.mine(db)
    assert plan.injected, "device loss never fired"
    assert res.itemsets == clean.itemsets, "elastic resume changed results"
    mesh = miner.active_runner.engine.mesh
print("elastic smoke OK: lost 4/8 devices at k=3, resumed on",
      dict(zip(mesh.axis_names, mesh.devices.shape)), "- counts bit-identical")
PY

echo "== smoke: streaming mining service (ingest/evict -> query parity) =="
python - <<'PY'
import numpy as np
from repro.core import FrequentItemsetMiner
from repro.data import basket_stream
from repro.serve import MiningService

svc = MiningService(min_support=0.05, store="perfect_hash", n_slots=6,
                    slot_size=48, staleness=0.5, max_k=6)
delta_served = 0
stream = basket_stream("T10I4D100K", batch_size=48, scale=0.005, seed=11,
                       repeat=True, max_batches=10)
for ab in stream:
    svc.ingest(ab.transactions)
    res = svc.query()
    oracle = FrequentItemsetMiner(min_support=0.05, store="perfect_hash",
                                  max_k=6).mine(svc.window())
    assert res.itemsets == oracle.itemsets, (
        f"mid-stream query diverged from batch mine at batch {ab.seq}")
    delta_served += 0 if res.refreshed else 1
st = svc.stats()
svc.close()
print(f"serving smoke OK: 10 ingest/query rounds bit-identical to batch "
      f"miner ({delta_served} delta-served, {st['refreshes']} refreshes, "
      f"{st['delta_jobs']} delta jobs, window {st['window']})")
PY

echo "== smoke: per-basket eviction + certified stale serving =="
python - <<'PY'
import numpy as np
from repro.core import FrequentItemsetMiner
from repro.data import basket_stream
from repro.serve import MiningService

svc = MiningService(min_support=0.05, store="packed_bitmap", n_slots=4,
                    slot_size=48, staleness=0.5, max_k=6, eviction="basket")
stream = basket_stream("T10I4D100K", batch_size=48, scale=0.005, seed=11,
                       repeat=True, max_batches=8)
stale = 0
for ab in stream:
    svc.ingest(ab.transactions)
    if ab.seq == 3:
        svc.evict(5)                       # per-basket, mid-stream
    res = svc.query(staleness=4.0)         # never blocks on a refresh
    cert = res.certificate
    assert cert is not None
    if cert.is_exact(res.min_count):
        oracle = FrequentItemsetMiner(min_support=0.05, store="packed_bitmap",
                                      max_k=6).mine(svc.window())
        assert res.itemsets == oracle.itemsets, (
            f"certified-exact answer diverged at batch {ab.seq}")
    else:
        stale += 1
exact = svc.query()                        # exact over the final window
oracle = FrequentItemsetMiner(min_support=0.05, store="packed_bitmap",
                              max_k=6).mine(svc.window())
assert exact.itemsets == oracle.itemsets, "final exact query diverged"
cap = 4 * 48
assert exact.n_transactions <= cap, (exact.n_transactions, cap)
st = svc.stats()
svc.close()
print(f"hardening smoke OK: basket-capped window ({exact.n_transactions} <= "
      f"{cap}), mid-stream evict(5), {stale} certified-stale answers, final "
      f"exact query bit-identical ({st['refreshes']} refreshes)")
PY

echo "== smoke: out-of-core chunked streaming (bounded-memory parity) =="
python - <<'PY'
import os
import tempfile
import numpy as np
from repro.core import FrequentItemsetMiner, brute_force_frequent
from repro.data import ChunkedDatasetReader, get_dataset, write_dat

db = get_dataset("T10I4D100K", scale=0.002, seed=11)
with tempfile.TemporaryDirectory() as d:
    path = os.path.join(d, "db.dat.gz")
    write_dat(path, db)
    # A budget of ~1/5 of the padded matrix: the reader must stream the
    # file in >= 4 bounded chunks, never holding the whole DB on host.
    probe = ChunkedDatasetReader(path)
    budget = (len(db) * probe.width * 4) // 5
    reader = ChunkedDatasetReader(path, memory_budget_bytes=budget)
    assert reader.n_chunks >= 4, reader.describe()
    res = FrequentItemsetMiner(min_support=0.05, store="packed_bitmap",
                               max_k=6).mine(reader)
    mem = FrequentItemsetMiner(min_support=0.05, store="packed_bitmap",
                               max_k=6).mine(db)
    oracle = brute_force_frequent(db, res.min_count)
    assert res.itemsets == mem.itemsets == oracle, "chunked mine diverged"
    assert res.n_transactions == len(db)
    assert all(p.chunks == reader.n_chunks for p in res.levels)
print(f"out-of-core smoke OK: {reader.describe()} == in-memory == brute "
      f"force ({len(res.itemsets)} itemsets, budget {budget} bytes)")
PY

echo "verify OK"
