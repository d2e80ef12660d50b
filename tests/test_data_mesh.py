"""The ``data`` mesh path (``ShardedRunner``) on four forced CPU devices:
its mines equal the plain reference and the one-device mine, each shard's
partial counts are a share of the whole, and the jobs and spans record the
shards and the bytes each device adds into the psum.

Each check runs in a child process started with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``.  The draw has
N = 1,203 baskets: 4 does not divide it (the last shard is padded), and
with ``block_n`` 128 each shard of 301 rows ends in a ragged tail block.
"""

import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]

_PRELUDE = """
import sys
import numpy as np
import jax
from bench import data, reference
from repro.core import FrequentItemsetMiner
from repro.launch.mesh import make_data_mesh

assert jax.device_count() == 4, jax.devices()
N, BLOCK_N, SUPPORT = 1203, 128, 0.02
MAT = data.generate(dict(kind="quest", n_transactions=N,
                         avg_transaction_len=10, avg_pattern_len=4,
                         n_items=120, n_patterns=40, corruption_mean=0.5,
                         seed=5))
TX = data.to_lists(MAT)
MESH = make_data_mesh(4)


def miner(**kw):
    return FrequentItemsetMiner(min_support=SUPPORT, block_n=BLOCK_N, **kw)
"""

_EQUALS = """
from repro.core.runtime import ShardedRunner

one = miner().mine(TX)
m = miner(mesh=MESH)
four = m.mine(TX)
assert isinstance(m.active_runner, ShardedRunner)
want = reference.frequent_itemsets(MAT, SUPPORT)
assert max(len(s) for s in want) >= 3, "too shallow a lattice to check"
assert four.itemsets == want, "sharded mine != reference"
assert one.itemsets == want, "one-device mine != reference"
"""

_SHARES = """
from jax.sharding import PartitionSpec as P
from repro.core.runtime.engine import MapReduceEngine
from repro.core.runtime.runners import JaxRunner

runner = JaxRunner(block_n=BLOCK_N)
runner.ingest(TX)
hist, _ = runner.job1()
items = np.nonzero(hist >= reference.min_count(SUPPORT, N))[0]
runner.place(items)
enc = runner.engine._enc
iu, ju = np.triu_indices(len(items), 1)
chunk = np.stack([iu, ju], axis=1).astype(np.int32)
whole = runner.engine.count_candidates(chunk)

eng = MapReduceEngine(mesh=MESH, block_n=BLOCK_N)
eng.place(enc)
cands = eng._dispatch_encode(chunk)
local = jax.jit(jax.shard_map(
    lambda tr, cd: eng._blocked_count(tr, cd)[None], mesh=MESH,
    in_specs=(jax.tree.map(lambda _: P("data"), eng._trans_device),
              eng._cand_specs(cands)),
    out_specs=P("data")))(eng._trans_device, cands)
parts = np.asarray(local)[:, :len(chunk)].astype(np.int64)
assert parts.shape == (4, len(chunk)), parts.shape
assert (parts.sum(axis=0) == whole).all(), "shares do not add up"
assert all((p < whole).any() for p in parts), "a shard counted it all"
assert (eng.count_candidates(chunk) == whole).all(), "psum != one device"
"""

_COUNTERS = """
from repro.core.runtime import spans

seen = []
init = spans.span.__init__


def recording_init(self, name, **counts):
    seen.append((name, counts))
    init(self, name, **counts)


spans.span.__init__ = recording_init


def mine(**kw):
    seen.clear()
    m = miner(**kw)
    res = m.mine(TX)
    puts = [(n, c) for n, c in seen
            if n in ("repro.job1.put", "repro.place.put")]
    return res, m.active_runner.n_raw_items, puts


four, n_raw, puts = mine(mesh=MESH)
rows = -(-N // 4)
assert puts == [("repro.job1.put", {"shards": 4, "rows": rows}),
                ("repro.place.put", {"shards": 4, "rows": rows})], puts
job1, *levels = four.levels
assert levels and all(lv.data_shards == 4 for lv in four.levels)
assert job1.reduce_bytes == 4 * n_raw, (job1.reduce_bytes, n_raw)
for lv in levels:  # one chunk a level here, padded to 128 rows
    assert lv.n_candidates < 32_768
    assert lv.reduce_bytes == 4 * 128 * -(-lv.n_candidates // 128), lv

# The fused ladder and a combined (FPC) job fill the counters too.
for kw in ({"device_loop": True, "trim": False}, {"strategy": "fpc"}):
    res, _, _ = mine(mesh=MESH, **kw)
    assert res.itemsets == four.itemsets, kw
    assert all(lv.data_shards == 4 and lv.reduce_bytes > 0
               for lv in res.levels), (kw, res.levels)

one, _, puts = mine()
assert puts == [("repro.job1.put", {"shards": 1, "rows": N}),
                ("repro.place.put", {"shards": 1, "rows": N})], puts
assert all(lv.data_shards == 1 and lv.reduce_bytes == 0
           for lv in one.levels)
"""

_GRAM = """
import itertools
from repro.core.runtime import ShardedRunner
from repro.core.runtime.engine import MapReduceEngine
from repro.core.stores import encode_db

# A mine through ShardedRunner: each shard counts every k = 2 chunk by the
# Gram path (F_pad 128), no deeper one; the result equals the reference.
m = miner(mesh=MESH, cand_block=128)
res = m.mine(TX)
assert isinstance(m.active_runner, ShardedRunner)
assert res.itemsets == reference.frequent_itemsets(MAT, SUPPORT)
job1, c2, *deeper = res.levels
assert c2.gram_chunks == -(-c2.n_candidates // 128) > 1, c2
assert deeper and all(lv.gram_chunks == 0 for lv in deeper)

# Both sides of the predicate on the mesh, over F_pad 384: chunks of 512
# pairs by the Gram path, of 128 by the gathers; both psum to the counts.
enc = encode_db(TX, n_items=300)
pairs = np.array(list(itertools.combinations(range(120), 2)), np.int32)
want = (enc.bitmap[:, pairs[:, 0]] & enc.bitmap[:, pairs[:, 1]]).sum(axis=0)
for cand_block, gram in ((512, -(-len(pairs) // 512)), (128, 0)):
    eng = MapReduceEngine(mesh=MESH, block_n=BLOCK_N, cand_block=cand_block)
    eng.place(enc)
    got = eng.count_candidates(pairs)
    assert (got == want).all(), cand_block
    assert eng.gram_chunks == gram, (cand_block, eng.gram_chunks)
"""


def _four_devices(code: str) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (str(ROOT), str(ROOT / "src"), env.get("PYTHONPATH"))
        if p)
    return subprocess.run(
        [sys.executable, "-c", _PRELUDE + code + "\nprint('MESH_OK')\n"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)


@pytest.mark.parametrize("code", [_EQUALS, _SHARES, _COUNTERS, _GRAM],
                         ids=["mine_equals_reference_and_one_device",
                              "shard_partials_add_up_to_one_device_counts",
                              "data_shards_reduce_bytes_and_put_spans",
                              "gram_and_gather_pair_counts_on_the_mesh"])
def test_data_mesh_on_four_devices(code):
    out = _four_devices(code)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "MESH_OK" in out.stdout
