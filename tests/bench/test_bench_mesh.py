"""The four-chip cell ``t10x4.batch`` rehearsed on four forced CPU devices
at a tiny size, once with the reducer broken underneath, and the reader of
``reduce_mb.batch`` on records of one device.

A rehearsal runs ``bench.harness.run`` in a child process started with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``, so that the cell's
mesh of four exists; the look for a chip is skipped and the rest of the
run is the benchmark's own.
"""

import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import harness
from repro.core import FrequentItemsetMiner

from test_bench_rehearsal import expected, well_formed

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())

# 2,003 baskets: four shards of 501 rows, the last one padded.
SHRINK = {"generator": {"n_transactions": 2003, "n_items": 200,
                        "n_patterns": 50},
          "traffic": {"min_support": 0.02}}

_RUN = """
import sys
from pathlib import Path
from bench import harness
harness.run("t10x4.batch", {seed}, 0.5, {trace}, require_tpu=False,
            shrink={shrink!r}, cache_dir=Path({cache!r}),
            compile_cache=False)
"""

# The fault: the psum of partial counts left out, in Job1 and in every
# count wave.  Each device keeps its own shard's counts, and the result
# read back is one device's (shard_map is told not to check that the
# output it calls replicated is).
_NO_PSUM = """
import functools
import jax
jax.shard_map = functools.partial(jax.shard_map, check_vma=False)
jax.lax.psum = lambda x, axis_name, **kw: x
"""


def rehearse(tmp_path, seed, trace, fault=""):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (str(ROOT), str(ROOT / "src"), env.get("PYTHONPATH"))
        if p)
    code = fault + _RUN.format(seed=seed, trace=trace, shrink=SHRINK,
                               cache=str(tmp_path))
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-3000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    return well_formed(last, out.stdout, out.stderr), out.stderr


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_four_device_rehearsal(trace, tmp_path):
    last, err = rehearse(tmp_path, 2**40 + 15, trace)
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 1
    assert last["device"]["platform"] == "cpu"
    assert last["device"]["count"] == 4
    assert "mesh: {'data': 4} over devices [0, 1, 2, 3]" in err
    assert "encoded-dataset cache hits in the window: 0" in err
    got = {k: v["unit"] for k, v in last["metrics"].items()}
    if not trace:
        assert got == expected("t10x4.batch", "end_to_end")
        assert set(got) == {"mine_s", "setup_s"}
        return
    # The CPU has no device plane: the device-trace metrics are left out.
    sources = {m["name"]: m["source"] for m in BENCH["per_layer"]}
    assert got == {k: u for k, u in expected("t10x4.batch",
                                             "per_layer").items()
                   if sources[k] != "device_trace"}
    assert last["metrics"]["reduce_mb.batch"]["value"] > 0


def test_psum_left_out_makes_the_run_incorrect(tmp_path):
    last, _ = rehearse(tmp_path, 11, False, fault=_NO_PSUM)
    assert last["correct"] is False
    assert last["checks"]["wrong_support"]["value"] > 0 or last["failed"] > 0


def _records(levels):
    return SimpleNamespace(mines=[SimpleNamespace(levels=levels)])


def test_reduce_mb_is_silent_on_one_device():
    read = harness.reader("reduce_mb.batch")
    res = FrequentItemsetMiner(min_support=0.1).mine(
        [[1, 2, 3], [1, 2], [2, 3, 4], [1, 3], [1, 2, 3, 4]] * 20)
    levels = [dataclasses.asdict(lv) for lv in res.levels]
    assert len(levels) >= 2
    assert read(_records(levels)) is None
    # A program whose JobProfile has no data-mesh fields (before they came).
    bare = [{k: v for k, v in lv.items()
             if k not in ("data_shards", "reduce_bytes")} for lv in levels]
    assert read(_records(bare)) is None
    sharded = [dict(lv, data_shards=4, reduce_bytes=250_000)
               for lv in levels]
    assert read(_records(sharded)) == pytest.approx(0.25 * len(levels))
