"""Each cell's run rehearsed on the CPU at a tiny size through
``bench.harness.run``, and the command's refusal to run without a TPU."""

import io
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())

# Tiny sizes: the configuration's generator and the traffic, overridden.
SHRINK = {
    "t10.batch": {"generator": {"n_transactions": 2000, "n_items": 200,
                                "n_patterns": 50},
                  "traffic": {"min_support": 0.02}},
    "t40.batch": {"generator": {"n_transactions": 600, "n_items": 300,
                                "n_patterns": 200},
                  "traffic": {"min_support": 0.12}},
    "t10.stream": {"generator": {"n_transactions": 3000, "n_items": 200,
                                 "n_patterns": 50},
                   "traffic": {"n_slots": 4, "slot_size": 256,
                               "batch_size": 64, "min_support": 0.03,
                               "trace_cycles": 4, "check_queries": 6}},
}


def rehearse(workload, seed, trace, tmp_path, seconds=0.5):
    out, err = io.StringIO(), io.StringIO()
    res = harness.run(workload, seed, seconds, trace, require_tpu=False,
                      shrink=SHRINK[workload], cache_dir=tmp_path,
                      compile_cache=False, out=out, err=err)
    return res, out.getvalue(), err.getvalue()


def expected(workload, group):
    c = harness.cell(workload)
    return {m["name"]: m["unit"] for m in c[group]}


def well_formed(res, out, err):
    last = json.loads(out.strip().splitlines()[-1])
    assert last == json.loads(json.dumps(res))
    assert list(last)[:4] == ["correct", "attempted", "failed", "metrics"]
    assert list(last)[-1] == "checks"
    assert set(last["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    # The numbers compared, each beside its limit, end standard error.
    tail = err.strip().splitlines()[-len(last["checks"]):]
    assert [ln.split(":")[0] for ln in tail] == [
        f"check {k}" for k in last["checks"]]
    return last


@pytest.mark.parametrize("workload", ["t10.batch", "t40.batch",
                                      "t10.stream"])
def test_rehearsal_prints_a_well_formed_last_line(workload, tmp_path):
    res, out, err = rehearse(workload, 2**40 + 7, False, tmp_path)
    last = well_formed(res, out, err)
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 1
    assert {k: v["unit"] for k, v in last["metrics"].items()} == expected(
        workload, "end_to_end")
    assert last["device"]["platform"] == "cpu"
    assert "breakdown" not in last
    if workload.endswith(".batch"):
        assert "encoded-dataset cache hits in the window: 0" in err


@pytest.mark.parametrize("workload", ["t10.batch", "t10.stream"])
def test_traced_rehearsal_reports_per_layer_metrics(workload, tmp_path):
    res, out, err = rehearse(workload, 3, True, tmp_path)
    last = well_formed(res, out, err)
    assert last["correct"] is True
    want = expected(workload, "per_layer")
    # The CPU has no device plane: the device-trace metrics are left out.
    sources = {m["name"]: m["source"] for m in BENCH["per_layer"]}
    got = {k: v["unit"] for k, v in last["metrics"].items()}
    assert got == {k: u for k, u in want.items()
                   if sources[k] != "device_trace"}


def _cpu_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (str(ROOT / "src"), env.get("PYTHONPATH")) if p)
    return env


def test_too_few_devices_is_refused():
    import jax

    with pytest.raises(harness.NoDevice, match="4 chips"):
        harness.device_info(len(jax.devices()) + 3, require_tpu=False)


def test_command_refuses_without_tpu():
    out = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "t10.batch", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env=_cpu_env())
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
    assert "no TPU" in out.stderr
