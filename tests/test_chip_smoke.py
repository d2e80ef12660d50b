"""``chip_smoke.py`` rehearsed on the CPU: every phase at a tiny size, the
four-device meshes on forced host devices, and its refusal to run — or to
print a verdict — when JAX finds no TPU."""

import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
_spec = importlib.util.spec_from_file_location("chip_smoke",
                                               ROOT / "chip_smoke.py")
chip_smoke = importlib.util.module_from_spec(_spec)
sys.modules["chip_smoke"] = chip_smoke  # dataclasses look the module up
_spec.loader.exec_module(chip_smoke)


def _cpu_env(**extra) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu", **extra)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (str(ROOT / "src"), env.get("PYTHONPATH")) if p)
    return env


def test_rehearsal_one_chip_phases_match_oracle():
    lines = []
    chip_smoke.run_one_chip(chip_smoke.REHEARSAL,
                            chip_smoke.Run(log=lines.append))
    done = [line for line in lines if " done " in line]
    names = {line.split("]")[0].lstrip("[") for line in done}
    assert {"batch/oracle", "batch/chunked", "service",
            "kernels/support_count",
            "kernels/packed_support_count"} <= names
    assert sum(n.startswith("batch/host_loop/") for n in names) == 5
    assert sum(n.startswith("batch/ladder/") for n in names) == 5
    assert all("platform=cpu" in line and "wall_s=" in line
               and "compile_s=" in line for line in done)


def test_rehearsal_four_devices_both_meshes():
    code = ("import chip_smoke as c; "
            "c.run_four_chips(c.REHEARSAL, c.Run())")
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True,
        text=True, timeout=600,
        env=_cpu_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert out.returncode == 0, out.stderr[-2000:]
    for name in ("mesh/data/host_loop", "mesh/data/ladder",
                 "mesh/data_x_cand/host_loop", "mesh/data_x_cand/ladder"):
        assert f"[{name}] done platform=cpu" in out.stdout
    assert "devices=4" in out.stdout


def test_refuses_without_tpu():
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         cwd=ROOT, capture_output=True, text=True,
                         timeout=300, env=_cpu_env())
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no TPU" in out.stderr


def test_failed_phase_is_reported_and_the_next_runs():
    lines = []
    run = chip_smoke.Run(log=lines.append, keep_going=True)
    with run.phase("bad"):
        raise AssertionError("mismatch")
    with run.phase("good"):
        pass
    assert run.failures == ["bad: AssertionError: mismatch"]
    assert any(line.startswith("[bad] FAILED") for line in lines)
    assert any(line.startswith("[good] done") for line in lines)
    with pytest.raises(AssertionError):
        with run.phase("oracle", fatal=True):
            raise AssertionError("needed by later phases")
