"""The entry points' persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
when it is set, else one fixed, git-ignored directory in the checkout.

Each case runs in a fresh interpreter: turning the cache on is process-wide.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

_PROBE = """
import json, jax, jax.numpy as jnp
from repro.launch.compile_cache import DEFAULT_DIR, enable_compile_cache
path = enable_compile_cache()
jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(8)).block_until_ready()
print(json.dumps({"path": path, "default": str(DEFAULT_DIR),
                  "config": jax.config.jax_compilation_cache_dir}))
"""


def _probe(**env_extra) -> dict:
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", **env_extra)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (str(ROOT / "src"), env.get("PYTHONPATH")) if p)
    out = subprocess.run([sys.executable, "-c", _PROBE], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_env_var_moves_the_cache(tmp_path):
    got = _probe(JAX_COMPILATION_CACHE_DIR=str(tmp_path),
                 JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    assert got["path"] == got["config"] == str(tmp_path)
    assert any(tmp_path.iterdir()), "no compiled program was cached"


def test_default_is_one_ignored_path_in_the_checkout():
    got = _probe(JAX_ENABLE_COMPILATION_CACHE="false")
    assert got["path"] == got["config"] == got["default"]
    assert Path(got["default"]) == ROOT / ".jax_compile_cache"
    ignored = (ROOT / ".gitignore").read_text().splitlines()
    assert ".jax_compile_cache/" in ignored
