"""Every module under ``repro`` imports cleanly.

Cheap rot detector: a stale import, a missing optional-dep gate, or a
syntax error in a rarely-exercised module (launch/, serve/, configs/)
surfaces here instead of in the first user's traceback — and the coverage
gate sees every module's definitions, so "uncovered" always means untested
code paths, never unimported files.
"""

import importlib
import json
import os
import pkgutil
import subprocess
import sys

import repro


def test_all_repro_modules_import():
    failures = []
    for mod in pkgutil.walk_packages(repro.__path__, prefix="repro."):
        try:
            importlib.import_module(mod.name)
        except Exception as e:  # collect all, report once
            failures.append(f"{mod.name}: {type(e).__name__}: {e}")
    assert not failures, "\n".join(failures)


# The mining path's import closure: what a miner, the service, the data and
# analytics layers and the elastic rebuild load, and what they must not.
MINING_IMPORTS = ("repro.core", "repro.serve", "repro.data",
                  "repro.analytics", "repro.distributed.elastic")
LM_STACK = ("repro.models", "repro.train", "repro.configs",
            "repro.distributed.sharding", "repro.distributed.compression",
            "repro.launch.hlo_stats")

_BOUNDARY_PROBE = """
import importlib, json, sys
for name in {mining!r}:
    importlib.import_module(name)
from repro.core import FrequentItemsetMiner
db = [[i % 7, (3 * i) % 11, (5 * i) % 13] for i in range(50)]
assert FrequentItemsetMiner(min_support=0.1).mine(db).itemsets
print(json.dumps(sorted(sys.modules)))
"""


def test_mining_path_imports_nothing_of_the_lm_stack():
    """A fresh interpreter that imports the mining surface and mines a
    50-basket DB holds no module of the inherited LM stack."""
    src = os.path.dirname(os.path.dirname(os.path.abspath(repro.__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (src, env.get("PYTHONPATH")) if p)
    out = subprocess.run(
        [sys.executable, "-c", _BOUNDARY_PROBE.format(mining=MINING_IMPORTS)],
        env=env, capture_output=True, text=True, timeout=300, check=True)
    loaded = json.loads(out.stdout.strip().splitlines()[-1])
    leaked = [m for m in loaded
              if any(m == p or m.startswith(p + ".") for p in LM_STACK)]
    assert not leaked, leaked
