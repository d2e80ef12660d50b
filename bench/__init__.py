"""The on-chip benchmark of the Apriori miner: ``python bench/run.py``.

Everything that defines the yardstick lives here: the deployments
(``configs/``), the traffic mixes (``traffic/``) and the drivers of their
kinds (``kinds/``), one reader per per-layer metric (``metrics/``), the
data generators (``gen/``), the plain reference (``reference.py``), the
trace reduction (``trace.py``) and the table of peaks (``peaks.json``).
The program under test is only imported.
"""

import importlib.util
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent


def load(group: str, name: str):
    """The module ``bench/<group>/<name>.py``, found by the name a data file
    gives: a traffic kind, a generator or a per-layer metric's reader.  A
    later piece is a file of its own; nothing here lists them."""
    path = HERE / group / f"{name}.py"
    if not path.is_file():
        raise ValueError(f"no {group} {name!r}: {path} is missing")
    key = f"bench_{group}_{name}".replace(".", "_")
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        spec.loader.exec_module(mod)
    return sys.modules[key]
