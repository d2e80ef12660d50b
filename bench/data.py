"""The benchmark's data: one fixed base dataset per deployment, and a
seeded copy of it for every run.

A FIMI dataset is one fixed file, so each deployment has one base dataset:
the configuration's generator at its own generator seed.  It is generated
on a checkout's first run and kept under ``bench/.cache/`` (git-ignored).
Every run then derives its input from ``--seed``: the rows in a seeded
order and the item ids relabelled by a seeded permutation of the ids the
base uses.  Each seed thus gives different baskets of the same sizes (the
same basket lengths, the same number of frequent itemsets at every level),
so the work does not change with the seed, only its order.
"""

from __future__ import annotations

import hashlib
import json
import os
from pathlib import Path
from typing import List

import numpy as np

from bench import load

CACHE = Path(__file__).resolve().parent / ".cache"
PAD = -1


def padded(transactions) -> np.ndarray:
    """(N, L) int32 matrix of each basket's sorted ids, ``PAD``-filled."""
    n = len(transactions)
    lmax = max((len(t) for t in transactions), default=1)
    out = np.full((n, max(1, lmax)), PAD, dtype=np.int32)
    for i, t in enumerate(transactions):
        out[i, : len(t)] = sorted(t)
    return out


def to_lists(mat: np.ndarray) -> List[List[int]]:
    """Rows of a ``PAD``-filled matrix as lists of ids (pads dropped)."""
    lens = (mat != PAD).sum(axis=1)
    return [r[:n] for r, n in zip(mat.tolist(), lens.tolist())]


def generate(gen: dict) -> np.ndarray:
    """The base dataset a configuration's ``generator`` block describes:
    the ``generate`` of ``bench/gen/<kind>.py`` called with the block's
    other keys."""
    params = {k: v for k, v in gen.items() if k != "kind"}
    return padded(load("gen", gen["kind"]).generate(**params))


def base_dataset(name: str, gen: dict, cache: Path = CACHE) -> np.ndarray:
    """The deployment's base dataset, generated once per checkout and kept
    under a name that changes with any generator parameter."""
    key = hashlib.sha1(json.dumps(gen, sort_keys=True).encode()).hexdigest()
    path = cache / f"{name}-{key[:12]}.npy"
    if path.exists():
        return np.load(path)
    mat = generate(gen)
    cache.mkdir(parents=True, exist_ok=True)
    tmp = cache / f"{name}.{os.getpid()}.tmp.npy"
    np.save(tmp, mat)
    os.replace(tmp, path)
    return mat


def seeded(base: np.ndarray, seed: int, reorder: bool = True) -> np.ndarray:
    """The run's input: ``base`` with its item ids relabelled by a seeded
    permutation of the ids it uses and, with ``reorder``, its rows in a
    seeded order; each row sorted again."""
    rng = np.random.default_rng(seed)
    used = np.unique(base[base != PAD])
    lookup = np.full(int(used[-1]) + 2, PAD, dtype=np.int32)
    lookup[used] = used[rng.permutation(len(used))]
    rows = base[rng.permutation(base.shape[0])] if reorder else base
    mapped = lookup[np.where(rows == PAD, len(lookup) - 1, rows)]
    # Pads (-1) sort first; move them back to the end of each row.
    mapped = np.sort(np.where(mapped == PAD, np.iinfo(np.int32).max, mapped),
                     axis=1)
    return np.where(mapped == np.iinfo(np.int32).max, PAD,
                    mapped).astype(np.int32)
