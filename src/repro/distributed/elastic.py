"""Elastic restart of a mine: rebuild the counting mesh on the survivors.

When devices are lost mid-mine, ``FrequentItemsetMiner`` rebuilds the
largest usable ``data`` (or ``data x cand``) mesh from the devices left
(:func:`surviving_devices`, :func:`elastic_data_cand_mesh`), restores its
level checkpoint and resumes; counts are identical on every mesh shape.
"""

from __future__ import annotations

from typing import Sequence

import numpy as np
import jax


def surviving_devices(mesh, lost: int) -> list:
    """The devices left after ``lost`` die (simulated: the tail of the mesh's
    device grid is the casualty set, so reruns are deterministic)."""
    devices = list(np.asarray(mesh.devices).ravel())
    if lost >= len(devices):
        return []
    return devices[: len(devices) - lost]


def elastic_data_cand_mesh(devices: Sequence, want_cand: bool = False):
    """Largest usable counting mesh on the surviving devices.

    ``want_cand=False`` rebuilds the 1-D ``data`` mesh over every survivor.
    ``want_cand=True`` rebuilds a 2-D ``data x cand`` grid: ``cand`` takes
    the largest power of two not above sqrt(n) that divides ``n`` (mirroring
    ``launch.mesh.make_data_cand_mesh``'s default), shrinking candidate
    parallelism before data parallelism since the data axis carries the
    transaction tensors.  Counts are bit-identical on every mesh shape (the
    sharding parity suites pin that), so elasticity never changes results —
    only how much memory and parallelism the resumed run gets.
    """
    devices = list(devices)
    n = len(devices)
    if n == 0:
        raise ValueError("no surviving devices to rebuild a mesh on")
    if not want_cand:
        return jax.sharding.Mesh(np.asarray(devices).reshape(n), ("data",))
    n_cand = 1
    while n_cand * 2 * n_cand * 2 <= n and n % (n_cand * 2) == 0:
        n_cand *= 2
    n_data = n // n_cand
    usable = n_data * n_cand
    grid = np.asarray(devices[:usable]).reshape(n_data, n_cand)
    return jax.sharding.Mesh(grid, ("data", "cand"))
