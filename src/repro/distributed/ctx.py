"""Multi-host initialization and the matching device->host fetch.

The maxtext launch idiom: the same binary on every host, its role decided
entirely by environment variables.  A launcher exports
``REPRO_COORDINATOR`` / ``REPRO_NUM_PROCESSES`` / ``REPRO_PROCESS_ID`` and
every process calls :func:`initialize_multihost` before touching jax device
state; with the trio unset it is a no-op, so the single-process path is
byte-for-byte unchanged.  After initialization ``jax.device_count()`` is
*global* (processes x local devices), so the existing mesh builders
(``launch.mesh.make_data_mesh`` / ``make_data_cand_mesh``) span processes
with no changes — the counting engine's shard_map psum becomes a real
cross-process collective.  On the CPU backend cross-process collectives need
the gloo implementation, which must be selected before
``jax.distributed.initialize`` — that ordering is exactly why this is one
idempotent entry point instead of launcher boilerplate.
:func:`fetch_global` is the matching device->host fetch: fully-addressable
or fully-replicated arrays (every engine output on the data-sharded path)
fetch directly, anything else goes through ``process_allgather``.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Mapping, Optional

import numpy as np
import jax

# -- multi-host initialization (env-driven, the maxtext launch idiom) --------

ENV_COORDINATOR = "REPRO_COORDINATOR"      # host:port of process 0
ENV_NUM_PROCESSES = "REPRO_NUM_PROCESSES"  # total processes in the job
ENV_PROCESS_ID = "REPRO_PROCESS_ID"        # this process's index [0, N)


@dataclasses.dataclass(frozen=True)
class MultihostSpec:
    """One process's view of the job: who coordinates, how many, which am I."""

    coordinator: str
    num_processes: int
    process_id: int


def multihost_env(env: Optional[Mapping[str, str]] = None
                  ) -> Optional[MultihostSpec]:
    """Parse the launch env trio. ``None`` when unset (single-process run);
    a *partially* set trio is a launcher bug and raises rather than silently
    running single-process on one host of a would-be cluster."""
    env = os.environ if env is None else env
    raw = {name: env.get(name) for name in
           (ENV_COORDINATOR, ENV_NUM_PROCESSES, ENV_PROCESS_ID)}
    if all(v is None for v in raw.values()):
        return None
    missing = [name for name, v in raw.items() if v is None]
    if missing:
        raise ValueError(
            f"partial multihost environment: {missing} unset while "
            f"{[n for n, v in raw.items() if v is not None]} set — export "
            "all three or none")
    try:
        num = int(raw[ENV_NUM_PROCESSES])
        pid = int(raw[ENV_PROCESS_ID])
    except ValueError as e:
        raise ValueError(f"non-integer multihost environment: {e}") from None
    if num < 1:
        raise ValueError(f"{ENV_NUM_PROCESSES} must be >= 1, got {num}")
    if not 0 <= pid < num:
        raise ValueError(
            f"{ENV_PROCESS_ID} must be in [0, {num}), got {pid}")
    return MultihostSpec(raw[ENV_COORDINATOR], num, pid)


_MULTIHOST_ACTIVE: Optional[MultihostSpec] = None


def initialize_multihost(spec: Optional[MultihostSpec] = None,
                         env: Optional[Mapping[str, str]] = None
                         ) -> Optional[MultihostSpec]:
    """Idempotent ``jax.distributed`` init from the env trio (or ``spec``).

    No-op (returns ``None``) when the trio is unset.  Must run before
    anything touches jax device state: the CPU backend's cross-process
    collectives require selecting the gloo implementation *before*
    ``jax.distributed.initialize``, which itself must precede backend
    initialization.  Calling again with the same spec returns it; a
    *different* spec raises (one process is one cluster member, forever).
    """
    global _MULTIHOST_ACTIVE
    if spec is None:
        spec = multihost_env(env)
    if spec is None:
        return None
    if _MULTIHOST_ACTIVE is not None:
        if _MULTIHOST_ACTIVE != spec:
            raise RuntimeError(
                f"multihost already initialized as {_MULTIHOST_ACTIVE}, "
                f"refusing to re-initialize as {spec}")
        return _MULTIHOST_ACTIVE
    # Harmless on accelerator backends; required on CPU, where the default
    # collectives implementation cannot span processes.
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=spec.coordinator,
                               num_processes=spec.num_processes,
                               process_id=spec.process_id)
    _MULTIHOST_ACTIVE = spec
    return spec


def process_index() -> int:
    """This process's index (0 when jax is uninitialized or single-process)."""
    try:
        return int(jax.process_index())
    except Exception:
        return 0


def process_count() -> int:
    try:
        return int(jax.process_count())
    except Exception:
        return 1


def fetch_global(x) -> np.ndarray:
    """Device->host fetch that works on every sharding, including arrays
    spanning non-addressable devices of a process-spanning mesh.

    Fully-addressable (the whole single-process world) and fully-replicated
    arrays (every psum-reduced engine output) fetch directly; a
    cross-process *partitioned* array needs the explicit allgather — which
    is a collective, so all processes must fetch in the same order (the
    engine's strictly-FIFO result queue guarantees exactly that).
    """
    if isinstance(x, np.ndarray):
        return x
    if not isinstance(x, jax.Array):
        return np.asarray(x)
    if x.is_fully_addressable or x.is_fully_replicated:
        return np.asarray(x)
    from jax.experimental import multihost_utils

    return np.asarray(multihost_utils.process_allgather(x, tiled=True))

