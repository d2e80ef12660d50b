"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before first init.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def compat_make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the counting runtime
    places data with ``NamedSharding`` and lets the partitioner propagate
    it, which is what ``Auto`` axes mean."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat_make_mesh(shape, axes)


def make_data_mesh(n_shards=None):
    """1-D data-parallel mesh for the counting runtime's ShardedRunner:
    transactions shard over ``data``, candidates replicate."""
    n = n_shards or jax.device_count()
    return compat_make_mesh((n,), ("data",))


def make_data_cand_mesh(n_data=None, n_cand=None):
    """2-D ``data x cand`` mesh for candidate-axis sharding: transactions
    shard over ``data`` (replicated over ``cand``), each counting wave's
    candidate tensors shard over ``cand`` (replicated over ``data``).

    With no sizes given, ``cand`` takes the largest power of two not above
    sqrt(device_count) that divides it (8 devices -> 4x2 data x cand), so
    both the transaction and the candidate axis get parallelism.

    Oversubscription fails here with the requested grid spelled out, not as
    an opaque error inside ``jax.make_mesh`` after the runner is half-built
    (shard-local encode makes a wrong mesh shape expensive to debug: every
    per-store layout in ``candidate_shard_axes()`` keys off these axes).
    """
    total = jax.device_count()
    if n_cand is None:
        if n_data is not None:
            n_cand = max(1, total // n_data)
        else:
            n_cand = 1
            while n_cand * 2 * n_cand * 2 <= total and total % (n_cand * 2) == 0:
                n_cand *= 2
    if n_data is None:
        n_data = max(1, total // n_cand)
    if n_data * n_cand > total:
        raise ValueError(
            f"data x cand mesh {n_data}x{n_cand} needs {n_data * n_cand} "
            f"devices but only {total} exist (force more with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=N)"
        )
    return compat_make_mesh((n_data, n_cand), ("data", "cand"))


def make_host_mesh(model_axis: int = 1):
    """Mesh over whatever devices exist (tests / single host)."""
    n = jax.device_count()
    assert n % model_axis == 0
    return compat_make_mesh((n // model_axis, model_axis), ("data", "model"))
