"""jit'd public wrappers for the support-count kernels: pad inputs to block
multiples, dispatch to the Pallas kernel (interpret mode on CPU, Mosaic on
TPU), trim pads.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.support_count.kernel import support_count_pallas
from repro.kernels.support_count.packed import packed_support_count_pallas


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def resolve_interpret(interpret: bool | None = None) -> bool:
    """Whether a kernel call runs in Pallas interpret mode.

    ``None`` picks interpret mode on the CPU backend (where the kernel body
    is validated) and Mosaic everywhere else.  Interpret mode is refused on
    the TPU, so a chip run never counts through the interpreter.
    """
    backend = jax.default_backend()
    if interpret is None:
        return backend == "cpu"
    if interpret and backend == "tpu":
        raise ValueError("interpret mode is CPU-only; on the TPU the kernel "
                         "compiles with Mosaic (pass interpret=False or None)")
    return bool(interpret)


@functools.partial(
    jax.jit, static_argnames=("block_n", "block_c", "block_f", "interpret")
)
def _padded_call(bitmap, khot, kvec, *, block_n, block_c, block_f, interpret):
    n, f = bitmap.shape
    c = khot.shape[0]
    np_, cp, fp = _round_up(n, block_n), _round_up(c, block_c), _round_up(f, block_f)
    bitmap = jnp.pad(bitmap, ((0, np_ - n), (0, fp - f)))
    khot = jnp.pad(khot, ((0, cp - c), (0, fp - f)))
    # Padded candidates get k=-1: a zero dot never equals -1, so count 0.
    kvec = jnp.pad(kvec, (0, cp - c), constant_values=-1)
    out = support_count_pallas(
        bitmap, khot, kvec,
        block_n=block_n, block_c=block_c, block_f=block_f, interpret=interpret,
    )
    return out[:c]


def support_count(
    bitmap,
    khot,
    kvec,
    *,
    block_n: int = 512,
    block_c: int = 512,
    block_f: int = 512,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Count, for every candidate row of ``khot``, the number of ``bitmap``
    rows that contain all of its items. See kernel.py for the blocked design.

    ``interpret`` follows :func:`resolve_interpret`.
    """
    interpret = resolve_interpret(interpret)
    bitmap = jnp.asarray(bitmap)
    khot = jnp.asarray(khot)
    kvec = jnp.asarray(kvec, dtype=jnp.int32)
    # Clamp blocks for small problems (keeps the grid non-degenerate); the
    # bf16 transaction block keeps a 16-row sublane multiple.
    block_n = min(block_n, _round_up(bitmap.shape[0], 16))
    block_c = min(block_c, _round_up(khot.shape[0], 128))
    block_f = min(block_f, _round_up(bitmap.shape[1], 128))
    return _padded_call(
        bitmap, khot, kvec,
        block_n=block_n, block_c=block_c, block_f=block_f, interpret=interpret,
    )


@functools.partial(
    jax.jit, static_argnames=("block_n", "block_c", "block_w", "interpret")
)
def _packed_padded_call(packed, cpacked, kvec, *, block_n, block_c, block_w,
                        interpret):
    n, w = packed.shape
    c = cpacked.shape[0]
    np_, cp, wp = _round_up(n, block_n), _round_up(c, block_c), _round_up(w, block_w)
    packed = jnp.pad(packed, ((0, np_ - n), (0, wp - w)))
    cpacked = jnp.pad(cpacked, ((0, cp - c), (0, wp - w)))
    # Padded candidates get k=-1: a non-negative popcount never equals -1.
    kvec = jnp.pad(kvec, (0, cp - c), constant_values=-1)
    out = packed_support_count_pallas(
        packed, cpacked.T, kvec,
        block_n=block_n, block_c=block_c, block_w=block_w, interpret=interpret,
    )
    return out[:c]


def packed_support_count(
    packed,
    cpacked,
    kvec,
    *,
    block_n: int = 256,
    block_c: int = 256,
    block_w: int = 128,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Count, for every packed candidate row of ``cpacked``, the number of
    ``packed`` transaction rows whose AND-popcount reaches k. See packed.py
    for the blocked design.

    The word block is the lane dimension of the transaction block, so it is
    either all W words (W <= 128, no padding) or ``block_w`` rounded up to a
    multiple of 128.  ``interpret`` follows :func:`resolve_interpret`.
    """
    interpret = resolve_interpret(interpret)
    packed = jnp.asarray(packed, dtype=jnp.uint32)
    cpacked = jnp.asarray(cpacked, dtype=jnp.uint32)
    kvec = jnp.asarray(kvec, dtype=jnp.int32)
    # Clamp blocks for small problems (keeps the grid non-degenerate).
    block_n = min(block_n, _round_up(packed.shape[0], 8))
    block_c = min(block_c, _round_up(cpacked.shape[0], 128))
    w = packed.shape[1]
    block_w = w if w <= 128 else _round_up(block_w, 128)
    return _packed_padded_call(
        packed, cpacked, kvec,
        block_n=block_n, block_c=block_c, block_w=block_w, interpret=interpret,
    )
