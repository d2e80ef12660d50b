"""Blocked Pallas TPU kernel: bitmap-matmul support counting with fused
threshold-compare and in-kernel partial-sum accumulation (the MapReduce
"combiner" folded into the matmul epilogue).

Grid: (C_blocks, N_blocks, F_blocks) — for one candidate block we stream
transaction blocks through VMEM, compute the (Nb, Cb) match-dot on the MXU
tile-by-tile over F, compare against k in the epilogue of the last F tile and
accumulate the per-candidate hit count into the output block. The N dimension
is the reduction the combiner performs; output block index depends only on the
candidate block, so XLA keeps it resident while N streams.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(t_ref, c_ref, kvec_ref, out_ref, acc_ref, *, n_fblocks: int):
    nb = pl.program_id(1)
    fb = pl.program_id(2)

    @pl.when(fb == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # MXU tile: (Nb, Fb) x (Cb, Fb)^T partial dot, f32 accumulation.
    acc_ref[...] += jax.lax.dot_general(
        t_ref[...], c_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(fb == n_fblocks - 1)
    def _epilogue():
        # Fused compare + combiner: per-candidate hit count for this N block.
        # kvec and the output are (1, Cb) rows: candidates run along lanes.
        matched = acc_ref[...] == kvec_ref[...].astype(jnp.float32)
        partial = jnp.sum(matched.astype(jnp.int32), axis=0, keepdims=True)

        @pl.when(nb == 0)
        def _init():
            out_ref[...] = partial

        @pl.when(nb != 0)
        def _accum():
            out_ref[...] += partial


def support_count_pallas(
    bitmap: jnp.ndarray,  # (N, F) bf16 {0,1}
    khot: jnp.ndarray,    # (C, F) bf16 k-hot
    kvec: jnp.ndarray,    # (C,) int32
    *,
    block_n: int = 512,
    block_c: int = 512,
    block_f: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    n, f = bitmap.shape
    c, f2 = khot.shape
    assert f == f2 and kvec.shape == (c,)
    assert n % block_n == 0 and c % block_c == 0 and f % block_f == 0, (
        f"shapes ({n},{f})x({c},{f}) must divide blocks "
        f"({block_n},{block_c},{block_f}); pad via ops.support_count"
    )
    n_fblocks = f // block_f
    grid = (c // block_c, n // block_n, n_fblocks)

    return pl.pallas_call(
        functools.partial(_kernel, n_fblocks=n_fblocks),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, block_f), lambda cb, nb, fb: (nb, fb)),
            pl.BlockSpec((block_c, block_f), lambda cb, nb, fb: (cb, fb)),
            pl.BlockSpec((1, block_c), lambda cb, nb, fb: (0, cb)),
        ],
        out_specs=pl.BlockSpec((1, block_c), lambda cb, nb, fb: (0, cb)),
        out_shape=jax.ShapeDtypeStruct((1, c), jnp.int32),
        scratch_shapes=[pltpu.VMEM((block_n, block_c), jnp.float32)],
        interpret=interpret,
    )(bitmap.astype(jnp.bfloat16), khot.astype(jnp.bfloat16),
      kvec.reshape(1, c))[0]
