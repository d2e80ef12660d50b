"""Blocked Pallas TPU kernel: packed-bitmap popcount support counting with
fused threshold-compare and in-kernel partial-sum accumulation (the MapReduce
"combiner" folded into the popcount epilogue).

Transactions and candidates arrive packed 32 item columns per uint32 word, so
the tensors streamed through VMEM are 8x smaller than the uint8 bitmap and
16-32x smaller than the bf16/f32 k-hot operands of the matmul kernel. The
match-dot is replaced by pure VPU integer work: for each word w,
``popcount(t[:, w] & c[w, :])`` contributes the number of shared items in
that 32-column slab, accumulated over words into an (Nb, Cb) int32 scratch.

Candidates come in word-major, (W, C): a word of every candidate in the
block is then one lane-major row, and a word of every transaction is one
column of the (Nb, Wb) block. The word loop is unrolled statically over the
block's ``block_w`` words, so every slice is static (Mosaic has no lowering
for a dynamic slice along lanes).

Grid: (C_blocks, N_blocks, W_blocks) — same shape as the MXU kernel: for one
candidate block we stream transaction word-blocks through VMEM, accumulate
shared-item counts word-by-word, compare against k in the epilogue of the
last W block and fold the per-candidate hit count into the output block. The
output block index depends only on the candidate block, so XLA keeps it
resident while N streams.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(t_ref, c_ref, kvec_ref, out_ref, acc_ref, *, n_wblocks: int,
            block_w: int):
    nb = pl.program_id(1)
    wb = pl.program_id(2)

    @pl.when(wb == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    t = t_ref[...]  # (Nb, Wb) uint32
    c = c_ref[...]  # (Wb, Cb) uint32
    acc = acc_ref[...]
    for w in range(block_w):  # static unroll: every slice below is static
        shared = jax.lax.population_count(t[:, w : w + 1] & c[w : w + 1, :])
        acc = acc + shared.astype(jnp.int32)  # (Nb, Cb)
    acc_ref[...] = acc

    @pl.when(wb == n_wblocks - 1)
    def _epilogue():
        # Fused compare + combiner: per-candidate hit count for this N block.
        # kvec and the output are (1, Cb) rows: candidates run along lanes.
        matched = acc_ref[...] == kvec_ref[...]
        partial = jnp.sum(matched.astype(jnp.int32), axis=0, keepdims=True)

        @pl.when(nb == 0)
        def _init():
            out_ref[...] = partial

        @pl.when(nb != 0)
        def _accum():
            out_ref[...] += partial


def packed_support_count_pallas(
    packed: jnp.ndarray,   # (N, W) uint32, 32 item columns per word
    cpacked_t: jnp.ndarray,  # (W, C) uint32 packed k-hot rows, word-major
    kvec: jnp.ndarray,     # (C,) int32; pad rows carry -1 (never matched)
    *,
    block_n: int = 256,
    block_c: int = 256,
    block_w: int = 32,
    interpret: bool = False,
) -> jnp.ndarray:
    n, w = packed.shape
    w2, c = cpacked_t.shape
    assert w == w2 and kvec.shape == (c,)
    assert n % block_n == 0 and c % block_c == 0 and w % block_w == 0, (
        f"shapes ({n},{w})x({w},{c}) must divide blocks "
        f"({block_n},{block_c},{block_w}); pad via ops.packed_support_count"
    )
    n_wblocks = w // block_w
    grid = (c // block_c, n // block_n, n_wblocks)

    return pl.pallas_call(
        functools.partial(_kernel, n_wblocks=n_wblocks, block_w=block_w),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, block_w), lambda cb, nb, wb: (nb, wb)),
            pl.BlockSpec((block_w, block_c), lambda cb, nb, wb: (wb, cb)),
            pl.BlockSpec((1, block_c), lambda cb, nb, wb: (0, cb)),
        ],
        out_specs=pl.BlockSpec((1, block_c), lambda cb, nb, wb: (0, cb)),
        out_shape=jax.ShapeDtypeStruct((1, c), jnp.int32),
        scratch_shapes=[pltpu.VMEM((block_n, block_c), jnp.int32)],
        interpret=interpret,
    )(packed.astype(jnp.uint32), cpacked_t.astype(jnp.uint32),
      kvec.astype(jnp.int32).reshape(1, c))[0]
