"""Packed-bitmap popcount store — beyond-paper candidate store.

Transactions and candidates are packed 32 item columns per uint32 word:
T (N, W) and C (Cc, W) with W = F_pad/32. Containment is bitwise:
``popcount(t & c) == k`` — 1 bit per item column instead of the uint8
bitmap's 8 (and the bf16/f32 k-hot operands' 16/32), so the transaction
tensor streamed through the counting wave is 8-32x smaller. The work is
pure VPU integer arithmetic (AND + popcount + add over W words), no matmul.

The blocked Pallas kernel lives in ``repro.kernels.support_count.packed``;
the pure-jnp path here is also that kernel's oracle. Set ``use_kernel=True``
to run the Pallas kernel (Mosaic on TPU, interpret mode on CPU).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.stores.base import DeltaCountMixin, EncodedDB, WORD_BITS


def pack_candidates_device(cand: jnp.ndarray, n_words: int) -> jnp.ndarray:
    """(C, k) int32 item matrix -> (C, W) uint32 packed rows, on device.

    Pure JAX (jit-safe): k and W are static. Bits are OR-ed in, so the
    engine's pad rows (item f_pad - 1 repeated k times) set exactly one bit
    in the always-zero column and can never reach popcount == k.
    """
    c, k = cand.shape
    words = cand // WORD_BITS                              # (C, k)
    bits = (cand % WORD_BITS).astype(jnp.uint32)           # (C, k)
    word_ids = jnp.arange(n_words, dtype=cand.dtype)       # (W,)
    packed = jnp.zeros((c, n_words), jnp.uint32)
    for j in range(k):
        hit = word_ids[None, :] == words[:, j : j + 1]     # (C, W)
        bitval = jnp.uint32(1) << bits[:, j]               # (C,)
        packed = packed | jnp.where(hit, bitval[:, None], jnp.uint32(0))
    return packed


class PackedBitmapStore(DeltaCountMixin):
    name = "packed_bitmap"
    use_kernel = False  # class switch: True counts with the Pallas kernel

    @staticmethod
    def transaction_inputs(enc: EncodedDB) -> dict:
        return {"packed": enc.packed}

    @staticmethod
    def device_transaction_inputs(padded, bitmap) -> dict:
        """jit-safe twin of ``transaction_inputs`` over the device-resident
        (N, L) padded ids + (N, F_pad) bitmap pair — the level ladder rebuilds
        the store tensors on device after every trim.  The lane weights are
        distinct powers of two, so the sum over the 32-lane axis equals the
        bitwise OR of ``base.pack_bitmap`` bit for bit."""
        n, f = bitmap.shape
        lanes = bitmap.reshape(n, f // WORD_BITS, WORD_BITS).astype(jnp.uint32)
        weights = jnp.uint32(1) << jnp.arange(WORD_BITS, dtype=jnp.uint32)
        packed = jnp.sum(lanes * weights, axis=2, dtype=jnp.uint32)
        return {"packed": packed}

    @classmethod
    def encode_candidates(cls, cand: jnp.ndarray, *, f_pad: int) -> dict:
        """Emit only the layout the active counting path reads: the Pallas
        kernel wants row-major (C, W); the jnp path wants the word-major
        (W, C) transpose *materialized* (a use-site ``.T`` stays a strided
        view inside the count loop and is ~10x slower on CPU). Flip
        ``use_kernel`` before ``engine.place`` — the encoder jit caches the
        layout per candidate shape.
        """
        c, k = cand.shape
        packed = pack_candidates_device(cand, f_pad // WORD_BITS)
        body = {"packed": packed} if cls.use_kernel else {"packedT": packed.T}
        return {**body, "kvec": jnp.full((c,), k, jnp.int32)}

    @classmethod
    def candidate_shard_axes(cls) -> dict:
        """Tensor name -> axis carrying C.  Doubles as the out_specs of the
        shard-local ``encode_candidates`` shard_map (engine): every tensor
        ``encode_candidates`` returns must be listed here.

        The jnp path materializes the word-major transpose, so its C axis is
        axis 1 (the non-leading shard axis exercises the engine's
        per-tensor PartitionSpec construction); the kernel path keeps
        row-major (C, W)."""
        body = {"packed": 0} if cls.use_kernel else {"packedT": 1}
        return {**body, "kvec": 0}

    @classmethod
    def count_block(cls, trans: dict, cands: dict) -> jnp.ndarray:
        if cls.use_kernel:
            from repro.kernels.support_count import packed_support_count

            return packed_support_count(
                trans["packed"], cands["packed"], cands["kvec"]
            )
        # Word-wise containment: t contains c iff (t_w & c_w) == c_w for every
        # word — algebraically the same test as popcount(t & c) == k (the form
        # the Pallas kernel uses), but with a 1-byte boolean accumulator and
        # word-major (contiguous) candidate reads, which is what vectorizes
        # best on the CPU backend.
        t, cT = trans["packed"], cands["packedT"]          # (Nb, W), (W, C)
        matched = jnp.ones((t.shape[0], cT.shape[1]), bool)
        for w in range(cT.shape[0]):  # W is static; unrolled word loop
            cw = cT[w][None, :]
            matched = matched & ((t[:, w, None] & cw) == cw)
        return jnp.sum(matched.astype(jnp.int32), axis=0)
