"""Perfect-hash store — the hash-table trie, TPU-native.

The dense item remap *is* the perfect hash: descending one trie level for
candidate item ``i`` is a single O(1) gather ``bitmap[:, i]``. A block is
counted one of two ways, chosen by ``pair_by_gram`` from static shapes:

- **gathers** (any k): a candidate matches a transaction iff all k gathers
  hit — k gathers replace the k hashed child-steps of the paper's hash-table
  trie. The level loop is unrolled (k is static per level) so peak memory is
  one (Nb, C) lane mask, never (Nb, C, k).
- **Gram matrix** (k = 2 only): every pair's support in the block is an
  entry of BᵀB, B the (Nb, F_pad) 0/1 bitmap block, so one int8 matmul with
  int32 accumulation on the MXU counts all F_pad² pairs and the candidates
  read their entries. Exact: an entry is at most Nb. Pad candidates
  (``pad_candidates``) point at the all-zero column F_pad - 1, whose entry
  is 0.

The Gram path costs Nb·F_pad² MACs whatever C is, the gathers 2·Nb·C bytes;
the Gram wins while F_pad² / C stays under the ratio of the two rates, and
``GRAM_RATIO`` sits below the crossover measured on a TPU v5e (PERF.md).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.stores.base import DeltaCountMixin, EncodedDB

# Largest F_pad² / C at which a k = 2 block is counted by its Gram matrix.
GRAM_RATIO = 512


def gathered_counts(bitmap: jnp.ndarray, cand: jnp.ndarray) -> jnp.ndarray:
    """(Nb, F_pad) uint8, (C, k) -> int32[C]: k gathers ANDed, summed."""
    matched = bitmap[:, cand[:, 0]]  # level-1 gather: (Nb, C)
    for level in range(1, cand.shape[1]):
        matched = matched & bitmap[:, cand[:, level]]
    return jnp.sum(matched.astype(jnp.int32), axis=0)


def gram_pair_counts(bitmap: jnp.ndarray, cand: jnp.ndarray) -> jnp.ndarray:
    """(Nb, F_pad) uint8, (C, 2) -> int32[C]: entries of the block's BᵀB."""
    b = bitmap.astype(jnp.int8)
    gram = jax.lax.dot_general(b, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.int32)
    return gram[cand[:, 0], cand[:, 1]]


class PerfectHashStore(DeltaCountMixin):
    name = "perfect_hash"

    @staticmethod
    def transaction_inputs(enc: EncodedDB) -> dict:
        return {"bitmap": enc.bitmap}

    @staticmethod
    def device_transaction_inputs(padded, bitmap) -> dict:
        """jit-safe twin of ``transaction_inputs`` over the device-resident
        (N, L) padded ids + (N, F_pad) bitmap pair — the level ladder rebuilds
        the store tensors on device after every trim."""
        return {"bitmap": bitmap}

    @staticmethod
    def encode_candidates(cand: "jnp.ndarray", *, f_pad: int) -> dict:
        return {"cand": cand}

    @staticmethod
    def candidate_shard_axes() -> dict:
        """Tensor name -> axis carrying C.  Doubles as the out_specs of the
        shard-local ``encode_candidates`` shard_map (engine): every tensor
        ``encode_candidates`` returns must be listed here."""
        return {"cand": 0}

    @staticmethod
    def pair_by_gram(k: int, f_pad: int, c: int) -> bool:
        """Whether ``count_block`` counts a (C, k) block over F_pad item
        columns by its Gram matrix: k = 2 and F_pad² <= GRAM_RATIO · C."""
        return k == 2 and f_pad * f_pad <= GRAM_RATIO * c

    @classmethod
    def count_block(cls, trans: dict, cands: dict) -> jnp.ndarray:
        """trans["bitmap"]: (Nb, F_pad) uint8; cands["cand"]: (C, k) -> int32[C]."""
        bitmap, cand = trans["bitmap"], cands["cand"]
        if cls.pair_by_gram(cand.shape[1], bitmap.shape[1], cand.shape[0]):
            return gram_pair_counts(bitmap, cand)
        return gathered_counts(bitmap, cand)
