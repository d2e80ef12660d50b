"""Sorted-prefix store — the trie, TPU-native.

A trie resolves each level by scanning the node's ordered children; the
array-layout dual scans the *transaction row* for the candidate's next item —
one comparison against each of the row's L slots per level (the scan cost
model) instead of the perfect-hash store's O(1) gather.  The count does not
use the row's sort order: a binary search over it is a per-row gather, which
the TPU runs one index at a time (a host-loop mine of T10I4D100K at support
0.0025 did not finish in 23 minutes on a v5e that way).
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.core.stores.base import DeltaCountMixin, EncodedDB


class SortedPrefixStore(DeltaCountMixin):
    name = "sorted_prefix"

    @staticmethod
    def transaction_inputs(enc: EncodedDB) -> dict:
        return {"padded": enc.padded}

    @staticmethod
    def device_transaction_inputs(padded, bitmap) -> dict:
        """jit-safe twin of ``transaction_inputs`` over the device-resident
        (N, L) padded ids + (N, F_pad) bitmap pair — the level ladder rebuilds
        the store tensors on device after every trim."""
        return {"padded": padded}

    @staticmethod
    def encode_candidates(cand: jnp.ndarray, *, f_pad: int) -> dict:
        return {"cand": cand}

    @staticmethod
    def candidate_shard_axes() -> dict:
        """Tensor name -> axis carrying C.  Doubles as the out_specs of the
        shard-local ``encode_candidates`` shard_map (engine): every tensor
        ``encode_candidates`` returns must be listed here."""
        return {"cand": 0}

    @staticmethod
    def count_block(trans: dict, cands: dict) -> jnp.ndarray:
        """trans["padded"]: (Nb, L) sorted int32 (ITEM_PAD tail); cand (C, k)."""
        padded, cand = trans["padded"], cands["cand"]
        k = cand.shape[1]

        def level_found(items):  # items: (C,) -> (Nb, C) bool
            return jnp.any(padded[:, :, None] == items[None, None, :], axis=1)

        matched = level_found(cand[:, 0])
        for level in range(1, k):
            matched = matched & level_found(cand[:, level])
        return jnp.sum(matched.astype(jnp.int32), axis=0)
