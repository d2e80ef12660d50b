"""Bitmap-MXU store — beyond-paper candidate store (DESIGN.md §2.2).

Transactions are multi-hot rows T (N, F); candidates are k-hot rows C (Cc, F).
Containment is arithmetic: ``(T @ Cᵀ)[n, c] == k_c`` — a dense bf16 matmul that
runs on the MXU, converting the paper's pointer-chasing subset() into the
highest-arithmetic-intensity primitive the hardware has. The Pallas kernel in
``repro.kernels.support_count`` implements the blocked/fused version; the
pure-jnp path here is also the kernel's oracle. Set ``use_kernel=True`` to run
the Pallas kernel (Mosaic on TPU, interpret mode on CPU).
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from repro.core.stores.base import DeltaCountMixin, EncodedDB


def candidates_to_khot(cand: np.ndarray, f_pad: int) -> tuple[np.ndarray, np.ndarray]:
    """(C, k) item matrix -> (C, F_pad) k-hot f32 rows + int32 k vector.

    Host-side reference encoder; the engine's per-wave hot path uses the
    device-side ``encode_candidates`` instead so only (C, k) int32 crosses
    the host boundary.
    """
    c, k = cand.shape
    khot = np.zeros((c, f_pad), dtype=np.float32)
    rows = np.repeat(np.arange(c), k)
    np.add.at(khot, (rows, cand.reshape(-1)), 1.0)
    # Pad rows stack k hits on the always-zero column; their dot is 0 != k.
    kvec = np.full((c,), k, dtype=np.int32)
    return khot, kvec


class BitmapMXUStore(DeltaCountMixin):
    name = "bitmap"
    use_kernel = False  # class switch: True counts with the Pallas kernel

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
    def encode_candidates(cand: jnp.ndarray, *, f_pad: int) -> dict:
        """Device-side k-hot scatter from the (C, k) item matrix (jit-safe)."""
        c, k = cand.shape
        rows = jnp.repeat(jnp.arange(c), k)
        khot = jnp.zeros((c, f_pad), jnp.float32).at[rows, cand.reshape(-1)].add(1.0)
        return {"khot": khot, "kvec": jnp.full((c,), k, jnp.int32)}

    @staticmethod
    def candidate_shard_axes() -> dict:
        """Tensor name -> axis carrying C.  Doubles as the out_specs of the
        shard-local ``encode_candidates`` shard_map (engine): every tensor
        ``encode_candidates`` returns must be listed here.  The k-hot
        scatter then builds only the (C/n_cand_shards, F_pad) rows of the
        local shard — the f32 k-hot matrix is the biggest candidate tensor
        of any store, exactly the one worth never materializing in full."""
        return {"khot": 0, "kvec": 0}

    @classmethod
    def count_block(cls, trans: dict, cands: dict) -> jnp.ndarray:
        if cls.use_kernel:
            from repro.kernels.support_count import support_count

            return support_count(trans["bitmap"], cands["khot"], cands["kvec"])
        t = trans["bitmap"].astype(jnp.bfloat16)
        c = cands["khot"].astype(jnp.bfloat16)
        dots = jnp.dot(t, c.T, preferred_element_type=jnp.float32)  # (Nb, C)
        matched = dots == cands["kvec"].astype(jnp.float32)[None, :]
        return jnp.sum(matched.astype(jnp.int32), axis=0)
