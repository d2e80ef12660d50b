"""Beyond-paper: the five TPU array-layout stores on one counting wave, the
headline packed-popcount vs bitmap-matmul comparison with bytes-per-transaction
accounting, plus both Pallas support-count kernels (checked against their
pure-jnp oracles, which are what the rows time; each row names the mode the
kernel ran in: ``interpret`` on the CPU, ``mosaic`` on the TPU).
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.engine import MapReduceEngine
from repro.core.stores import ARRAY_STORES, encode_db
from repro.data import paper_datasets

from benchmarks.common import SCALE, c2_wave, row, timed


def run() -> list:
    db = paper_datasets(scale=SCALE)["T10I4D100K"]
    # one realistic candidate wave: frequent pairs from frequent items
    db_dense, n_items, mat = c2_wave(db)
    enc = encode_db(db_dense, n_items=n_items)

    out = []
    counts_ref = None
    secs = {}
    for store in ARRAY_STORES:
        engine = MapReduceEngine(store=store)
        engine.place(enc)
        engine.count_candidates(mat)  # compile
        counts, sec = timed(engine.count_candidates, mat, repeat=2)
        secs[store] = sec
        if counts_ref is None:
            counts_ref = counts
        np.testing.assert_array_equal(counts, counts_ref)
        out.append(row(
            f"stores_jax/{store}/count_c2", sec * 1e6,
            f"C={mat.shape[0]};N={enc.n_transactions}",
        ))

    # Headline: packed popcount vs bitmap bf16-matmul on the same C2 wave.
    # bytes/txn streamed through the count: packed 1 bit per item column vs
    # the uint8 bitmap's 8 (and 32 for the f32 k-hot oracle operand).
    f_pad = enc.f_pad
    out.append(row(
        "stores_jax/packed_vs_bitmap/count_c2",
        secs["packed_bitmap"] * 1e6,
        f"speedup_vs_bitmap={secs['bitmap'] / secs['packed_bitmap']:.2f}x;"
        f"bytes_per_txn_packed={f_pad // 8};bytes_per_txn_bitmap_u8={f_pad};"
        f"bytes_per_txn_khot_f32={4 * f_pad};txn_bytes_reduction_vs_f32="
        f"{32}x;reduction_vs_u8=8x",
    ))

    # Pallas kernels on a trimmed slice: correctness + oracle timing
    from repro.core.stores.bitmap import candidates_to_khot
    from repro.core.stores.packed_bitmap import pack_candidates_device
    from repro.kernels.support_count import (
        packed_support_count,
        packed_support_count_ref,
        support_count,
        support_count_ref,
    )
    from repro.kernels.support_count.ops import resolve_interpret

    mode = "interpret" if resolve_interpret() else "mosaic"

    n_small, c_small = 2048, 512
    bm = enc.bitmap[:n_small].astype(np.float32)
    khot, kvec = candidates_to_khot(mat[:c_small], enc.f_pad)
    ref, ref_s = timed(
        lambda: jax.block_until_ready(
            support_count_ref(jnp.array(bm), jnp.array(khot), jnp.array(kvec))),
        repeat=3)
    got = support_count(bm, khot, kvec)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    out.append(row("kernel/support_count_ref(jnp)", ref_s * 1e6,
                   f"N={n_small};C={c_small};kernel_mode={mode}"))

    packed = enc.packed[:n_small]
    cpacked = np.asarray(
        pack_candidates_device(jnp.asarray(mat[:c_small]), enc.n_words))
    pref, pref_s = timed(
        lambda: jax.block_until_ready(packed_support_count_ref(
            jnp.array(packed), jnp.array(cpacked), jnp.array(kvec))),
        repeat=3)
    pgot = packed_support_count(packed, cpacked, kvec)
    np.testing.assert_array_equal(np.asarray(pgot), np.asarray(pref))
    np.testing.assert_array_equal(np.asarray(pref), np.asarray(ref))
    out.append(row("kernel/packed_support_count_ref(jnp)", pref_s * 1e6,
                   f"N={n_small};C={c_small};W={enc.n_words};"
                   f"kernel_mode={mode}"))
    return out
