"""Packed-bitmap pipeline: cross-store bit-exactness on ragged shapes, the
device-side candidate encoder, the packed Pallas kernel, and the array-native
apriori_gen_matrix. No optional deps — this module always runs.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.engine import MapReduceEngine
from repro.core.itemsets import (
    apriori_gen,
    apriori_gen_matrix,
    brute_force_counts,
    level_to_matrix,
    matrix_to_level,
    sort_level,
)
from repro.core.stores import ARRAY_STORES, encode_db, pack_bitmap
from repro.core.stores.base import pad_candidates
from repro.core.stores.bitmap import candidates_to_khot
from repro.core.stores.packed_bitmap import pack_candidates_device
from repro.kernels.support_count import (
    packed_support_count,
    packed_support_count_ref,
    support_count_ref,
)


def _random_db(rng, n, n_items, max_len, with_empty=True):
    db = [
        sorted(set(rng.choice(n_items, rng.integers(1, max_len + 1), replace=True)))
        for _ in range(n)
    ]
    if with_empty and n > 1:
        db[1] = []  # empty transaction must match nothing
    return [[int(x) for x in t] for t in db]


def _random_cands(rng, items, k, c):
    cands = sorted({
        tuple(sorted(rng.choice(items, k, replace=False))) for _ in range(c)
    })
    return [tuple(int(x) for x in s) for s in cands]


# Ragged shapes: N and C not multiples of any block size, F just past a word
# (129 > 4*32) and past a lane (130 > 128) boundary, tiny F, single row.
RAGGED = [
    (37, 129, 2, 11),
    (5, 130, 3, 7),
    (50, 20, 2, 17),
    (1, 33, 1, 3),
    (63, 257, 3, 13),
]


@pytest.mark.parametrize("n,n_items,k,c", RAGGED)
def test_packed_matches_brute_force_and_all_stores(n, n_items, k, c):
    rng = np.random.default_rng(n * 1000 + n_items)
    db = _random_db(rng, n, n_items, max_len=min(n_items, 12))
    items = sorted({i for t in db for i in t})
    if len(items) < k:
        pytest.skip("degenerate draw")
    cands = _random_cands(rng, items, k, c)
    mat = level_to_matrix(cands)
    want = brute_force_counts(db, cands)
    want_arr = np.array([want[s] for s in cands])

    enc = encode_db(db, n_items=n_items)
    results = {}
    for store in ARRAY_STORES:
        engine = MapReduceEngine(store=store, block_n=16)
        engine.place(enc)
        results[store] = np.asarray(engine.count_candidates(mat))
    np.testing.assert_array_equal(results["packed_bitmap"], want_arr)
    for store, got in results.items():
        np.testing.assert_array_equal(got, want_arr, err_msg=store)


def test_packed_view_matches_bitmap():
    rng = np.random.default_rng(0)
    db = _random_db(rng, 41, 200, 15)
    enc = encode_db(db, n_items=200)
    packed = enc.packed
    assert packed.shape == (enc.n_transactions, enc.f_pad // 32)
    assert packed.dtype == np.uint32
    # Unpack and compare bit-for-bit against the uint8 bitmap.
    unpacked = (
        (packed[:, :, None] >> np.arange(32, dtype=np.uint32)) & 1
    ).reshape(packed.shape[0], -1)
    np.testing.assert_array_equal(unpacked.astype(np.uint8), enc.bitmap)
    # The padded view extends the cached packed tensor with zero rows.
    enc2 = enc.pad_transactions_to(enc.n_transactions + 7)
    np.testing.assert_array_equal(enc2.packed[: enc.n_transactions], packed)
    assert not enc2.packed[enc.n_transactions :].any()


def test_device_candidate_encoder_matches_host_khot():
    from repro.core.stores.bitmap import BitmapMXUStore

    rng = np.random.default_rng(1)
    f_pad = 256
    cand = np.stack([
        np.sort(rng.choice(200, 3, replace=False)) for _ in range(17)
    ]).astype(np.int32)
    cand_p = pad_candidates(cand, f_pad)
    khot_host, kvec_host = candidates_to_khot(cand_p, f_pad)
    dev = BitmapMXUStore.encode_candidates(jnp.asarray(cand_p), f_pad=f_pad)
    np.testing.assert_array_equal(np.asarray(dev["khot"]), khot_host)
    np.testing.assert_array_equal(np.asarray(dev["kvec"]), kvec_host)


def test_device_candidate_packer_pad_rows_never_match():
    # Pad rows repeat item f_pad-1; OR-packing must leave exactly one bit.
    f_pad = 128
    cand = np.full((4, 3), f_pad - 1, np.int32)
    packed = np.asarray(pack_candidates_device(jnp.asarray(cand), f_pad // 32))
    counts = np.array([bin(int(w)).count("1") for w in packed.reshape(-1)])
    assert counts.sum() == 4  # one bit per row, in the always-zero column


@pytest.mark.parametrize("n,w,c,k", [
    (8, 4, 4, 1),
    (100, 5, 70, 2),       # every dim ragged vs blocks
    (256, 8, 128, 3),
    (513, 9, 300, 5),
    (64, 16, 1024, 4),     # C > block
    (40, 130, 100, 3),     # W > 128: two 128-word blocks, W padded
])
def test_packed_kernel_matches_ref(n, w, c, k):
    rng = np.random.default_rng(n * 31 + c)
    f = w * 32
    bitmap = np.zeros((n, f), np.uint8)
    bitmap[:, : f - 1] = rng.random((n, f - 1)) < 0.35
    packed = pack_bitmap(bitmap)
    cand = np.stack([
        np.sort(rng.choice(f - 1, k, replace=False)) for _ in range(c)
    ]).astype(np.int32)
    cpacked = np.asarray(pack_candidates_device(jnp.asarray(cand), w))
    kvec = np.full(c, k, np.int32)
    ref = np.asarray(packed_support_count_ref(packed, cpacked, kvec))
    got = np.asarray(packed_support_count(
        packed, cpacked, kvec, block_n=128, block_c=128, block_w=4))
    np.testing.assert_array_equal(ref, got)
    # The packed oracle agrees with the dense k-hot matmul oracle.
    khot, _ = candidates_to_khot(cand, f)
    dense = np.asarray(support_count_ref(bitmap.astype(np.float32), khot,
                                         kvec))
    np.testing.assert_array_equal(ref, dense)


def test_apriori_gen_matrix_matches_python():
    rng = np.random.default_rng(5)
    for _ in range(60):
        k = int(rng.integers(1, 5))
        n_items = int(rng.integers(k, 16))
        level = sort_level(
            tuple(sorted(rng.choice(n_items, k, replace=False).tolist()))
            for _ in range(int(rng.integers(0, 40)))
        )
        got = matrix_to_level(apriori_gen_matrix(level_to_matrix(level)))
        assert got == apriori_gen(level)
    assert apriori_gen_matrix(np.zeros((0, 0), np.int32)).size == 0
