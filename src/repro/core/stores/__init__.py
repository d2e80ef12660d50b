"""TPU array-layout candidate stores (see DESIGN.md §2.2).

Each store re-expresses one of the paper's candidate data structures as a
fixed-shape array program suitable for jit/shard_map:

=================  =====================  =========================================
paper structure    store                  per-level matching primitive
=================  =====================  =========================================
hash-table trie    ``perfect_hash``       one O(1) gather into the transaction bitmap
                                          (k = 2: the block's Gram matrix BᵀB on the MXU)
trie               ``sorted_prefix``      binary search in the sorted transaction
hash tree          ``hash_bucket``        bucket probe + linear scan over the bucket
(beyond paper)     ``bitmap``             dense (T·Cᵀ == k) matmul on the MXU
(beyond paper)     ``packed_bitmap``      popcount(t & c) == k over 32-items/word
=================  =====================  =========================================

All stores implement ``count_block(enc_block, cand) -> int32[C]`` as a pure JAX
function over a block of encoded transactions, and produce identical counts.
Candidate tensors are built *on device* by each store's jit-safe
``encode_candidates(cand, f_pad=...)`` from the small (C, k) int32 matrix —
the only per-wave host-to-device transfer.
"""

from repro.core.stores.base import (
    DeltaCountMixin, EncodedDB, dense_remap_padded, encode_db,
    encode_db_from_padded, pack_bitmap, pack_transactions, pad_candidates,
    padded_from_transactions, ITEM_PAD, WORD_BITS,
)
from repro.core.stores.perfect_hash import PerfectHashStore
from repro.core.stores.sorted_prefix import SortedPrefixStore
from repro.core.stores.hash_bucket import HashBucketStore
from repro.core.stores.bitmap import BitmapMXUStore
from repro.core.stores.packed_bitmap import PackedBitmapStore

ARRAY_STORES = {
    "perfect_hash": PerfectHashStore,
    "sorted_prefix": SortedPrefixStore,
    "hash_bucket": HashBucketStore,
    "bitmap": BitmapMXUStore,
    "packed_bitmap": PackedBitmapStore,
}

__all__ = [
    "DeltaCountMixin",
    "EncodedDB",
    "dense_remap_padded",
    "encode_db",
    "encode_db_from_padded",
    "pack_transactions",
    "padded_from_transactions",
    "pack_bitmap",
    "pad_candidates",
    "ITEM_PAD",
    "WORD_BITS",
    "PerfectHashStore",
    "SortedPrefixStore",
    "HashBucketStore",
    "BitmapMXUStore",
    "PackedBitmapStore",
    "ARRAY_STORES",
]
