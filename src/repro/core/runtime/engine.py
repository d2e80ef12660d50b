"""MapReduce-on-JAX counting engine (Hadoop job ≙ one jit'd count step).

Mapper  = per-device count over its transaction shard (``data`` mesh axes);
Combiner = the in-shard reduction inside ``count_block`` (sum over Nb);
Shuffle+Reducer = ``lax.psum`` of the per-shard count vectors over the data
axes, followed by host-side min-support thresholding.

The transaction tensors are placed (sharded) once and reused across levels;
each level's candidate arrays are replicated — the analogue of Hadoop's
distributed cache shipping L_{k-1} to every mapper. A new candidate shape
triggers one compile, the analogue of per-iteration job submission.

**Candidate-axis sharding** (``cand_axes``): with a 2-D ``data x cand`` mesh
the work decomposition becomes a true grid — transactions shard over the
``data`` axes (replicated over ``cand``) and each wave's candidate tensors
shard over the ``cand`` axes (replicated over ``data``), so a wave whose
candidate tensors are too big to replicate per device fits in
``1/n_cand_shards`` of the memory. Each device counts its candidate shard
over its transaction shard; counts are psum'd along ``data`` and the
per-candidate-shard vectors are stitched back to the full ``C`` axis by the
``out_specs`` partition (the mesh-level allgather along ``cand``) — pure
integer adds and concatenation, so counts stay bit-identical to the
replicated path. Per-store candidate layouts (row-major, word-major
transposed, ...) declare which axis carries ``C`` via
``candidate_shard_axes()``.

Per wave, only the small (C, k) int32 candidate matrix crosses the host
boundary; the store-specific candidate tensors (k-hot rows, packed words,
bucket hashes) are built on device by the store's jit'd ``encode_candidates``.
With candidate-axis sharding the encode itself is **shard-local**: the (C, k)
matrix is placed partitioned over the ``cand`` axes and ``encode_candidates``
runs inside a ``shard_map`` whose out_specs come from the store's
``candidate_shard_axes()`` layout map — each device encodes only its own
``C/n_cand_shards`` candidate rows instead of encoding the full wave and
resharding, so per-device encode flops and memory shrink with the mesh.

Wave dispatch is **async and double-buffered at both pipeline stages**:
``count_candidates_async`` splits a wave into ``cand_block`` chunks and
dispatches each without blocking (JAX async dispatch).  Encode and count are
separate dispatches: up to ``encode_ahead`` chunks sit fully encoded in an
encode-slot FIFO before their count is submitted, and up to ``inflight``
submitted chunk results stay outstanding in the count FIFO before the oldest
is forced to host.  While the host blocks fetching the count of chunk i, the
device already holds the *encode* of chunks i+1..i+encode_ahead (and their
queued counts), so the encode of the next chunk is never serialized behind
the count of the current one.  The host is additionally free to run the next
level's ``apriori_gen_matrix`` while the device counts — ``inflight=0``
degenerates to the old blocking per-chunk behaviour (no encode lookahead),
and the returned counts are bit-identical at any depth (both queues only
reorder *waiting*, never arithmetic).

Job1 (the 1-itemset histogram) is a device job through the same machinery:
``count_items_device`` scatter-adds the padded transaction matrix into a
histogram, sharded over the same data axes and reduced with the same psum.
"""

from __future__ import annotations

import collections
import functools
import time

from typing import Deque, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.runtime.spans import span
from repro.core.stores import ARRAY_STORES, EncodedDB, pad_candidates
from repro.core.stores.base import ITEM_PAD
from repro.distributed.ctx import fetch_global


class PendingCounts:
    """Handle for an in-flight counting wave; ``result()`` blocks and joins.

    Chunk results are resolved strictly in dispatch order through the
    engine's FIFO, so counts are independent of the ``inflight`` depth.
    """

    def __init__(self, engine: "MapReduceEngine", n_chunks: int) -> None:
        self._engine = engine
        self._parts: List[Optional[np.ndarray]] = [None] * n_chunks
        self._cancelled = False

    @property
    def done(self) -> bool:
        return all(p is not None for p in self._parts)

    def poll(self) -> bool:
        """Non-blocking progress check: fetch whatever chunk results the
        device has already finished (in FIFO order), then report whether
        this wave is fully joined.  Never blocks on device compute — the
        background-refresh pump calls this from the ingest path, where a
        stall would defeat the point of refreshing off the query path.
        """
        if self._cancelled:
            raise RuntimeError(
                "counting wave was cancelled: place() re-placed the DB "
                "while this handle's chunks were still in flight"
            )
        self._engine.drain_ready()
        return self.done

    def result(self) -> np.ndarray:
        while not self.done:
            if self._cancelled or not self._engine._queue:
                raise RuntimeError(
                    "counting wave was cancelled: place() re-placed the DB "
                    "while this handle's chunks were still in flight"
                )
            self._engine._force_oldest()
        if not self._parts:
            return np.zeros((0,), np.int64)
        return np.concatenate(self._parts)


class MapReduceEngine:
    def __init__(
        self,
        store: str = "perfect_hash",
        mesh: Optional[Mesh] = None,
        data_axes: Tuple[str, ...] = ("data",),
        cand_axes: Tuple[str, ...] = (),
        block_n: int = 2048,
        cand_block: int = 32_768,
        inflight: Optional[int] = 1,
        encode_ahead: int = 2,
    ) -> None:
        if store not in ARRAY_STORES:
            raise ValueError(f"unknown store {store!r}; pick from {list(ARRAY_STORES)}")
        if cand_axes and mesh is None:
            raise ValueError("cand_axes requires a mesh with those axes")
        if mesh is not None:
            # Fail at construction, not with a KeyError inside the first
            # count: every named axis must exist on the mesh (passing
            # cand_axes with a data-only mesh is the easy mistake).
            missing = [a for a in tuple(data_axes) + tuple(cand_axes)
                       if a not in mesh.shape]
            if missing:
                raise ValueError(
                    f"mesh has axes {list(mesh.shape)}, missing {missing}"
                )
        self.store = ARRAY_STORES[store]
        self.store_name = store
        self.mesh = mesh
        self.data_axes = data_axes
        self.cand_axes = tuple(cand_axes)
        self.block_n = block_n
        self.cand_block = cand_block  # bounds per-dispatch candidate memory
        # inflight=None => auto: pick the depth from the first clean chunk's
        # measured device latency vs host dispatch time (see
        # count_candidates_async); until tuned, run classic double buffering.
        self.inflight_auto = inflight is None
        self._inflight_tuned = False
        self.inflight = 1 if inflight is None else inflight
        # How many chunks may sit fully encoded (device-side) ahead of their
        # count dispatch — the encode-stage double buffer.  0 pins encode to
        # count (the pre-pipelined schedule); inflight=0 also forces 0 so the
        # fully synchronous path stays exactly chunk-by-chunk.
        self.encode_ahead = encode_ahead
        # Per-chunk work (min(C, cand_block) * k) the depth was last tuned
        # on, and the cumulative mid-run re-tunes (surfaced via JobProfile).
        self._tuned_work: Optional[int] = None
        self._retune_pending = False
        self.inflight_retunes = 0
        self._trans_device = None
        self._enc: Optional[EncodedDB] = None
        self._count_jit = None
        self._encode_jit = None
        self._cand_in_sharding = None  # sharding of the (C, k) encode input
        # FIFO of (pending, slot, device_counts, n_valid) across all waves.
        self._queue: Deque[tuple] = collections.deque()
        self._job1_jit = {}  # (N, L, n_items) -> compiled histogram job
        # Bytes of count vector each device has added into a psum over the
        # data axes (waves and Job1) since construction; a runner reads the
        # difference across one job's dispatches (JobProfile.reduce_bytes).
        self.psum_bytes = 0
        # Chunk counts dispatched whose ``count_block`` takes the store's
        # Gram path (``pair_by_gram`` on the shapes each device sees), since
        # construction; a runner reads the difference across one job's
        # dispatch (JobProfile.gram_chunks).
        self.gram_chunks = 0
        # Cross-place() compile caches: re-placing the same engine (repeat
        # mines, benchmark rounds) must not rebuild identical encode/count
        # jits — mesh and store are fixed per engine, so f_pad (encode) and
        # the candidate/transaction tree structures (count) are complete keys.
        self._place_jit_cache = {}
        # The device-resident level ladder's compiled-step cache (one entry
        # per static shape tuple; see runtime/device_loop.py).
        self.ladder_jit = {}

    # -- placement ---------------------------------------------------------
    @property
    def n_data_shards(self) -> int:
        if self.mesh is None:
            return 1
        return int(np.prod([self.mesh.shape[a] for a in self.data_axes]))

    def shard_rows(self, n: int) -> int:
        """Rows each data shard holds of an ``n``-row matrix, padding
        included (the matrix is padded to a multiple of the shards)."""
        return -(-n // self.n_data_shards)

    @property
    def n_cand_shards(self) -> int:
        if self.mesh is None or not self.cand_axes:
            return 1
        return int(np.prod([self.mesh.shape[a] for a in self.cand_axes]))

    def _cand_pspec(self, axis: Optional[int]) -> P:
        """PartitionSpec sharding dimension ``axis`` (the tensor's C axis)
        over the cand mesh axes; replicated when cand sharding is off."""
        if not self.cand_axes or axis is None:
            return P()
        return P(*([None] * axis), self.cand_axes)

    def abandon(self) -> None:
        """Void every outstanding chunk handle and drop the dispatch queue.

        Used when the placed DB is being replaced (``place``) and on
        simulated device loss: in-flight results reference buffers on a mesh
        that no longer exists, so blocked ``result()`` calls must fail
        loudly instead of fetching from it.
        """
        for pending, _, _, _ in self._queue:
            pending._cancelled = True
        self._queue.clear()

    def release(self) -> None:
        """Drop the placed DB, its device tensors and host encoding, with
        every outstanding handle.  The engine's jits hold its bound methods,
        so an engine dropped with its DB in place keeps that device memory
        until the next cyclic collection."""
        self.abandon()
        self._trans_device = None
        self._enc = None

    def place(self, enc: EncodedDB) -> None:
        """Shard transaction tensors over the data axes; keep them resident."""
        self.abandon()  # handles from a prior DB are void
        enc = enc.pad_transactions_to(
            self.shard_rows(enc.n_transactions) * self.n_data_shards)
        trans = self.store.transaction_inputs(enc)
        if self.mesh is not None:
            sharding = NamedSharding(self.mesh, P(self.data_axes))
            trans = {k: jax.device_put(v, sharding) for k, v in trans.items()}
        else:
            trans = {k: jnp.asarray(v) for k, v in trans.items()}
        self._trans_device = trans
        self._enc = enc
        self._count_jit = None  # built lazily (needs the candidate tree structure)
        # Device-side candidate encoder: (C, k) int32 -> the store's candidate
        # tensors, all built on device (jit caches per (C, k) shape).  With
        # candidate-axis sharding the encode is shard-local: the (C, k) input
        # arrives partitioned over ``cand`` and encode_candidates runs inside
        # shard_map, so each device encodes only its own candidate rows; the
        # store's candidate_shard_axes() layout map supplies the out_specs.
        ekey = ("encode", enc.f_pad,
                bool(getattr(self.store, "use_kernel", False)))
        cached = self._place_jit_cache.get(ekey)
        if cached is not None:
            self._encode_jit = cached
        elif self.mesh is not None and self.cand_axes:
            axes_map = self.store.candidate_shard_axes()
            out_specs = {name: self._cand_pspec(axis)
                         for name, axis in axes_map.items()}
            self._encode_jit = jax.jit(jax.shard_map(
                functools.partial(self.store.encode_candidates,
                                  f_pad=enc.f_pad),
                mesh=self.mesh,
                in_specs=(P(self.cand_axes),), out_specs=out_specs))
            self._place_jit_cache[ekey] = self._encode_jit
        else:
            self._encode_jit = jax.jit(functools.partial(
                self.store.encode_candidates, f_pad=enc.f_pad))
            self._place_jit_cache[ekey] = self._encode_jit
        self._cand_in_sharding = None
        if self.mesh is not None:
            self._cand_in_sharding = NamedSharding(
                self.mesh, P(self.cand_axes) if self.cand_axes else P())

    def _blocked_count(self, trans: dict, cands: dict) -> jnp.ndarray:
        """Mapper body: lax.map over Nb-blocks bounds peak (Nb, C) memory."""
        n = next(iter(trans.values())).shape[0]
        block_n = max(1, min(self.block_n, n))  # n == 0 guarded by callers
        n_blocks = max(1, n // block_n)
        usable = n_blocks * block_n

        def body(block):
            return self.store.count_block(block, cands)

        blocks = {k: v[:usable].reshape(n_blocks, block_n, *v.shape[1:])
                  for k, v in trans.items()}
        partial = jax.lax.map(lambda b: body(b), blocks).sum(axis=0)
        if usable < n:  # ragged tail block
            tail = {k: v[usable:] for k, v in trans.items()}
            partial = partial + body(tail)
        return partial

    def _cand_specs(self, cands_example: dict) -> dict:
        """Per-tensor candidate PartitionSpecs from the store's layout map."""
        axes_map = self.store.candidate_shard_axes() if self.cand_axes else {}
        return {k: self._cand_pspec(axes_map.get(k)) for k in cands_example}

    def _build_count_fn(self, cands_example: dict):
        if self.mesh is None:
            return jax.jit(self._blocked_count)

        data_spec = P(self.data_axes)

        def sharded(trans, cands):
            local = self._blocked_count(trans, cands)
            return jax.lax.psum(local, self.data_axes)  # shuffle + reduce

        # With candidate sharding each device returns counts for its C-shard
        # only; out_specs partitions the result over ``cand``, stitching the
        # shards back into the full C axis (the mesh-level allgather). The
        # psum makes the result provably replicated over ``data`` either way.
        fn = jax.shard_map(
            sharded,
            mesh=self.mesh,
            in_specs=(
                jax.tree.map(lambda _: data_spec, self._trans_device),
                self._cand_specs(cands_example),
            ),
            out_specs=P(self.cand_axes) if self.cand_axes else P(),
        )
        return jax.jit(fn)

    # -- counting ------------------------------------------------------------
    def _dispatch_encode(self, chunk: np.ndarray) -> dict:
        """Dispatch the device-side encode of one candidate chunk; returns
        the *unfetched* store candidate tensors (JAX async dispatch — nothing
        here blocks on compute).  Under candidate-axis sharding the (C, k)
        matrix is placed partitioned over ``cand`` and each device encodes
        only its own rows — the encoded tensors come out of the shard_map
        already carrying the layouts the count step consumes, so no reshard
        (and no replicated full-wave encode) happens in between."""
        cand_p = pad_candidates(chunk, self._enc.f_pad,
                                shards=self.n_cand_shards)
        self._note_gram(cand_p.shape[1], self._enc.f_pad,
                        cand_p.shape[0] // self.n_cand_shards)
        cand_np = np.ascontiguousarray(cand_p, dtype=np.int32)
        if self._cand_in_sharding is not None:
            # device_put straight from host memory: a committed single-device
            # array cannot be re-put onto a process-spanning sharding, numpy
            # can (every process holds the identical full wave).
            cand_dev = jax.device_put(cand_np, self._cand_in_sharding)
        else:
            cand_dev = jnp.asarray(cand_np)
        return self._encode_jit(cand_dev)

    def _note_gram(self, k: int, f_pad: int, c: int) -> None:
        """Count a chunk whose (C = ``c``, k) candidates each device counts
        by the store's Gram path; a store without ``pair_by_gram`` has none."""
        pair_by_gram = getattr(self.store, "pair_by_gram", None)
        if pair_by_gram is not None and pair_by_gram(k, f_pad, c):
            self.gram_chunks += 1

    def _dispatch_count(self, cands: dict):
        """Dispatch the count of an already-encoded chunk (non-blocking)."""
        if self._count_jit is None:
            # The compiled count depends only on the candidate/transaction
            # tree *structures* (shapes retrace inside the jit), so repeat
            # place() calls reuse it — a warm second mine never recompiles.
            ckey = ("count", tuple(sorted(cands)),
                    tuple(sorted(self._trans_device)),
                    bool(getattr(self.store, "use_kernel", False)))
            self._count_jit = self._place_jit_cache.get(ckey)
            if self._count_jit is None:
                self._count_jit = self._build_count_fn(cands)
                self._place_jit_cache[ckey] = self._count_jit
        dev = self._count_jit(self._trans_device, cands)
        if self.mesh is not None:  # each device psums its candidate shard
            self.psum_bytes += dev.nbytes // self.n_cand_shards
        return dev

    def _count_encoded(self, pending: "PendingCounts", encoded: Deque) -> None:
        """Move the oldest encode slot into the count FIFO; drain the count
        FIFO down to ``inflight`` outstanding results."""
        slot, cands, n_valid = encoded.popleft()
        dev = self._dispatch_count(cands)
        self._queue.append((pending, slot, dev, n_valid))
        while len(self._queue) > self.inflight:
            self._force_oldest()

    def _force_oldest(self) -> None:
        """Fetch the oldest outstanding chunk result to host (blocking).

        Routed through ``fetch_global`` so a cand-sharded result living on a
        process-spanning mesh resolves too (the allgather it needs is a
        collective, which is safe exactly because this queue is strict FIFO:
        every process fetches the same results in the same order)."""
        pending, slot, dev, c = self._queue.popleft()
        with span("repro.fetch", rows=c):
            pending._parts[slot] = fetch_global(dev)[:c].astype(np.int64)

    def drain_ready(self) -> int:
        """Fetch every *leading* queue entry whose device result is already
        computed; never block.  Results resolve strictly in dispatch order
        (same as ``_force_oldest``), so partial drains are safe at any point.
        Returns the number of chunks joined.
        """
        joined = 0
        while self._queue:
            dev = self._queue[0][2]
            try:
                ready = all(leaf.is_ready()
                            for leaf in jax.tree.leaves(dev))
            except AttributeError:
                ready = True  # no readiness API: device_get below is cheap
            if not ready:
                break
            self._force_oldest()
            joined += 1
        return joined

    def count_candidates_async(self, cand: np.ndarray) -> PendingCounts:
        """Dispatch a counting wave without blocking.

        cand: (C, k) dense-id candidate matrix.  The wave streams through in
        ``cand_block``-sized chunks; at most ``inflight`` chunk results stay
        queued on device before the oldest is forced to host.
        """
        assert self._enc is not None, "call place(enc) first"
        if cand.size == 0:
            return PendingCounts(self, 0)
        cand = np.ascontiguousarray(np.asarray(cand, dtype=np.int32))
        if self._enc.n_transactions == 0:
            # Degenerate DB: zero transactions support nothing; skip dispatch.
            pending = PendingCounts(self, 1)
            pending._parts[0] = np.zeros((cand.shape[0],), np.int64)
            return pending
        # The depth models per-*chunk* latency, so drift is judged on the
        # work of one dispatched chunk — a wave whose C shrinks but still
        # fills cand_block-sized chunks has identical chunk latency and must
        # not pay a pipeline-draining re-tune at every level transition.
        chunk_work = (min(int(cand.shape[0]), self.cand_block)
                      * int(cand.shape[1]))
        if (self.inflight_auto and self._inflight_tuned
                and self._tuned_work is not None
                and not (self._tuned_work / 2 <= chunk_work
                         <= self._tuned_work * 2)):
            # The wave's per-chunk (C, k) work drifted more than 2x from the
            # shape the depth was tuned on (chunk latency scales with work,
            # so the old depth is stale): re-tune on the next clean chunk.
            self._inflight_tuned = False
            self._retune_pending = True
        starts = range(0, cand.shape[0], self.cand_block)
        pending = PendingCounts(self, len(starts))
        # Encode slots: chunks whose device-side encode has been dispatched
        # but whose count has not — the encode of chunk i+1 (and beyond, up
        # to ``encode_ahead``) is submitted before the host ever blocks on
        # the count of chunk i.  inflight=0 keeps the old strictly
        # chunk-by-chunk schedule (no lookahead).
        encoded: Deque[tuple] = collections.deque()
        ahead = self.encode_ahead if self.inflight > 0 else 0
        for slot, i in enumerate(starts):
            chunk = cand[i : i + self.cand_block]
            if (self.inflight_auto and not self._inflight_tuned
                    and slot == 1 and chunk.shape[0] == self.cand_block):
                while encoded:  # the sample must not queue behind slot 0
                    self._count_encoded(pending, encoded)
                self._tune_inflight(pending, slot, chunk, chunk_work)
                ahead = self.encode_ahead if self.inflight > 0 else 0
                continue
            encoded.append((slot, self._dispatch_encode(chunk),
                            chunk.shape[0]))
            if len(encoded) > ahead:
                self._count_encoded(pending, encoded)
        while encoded:  # counts of the trailing encode slots (all async)
            self._count_encoded(pending, encoded)
        return pending

    def _tune_inflight(self, pending: PendingCounts, slot: int,
                       chunk: np.ndarray, chunk_work: int) -> None:
        """Auto-size the queue depth (``inflight=None``): depth = how many
        chunks the host can submit while one completes on device, i.e.
        device completion latency / host dispatch time, clamped to [1, 8].

        Sampling rules keep the measurement honest: a wave's first chunk
        pays jit compilation, so the sample is the wave's *second* chunk,
        and only when it is full ``cand_block`` size (a ragged tail chunk
        has a different padded shape and would recompile inside the sample).
        Until a clean sample arrives the engine runs at the classic
        double-buffering depth of 1 — single-chunk waves never tune and
        simply stay at depth 1, where the queue depth is moot.  When a later
        wave's per-chunk (C, k) work drifts more than 2x from ``_tuned_work``
        the next clean chunk re-runs this sampling (``inflight_retunes``
        counts those mid-run re-tunes).  Counts are bit-identical at any
        depth, so tuning never changes results, only waiting.
        """
        # Drain outstanding work first so the sampled chunk is not queued
        # behind a prior dispatch (one-off: only the tuning wave pays this).
        while self._queue:
            self._force_oldest()
        t0 = time.perf_counter()
        dev = self._dispatch_count(self._dispatch_encode(chunk))
        submit_s = time.perf_counter() - t0
        self._queue.append((pending, slot, dev, chunk.shape[0]))
        t0 = time.perf_counter()
        self._force_oldest()
        wait_s = time.perf_counter() - t0
        self.inflight = int(np.clip(
            round(wait_s / max(submit_s, 1e-6)), 1, 8))
        self._inflight_tuned = True
        self._tuned_work = chunk_work
        if self._retune_pending:  # a mid-run re-tune actually fired
            self.inflight_retunes += 1
            self._retune_pending = False

    def count_candidates(self, cand: np.ndarray) -> np.ndarray:
        """Blocking wrapper: (C, k) candidate matrix -> int64[C] counts."""
        return self.count_candidates_async(cand).result()

    # -- resident-session block counting (the serving delta path) ------------
    def count_block_async(self, enc_block: EncodedDB,
                          cand: np.ndarray) -> PendingCounts:
        """Count ``cand`` over an *ad-hoc* encoded transaction block instead
        of the placed DB — the streaming service's delta-update primitive.

        The block's store tensors ride the dispatch as inputs (nothing is
        re-placed, so the resident window DB and its jits are untouched) and
        results flow through the same double-buffered FIFO as the wave
        pipeline: a query-time ladder refresh and the ingest deltas of the
        next batch interleave on one queue instead of serializing.  Blocks
        are small (one window slot), so counting runs un-sharded on the
        default device — integer adds are order-exact, so delta counts are
        bit-identical under any mesh.
        """
        cand = np.ascontiguousarray(np.asarray(cand, dtype=np.int32))
        if cand.size == 0:
            return PendingCounts(self, 0)
        if enc_block.n_transactions == 0:
            pending = PendingCounts(self, 1)
            pending._parts[0] = np.zeros((cand.shape[0],), np.int64)
            return pending
        trans = {k: jnp.asarray(v)
                 for k, v in self.store.transaction_inputs(enc_block).items()}
        use_kernel = bool(getattr(self.store, "use_kernel", False))
        ekey = ("block_encode", enc_block.f_pad, use_kernel)
        encode = self._place_jit_cache.get(ekey)
        if encode is None:
            encode = jax.jit(functools.partial(
                self.store.encode_candidates, f_pad=enc_block.f_pad))
            self._place_jit_cache[ekey] = encode
        ckey = ("block_count", tuple(sorted(trans)), use_kernel)
        count = self._place_jit_cache.get(ckey)
        if count is None:
            count = jax.jit(self._blocked_count)
            self._place_jit_cache[ckey] = count
        starts = range(0, cand.shape[0], self.cand_block)
        pending = PendingCounts(self, len(starts))
        for slot, i in enumerate(starts):
            chunk = cand[i : i + self.cand_block]
            cand_p = pad_candidates(chunk, enc_block.f_pad)
            self._note_gram(cand_p.shape[1], enc_block.f_pad, cand_p.shape[0])
            dev = count(trans, encode(jnp.asarray(cand_p, dtype=jnp.int32)))
            self._queue.append((pending, slot, dev, chunk.shape[0]))
            while len(self._queue) > self.inflight:
                self._force_oldest()
        return pending

    def count_block(self, enc_block: EncodedDB, cand: np.ndarray) -> np.ndarray:
        """Blocking wrapper around :meth:`count_block_async`."""
        return self.count_block_async(enc_block, cand).result()

    # -- the device-resident level ladder ------------------------------------
    def level_ladder(self, min_count: int, trim: bool = True,
                     fault_plan=None):
        """A fused gen->encode->count->prune loop over the placed DB
        (``runtime/device_loop.py``): one dispatch per level, per-level state
        device-resident, optional on-device transaction trimming."""
        from repro.core.runtime.device_loop import LevelLadder

        return LevelLadder(self, min_count, trim=trim, fault_plan=fault_plan)

    # -- L1 (Job1: OneItemsetMapper + reducer) -------------------------------
    def count_items_device(self, padded: np.ndarray, n_items: int) -> np.ndarray:
        """Device-side Job1: histogram of the (N, L) padded id matrix.

        One scatter-add job over the encoded DB — rows hold *unique* sorted
        ids padded with ITEM_PAD, so presence counting falls out of a plain
        bincount.  Sharded over the same data axes (and reduced with the same
        psum) as every other counting job; no per-transaction Python loop.
        """
        n = padded.shape[0]
        if n == 0 or n_items == 0:
            return np.zeros((n_items,), np.int64)
        shards = self.n_data_shards
        rows = self.shard_rows(n)
        n_padded = rows * shards
        if n_padded != n:
            pad = np.full((n_padded - n, padded.shape[1]), ITEM_PAD, np.int32)
            padded = np.concatenate([padded, pad])

        def hist_local(p):
            # ITEM_PAD rows (and any id >= n_items) land in the dump slot.
            ids = jnp.where(p < n_items, p, n_items)
            h = jnp.zeros((n_items + 1,), jnp.int32).at[ids.ravel()].add(1)
            return h[:n_items]

        key = (padded.shape, n_items)
        with span("repro.job1.put", shards=shards, rows=rows):
            if self.mesh is None:
                dev = jnp.asarray(padded)
            else:
                dev = jax.device_put(
                    padded, NamedSharding(self.mesh, P(self.data_axes)))
        if key not in self._job1_jit:
            if self.mesh is None:
                self._job1_jit[key] = jax.jit(hist_local)
            else:
                def sharded(p):
                    return jax.lax.psum(hist_local(p), self.data_axes)

                self._job1_jit[key] = jax.jit(jax.shard_map(
                    sharded, mesh=self.mesh,
                    in_specs=(P(self.data_axes),), out_specs=P()))
        hist = self._job1_jit[key](dev)
        if self.mesh is not None:  # each device psums a whole histogram
            self.psum_bytes += hist.nbytes
        return fetch_global(hist).astype(np.int64)

    @staticmethod
    def count_items(transactions, n_items: int) -> np.ndarray:
        """Host fallback for Job1 (kept as the device path's oracle)."""
        if len(transactions) == 0:
            return np.zeros((n_items,), np.int64)
        flat = np.concatenate([np.unique(np.asarray(t, np.int64)) for t in transactions])
        return np.bincount(flat, minlength=n_items).astype(np.int64)
