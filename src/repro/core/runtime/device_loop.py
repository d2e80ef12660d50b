"""Device-resident level ladder: fused gen -> encode -> count -> prune.

The host-loop schedule pays four host crossings per level (generate C_k on
host NumPy, ship the (C, k) matrix, fetch counts, filter on host).  The
ladder fuses the whole level step into ONE compiled dispatch: the frequent
level matrix, the candidate join+prune, the store encode, the blocked count
and the min-support compaction all run on device, and only three tiny
fetches (a 2-int stats vector plus the surviving rows/counts) cross back.

Two pieces make the fusion possible with static shapes:

* **jit-able join/prune** — ``_gen_prune`` re-expresses
  ``itemsets.apriori_gen_matrix`` as fixed-shape array ops: an all-pairs
  same-(k-1)-prefix mask over the padded level matrix, a ``jnp.nonzero(...,
  size=c_pad)`` pair extraction (row-major order == lexicographic candidate
  order, matching the host generator row-for-row), and per-column
  row-membership tests for the drop-one prune.  ``apriori_gen_device`` /
  ``filter_candidates_device`` wrap the same primitives for standalone use
  (the non-fused runners' device-side SPC cut-back).
* **host-exact pair count** — ``join_pair_count`` sizes ``c_pad`` on host
  from the level's contiguous prefix groups, so the device nonzero never
  truncates and the only dynamic quantity crossing per level is scalar.

**Transaction trimming** (the authors' follow-up, arXiv:1807.06070): at the
top of each level the ladder drops items that fell out of the frequent level
(downward closure: no future candidate can contain them) and transactions
with fewer than k+1 surviving items (they can never support a (k+1)-set),
then re-compacts rows/columns on device so ``N_pad``/``F_pad``/``L`` shrink
as k grows.  Trimming runs at the TOP of the loop from the current level
only, so a mid-ladder checkpoint restore (the level matrix in original ids)
reproduces the trim state exactly: the one-shot trim from the restored level
equals the cumulative trims of an uninterrupted run — same surviving rows,
same alive items, same padded dims — making resume bit-identical with no
persisted trim state.  Item ids are re-ranked densely after each trim;
``_cur_ids`` maps ladder ids back to the miner's dense id space (the map is
monotone, so lexicographic row order is preserved end-to-end).

Sharding: transaction tensors stay partitioned over the engine's ``data``
axes; the per-level count runs the engine's ``_blocked_count`` inside a
``shard_map`` with the same psum-over-data reduction, and candidate tensors
shard over the ``cand`` axes exactly like the host-loop path (``c_pad`` is
rounded to the cand-shard multiple; the store's ``encode_candidates`` runs
shard-local inside the body, so encoded tensors never leave their shard).

Compiled steps are cached on ``engine.ladder_jit`` keyed by every static
shape, so a second mine over the same shapes is compile-free.
"""

from __future__ import annotations

import functools
import time
from typing import Iterator, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.itemsets import join_pair_count
from repro.core.runtime.engine import MapReduceEngine
from repro.core.runtime.faults import DeviceLostError, FaultPlan
from repro.core.runtime.job import JobProfile
from repro.core.stores.base import ITEM_PAD

# Pad quanta: candidate and level row counts round up to these so the jit
# cache sees few distinct shapes per mine (c_pad additionally rounds to the
# cand-shard multiple so the candidate axis splits evenly over the mesh).
CAND_UNIT = 8
LEVEL_UNIT = 8


def _round_up(x: int, m: int) -> int:
    return -(-int(x) // int(m)) * int(m)


# -- jit-able join / prune / filter primitives ------------------------------

def _same_prefix_pairs(lvl: jnp.ndarray, n_valid) -> jnp.ndarray:
    """bool[l_pad, l_pad]: valid rows a < b sharing their (k-1)-prefix."""
    l_pad, k = lvl.shape
    idx = jnp.arange(l_pad)
    valid = idx < n_valid
    ok = valid[:, None] & valid[None, :] & (idx[:, None] < idx[None, :])
    for j in range(k - 1):
        ok = ok & (lvl[:, j][:, None] == lvl[:, j][None, :])
    return ok


def _rows_member_device(lvl: jnp.ndarray, n_valid,
                        queries: jnp.ndarray) -> jnp.ndarray:
    """bool[Q]: is each query row among the first ``n_valid`` level rows?"""
    l_pad = lvl.shape[0]
    eq = (jnp.arange(l_pad) < n_valid)[None, :]
    for j in range(queries.shape[1]):
        eq = eq & (queries[:, j][:, None] == lvl[:, j][None, :])
    return jnp.any(eq, axis=1)


def _gen_prune(lvl: jnp.ndarray, n_valid, c_pad: int):
    """Join + prune on device.

    Returns ``(cand, keep)``: a (c_pad, k+1) candidate matrix whose first
    ``sum(keep)``-masked rows are exactly ``apriori_gen_matrix`` of the valid
    level rows, in the same lexicographic order (``jnp.nonzero`` emits pair
    indices in row-major order — group by group, then by the two last items
    ascending — which IS the candidates' lexicographic order), and the bool
    keep mask (join pairs surviving the drop-one prune).
    """
    l_pad, k = lvl.shape
    pair_ok = _same_prefix_pairs(lvl, n_valid)
    n_pairs = jnp.sum(pair_ok)
    a_idx, b_idx = jnp.nonzero(pair_ok, size=c_pad, fill_value=0)
    cand = jnp.concatenate(
        [jnp.take(lvl, a_idx, axis=0), jnp.take(lvl, b_idx, axis=0)[:, -1:]],
        axis=1,
    )
    keep = jnp.arange(c_pad) < n_pairs
    for drop in range(k - 1):  # dropping position k-1 or k gives a parent
        subset = jnp.concatenate([cand[:, :drop], cand[:, drop + 1 :]], axis=1)
        keep = keep & _rows_member_device(lvl, n_valid, subset)
    return cand, keep


def _filter_keep(cand: jnp.ndarray, lvl: jnp.ndarray, n_valid) -> jnp.ndarray:
    """bool[C]: rows whose every (k1-1)-subset is a valid level row."""
    k1 = cand.shape[1]
    keep = jnp.ones((cand.shape[0],), bool)
    for drop in range(k1):
        subset = jnp.concatenate([cand[:, :drop], cand[:, drop + 1 :]], axis=1)
        keep = keep & _rows_member_device(lvl, n_valid, subset)
    return keep


_filter_jit = jax.jit(_filter_keep)


@functools.lru_cache(maxsize=None)
def _gen_jit(l_pad: int, k: int, c_pad: int):
    def gen(lvl, n_valid):
        cand, keep = _gen_prune(lvl, n_valid, c_pad)
        sel = jnp.nonzero(keep, size=c_pad, fill_value=c_pad)[0]
        out = jnp.take(cand, sel, axis=0, mode="fill", fill_value=ITEM_PAD)
        return out, jnp.sum(keep)

    return jax.jit(gen)


def apriori_gen_device(level_mat: np.ndarray) -> np.ndarray:
    """jit twin of ``itemsets.apriori_gen_matrix``: identical rows in the
    identical (lexicographic) order, computed on device with static-shape
    padding.  Standalone entry point — the fused ladder inlines the same
    ``_gen_prune`` into its per-level step instead."""
    mat = np.asarray(level_mat, dtype=np.int32)
    if mat.size == 0:
        return np.zeros(
            (0, (mat.shape[1] + 1) if mat.ndim == 2 else 0), np.int32)
    c, k = mat.shape
    n_pairs = join_pair_count(mat)
    if n_pairs == 0:
        return np.zeros((0, k + 1), np.int32)
    l_pad = _round_up(c, 64)
    c_pad = _round_up(n_pairs, 64)
    lvl = np.full((l_pad, k), ITEM_PAD, np.int32)
    lvl[:c] = mat
    out, n_keep = _gen_jit(l_pad, k, c_pad)(jnp.asarray(lvl), np.int32(c))
    return np.asarray(jax.device_get(out))[: int(n_keep)]


def filter_candidates_device(cand: np.ndarray,
                             level_mat: np.ndarray) -> np.ndarray:
    """jit twin of ``itemsets.filter_candidates_matrix`` (order-preserving
    SPC cut-back): keep a candidate row iff every k-subset is a level row.
    Pad query/level rows are all-ITEM_PAD and can never match a real level
    row, so padding to the 128-row jit quantum never changes the answer."""
    cand = np.asarray(cand, dtype=np.int32)
    if cand.size == 0 or level_mat.size == 0:
        return np.zeros((0, cand.shape[1] if cand.ndim == 2 else 0), np.int32)
    lvl_m = np.asarray(level_mat, dtype=np.int32)
    q, k1 = cand.shape
    n, k = lvl_m.shape
    cand_p = np.full((_round_up(q, 128), k1), ITEM_PAD, np.int32)
    cand_p[:q] = cand
    lvl_p = np.full((_round_up(n, 128), k), ITEM_PAD, np.int32)
    lvl_p[:n] = lvl_m
    keep = np.asarray(jax.device_get(
        _filter_jit(jnp.asarray(cand_p), jnp.asarray(lvl_p), np.int32(n))
    ))[:q]
    return cand[keep]


# -- the fused device-resident loop -----------------------------------------

class LevelLadder:
    """Device-resident fused level loop over a placed ``MapReduceEngine``.

    ``run(level_mat, start_k, max_k)`` is a strategy-shaped generator
    (one ``(JobProfile, {itemset: count})`` per level, itemsets in the
    miner's dense id space) whose per-level hot path is a single compiled
    dispatch; with ``trim=True`` each level first drops dead items and
    transactions on device (see module docstring).
    """

    def __init__(self, engine: MapReduceEngine, min_count: int,
                 trim: bool = True,
                 fault_plan: Optional[FaultPlan] = None) -> None:
        self.engine = engine
        self.min_count = int(min_count)
        self.trim = bool(trim)
        self.fault_plan = fault_plan
        # Compiled-step cache lives on the engine so a second mine over the
        # same shapes (benchmark rounds, elastic resumes on the same mesh)
        # pays zero recompiles; the mesh is fixed per engine, so shapes +
        # store identity are a complete key.
        self._jits = engine.ladder_jit
        mesh = engine.mesh
        self._ds = (NamedSharding(mesh, P(engine.data_axes))
                    if mesh is not None else None)
        self._rs = NamedSharding(mesh, P()) if mesh is not None else None

    # -- state --------------------------------------------------------------
    def _init_state(self, level_mat: np.ndarray) -> None:
        enc = self.engine._enc
        self._n_pad, self._width = enc.padded.shape
        self._f_pad = enc.f_pad
        if self._ds is not None:
            self._padded = jax.device_put(enc.padded, self._ds)
            self._bitmap = jax.device_put(enc.bitmap, self._ds)
        else:
            self._padded = jnp.asarray(enc.padded)
            self._bitmap = jnp.asarray(enc.bitmap)
        self._trans = self._make_inputs()
        # ladder id -> miner dense id; trimming re-ranks ids densely, and
        # this (monotone) map translates results back at the yield boundary.
        self._cur_ids = np.arange(enc.n_items, dtype=np.int64)
        self._lvl_host = np.asarray(level_mat, dtype=np.int32)
        n, k = self._lvl_host.shape
        self._n_valid = n
        self._l_pad = max(LEVEL_UNIT, _round_up(n, LEVEL_UNIT))
        lvl = np.full((self._l_pad, k), ITEM_PAD, np.int32)
        lvl[:n] = self._lvl_host
        self._lvl_dev = (jax.device_put(lvl, self._rs)
                         if self._rs is not None else jnp.asarray(lvl))

    def _make_inputs(self) -> dict:
        """(Re)build the store's transaction tensors from the device-resident
        padded/bitmap pair — on device, so a trim never round-trips the DB."""
        store = self.engine.store
        key = ("inputs", self.engine.store_name, self._n_pad, self._width,
               self._f_pad)
        fn = self._jits.get(key)
        if fn is None:
            build = store.device_transaction_inputs
            if self._ds is not None:
                shapes = jax.eval_shape(
                    build,
                    jax.ShapeDtypeStruct((self._n_pad, self._width),
                                         jnp.int32),
                    jax.ShapeDtypeStruct((self._n_pad, self._f_pad),
                                         jnp.uint8),
                )
                fn = jax.jit(build, out_shardings=jax.tree.map(
                    lambda _: self._ds, shapes))
            else:
                fn = jax.jit(build)
            self._jits[key] = fn
        return fn(self._padded, self._bitmap)

    def _check_fault(self, k1: int) -> None:
        if self.fault_plan is None:
            return
        spec = self.fault_plan.device_loss(k=k1)
        if spec is not None:
            # Simulated device loss at level dispatch: outstanding state is
            # abandoned; the driver's elastic-restart loop owns recovery.
            self.engine.abandon()
            raise DeviceLostError(lost=spec.lost, k=k1)

    # -- the fused per-level step -------------------------------------------
    def _get_step(self, k1: int, c_pad: int):
        eng = self.engine
        store = eng.store
        key = ("step", eng.store_name, k1, c_pad, self._n_pad, self._width,
               self._f_pad, self._l_pad,
               bool(getattr(store, "use_kernel", False)))
        fn = self._jits.get(key)
        if fn is not None:
            return fn
        f_pad = self._f_pad
        encode_fn = functools.partial(store.encode_candidates, f_pad=f_pad)
        data_spec = P(eng.data_axes)
        cand_spec = P(eng.cand_axes) if eng.cand_axes else P()

        def step(trans, lvl, n_valid, min_count):
            cand, keep = _gen_prune(lvl, n_valid, c_pad)
            # Non-surviving rows become standard pad rows (the always-zero
            # bitmap column repeated k1 times) before the encode, so every
            # store counts them as 0 — same trick as ``pad_candidates``.
            pad_row = jnp.full((1, k1), f_pad - 1, jnp.int32)
            cand_safe = jnp.where(keep[:, None], cand, pad_row)
            if eng.mesh is not None:
                def body(tr, cd):
                    # Shard-local encode + blocked count + psum: identical
                    # arithmetic to the host-loop count path.
                    local = eng._blocked_count(tr, encode_fn(cd))
                    return jax.lax.psum(local, eng.data_axes)

                counts = jax.shard_map(
                    body, mesh=eng.mesh,
                    in_specs=(jax.tree.map(lambda _: data_spec, trans),
                              cand_spec),
                    out_specs=cand_spec,
                )(trans, cand_safe)
            else:
                counts = eng._blocked_count(trans, encode_fn(cand_safe))
            freq_mask = keep & (counts >= min_count)
            # Order-preserving compaction: surviving rows stay lex-sorted.
            sel = jnp.nonzero(freq_mask, size=c_pad, fill_value=c_pad)[0]
            freq = jnp.take(cand, sel, axis=0, mode="fill",
                            fill_value=ITEM_PAD)
            fcounts = jnp.take(counts, sel, mode="fill", fill_value=0)
            stats = jnp.stack([jnp.sum(freq_mask), jnp.sum(keep)])
            return freq, fcounts, stats

        if eng.mesh is not None:
            fn = jax.jit(step, out_shardings=(self._rs, self._rs, self._rs))
        else:
            fn = jax.jit(step)
        self._jits[key] = fn
        return fn

    # -- trimming (arXiv:1807.06070, on device) ------------------------------
    def _trim(self, k1: int) -> None:
        """Drop dead items/transactions and re-compact the device DB.

        ``alive`` = items of the current level (downward closure: exact);
        ``live`` = transactions with >= k1 alive items (a (k1)-candidate
        needs k1 of them: exact).  Rows, bitmap columns and item ids are
        re-compacted order-preservingly, so lex order and counts are
        untouched; if no padded dimension would shrink the trim is skipped
        (id space unchanged — unobservable through ``_cur_ids``).
        """
        n_pad, width = self._n_pad, self._width
        f_pad, l_pad = self._f_pad, self._l_pad
        k = self._lvl_host.shape[1]

        skey = ("trim_stats", l_pad, k, n_pad, width, f_pad)
        sfn = self._jits.get(skey)
        if sfn is None:
            def stats_fn(lvl, n_valid, padded, thresh):
                lvalid = (jnp.arange(l_pad) < n_valid)[:, None]
                ids = jnp.where(lvalid & (lvl < f_pad), lvl, f_pad - 1)
                alive = jnp.zeros((f_pad,), bool).at[ids.reshape(-1)].set(True)
                alive = alive.at[f_pad - 1].set(False)  # the dump slot
                safe = jnp.where(padded < f_pad, padded, f_pad - 1)
                cnt = jnp.sum(jnp.take(alive, safe).astype(jnp.int32), axis=1)
                live = cnt >= thresh
                max_len = jnp.max(jnp.where(live, cnt, 0))
                stats = jnp.stack([jnp.sum(live.astype(jnp.int32)),
                                   jnp.sum(alive.astype(jnp.int32)), max_len])
                return stats, live, alive

            if self.engine.mesh is not None:
                sfn = jax.jit(stats_fn,
                              out_shardings=(self._rs, self._ds, self._rs))
            else:
                sfn = jax.jit(stats_fn)
            self._jits[skey] = sfn
        stats, live, alive = sfn(self._lvl_dev, np.int32(self._n_valid),
                                 self._padded, np.int32(k1))
        n_live, n_alive, max_len = (int(x) for x in np.asarray(stats))

        shards = self.engine.n_data_shards
        new_n_pad = min(n_pad, max(shards, _round_up(max(n_live, 1), shards)))
        new_f_pad = min(f_pad, ((n_alive // 128) + 1) * 128)
        # Every live row fits: it has <= max_len alive items, dead rows have
        # < k1 <= max_len; the floor of 2 keeps degenerate shapes lane-sane.
        new_width = min(width, max(2, max_len))
        if (new_n_pad, new_f_pad, new_width) == (n_pad, f_pad, width):
            return  # nothing shrinks; skip the remap entirely

        akey = ("trim_apply", l_pad, k, n_pad, width, f_pad,
                new_n_pad, new_width, new_f_pad)
        afn = self._jits.get(akey)
        if afn is None:
            def apply_fn(padded, bitmap, lvl, live, alive, n_valid):
                # Dense re-rank of alive items; monotone, so sorted rows and
                # the lex order of the level matrix are preserved.
                new_of_old = jnp.cumsum(alive.astype(jnp.int32)) - 1
                safe = jnp.where(padded < f_pad, padded, f_pad - 1)
                hit = (padded < f_pad) & jnp.take(alive, safe)
                remapped = jnp.where(hit, jnp.take(new_of_old, safe),
                                     ITEM_PAD)
                remapped = jnp.sort(remapped, axis=1)[:, :new_width]
                remapped = remapped.astype(jnp.int32)
                ridx = jnp.nonzero(live, size=new_n_pad, fill_value=n_pad)[0]
                new_padded = jnp.take(remapped, ridx, axis=0, mode="fill",
                                      fill_value=ITEM_PAD)
                cidx = jnp.nonzero(alive, size=new_f_pad,
                                   fill_value=f_pad - 1)[0]
                new_bitmap = jnp.take(
                    jnp.take(bitmap, ridx, axis=0, mode="fill", fill_value=0),
                    cidx, axis=1)
                lvalid = (jnp.arange(l_pad) < n_valid)[:, None]
                lsafe = jnp.where(lvl < f_pad, lvl, f_pad - 1)
                new_lvl = jnp.where(lvalid, jnp.take(new_of_old, lsafe),
                                    ITEM_PAD).astype(jnp.int32)
                return new_padded, new_bitmap, new_lvl, cidx

            if self.engine.mesh is not None:
                afn = jax.jit(apply_fn, out_shardings=(
                    self._ds, self._ds, self._rs, self._rs))
            else:
                afn = jax.jit(apply_fn)
            self._jits[akey] = afn
        new_padded, new_bitmap, new_lvl, cidx = afn(
            self._padded, self._bitmap, self._lvl_dev, live, alive,
            np.int32(self._n_valid))

        cidx_h = np.asarray(jax.device_get(cidx))[:n_alive].astype(np.int64)
        self._cur_ids = self._cur_ids[cidx_h]
        remap = np.zeros((f_pad,), np.int32)
        remap[cidx_h] = np.arange(n_alive, dtype=np.int32)
        self._lvl_host = remap[self._lvl_host]
        self._padded, self._bitmap, self._lvl_dev = (new_padded, new_bitmap,
                                                     new_lvl)
        self._n_pad, self._f_pad, self._width = new_n_pad, new_f_pad, new_width
        self._trans = self._make_inputs()

    # -- the generator -------------------------------------------------------
    def run(self, level_mat: np.ndarray, start_k: int,
            max_k: int) -> Iterator[Tuple[JobProfile, dict]]:
        mat = np.asarray(level_mat, dtype=np.int32)
        if mat.size == 0 or start_k > max_k:
            return
        if mat.ndim != 2 or mat.shape[1] != start_k - 1:
            raise ValueError(
                f"level matrix width {mat.shape} does not match "
                f"start_k={start_k} (expected width {start_k - 1})")
        if self.engine._enc is None:
            raise RuntimeError("place() the database before running the ladder")
        if self.engine._enc.n_transactions == 0:
            return
        self._init_state(mat)
        k = start_k - 1  # current frequent-level width
        while k + 1 <= max_k:
            k1 = k + 1
            n_pairs = join_pair_count(self._lvl_host)
            if n_pairs == 0:
                return
            self._check_fault(k1)
            t0 = time.perf_counter()
            trim_s = 0.0
            if self.trim:
                self._trim(k1)
                trim_s = time.perf_counter() - t0
            c_pad = _round_up(
                n_pairs, CAND_UNIT * max(1, self.engine.n_cand_shards))
            step = self._get_step(k1, c_pad)
            freq_dev, counts_dev, stats_dev = step(
                self._trans, self._lvl_dev, np.int32(self._n_valid),
                np.int32(self.min_count))
            stats = np.asarray(jax.device_get(stats_dev))
            n_freq, n_cand = int(stats[0]), int(stats[1])
            freq_l = np.asarray(jax.device_get(freq_dev[:n_freq]))
            counts = np.asarray(
                jax.device_get(counts_dev[:n_freq])).astype(np.int64)
            wall = time.perf_counter() - t0
            prof = JobProfile(
                k=k1, n_candidates=n_cand, n_frequent=n_freq, seconds=wall,
                count_seconds=wall - trim_s, reduce_seconds=trim_s,
                n_pad=self._n_pad, f_pad=self._f_pad,
            )
            # Translate ladder ids -> miner dense ids at the yield boundary
            # (monotone map: rows stay lex-sorted for the driver/checkpoint).
            out = {}
            if n_freq:
                freq_miner = self._cur_ids[freq_l]
                out = {tuple(int(x) for x in freq_miner[i]): int(counts[i])
                       for i in range(n_freq)}
            yield prof, out
            if n_freq == 0:
                return
            # Advance: the surviving rows ARE the next level, already on
            # device — slice to the level pad and keep climbing.
            self._lvl_host = freq_l.astype(np.int32)
            self._n_valid = n_freq
            self._l_pad = min(c_pad,
                              max(LEVEL_UNIT, _round_up(n_freq, LEVEL_UNIT)))
            self._lvl_dev = freq_dev[: self._l_pad]
            k = k1


def ladder(runner, level, min_count: int, start_k: int, max_k: int,
           trim: bool = True) -> Iterator[Tuple[JobProfile, dict]]:
    """Strategy-compatible entry point for the device-resident ladder.

    Drop-in for ``strategies.spc`` on engine-backed runners; ``SimRunner``
    (no engine) keeps the host loop as the oracle and is rejected loudly.
    """
    engine = getattr(runner, "engine", None)
    if engine is None:
        raise ValueError(
            "device_loop requires an engine-backed runner (JaxRunner/"
            "ShardedRunner); SimRunner keeps the host loop as the oracle")
    if getattr(runner, "_reader", None) is not None:
        raise ValueError(
            "device_loop=True needs the DB resident on device; out-of-core "
            "chunked ingestion streams it instead — mine with "
            "device_loop=False (the host SPC loop)")
    lad = LevelLadder(engine, min_count, trim=trim,
                      fault_plan=getattr(runner, "fault_plan", None))
    yield from lad.run(np.asarray(level, dtype=np.int32), start_k, max_k)
