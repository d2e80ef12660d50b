"""Compile the main path's device programs for a described TPU v5e.

No chip is needed: the TPU compiler compiles for a ``v5e:2x2`` topology
that is described, not attached, and refuses what the chip would refuse —
Mosaic layouts and slices the kernels may not use, and programs that do not
fit the chip's HBM.  Shapes are T10I4D100K's at support 0.0025 (the size
``chip_smoke.py`` mines): N = 100,000 baskets, 941 frequent items
(F_pad = 1024, W = 32 packed words), baskets up to 34 items wide, and
C2 = 442,270 candidate pairs counted in 32,768-row chunks.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and it keeps it until it exits.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

N, WIDTH, F_PAD, W = 100_000, 34, 1024, 32
W_WIDE = 130  # more than 128 words: a word block of 128 lanes, W padded
CHUNK, C2, L1 = 32_768, 442_270, 941
HBM_BYTES = 16e9  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around them.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("TPU_LOG_DIR", "disabled")
            try:
                topo = topologies.get_topology_desc(platform="tpu",
                                                    topology_name="v5e:2x2")
            except Exception as e:  # no TPU compiler in this installation
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
            yield topo
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _total_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)


@pytest.mark.parametrize("kernel", ["support_count", "packed_support_count",
                                    "packed_support_count_wide"])
def test_kernel_compiles_for_v5e(one_chip, kernel):
    """Both kernels at T10 width; the packed one also past 128 words
    (4,160 items), where the word block is 128 lanes and W is padded."""
    from repro.kernels.support_count import packed_support_count, support_count

    if kernel == "support_count":
        fn = functools.partial(support_count, interpret=False)
        args = ((N, F_PAD), jnp.uint8), ((CHUNK, F_PAD), jnp.float32)
    else:
        w = W if kernel == "packed_support_count" else W_WIDE
        fn = functools.partial(packed_support_count, interpret=False)
        args = ((N, w), jnp.uint32), ((CHUNK, w), jnp.uint32)
    args = [_shape(one_chip, s, d) for s, d in args]
    args.append(_shape(one_chip, (CHUNK,), jnp.int32))
    lowered = jax.jit(fn).lower(*args)
    assert "tpu_custom_call" in lowered.as_text()
    assert _total_bytes(lowered.compile()) < HBM_BYTES


@pytest.mark.parametrize("store", ["perfect_hash", "packed_bitmap"])
def test_count_step_compiles_for_v5e(one_chip, store):
    """One host-loop chunk: the engine's blocked count over all N rows."""
    from repro.core.runtime.engine import MapReduceEngine

    eng = MapReduceEngine(store=store)
    trans = jax.eval_shape(eng.store.device_transaction_inputs,
                           _shape(one_chip, (N, WIDTH), jnp.int32),
                           _shape(one_chip, (N, F_PAD), jnp.uint8))
    cands = jax.eval_shape(
        functools.partial(eng.store.encode_candidates, f_pad=F_PAD),
        _shape(one_chip, (CHUNK, 2), jnp.int32))
    place = lambda tree: {k: _shape(one_chip, v.shape, v.dtype)
                          for k, v in tree.items()}
    compiled = jax.jit(eng._blocked_count).lower(
        place(trans), place(cands)).compile()
    assert _total_bytes(compiled) < HBM_BYTES


@pytest.mark.parametrize("store", ["perfect_hash", "sorted_prefix",
                                   "hash_bucket"])
def test_ladder_k2_step_fits_one_v5e(one_chip, store):
    """The fused ladder's k=2 step counts all of C2 in one dispatch; it must
    fit one chip's HBM.  hash_bucket's step is the largest of the five
    stores (about 3.0 GB of temporaries)."""
    from repro.core.runtime.engine import MapReduceEngine

    compiled = _ladder_k2_step(MapReduceEngine(store=store), one_chip,
                               one_chip)
    assert _total_bytes(compiled) < HBM_BYTES


def test_sharded_ladder_k2_step_compiles_for_v5e_2x2(topo):
    """The four-chip smoke's ``data`` x ``cand`` ladder: the k=2 step over
    a 2x2 mesh of the described chips, each holding half the baskets and
    half of C2."""
    from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec

    from repro.core.runtime.engine import MapReduceEngine

    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "cand"),
                axis_types=(AxisType.Auto,) * 2)
    eng = MapReduceEngine(mesh=mesh, cand_axes=("cand",))
    compiled = _ladder_k2_step(eng, NamedSharding(mesh, PartitionSpec("data")),
                               NamedSharding(mesh, PartitionSpec()))
    assert "all-reduce" in compiled.as_text()  # the psum over ``data``
    assert _total_bytes(compiled) < HBM_BYTES


def _ladder_k2_step(eng, data_sharding, replicated):
    """Compile the ladder's k=2 step over L1 frequent items and N baskets
    (rows placed with ``data_sharding``)."""
    from repro.core.runtime.device_loop import CAND_UNIT, LevelLadder, _round_up

    ladder = LevelLadder(eng, min_count=250, trim=False)
    l_pad = _round_up(L1, 8)
    ladder._n_pad, ladder._width, ladder._f_pad, ladder._l_pad = (
        N, WIDTH, F_PAD, l_pad)
    step = ladder._get_step(
        2, _round_up(C2, CAND_UNIT * max(1, eng.n_cand_shards)))
    trans = jax.eval_shape(eng.store.device_transaction_inputs,
                           jax.ShapeDtypeStruct((N, WIDTH), jnp.int32),
                           jax.ShapeDtypeStruct((N, F_PAD), jnp.uint8))
    trans = {k: _shape(data_sharding, v.shape, v.dtype)
             for k, v in trans.items()}
    scalar = _shape(replicated, (), jnp.int32)
    return step.lower(trans, _shape(replicated, (l_pad, 1), jnp.int32),
                      scalar, scalar).compile()
