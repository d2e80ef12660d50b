"""MTP head, elastic restart, engine chunking, strategy-equivalence property."""

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest
pytest.importorskip("hypothesis")  # property tests need the optional dep
from hypothesis import given, settings, strategies as st

from repro.configs import get_reduced
from repro.core import FrequentItemsetMiner, brute_force_frequent
from repro.core.engine import MapReduceEngine
from repro.core.itemsets import level_to_matrix
from repro.core.stores import encode_db
from repro.distributed import checkpoint as ckpt
from repro.distributed.sharding import elastic_mesh, resume
from repro.models import model as M
from repro.models.params import materialize, spec


def test_mtp_head_trains():
    cfg = dataclasses.replace(get_reduced("deepseek-v3-671b"), mtp=True)
    rng = jax.random.PRNGKey(0)
    params = materialize(rng, M.abstract_params(cfg))
    assert "mtp" in params
    batch = {
        "tokens": jax.random.randint(rng, (2, 32), 0, cfg.vocab_size),
        "labels": jax.random.randint(rng, (2, 32), 0, cfg.vocab_size),
    }
    loss_mtp, _ = M.loss_fn(params, batch, cfg)
    cfg_off = dataclasses.replace(cfg, mtp=False)
    loss_plain, _ = M.loss_fn(params, batch, cfg_off)
    assert np.isfinite(float(loss_mtp))
    assert float(loss_mtp) > float(loss_plain)  # extra positive CE term
    grads = jax.grad(lambda p: M.loss_fn(p, batch, cfg)[0])(params)
    gnorm = float(sum(jnp.sum(jnp.abs(g.astype(jnp.float32)))
                      for g in jax.tree.leaves(grads["mtp"])))
    assert gnorm > 0  # the MTP branch receives gradient


def test_elastic_mesh_shapes():
    mesh = elastic_mesh(devices=jax.devices(), model_axis=16)
    assert mesh.devices.size >= 1
    assert mesh.shape["model"] == 1  # one CPU device: TP degree sheds to 1


def test_elastic_resume_roundtrip(tmp_path):
    d = str(tmp_path)
    abstract = {"w": spec((8, 4), ("batch", "mlp")),
                "b": spec((4,), ("mlp",), init="zeros")}
    state = materialize(jax.random.PRNGKey(1), abstract)
    ckpt.save(d, 3, state, extra={"note": "pre-failure"})
    # "lose" devices: resume on whatever mesh the survivors allow
    tree, step, extra = resume(d, abstract)
    assert step == 3 and extra["note"] == "pre-failure"
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(tree)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_engine_candidate_chunking_equivalence():
    """Streaming candidate chunks == one-shot counting."""
    rng = np.random.default_rng(0)
    db = [sorted(set(rng.integers(0, 30, rng.integers(2, 9)).tolist()))
          for _ in range(150)]
    enc = encode_db(db, n_items=30)
    cands = level_to_matrix(
        sorted({tuple(sorted(rng.choice(30, 2, replace=False))) for _ in range(60)}))
    big = MapReduceEngine(store="bitmap")
    big.place(enc)
    small = MapReduceEngine(store="bitmap", cand_block=16)
    small.place(enc)
    np.testing.assert_array_equal(
        big.count_candidates(cands), small.count_candidates(cands))


@given(
    st.lists(st.lists(st.integers(0, 12), min_size=1, max_size=6),
             min_size=5, max_size=40),
    st.sampled_from(["fpc", "dpc"]),
)
@settings(max_examples=10, deadline=None)
def test_strategies_equal_spc(db, strategy):
    """Property: combined-pass strategies return exactly SPC's itemsets."""
    min_support = 0.15
    spc = FrequentItemsetMiner(min_support=min_support, strategy="spc").mine(db)
    other = FrequentItemsetMiner(min_support=min_support, strategy=strategy).mine(db)
    assert spc.itemsets == other.itemsets
    oracle = brute_force_frequent(db, spc.min_count)
    assert spc.itemsets == oracle
