"""The paper's experimental grid as a parity test.

Each case mines one cell of the grid -- candidate structure x minimum
support x mapper count on the T10I4D100K twin, plus the registry's three
adversarial generators -- on every backend: ``SimRunner`` (the paper's
Hadoop job with the cell's structure and mappers), ``JaxRunner`` and
``ShardedRunner`` on the 1-D ``data`` mesh, both with the
``packed_bitmap`` store.  ``run_parity_cell`` raises unless all three give
identical itemsets AND supports.  The grid lives in its own module so that
``--dist loadfile`` hands it a worker of its own.
"""

import pytest

from repro.core.runtime import (
    JaxRunner,
    ShardedRunner,
    SimRunner,
    run_parity_cell,
)
from repro.data import get_dataset
from repro.launch.mesh import make_data_mesh

STRUCTURES = ["hash_tree", "trie", "hash_table_trie"]
STORE = "packed_bitmap"
# The T10 twin's most frequent item sits near 4.6% support, so the ladder
# starts at 0.03: a higher rung would certify parity over empty results.
SUPPORTS = [0.03, 0.02]
MAPPERS = [1, 4]
SCALE = 0.01
MAX_K = 8

# (dataset, structure, min_support, mappers, max_k)
GRID = [("T10I4D100K", structure, support, mappers, MAX_K)
        for structure in STRUCTURES
        for support in SUPPORTS
        for mappers in MAPPERS]
# The adversarial generators, one point each at a support fitted to the
# shape: long_tail's head items sit near 0.85 support; wide_sparse has no
# frequent pair above a few permille.
ADVERSARIAL = [
    ("long_tail", "trie", 0.3, 2, 5),
    ("near_duplicate", "trie", 0.05, 2, 3),
    ("wide_sparse", "trie", 0.002, 2, 3),
]


def _case_id(case):
    dataset, structure, support, mappers, _ = case
    return f"{dataset}-{structure}-s{support:g}-m{mappers}"


@pytest.mark.parametrize("case", GRID + ADVERSARIAL, ids=_case_id)
def test_run_parity_cell_backends_agree(case):
    dataset, structure, support, mappers, max_k = case
    db = get_dataset(dataset, scale=SCALE, seed=0)
    cell = run_parity_cell(db, support, {
        "sim": lambda: SimRunner(structure=structure, n_mappers=mappers),
        "jax": lambda: JaxRunner(store=STORE),
        "sharded": lambda: ShardedRunner(store=STORE, mesh=make_data_mesh()),
    }, max_k=max_k)
    assert set(cell.backends) == {"sim", "jax", "sharded"}
    assert cell.n_itemsets > 0
    assert len(cell.digest) == 16
    # The sim cell keeps the paper's cluster model, the engine cells wall
    # time.
    assert cell.backends["sim"]["parallel_seconds"] > 0
    assert cell.backends["jax"]["seconds"] > 0
    assert cell.backends["sharded"]["seconds"] > 0
