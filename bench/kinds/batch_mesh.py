"""Traffic kind ``batch_mesh``: the ``batch`` kind's whole mines on the
configuration's ``data`` mesh.

Every miner, the set-up's warm-up mine among them, is
``FrequentItemsetMiner(min_support=..., mesh=<mesh>)``: the baskets shard
over the mesh's ``data`` axis, each chip counts the candidates over its
split (the mapper) and the partial counts are summed by a psum (the
reducer).  So every sharded program is compiled, or loaded from the
persistent cache, before the window starts.  The mesh is the
configuration's ``mesh``, built by ``repro.launch.mesh.make_data_mesh``,
and takes exactly the configuration's ``chips``.  The window, summary,
check and end-to-end metrics are the ``batch`` kind's.
"""

from __future__ import annotations

import functools

from bench import load

batch = load("kinds", "batch")
control_windows = batch.control_windows


def data_mesh(config: dict):
    """The 1-D ``data`` mesh of the configuration's ``mesh``; refuses
    another axis, and a size other than the configuration's chips."""
    from repro.launch.mesh import make_data_mesh

    axes = config.get("mesh") or {}
    if set(axes) != {"data"}:
        raise ValueError(f"batch_mesh runs on a 1-D data mesh; the "
                         f"configuration's mesh is {axes!r}")
    if axes["data"] != config["chips"]:
        raise ValueError(f"a data mesh of {axes['data']} on a cell of "
                         f"{config['chips']} chips")
    return make_data_mesh(axes["data"])


class Kind(batch.Kind):
    def __init__(self, run, config: dict, traffic: dict, data) -> None:
        self.mesh = data_mesh(config)
        run.log(f"mesh: {dict(self.mesh.shape)} over devices "
                f"{[d.id for d in self.mesh.devices.flat]}")
        super().__init__(run, config, traffic, data)

    @property
    def _miner(self):
        """The miner class the ``batch`` kind builds each mine's miner
        from, given the mesh."""
        return functools.partial(self._factory, mesh=self.mesh)

    @_miner.setter
    def _miner(self, factory) -> None:
        self._factory = factory
