"""repro — MapReduce Apriori with pluggable candidate stores, on JAX/TPU.

Public surface:
  repro.core        — the paper's contribution (miner, engine, stores, hadoop_sim)
  repro.kernels     — Pallas support-count kernels (+ ref oracles)
  repro.serve       — the streaming frequent-itemset ``MiningService``
  repro.distributed — multi-host set-up, checkpointing, elastic mesh rebuild
  repro.data        — transaction generators, datasets, chunked reader, streams
  repro.analytics   — frequent token-set mining over training streams
  repro.launch      — meshes, compile cache, mining and multi-host launchers

Inherited LM scaffolding, outside the public surface and imported by no
mining path (``tests/test_imports.py`` pins that boundary):
  repro.models, repro.configs, repro.train, ``repro.serve.lm``,
  ``repro.distributed.sharding`` / ``compression``, and the LM launchers
  (``repro.launch.train`` / ``dryrun`` / ``steps`` / ``specs`` / ``hlo_stats``).
"""

__version__ = "1.0.0"
