"""Logical-axis sharding rules (MaxText-style) for the production meshes.

Mesh axes:
  pod    — outermost DP axis across pods (multi-pod mesh only)
  data   — within-pod DP / FSDP axis
  model  — tensor/expert-parallel axis

Logical names used by the model code:

  batch     activation batch dim            -> (pod, data)
  fsdp      parameter ZeRO shard dim        -> (pod, data)
  heads     attention heads / q-proj out    -> model
  kv_heads  kv heads                        -> model
  mlp       FFN hidden                      -> model
  vocab     embedding rows / logits         -> model
  experts   MoE expert dim                  -> model
  embed     d_model                         -> None (replicated; FSDP takes
            the other dim of every matrix, so nothing is fully replicated)
  seq       sequence dim of activations     -> None (context-parallel opt-in)
  cache_seq KV-cache sequence dim           -> None
  layers    scan/stack dim                  -> None

Also here, on the LM side: the activation-sharding context the layers
constrain their intermediates under, and elastic restart of a parameter
tree onto a (data, model) mesh of the surviving devices.  The miner's own
elastic rebuild (``distributed.elastic``) needs none of this.
"""

from __future__ import annotations

import contextlib
import dataclasses
from contextvars import ContextVar
from typing import Optional, Sequence, Tuple

import numpy as np
import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.distributed import checkpoint as ckpt
from repro.models.params import logical_to_pspec, shape_structs, shardings


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    rules: dict

    def pspec(self, axes) -> P:
        return logical_to_pspec(axes, self.rules)

    def with_overrides(self, **kv) -> "ShardingRules":
        new = dict(self.rules)
        new.update(kv)
        return ShardingRules(new)


def default_rules(multi_pod: bool = False, *, fsdp: bool = True,
                  context_parallel: bool = False) -> ShardingRules:
    dp: Tuple[str, ...] = ("pod", "data") if multi_pod else ("data",)
    rules = {
        "batch": dp,
        "fsdp": dp if fsdp else None,
        "heads": "model",
        "kv_heads": "model",
        "mlp": "model",
        "vocab": "model",
        # EP degree = model axis. (Measured: extending experts over the data
        # axes makes GSPMD all-gather the (G,S,E,C) dispatch tensor — 19 TB —
        # because the einsum contracts the data-sharded token dim against a
        # data-sharded expert dim. Full-mesh EP needs an explicit shard_map
        # all-to-all.)
        "experts": "model",
        "embed": None,
        "embed_tp": "model",   # alternative: shard d_model itself (decode TP)
        "seq": dp if context_parallel else None,
        # KV caches shard their sequence dim over the model axis: kv_heads
        # rarely divide a 16-way axis (10, 8, 4, 2, 1 heads), and an
        # unsharded 32k cache replicates ~50-190 GB/device. Decode attention
        # over the seq-sharded cache costs one small psum for the softmax.
        "cache_seq": "model",
        "layers": None,
        "state": "model",      # SSM / RG-LRU recurrent width
        # Flash-tile fallback chain: when neither kv_heads nor the group dim
        # divides the model axis (starcoder 4x12, qwen 2x6, gemma2 4x2 ...),
        # the q-chunk dim carries it instead — sequence-parallel attention
        # tiles. Divisible-head archs dedup this away.
        "attn_q": "model",
    }
    return ShardingRules(rules)


def batch_pspec(rules: ShardingRules) -> P:
    return rules.pspec(("batch", None))


def act_pspec(rules: ShardingRules, *axes) -> P:
    return rules.pspec(axes)


# -- activation-sharding context ---------------------------------------------
# Model code is mesh-agnostic; launchers activate (mesh, rules) here and the
# layers call :func:`constrain` on intermediate activations.  Without an
# active context, constrain is a no-op (single-device tests).  This is the
# GSPMD discipline that keeps the partitioner from replicating intermediates
# inside remat'd scan bodies (observed: an unconstrained forward
# attention-score dot materialized the full global batch per device — 17x
# FLOP inflation).

_ACTIVE: ContextVar[Optional[Tuple[object, object]]] = ContextVar(
    "repro_sharding_ctx", default=None)


@contextlib.contextmanager
def use_sharding(mesh, rules):
    token = _ACTIVE.set((mesh, rules))
    try:
        yield
    finally:
        _ACTIVE.reset(token)


def constrain(x, *axes):
    """Constrain ``x``'s sharding by logical axis names (None = replicated)."""
    active = _ACTIVE.get()
    if active is None:
        return x
    mesh, rules = active
    pspec = logical_to_pspec(axes, rules.rules, x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, pspec))


# -- elastic restart of a parameter tree --------------------------------------
# Checkpoints store *global* tensors (see checkpoint.py), so elasticity
# reduces to: build the largest usable mesh from the surviving devices,
# re-derive shardings from the same logical rules, and restore.


def elastic_mesh(
    devices: Optional[Sequence] = None,
    model_axis: int = 16,
    axis_names: Tuple[str, str] = ("data", "model"),
):
    """Largest (data, model) mesh on the surviving devices.

    Keeps the model axis at ``model_axis`` if possible (TP degree is baked
    into compiled kernels' efficiency, so prefer shedding data parallelism);
    otherwise falls back to the largest power-of-two split.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    model = model_axis
    while model > 1 and n % model:
        model //= 2
    data = n // model
    usable = data * model
    mesh_devices = np.asarray(devices[:usable]).reshape(data, model)
    return jax.sharding.Mesh(mesh_devices, axis_names)


def resume(ckpt_dir: str, abstract_state, mesh=None, rules: ShardingRules = None,
           step: Optional[int] = None):
    """Restore ``abstract_state`` (tree of ParamSpec) onto ``mesh``.

    Returns (state_tree, step, extra) with every tensor device_put with the
    sharding the current mesh dictates — regardless of the mesh that saved it.
    """
    mesh = mesh if mesh is not None else elastic_mesh()
    rules = rules or default_rules()
    sh = shardings(abstract_state, mesh, rules.rules)
    like = shape_structs(abstract_state)
    out = ckpt.restore(ckpt_dir, like, step=step, shardings=sh)
    if out is None:
        raise FileNotFoundError(f"no checkpoint in {ckpt_dir}")
    return out
