"""Mixture-of-Experts with GShard-style capacity dispatch.

Tokens are grouped along the (data-sharded) batch axis; experts live on the
``model`` mesh axis (expert parallelism). Dispatch/combine are expressed as
einsums against a (G, S, E, C) one-hot tensor so XLA inserts the all-to-alls;
capacity-overflow tokens are dropped (combine weight 0), the standard GShard
trade. The router runs in f32 and an auxiliary load-balance loss is returned.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain
from repro.models.params import spec
from repro.models.layers import mlp_abstract, mlp, _act


def moe_abstract(cfg: ModelConfig):
    m = cfg.moe
    d, f, e = cfg.d_model, m.d_ff_expert, m.n_experts
    p = {
        "router": spec((d, e), ("fsdp", "experts"), dtype=jnp.float32),
        "w_up": spec((e, d, f), ("experts", "fsdp", None)),
        "w_gate": spec((e, d, f), ("experts", "fsdp", None)),
        "w_down": spec((e, f, d), ("experts", None, "fsdp")),
    }
    if m.n_shared:
        p["shared"] = mlp_abstract(cfg, d_ff=m.d_ff_expert * m.n_shared)
    return p


def _capacity(tokens_per_group: int, cfg: ModelConfig) -> int:
    m = cfg.moe
    c = int(tokens_per_group * m.top_k * m.capacity_factor / m.n_experts)
    return max(8, ((c + 7) // 8) * 8)


def moe_layer(params, x, cfg: ModelConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, D) -> (out, aux_loss).

    Tokens are reshaped into groups of ``moe.group_size``; capacity (and the
    dispatch tensor) scales with the group size, not the full batch — with
    S_g = 1024 and top-8 over 256 experts the dispatch tensor stays ~2% the
    size of the activations it routes.
    """
    m = cfg.moe
    b, s0, d = x.shape
    tokens = b * s0
    sg = min(m.group_size, tokens)
    while tokens % sg:  # largest divisor of the token count <= group_size
        sg -= 1
    x = x.reshape(tokens // sg, sg, d)
    g, s, _ = x.shape
    e = m.n_experts
    cap = _capacity(s, cfg)

    logits = constrain(
        jnp.einsum("gsd,de->gse", x.astype(jnp.float32), params["router"]),
        "batch", None, None)
    probs = jax.nn.softmax(logits, axis=-1)                      # (G,S,E)

    # Iterative top-k slot assignment with per-slot capacity cumsum.
    remaining = probs
    dispatch = jnp.zeros((g, s, e, cap), x.dtype)
    combine = jnp.zeros((g, s, e, cap), jnp.float32)
    position_in_expert = jnp.zeros((g, e), jnp.int32)
    weight_sum = jnp.zeros((g, s), jnp.float32)
    for _ in range(m.top_k):
        idx = jnp.argmax(remaining, axis=-1)                     # (G,S)
        onehot = jax.nn.one_hot(idx, e, dtype=jnp.float32)       # (G,S,E)
        gate = (remaining * onehot).sum(-1)                      # (G,S)
        remaining = remaining * (1.0 - onehot)
        pos = position_in_expert[:, None, :] + jnp.cumsum(onehot, axis=1) - onehot
        pos = (pos * onehot).sum(-1).astype(jnp.int32)           # (G,S) slot idx
        fits = pos < cap
        pos_oh = jax.nn.one_hot(pos, cap, dtype=jnp.float32)     # (G,S,C)
        contrib = onehot[..., None] * pos_oh[:, :, None, :] * fits[..., None, None]
        dispatch = dispatch + contrib.astype(x.dtype)
        combine = combine + contrib * gate[..., None, None]
        position_in_expert = position_in_expert + (
            onehot * fits[..., None]).sum(axis=1).astype(jnp.int32)
        weight_sum = weight_sum + gate * fits

    # Renormalize kept top-k gates (DeepSeek-style normalized routing).
    combine = combine / jnp.maximum(weight_sum[..., None, None], 1e-9)

    dispatch = constrain(dispatch, "batch", None, "experts", None)
    combine = constrain(combine, "batch", None, "experts", None)
    xin = jnp.einsum("gsec,gsd->egcd", dispatch, x)              # all-to-all in
    xin = constrain(xin, "experts", "batch", None, None)
    h = _act(cfg.mlp_act)(jnp.einsum("egcd,edf->egcf", xin, params["w_up"]))
    if "w_gate" in params:
        h = h * jnp.einsum("egcd,edf->egcf", xin, params["w_gate"])
    h = constrain(h, "experts", "batch", None, None)
    hout = jnp.einsum("egcf,efd->egcd", h, params["w_down"])     # expert FFN
    hout = constrain(hout, "experts", "batch", None, None)
    out = jnp.einsum("egcd,gsec->gsd", hout, combine.astype(x.dtype))
    out = constrain(out, "batch", None, None)

    if "shared" in params:
        out = out + mlp(params["shared"], x, cfg)

    # Load-balance aux: E * mean_e(fraction_dispatched_e * mean_prob_e).
    frac = dispatch.sum(axis=(1, 3)) / max(1, s * m.top_k)       # (G,E)
    mean_prob = probs.mean(axis=1)                               # (G,E)
    aux = e * jnp.mean(jnp.sum(frac.astype(jnp.float32) * mean_prob, axis=-1))
    return out.reshape(b, s0, d), aux
