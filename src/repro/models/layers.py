"""Base layers: norms, embeddings, rope, MLP."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain
from repro.models.params import spec


# -- rmsnorm ---------------------------------------------------------------

def rmsnorm_abstract(dim: int):
    return {"scale": spec((dim,), ("embed",), dtype=jnp.float32, init="ones")}


def rmsnorm(params, x, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps) * params["scale"]
    return out.astype(dt)


# -- embedding ---------------------------------------------------------------

def embedding_abstract(cfg: ModelConfig):
    return {"table": spec((cfg.vocab_size, cfg.d_model), ("vocab", "fsdp"),
                          init="embed", scale=0.02)}


def embed(params, tokens):
    return constrain(params["table"][tokens], "batch", None, None)


def unembed(params, x, softcap: Optional[float] = None):
    logits = jnp.einsum("...d,vd->...v", x, params["table"]).astype(jnp.float32)
    if softcap is not None:
        logits = jnp.tanh(logits / softcap) * softcap
    return logits


# -- rope --------------------------------------------------------------------

def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., S, H, D) with D even; positions: (..., S)."""
    d = x.shape[-1]
    half = d // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., :, None, None].astype(jnp.float32) * freq  # (..., S,1,half)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


# -- mlp -----------------------------------------------------------------------

def mlp_abstract(cfg: ModelConfig, d_ff: Optional[int] = None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    p = {
        "w_up": spec((d, f), ("fsdp", "mlp")),
        "w_down": spec((f, d), ("mlp", "fsdp")),
    }
    if cfg.mlp_gated:
        p["w_gate"] = spec((d, f), ("fsdp", "mlp"))
    if cfg.mlp_bias:
        p["b_up"] = spec((f,), ("mlp",), init="zeros")
        p["b_down"] = spec((d,), ("embed",), init="zeros")
    return p


def _act(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu}[name]


def mlp(params, x, cfg: ModelConfig):
    up = jnp.einsum("...d,df->...f", x, params["w_up"])
    if "b_up" in params:
        up = up + params["b_up"]
    act = _act(cfg.mlp_act)
    h = act(up) * jnp.einsum("...d,df->...f", x, params["w_gate"]) if cfg.mlp_gated else act(up)
    h = constrain(h, "batch", *(None,) * (h.ndim - 2), "mlp")
    out = jnp.einsum("...f,fd->...d", h, params["w_down"])
    if "b_down" in params:
        out = out + params["b_down"]
    return out
