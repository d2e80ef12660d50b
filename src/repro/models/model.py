"""User-facing model API: abstract params, loss, prefill, decode.

Works for every assigned architecture: token LMs, the audio encoder (frame
embeddings in, frame classes out) and the vision-text model (precomputed patch
embeddings consumed by interleaved cross-attention layers).
"""

from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain
from repro.models import layers as L
from repro.models import transformer as T
from repro.models.params import spec


# -- parameters -----------------------------------------------------------------

def abstract_params(cfg: ModelConfig):
    p: Dict = {"blocks": T.stack_abstract(cfg), "final_norm": L.rmsnorm_abstract(cfg.d_model)}
    if cfg.frontend != "audio_frames":
        p["embed"] = L.embedding_abstract(cfg)
    if not cfg.tie_embeddings:
        p["lm_head"] = {"w": spec((cfg.d_model, cfg.vocab_size), ("fsdp", "vocab"))}
    if cfg.mtp:
        from repro.models import blocks as B

        kinds = B.layer_kinds(cfg)[-1]
        p["mtp"] = {
            "h_norm": L.rmsnorm_abstract(cfg.d_model),
            "e_norm": L.rmsnorm_abstract(cfg.d_model),
            "proj": {"w": spec((2 * cfg.d_model, cfg.d_model), (None, "fsdp"))},
            "block": B.layer_abstract(cfg, *kinds),
        }
    return p


def _embed_in(params, batch, cfg: ModelConfig):
    if cfg.frontend == "audio_frames":
        x = batch["frames"]
    else:
        x = L.embed(params["embed"], batch["tokens"])
        if cfg.post_norms:  # gemma-family convention: scale embeddings
            x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    return x


def _logits(params, x, cfg: ModelConfig):
    if cfg.tie_embeddings:
        return L.unembed(params["embed"], x, cfg.final_softcap)
    logits = jnp.einsum("...d,dv->...v", x, params["lm_head"]["w"]).astype(jnp.float32)
    if cfg.final_softcap is not None:
        logits = jnp.tanh(logits / cfg.final_softcap) * cfg.final_softcap
    return logits


def forward(params, batch, cfg: ModelConfig):
    """Full-sequence hidden states. Returns (x, aux)."""
    x = _embed_in(params, batch, cfg)
    b, s = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    return T.stack_apply(
        params["blocks"], x, cfg, positions=positions,
        vis_embeds=batch.get("vis_embeds"),
    )


def _ce_chunk(params, x_chunk, labels_chunk, cfg):
    logits = _logits(params, x_chunk, cfg)              # (B, c, V) f32
    logits = constrain(logits, "batch", None, "vocab")
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels_chunk[..., None], axis=-1)[..., 0]
    mask = labels_chunk >= 0
    return jnp.sum((lse - ll) * mask), jnp.sum(mask)


def loss_fn(params, batch, cfg: ModelConfig, aux_coef: float = 0.01,
            seq_chunk: int = 512):
    """Mean next-token CE (labels < 0 are masked) + MoE aux loss.

    The unembedding is evaluated in sequence chunks inside a scan so the
    (B, S, V) logits tensor is never materialized (V up to 256k).
    """
    x, aux = forward(params, batch, cfg)
    labels = batch["labels"]
    b, s, d = x.shape
    seq_chunk = min(seq_chunk, s)
    n_chunks = s // seq_chunk
    usable = n_chunks * seq_chunk

    def body(carry, inp):
        xc, lc = inp
        tot, cnt = _ce_chunk(params, xc, lc, cfg)
        return (carry[0] + tot, carry[1] + cnt), None

    xs = (
        x[:, :usable].reshape(b, n_chunks, seq_chunk, d).swapaxes(0, 1),
        labels[:, :usable].reshape(b, n_chunks, seq_chunk).swapaxes(0, 1),
    )
    zero = (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32))
    if cfg.unroll_loops:
        tot, cnt = zero
        for c in range(n_chunks):
            (tot, cnt), _ = body((tot, cnt), (xs[0][c], xs[1][c]))
    else:
        (tot, cnt), _ = jax.lax.scan(jax.checkpoint(body), zero, xs)
    if usable < s:
        t2, c2 = _ce_chunk(params, x[:, usable:], labels[:, usable:], cfg)
        tot, cnt = tot + t2, cnt + c2
    loss = tot / jnp.maximum(cnt, 1.0)
    total = loss + aux_coef * aux
    metrics = {"ce": loss, "aux": aux}

    if cfg.mtp and "mtp" in params:
        total = total + cfg.mtp_lambda * _mtp_loss(params, x, batch, cfg)
    return total, metrics


def _mtp_loss(params, h, batch, cfg: ModelConfig):
    """DeepSeek-V3 multi-token prediction: one extra block consumes the trunk
    hidden state at t fused with the embedding of token t+1 and predicts the
    label at t+1 (i.e. token t+2). Positions without a t+2 label are masked.
    """
    from repro.models import blocks as B

    mp = params["mtp"]
    labels = batch["labels"]
    b, s = labels.shape
    # embedding of the next input token = the label at t (token t+1)
    nxt = jnp.clip(labels, 0, cfg.vocab_size - 1)
    e_next = L.embed(params["embed"], nxt)
    fused = jnp.concatenate(
        [L.rmsnorm(mp["h_norm"], h), L.rmsnorm(mp["e_norm"], e_next)], axis=-1)
    x = jnp.einsum("...e,ed->...d", fused, mp["proj"]["w"])
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    kinds = B.layer_kinds(cfg)[-1]
    x, _ = B.layer_apply(mp["block"], x, *kinds, cfg, positions=positions,
                         vis_embeds=batch.get("vis_embeds"))
    # predict token t+2: label for position t is labels[t+1]
    mtp_labels = jnp.concatenate(
        [labels[:, 1:], jnp.full((b, 1), -1, labels.dtype)], axis=1)
    tot, cnt = _ce_chunk(params, x, mtp_labels, cfg)
    return tot / jnp.maximum(cnt, 1.0)


# -- serving ----------------------------------------------------------------------

def abstract_cache(cfg: ModelConfig, batch: int, max_len: int):
    return T.cache_abstract(cfg, batch, max_len)


def prefill(params, batch, cfg: ModelConfig, cache):
    """Returns (last-position logits (B, V), filled cache)."""
    x = _embed_in(params, batch, cfg)
    b, s = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    x, cache = T.stack_prefill(
        params["blocks"], x, cfg, cache, positions=positions,
        vis_embeds=batch.get("vis_embeds"),
    )
    x = L.rmsnorm(params["final_norm"], x[:, -1:])
    return _logits(params, x, cfg)[:, 0], cache


def decode_step(params, tokens, cache, cache_len, cfg: ModelConfig):
    """tokens: (B, 1) int32; cache_len: () int32 length incl. this token.

    Returns (logits (B, V), new cache).
    """
    x = _embed_in(params, {"tokens": tokens}, cfg)
    b = x.shape[0]
    positions = jnp.broadcast_to(cache_len[None, None] - 1, (b, 1))
    x, cache = T.stack_decode(
        params["blocks"], x, cfg, cache, cache_len, positions=positions
    )
    x = L.rmsnorm(params["final_norm"], x)
    return _logits(params, x, cfg)[:, 0], cache
