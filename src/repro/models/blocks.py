"""Single-layer assembly: norm → temporal mixer → norm → channel mixer.

A layer is described by (temporal, channel) kind strings resolved from the
config's cyclic ``pattern`` and MoE dense-head rules:

  temporal ∈ {"attn", "local", "cross", "mla", "rglru", "ssd"}
  channel  ∈ {"mlp", "moe", "dense_head", "none"}

Every kind provides abstract params, a full-sequence apply, and a decode-step
apply over its piece of the cache.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain
from repro.models import attention as attn
from repro.models import layers as L
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import ssm as ssm_mod
from repro.models.params import spec


def layer_kinds(cfg: ModelConfig):
    """Resolve (temporal, channel) for every layer index."""
    kinds = []
    for i in range(cfg.n_layers):
        temporal = cfg.pattern[i % len(cfg.pattern)]
        if temporal == "ssd":
            channel = "none" if cfg.d_ff == 0 else "mlp"
        elif cfg.moe is not None:
            channel = "dense_head" if i < cfg.moe.n_dense_layers else "moe"
        else:
            channel = "mlp"
        kinds.append((temporal, channel))
    return kinds


# -- abstract ----------------------------------------------------------------

def layer_abstract(cfg: ModelConfig, temporal: str, channel: str):
    d = cfg.d_model
    p = {"ln1": L.rmsnorm_abstract(d)}
    if temporal in ("attn", "local", "cross"):
        p["attn"] = attn.gqa_abstract(cfg)
        if temporal == "cross":
            p["attn_gate"] = spec((), (), dtype=jnp.float32, init="zeros")
            p["kv_ln"] = L.rmsnorm_abstract(d)
    elif temporal == "mla":
        p["attn"] = attn.mla_abstract(cfg)
    elif temporal == "rglru":
        p["rec"] = rglru_mod.rglru_abstract(cfg)
    elif temporal == "ssd":
        p["ssd"] = ssm_mod.ssd_abstract(cfg)
    else:
        raise ValueError(temporal)

    if channel != "none":
        p["ln2"] = L.rmsnorm_abstract(d)
    if channel == "mlp":
        p["mlp"] = L.mlp_abstract(cfg)
    elif channel == "dense_head":
        p["mlp"] = L.mlp_abstract(cfg, d_ff=cfg.moe.dense_ff or cfg.d_ff)
    elif channel == "moe":
        p["moe"] = moe_mod.moe_abstract(cfg)

    if cfg.post_norms:
        p["post_ln1"] = L.rmsnorm_abstract(d)
        if channel != "none":
            p["post_ln2"] = L.rmsnorm_abstract(d)
    return p


# -- full-sequence apply -------------------------------------------------------

def layer_apply(
    lp, x, temporal: str, channel: str, cfg: ModelConfig, *,
    positions, vis_embeds=None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B,S,D) -> (x, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    x = constrain(x, "batch", None, None)
    h = L.rmsnorm(lp["ln1"], x)
    if temporal in ("attn", "local"):
        q, k, v = attn.gqa_project_qkv(lp["attn"], h, positions=positions, cfg=cfg)
        window = cfg.window if temporal == "local" else None
        o = attn.flash_attention(
            q, k, v, causal=cfg.causal, window=window,
            softcap=cfg.attn_softcap, q_chunk=cfg.attn_chunk,
            kv_chunk=cfg.attn_chunk, unroll=cfg.unroll_loops,
            score_dtype=jnp.float32 if cfg.attn_scores_f32 else jnp.bfloat16,
        )
        t_out = attn.gqa_output(lp["attn"], o)
    elif temporal == "cross":
        kv = L.rmsnorm(lp["kv_ln"], vis_embeds)
        q, k, v = attn.gqa_project_qkv(lp["attn"], h, kv_x=kv, cfg=cfg,
                                       use_rope=False)
        o = attn.flash_attention(
            q, k, v, causal=False, q_chunk=cfg.attn_chunk,
            kv_chunk=cfg.attn_chunk, unroll=cfg.unroll_loops,
            score_dtype=jnp.float32 if cfg.attn_scores_f32 else jnp.bfloat16,
        )
        t_out = attn.gqa_output(lp["attn"], o)
        t_out = t_out * jnp.tanh(lp["attn_gate"]).astype(t_out.dtype)
    elif temporal == "mla":
        t_out = attn.mla_attention(lp["attn"], h, positions, cfg,
                                   q_chunk=cfg.attn_chunk, kv_chunk=cfg.attn_chunk)
    elif temporal == "rglru":
        t_out = rglru_mod.rglru_layer(lp["rec"], h, cfg)
    elif temporal == "ssd":
        t_out = ssm_mod.ssd_layer(lp["ssd"], h, cfg)
    else:
        raise ValueError(temporal)
    if cfg.post_norms:
        t_out = L.rmsnorm(lp["post_ln1"], t_out)
    x = x + t_out

    if channel != "none":
        h = L.rmsnorm(lp["ln2"], x)
        if channel in ("mlp", "dense_head"):
            c_out = L.mlp(lp["mlp"], h, cfg)
        else:
            c_out, aux = moe_mod.moe_layer(lp["moe"], h, cfg)
        if cfg.post_norms:
            c_out = L.rmsnorm(lp["post_ln2"], c_out)
        x = x + c_out
    return x, aux


# -- caches --------------------------------------------------------------------

def cache_abstract(cfg: ModelConfig, temporal: str, batch: int, max_len: int):
    hd = cfg.resolved_head_dim
    if temporal in ("attn", "local"):
        return {
            "k": spec((batch, max_len, cfg.n_kv_heads, hd),
                      ("batch", "cache_seq", "kv_heads", None), init="zeros"),
            "v": spec((batch, max_len, cfg.n_kv_heads, hd),
                      ("batch", "cache_seq", "kv_heads", None), init="zeros"),
        }
    if temporal == "cross":
        return {
            "k": spec((batch, cfg.n_vis_tokens, cfg.n_kv_heads, hd),
                      ("batch", None, "kv_heads", None), init="zeros"),
            "v": spec((batch, cfg.n_vis_tokens, cfg.n_kv_heads, hd),
                      ("batch", None, "kv_heads", None), init="zeros"),
        }
    if temporal == "mla":
        m = cfg.mla
        return {
            "c": spec((batch, max_len, m.kv_lora_rank),
                      ("batch", "cache_seq", None), init="zeros"),
            "krope": spec((batch, max_len, m.rope_head_dim),
                          ("batch", "cache_seq", None), init="zeros"),
        }
    if temporal == "rglru":
        return rglru_mod.rglru_decode_state_abstract(cfg, batch)
    if temporal == "ssd":
        return ssm_mod.ssd_decode_state_abstract(cfg, batch)
    raise ValueError(temporal)


def layer_prefill(
    lp, x, temporal: str, channel: str, cfg: ModelConfig, *,
    positions, cache, vis_embeds=None,
):
    """Full-sequence forward that also fills this layer's cache in-place slots.

    Returns (x, new_cache). The prefill length S may be shorter than the cache
    allocation; remaining slots stay zero and are masked by cache_len.
    """
    aux_unused = None
    h = L.rmsnorm(lp["ln1"], x)
    s = x.shape[1]
    if temporal in ("attn", "local"):
        q, k, v = attn.gqa_project_qkv(lp["attn"], h, positions=positions, cfg=cfg)
        window = cfg.window if temporal == "local" else None
        o = attn.flash_attention(
            q, k, v, causal=cfg.causal, window=window, softcap=cfg.attn_softcap,
            q_chunk=cfg.attn_chunk, kv_chunk=cfg.attn_chunk,
            unroll=cfg.unroll_loops,
            score_dtype=jnp.float32 if cfg.attn_scores_f32 else jnp.bfloat16,
        )
        t_out = attn.gqa_output(lp["attn"], o)
        cache = {
            "k": jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), 0, 1),
            "v": jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), 0, 1),
        }
    elif temporal == "cross":
        kv = L.rmsnorm(lp["kv_ln"], vis_embeds)
        q, k, v = attn.gqa_project_qkv(lp["attn"], h, kv_x=kv, cfg=cfg, use_rope=False)
        o = attn.flash_attention(q, k, v, causal=False,
                                 q_chunk=cfg.attn_chunk, kv_chunk=cfg.attn_chunk,
                                 unroll=cfg.unroll_loops,
                                 score_dtype=jnp.float32 if cfg.attn_scores_f32
                                 else jnp.bfloat16)
        t_out = attn.gqa_output(lp["attn"], o)
        t_out = t_out * jnp.tanh(lp["attn_gate"]).astype(t_out.dtype)
        cache = {"k": k.astype(cache["k"].dtype), "v": v.astype(cache["v"].dtype)}
    elif temporal == "mla":
        t_out = attn.mla_attention(lp["attn"], h, positions, cfg,
                                   q_chunk=cfg.attn_chunk, kv_chunk=cfg.attn_chunk)
        c_kv, k_rope = attn.mla_latent(lp["attn"], h, positions, cfg)
        cache = {
            "c": jax.lax.dynamic_update_slice_in_dim(cache["c"], c_kv.astype(cache["c"].dtype), 0, 1),
            "krope": jax.lax.dynamic_update_slice_in_dim(cache["krope"], k_rope.astype(cache["krope"].dtype), 0, 1),
        }
    elif temporal == "rglru":
        # Run the full sequence, then capture the final recurrent state.
        t_out, cache = _rglru_prefill(lp["rec"], h, cfg, cache)
    elif temporal == "ssd":
        t_out, cache = _ssd_prefill(lp["ssd"], h, cfg, cache)
    else:
        raise ValueError(temporal)
    if cfg.post_norms:
        t_out = L.rmsnorm(lp["post_ln1"], t_out)
    x = x + t_out

    if channel != "none":
        h = L.rmsnorm(lp["ln2"], x)
        if channel in ("mlp", "dense_head"):
            c_out = L.mlp(lp["mlp"], h, cfg)
        else:
            c_out, _ = moe_mod.moe_layer(lp["moe"], h, cfg)
        if cfg.post_norms:
            c_out = L.rmsnorm(lp["post_ln2"], c_out)
        x = x + c_out
    return x, cache


def _rglru_prefill(params, h, cfg, old_cache):
    out = rglru_mod.rglru_layer(params, h, cfg)
    # Recompute the final hidden state cheaply: rerun gates on the last few
    # positions is not enough (h depends on full history), so reuse the scan:
    # rglru_layer already computed h_t internally; to avoid a second pass we
    # recompute via the same associative scan here.
    k = cfg.rglru.d_conv
    xr = jnp.einsum("...d,dw->...w", h, params["w_x"])
    pad = jnp.pad(xr, ((0, 0), (k - 1, 0), (0, 0)))
    conv_tail = pad[:, -(k - 1):] if k > 1 else pad[:, :0]
    xr_c = sum(pad[:, i : i + h.shape[1]] * params["conv_w"][i] for i in range(k))
    xr_c = xr_c + params["conv_b"]
    log_a, b = rglru_mod._gates(params, xr_c)

    def combine(lhs, rhs):
        la1, b1 = lhs
        la2, b2 = rhs
        return la1 + la2, b1 * jnp.exp(la2) + b2

    _, hs = jax.lax.associative_scan(combine, (log_a, b), axis=1)
    cache = {"h": hs[:, -1],
             "conv_buf": conv_tail.astype(old_cache["conv_buf"].dtype)}
    return out, cache


def _ssd_prefill(params, h, cfg, cache):
    out = ssm_mod.ssd_layer(params, h, cfg)
    # Final state via one streaming pass (shares code with decode for k slots).
    # For dry-run purposes we recompute the state with the chunked recurrence.
    state = _ssd_final_state(params, h, cfg)
    zxb = ssm_mod._split_proj(params, h, cfg)[1]
    conv_tail = zxb[:, -(cfg.ssm.d_conv - 1):].astype(cache["conv_buf"].dtype)
    return out, {"state": state, "conv_buf": conv_tail}


def _ssd_final_state(params, u, cfg):
    d_inner, hh, p, n = ssm_mod._dims(cfg)
    b, true_len, _ = u.shape
    q = min(cfg.ssm.chunk, true_len)
    if true_len % q:
        u = jnp.pad(u, ((0, 0), (0, q - true_len % q), (0, 0)))
    seqlen = u.shape[1]
    nc = seqlen // q
    z, xbc, dt = ssm_mod._split_proj(params, u, cfg)
    # Mask padded positions: dt=0 ⇒ unit decay and zero input contribution.
    pad_mask = (jnp.arange(seqlen) < true_len).astype(dt.dtype)
    dt = dt * pad_mask[None, :, None]
    xbc = ssm_mod._causal_conv(params, xbc, cfg)
    x = xbc[..., :d_inner].reshape(b, seqlen, hh, p)
    bmat = xbc[..., d_inner : d_inner + n]
    a = -jnp.exp(params["a_log"])
    da = dt * a
    dx = (x * dt[..., None].astype(x.dtype)).astype(jnp.float32)
    da_c = da.reshape(b, nc, q, hh).swapaxes(0, 1)
    x_c = dx.reshape(b, nc, q, hh, p).swapaxes(0, 1)
    b_c = bmat.reshape(b, nc, q, n).swapaxes(0, 1).astype(jnp.float32)

    def step(state, inp):
        dac, xc, bc = inp
        cum = jnp.cumsum(dac, axis=1)
        dsum = cum[:, -1]
        decay_states = jnp.exp(dsum[:, None, :] - cum)
        new = state * jnp.exp(dsum)[..., None, None] + jnp.einsum(
            "bsn,bsh,bshp->bhpn", bc, decay_states, xc
        )
        return new, None

    init = jnp.zeros((b, hh, p, n), jnp.float32)
    if cfg.unroll_loops:
        state = init
        for c in range(nc):
            state, _ = step(state, (da_c[c], x_c[c], b_c[c]))
    else:
        state, _ = jax.lax.scan(step, init, (da_c, x_c, b_c))
    return state


# -- decode step -----------------------------------------------------------------

def layer_decode(
    lp, x, temporal: str, channel: str, cfg: ModelConfig, *,
    cache, cache_len, positions,
):
    """x: (B,1,D) -> (x, new_cache). cache_len counts tokens incl. current."""
    h = L.rmsnorm(lp["ln1"], x)
    if temporal in ("attn", "local"):
        q, k, v = attn.gqa_project_qkv(lp["attn"], h, positions=positions, cfg=cfg)
        idx = cache_len - 1
        kc = jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), idx, 1)
        vc = jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), idx, 1)
        window = cfg.window if temporal == "local" else None
        o = attn.decode_attention(q, kc, vc, cache_len, window=window,
                                  softcap=cfg.attn_softcap)
        t_out = attn.gqa_output(lp["attn"], o)
        cache = {"k": kc, "v": vc}
    elif temporal == "cross":
        q = jnp.einsum("...d,dhk->...hk", h, lp["attn"]["wq"])
        if "bq" in lp["attn"]:
            q = q + lp["attn"]["bq"]
        o = attn.decode_attention(q, cache["k"], cache["v"],
                                  jnp.int32(cfg.n_vis_tokens))
        t_out = attn.gqa_output(lp["attn"], o)
        t_out = t_out * jnp.tanh(lp["attn_gate"]).astype(t_out.dtype)
    elif temporal == "mla":
        t_out, c, krope = attn.mla_decode(
            lp["attn"], h, cache["c"], cache["krope"], cache_len, positions, cfg
        )
        cache = {"c": c, "krope": krope}
    elif temporal == "rglru":
        t_out, cache = rglru_mod.rglru_decode(lp["rec"], h, cache, cfg)
    elif temporal == "ssd":
        t_out, cache = ssm_mod.ssd_decode(lp["ssd"], h, cache, cfg)
    else:
        raise ValueError(temporal)
    if cfg.post_norms:
        t_out = L.rmsnorm(lp["post_ln1"], t_out)
    x = x + t_out

    if channel != "none":
        h = L.rmsnorm(lp["ln2"], x)
        if channel in ("mlp", "dense_head"):
            c_out = L.mlp(lp["mlp"], h, cfg)
        else:
            c_out, _ = moe_mod.moe_layer(lp["moe"], h, cfg)
        if cfg.post_norms:
            c_out = L.rmsnorm(lp["post_ln2"], c_out)
        x = x + c_out
    return x, cache
