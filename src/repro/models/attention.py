"""Attention: GQA / MQA / sliding-window / cross / MLA, with a memory-bounded
chunked flash implementation (online softmax over KV blocks).

Causality is exploited *structurally*: the query axis is split into chunks in
an unrolled loop, and chunk i only issues matmuls against kv[: (i+1)·Qc] (or
the sliding window slice) — upper-triangular blocks are never computed, so the
HLO FLOP count matches the true causal cost (this matters for §Roofline).
Within each (q-chunk, kv-slice) pair, a lax.scan over KV blocks keeps the
materialized score tile at (Qc, Kc) regardless of sequence length.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain
from repro.models.params import spec
from repro.models.layers import rope

NEG_INF = -2.0**30


# ---------------------------------------------------------------------------
# flash core
# ---------------------------------------------------------------------------

def _flash_block(q, k, v, q_pos, k_pos, causal, window, softcap, scale, carry,
                 score_dtype=jnp.float32):
    """One (Qc, Kc) tile of online softmax. q: (B,Qc,H,D); k,v: (B,Kc,KV,D)."""
    m_prev, l_prev, acc_prev = carry
    groups = q.shape[2] // k.shape[2]
    qg = q.reshape(*q.shape[:2], k.shape[2], groups, q.shape[3])
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k,
                   preferred_element_type=jnp.float32).astype(score_dtype) * scale
    # Fallback chain: kv_heads (GQA with divisible KV) -> heads (MQA/MLA:
    # the group dim) -> attn_q (small-head archs: sequence-parallel tiles).
    s = constrain(s, "batch", "kv_heads", "heads", "attn_q", None)
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap
    mask = jnp.ones((q.shape[1], k.shape[1]), bool)
    if causal:
        mask &= q_pos[:, None] >= k_pos[None, :]
    if window is not None:
        mask &= q_pos[:, None] - k_pos[None, :] < window
    s = jnp.where(mask[None, None, None], s, jnp.asarray(NEG_INF, s.dtype))
    m = jnp.maximum(m_prev, s.max(axis=-1).astype(jnp.float32))  # (B,KV,G,Qc)
    # Guard fully-masked rows (m still NEG_INF): their p must be 0, not e^0.
    p = jnp.where(s <= NEG_INF / 2, jnp.asarray(0.0, s.dtype),
                  jnp.exp(s - m[..., None].astype(s.dtype)))
    alpha = jnp.exp(m_prev - m)
    l = l_prev * alpha + p.sum(axis=-1, dtype=jnp.float32)
    pv = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(v.dtype), v).astype(jnp.float32)
    pv = constrain(pv, "batch", "kv_heads", "heads", "attn_q", None)
    acc = acc_prev * alpha[..., None] + pv
    return m, l, acc


def flash_attention(
    q: jnp.ndarray,            # (B, S, H, D)
    k: jnp.ndarray,            # (B, T, KV, D)
    v: jnp.ndarray,            # (B, T, KV, D)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    q_offset: int = 0,         # absolute position of q[0] (prefill continuation)
    q_chunk: int = 1024,
    kv_chunk: int = 1024,
    unroll: bool = False,      # python loop over KV blocks (cost probes)
    score_dtype=jnp.float32,   # bf16: halves score-tile traffic (TPU proxy)
) -> jnp.ndarray:
    b, s, h, d = q.shape
    t, kvh = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    scale = d ** -0.5
    q_chunk = min(q_chunk, s)
    kv_chunk = min(kv_chunk, t)
    groups = h // kvh

    outs = []
    for i in range(0, s, q_chunk):
        qc = q[:, i : i + q_chunk]
        qlen = qc.shape[1]
        q_pos = q_offset + i + jnp.arange(qlen)
        # Structural skip: only the kv prefix (causal) / window slice is read.
        if causal:
            hi = min(t, i + qlen + q_offset)
        else:
            hi = t
        lo = 0
        if window is not None:
            lo = max(0, hi - window - qlen)
        lo = (lo // kv_chunk) * kv_chunk               # align for even blocks
        hi_pad = min(t, ((hi + kv_chunk - 1) // kv_chunk) * kv_chunk)
        ks, vs = k[:, lo:hi_pad], v[:, lo:hi_pad]
        nkv = ks.shape[1] // kv_chunk if ks.shape[1] % kv_chunk == 0 else None

        m0 = jnp.full((b, kvh, groups, qlen), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kvh, groups, qlen), jnp.float32)
        a0 = jnp.zeros((b, kvh, groups, qlen, dv), jnp.float32)

        if nkv is not None and nkv > 1:
            ks_b = ks.reshape(b, nkv, kv_chunk, kvh, d).swapaxes(0, 1)
            vs_b = vs.reshape(b, nkv, kv_chunk, kvh, dv).swapaxes(0, 1)
            kpos_b = lo + jnp.arange(nkv * kv_chunk).reshape(nkv, kv_chunk)

            def body(carry, blk):
                kb, vb, kp = blk
                return _flash_block(qc, kb, vb, q_pos, kp, causal, window,
                                    softcap, scale, carry,
                                    score_dtype=score_dtype), None

            if unroll:
                carry = (m0, l0, a0)
                for j in range(nkv):
                    carry, _ = body(carry, (ks_b[j], vs_b[j], kpos_b[j]))
                m, l, acc = carry
            else:
                (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0),
                                              (ks_b, vs_b, kpos_b))
        else:
            k_pos = lo + jnp.arange(ks.shape[1])
            m, l, acc = _flash_block(qc, ks, vs, q_pos, k_pos, causal, window,
                                     softcap, scale, (m0, l0, a0),
                                     score_dtype=score_dtype)
        out = acc / jnp.maximum(l[..., None], 1e-30)              # (B,KV,G,Qc,Dv)
        out = out.transpose(0, 3, 1, 2, 4).reshape(b, qlen, h, dv)
        outs.append(out.astype(q.dtype))
    return jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]


def decode_attention(q, k_cache, v_cache, cache_len, *, window=None, softcap=None):
    """Single-token attention against a static-size cache.

    q: (B, 1, H, D); caches: (B, T, KV, D); cache_len: () current length
    (the new token's k/v must already be written at cache_len - 1).
    """
    b, _, h, d = q.shape
    t, kvh = k_cache.shape[1], k_cache.shape[2]
    dv = v_cache.shape[-1]
    groups = h // kvh
    qg = q.reshape(b, kvh, groups, d)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache).astype(jnp.float32) * (d ** -0.5)
    s = constrain(s, "batch", "kv_heads", "heads", None)
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap
    pos = jnp.arange(t)
    valid = pos < cache_len
    if window is not None:
        valid &= pos >= cache_len - window
    s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bkgs,bskd->bkgd", p, v_cache)
    return out.reshape(b, 1, h, dv).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA projections
# ---------------------------------------------------------------------------

def gqa_abstract(cfg: ModelConfig, cross: bool = False):
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    p = {
        "wq": spec((d, h, hd), ("fsdp", "heads", None)),
        "wk": spec((d, kv, hd), ("fsdp", "kv_heads", None)),
        "wv": spec((d, kv, hd), ("fsdp", "kv_heads", None)),
        "wo": spec((h, hd, d), ("heads", None, "fsdp")),
    }
    if cfg.qkv_bias:
        p["bq"] = spec((h, hd), ("heads", None), init="zeros")
        p["bk"] = spec((kv, hd), ("kv_heads", None), init="zeros")
        p["bv"] = spec((kv, hd), ("kv_heads", None), init="zeros")
    return p


def gqa_project_qkv(params, x, kv_x=None, positions=None, cfg: ModelConfig = None,
                    use_rope: bool = True):
    kv_x = x if kv_x is None else kv_x
    q = constrain(jnp.einsum("...d,dhk->...hk", x, params["wq"]),
                  "batch", None, "heads", None)
    k = constrain(jnp.einsum("...d,dhk->...hk", kv_x, params["wk"]),
                  "batch", None, "kv_heads", None)
    v = constrain(jnp.einsum("...d,dhk->...hk", kv_x, params["wv"]),
                  "batch", None, "kv_heads", None)
    if "bq" in params:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    if use_rope and positions is not None:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def gqa_output(params, attn_out):
    return jnp.einsum("...hk,hkd->...d", attn_out, params["wo"])


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V3 multi-head latent attention)
# ---------------------------------------------------------------------------

def mla_abstract(cfg: ModelConfig):
    m = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    qr, kr = m.q_lora_rank, m.kv_lora_rank
    return {
        "wq_a": spec((d, qr), ("fsdp", None)),
        "q_norm": spec((qr,), (None,), dtype=jnp.float32, init="ones"),
        "wq_b": spec((qr, h, m.nope_head_dim + m.rope_head_dim), (None, "heads", None)),
        "wkv_a": spec((d, kr + m.rope_head_dim), ("fsdp", None)),
        "kv_norm": spec((kr,), (None,), dtype=jnp.float32, init="ones"),
        "wk_b": spec((kr, h, m.nope_head_dim), (None, "heads", None)),
        "wv_b": spec((kr, h, m.v_head_dim), (None, "heads", None)),
        "wo": spec((h, m.v_head_dim, d), ("heads", None, "fsdp")),
    }


def _norm(x, scale, eps=1e-6):
    xf = x.astype(jnp.float32)
    out = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps) * scale
    return out.astype(x.dtype)


def mla_latent(params, x, positions, cfg: ModelConfig):
    """Project to the compressed latent (what the KV cache stores)."""
    m = cfg.mla
    kv_a = jnp.einsum("...d,dr->...r", x, params["wkv_a"])
    c_kv, k_rope = kv_a[..., : m.kv_lora_rank], kv_a[..., m.kv_lora_rank :]
    c_kv = _norm(c_kv, params["kv_norm"])
    k_rope = rope(k_rope[..., None, :], positions, cfg.rope_theta)[..., 0, :]
    return c_kv, k_rope


def mla_queries(params, x, positions, cfg: ModelConfig):
    m = cfg.mla
    q_a = _norm(jnp.einsum("...d,dr->...r", x, params["wq_a"]), params["q_norm"])
    q = constrain(jnp.einsum("...r,rhk->...hk", q_a, params["wq_b"]),
                  "batch", None, "heads", None)
    q_nope, q_rope = q[..., : m.nope_head_dim], q[..., m.nope_head_dim :]
    q_rope = rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def mla_attention(params, x, positions, cfg: ModelConfig, q_chunk=1024, kv_chunk=1024):
    """Prefill/train path: absorbed attention over the latent (no per-head KV).

    Scores: q_nope·W_kbᵀ gives a query in latent space; rope part adds a
    shared-key term. Attention then runs over (latent ⊕ rope-key) of width
    kv_lora_rank + rope_head_dim — the MLA cache economy — and the output is
    re-expanded through W_vb.
    """
    m = cfg.mla
    c_kv, k_rope = mla_latent(params, x, positions, cfg)      # (B,S,kr), (B,S,rd)
    q_nope, q_rope = mla_queries(params, x, positions, cfg)   # (B,S,H,*)
    # Absorb W_kb into the query: q_lat (B,S,H,kr)
    q_lat = constrain(jnp.einsum("...hk,rhk->...hr", q_nope, params["wk_b"]),
                      "batch", None, "heads", None)
    q_eff = jnp.concatenate([q_lat, q_rope], axis=-1)
    k_eff = jnp.concatenate([c_kv, k_rope], axis=-1)[..., None, :]  # KV=1 head
    scale_fix = (m.nope_head_dim + m.rope_head_dim) ** -0.5 / (
        (m.kv_lora_rank + m.rope_head_dim) ** -0.5
    )
    o_lat = flash_attention(
        q_eff * scale_fix, k_eff, c_kv[..., None, :],
        causal=cfg.causal, q_chunk=q_chunk, kv_chunk=kv_chunk,
        unroll=cfg.unroll_loops,
    )  # (B,S,H,kr) — the value *is* the latent
    o = jnp.einsum("...hr,rhv->...hv", o_lat, params["wv_b"])
    return jnp.einsum("...hv,hvd->...d", o, params["wo"])


def mla_decode(params, x, c_cache, krope_cache, cache_len, positions, cfg):
    """Decode against the latent cache. x: (B,1,D)."""
    m = cfg.mla
    c_new, kr_new = mla_latent(params, x, positions, cfg)
    idx = cache_len - 1
    c_cache = jax.lax.dynamic_update_slice_in_dim(c_cache, c_new, idx, axis=1)
    krope_cache = jax.lax.dynamic_update_slice_in_dim(krope_cache, kr_new, idx, axis=1)
    q_nope, q_rope = mla_queries(params, x, positions, cfg)
    q_lat = jnp.einsum("...hk,rhk->...hr", q_nope, params["wk_b"])
    b, _, h, _ = q_lat.shape
    t = c_cache.shape[1]
    s = (
        jnp.einsum("bqhr,btr->bhqt", q_lat, c_cache)
        + jnp.einsum("bqhr,btr->bhqt", q_rope, krope_cache)
    ).astype(jnp.float32) * ((m.nope_head_dim + m.rope_head_dim) ** -0.5)
    valid = jnp.arange(t) < cache_len
    s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhqt,btr->bqhr", p.astype(c_cache.dtype), c_cache)
    o = jnp.einsum("...hr,rhv->...hv", o_lat, params["wv_b"])
    out = jnp.einsum("...hv,hvd->...d", o, params["wo"])
    return out, c_cache, krope_cache
