"""Mamba-2 SSD (state-space duality) mixer.

Chunked algorithm (Dao & Gu 2024): within-chunk outputs are block matmuls
(MXU-friendly quadratic-in-chunk terms), chunk-boundary states are carried by
a linear recurrence scanned over chunks. Decode is the O(1) recurrent update
on a (B, H, P, N) state.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain
from repro.models.params import spec


def _dims(cfg: ModelConfig):
    s = cfg.ssm
    d_inner = cfg.d_model * s.expand
    n_heads = d_inner // s.head_dim
    return d_inner, n_heads, s.head_dim, s.d_state


def ssd_abstract(cfg: ModelConfig):
    s = cfg.ssm
    d = cfg.d_model
    d_inner, h, p, n = _dims(cfg)
    conv_dim = d_inner + 2 * n  # x, B, C all pass the causal conv
    return {
        # fused in-proj: [z, x, B, C, dt]
        "w_in": spec((d, 2 * d_inner + 2 * n + h), ("fsdp", "state")),
        "conv_w": spec((s.d_conv, conv_dim), (None, "state")),
        "conv_b": spec((conv_dim,), ("state",), init="zeros"),
        "a_log": spec((h,), ("state",), dtype=jnp.float32, init="ones"),
        "d_skip": spec((h,), ("state",), dtype=jnp.float32, init="ones"),
        "dt_bias": spec((h,), ("state",), dtype=jnp.float32, init="zeros"),
        "norm_scale": spec((d_inner,), ("state",), dtype=jnp.float32, init="ones"),
        "w_out": spec((d_inner, d), ("state", "fsdp")),
    }


def _split_proj(params, u, cfg):
    d_inner, h, p, n = _dims(cfg)
    zxbcdt = jnp.einsum("...d,de->...e", u, params["w_in"])
    z = zxbcdt[..., :d_inner]
    xbc = zxbcdt[..., d_inner : 2 * d_inner + 2 * n]
    dt = zxbcdt[..., 2 * d_inner + 2 * n :]
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
    return z, xbc, dt


def _causal_conv(params, xbc, cfg):
    """Depthwise causal conv over sequence. xbc: (B, S, conv_dim)."""
    k = cfg.ssm.d_conv
    pad = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    out = sum(
        pad[:, i : i + xbc.shape[1]] * params["conv_w"][i] for i in range(k)
    )
    return jax.nn.silu(out + params["conv_b"])


def _segsum(a):
    """log-space cumulative decay matrix: out[i,j] = sum_{j<k<=i} a[k], lower-tri."""
    q = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    seg = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((q, q), bool))
    return jnp.where(mask, seg, -jnp.inf)


def ssd_layer(params, u, cfg: ModelConfig) -> jnp.ndarray:
    """u: (B, S, D) -> (B, S, D).

    SSD streamed as a lax.scan over chunks: each step does the within-chunk
    block matmuls (MXU) for one chunk and carries the (B,H,P,N) state, so peak
    memory is one chunk's decay matrix (B,H,Q,Q) instead of the full
    (B,NC,H,Q,Q) tensor — the TPU analogue of the fused SSD kernel.
    """
    s_cfg = cfg.ssm
    b, true_len, _ = u.shape
    d_inner, h, p, n = _dims(cfg)
    q = min(s_cfg.chunk, true_len)
    if true_len % q:  # causal: right-padding cannot affect earlier outputs
        u = jnp.pad(u, ((0, 0), (0, q - true_len % q), (0, 0)))
    seqlen = u.shape[1]
    nc = seqlen // q

    z, xbc, dt = _split_proj(params, u, cfg)          # dt: (B,S,H) f32
    xbc = constrain(_causal_conv(params, xbc, cfg), "batch", None, "state")
    x = xbc[..., :d_inner].reshape(b, seqlen, h, p)
    bmat = xbc[..., d_inner : d_inner + n]            # (B,S,N)
    cmat = xbc[..., d_inner + n :]                    # (B,S,N)

    a = -jnp.exp(params["a_log"])                     # (H,) negative
    da = dt * a                                       # (B,S,H) log-decay
    dx = (x * dt[..., None].astype(x.dtype)).astype(jnp.float32)

    # chunk views, chunk axis leading for the scan
    da_c = da.reshape(b, nc, q, h).swapaxes(0, 1)         # (NC,B,Q,H)
    x_c = dx.reshape(b, nc, q, h, p).swapaxes(0, 1)       # (NC,B,Q,H,P)
    b_c = bmat.reshape(b, nc, q, n).swapaxes(0, 1).astype(jnp.float32)
    c_c = cmat.reshape(b, nc, q, n).swapaxes(0, 1).astype(jnp.float32)

    def chunk_step(state, inp):
        dac, xc, bc, cc = inp                             # one chunk
        cum = jnp.cumsum(dac, axis=1)                     # (B,Q,H)
        dsum = cum[:, -1]                                 # (B,H)
        # within-chunk: scores shared over heads (n_groups = 1)
        l = jnp.exp(_segsum(dac.transpose(0, 2, 1)))      # (B,H,Q,Q)
        scores = jnp.einsum("bln,bsn->bls", cc, bc)       # (B,Q,Q)
        y_diag = jnp.einsum("bls,bhls,bshp->blhp", scores, l, xc)
        # contribution of the incoming state
        decay_out = jnp.exp(cum)                          # (B,Q,H)
        y_off = jnp.einsum("bln,blh,bhpn->blhp", cc, decay_out, state)
        # state update
        decay_states = jnp.exp(dsum[:, None, :] - cum)    # (B,Q,H)
        new_state = state * jnp.exp(dsum)[..., None, None] + jnp.einsum(
            "bsn,bsh,bshp->bhpn", bc, decay_states, xc
        )
        return new_state, y_diag + y_off

    init = jnp.zeros((b, h, p, n), jnp.float32)
    if cfg.unroll_loops:
        ys = []
        state = init
        for c in range(nc):
            state, yc = chunk_step(state, (da_c[c], x_c[c], b_c[c], c_c[c]))
            ys.append(yc)
        y = jnp.stack(ys)
    else:
        _, y = jax.lax.scan(chunk_step, init, (da_c, x_c, b_c, c_c))
    y = y.swapaxes(0, 1).reshape(b, seqlen, h, p)
    y = y + params["d_skip"][:, None] * x.astype(jnp.float32)
    y = y.reshape(b, seqlen, d_inner)[:, :true_len]
    z = z[:, :true_len]
    # gated RMSNorm (Mamba-2): norm(y * silu(z))
    y = y * jax.nn.silu(z.astype(jnp.float32))
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + 1e-6)
    y = y * params["norm_scale"]
    return jnp.einsum("...e,ed->...d", y.astype(u.dtype), params["w_out"])


def ssd_decode_state_abstract(cfg: ModelConfig, batch: int):
    d_inner, h, p, n = _dims(cfg)
    k = cfg.ssm.d_conv
    conv_dim = d_inner + 2 * n
    return {
        "state": spec((batch, h, p, n), ("batch", "state", None, None),
                      dtype=jnp.float32, init="zeros"),
        "conv_buf": spec((batch, k - 1, conv_dim), ("batch", None, "state"),
                         dtype=jnp.bfloat16, init="zeros"),
    }


def ssd_decode(params, u, cache, cfg: ModelConfig):
    """u: (B, 1, D); cache: {"state": (B,H,P,N) f32, "conv_buf": (B,k-1,conv)}."""
    d_inner, h, p, n = _dims(cfg)
    z, xbc, dt = _split_proj(params, u, cfg)          # xbc: (B,1,conv)
    buf = jnp.concatenate([cache["conv_buf"], xbc.astype(cache["conv_buf"].dtype)], axis=1)
    conv = sum(buf[:, i : i + 1] * params["conv_w"][i] for i in range(cfg.ssm.d_conv))
    xbc_t = jax.nn.silu(conv + params["conv_b"])      # (B,1,conv)
    x = xbc_t[..., :d_inner].reshape(-1, 1, h, p)
    bvec = xbc_t[..., d_inner : d_inner + n]
    cvec = xbc_t[..., d_inner + n :]

    a = -jnp.exp(params["a_log"])
    da = (dt[:, 0] * a).astype(jnp.float32)           # (B,H)
    dx = (x * dt[..., None].astype(x.dtype))[:, 0].astype(jnp.float32)  # (B,H,P)
    state = cache["state"] * jnp.exp(da)[..., None, None] + jnp.einsum(
        "bn,bhp->bhpn", bvec[:, 0].astype(jnp.float32), dx
    )
    y = jnp.einsum("bn,bhpn->bhp", cvec[:, 0].astype(jnp.float32), state)
    y = y + params["d_skip"][None, :, None] * x[:, 0].astype(jnp.float32)
    y = y.reshape(-1, 1, d_inner)
    y = y * jax.nn.silu(z.astype(jnp.float32))
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + 1e-6)
    y = y * params["norm_scale"]
    out = jnp.einsum("...e,ed->...d", y.astype(u.dtype), params["w_out"])
    return out, {"state": state, "conv_buf": buf[:, 1:]}
