"""Step functions lowered by the dry-run and used by the real launchers."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import LM_SHAPES, ModelConfig
from repro.distributed.sharding import ShardingRules, use_sharding
from repro.launch import specs as S
from repro.models import model as M
from repro.train import OptConfig
from repro.train.train_step import make_train_step, opt_abstract_with_ef
from repro.models.params import shape_structs


def _with_ctx(fn, mesh, rules):
    """Activate the activation-sharding context while tracing."""
    if mesh is None:
        return fn

    @functools.wraps(fn)
    def wrapped(*a, **kw):
        with use_sharding(mesh, rules):
            return fn(*a, **kw)

    return wrapped


def make_step(cfg: ModelConfig, shape_name: str, mesh, rules: ShardingRules,
              ocfg: OptConfig | None = None, microbatches: int = 1):
    """Returns (fn, example_args: tuple, donate: tuple[int, ...])."""
    sh = LM_SHAPES[shape_name]
    if ocfg is None:
        # >100B-param archs: bf16 moments, or optimizer state alone outgrows HBM.
        big = cfg.param_count() > 100e9
        ocfg = OptConfig(moments_dtype="bfloat16" if big else "float32")
    if sh.kind != "train":
        import dataclasses as _dc

        # Remat only pays for a backward pass; inference keeps no residuals.
        if cfg.remat != "none":
            cfg = _dc.replace(cfg, remat="none")
        # Decode: row-parallel weights — map the FSDP (d_model input) dim of
        # every matrix onto the model axis. Weights are then fully sharded
        # with zero per-step gathers, and the price is a psum over the
        # single-token activations (KBs). Heads that don't divide the axis
        # stop mattering: the head dims go unsharded, attention runs with all
        # heads against the sequence-sharded cache (sequence-parallel decode).
        # Prefill keeps FSDP + column-parallel: a row-parallel psum there
        # would reduce (B, 32k, F) activations per layer.
        # MoE giants are excluded: their expert weights take the model axis
        # on the expert dim, so fsdp->model would leave the d_model dim
        # unsharded and replicate ~TBs of experts per data shard (measured:
        # kimi decode 106 -> 400 GB/dev). They keep ZeRO sharding + gathers.
        if (rules is not None and sh.kind == "decode"
                and cfg.param_count() < 100e9):
            rules = rules.with_overrides(fsdp="model")
    params = S.param_specs(cfg, mesh, rules)

    if sh.kind == "train":
        opt = shape_structs(opt_abstract_with_ef(M.abstract_params(cfg), ocfg),
                            mesh, rules.rules)
        ts = _with_ctx(make_train_step(cfg, ocfg, microbatches=microbatches),
                       mesh, rules)
        batch = S.batch_specs(cfg, sh, mesh, rules)
        step = jax.ShapeDtypeStruct((), jnp.int32)
        return ts, (params, opt, batch, step), (0, 1)

    if sh.kind == "prefill":
        if cfg.encoder_only:
            # Encoder arch: "prefill" is one full encoder forward + frame logits.
            def encode_fn(params, batch):
                x, _ = M.forward(params, batch, cfg)
                from repro.models import layers as L

                x = L.rmsnorm(params["final_norm"], x)
                return M._logits(params, x, cfg).astype(jnp.bfloat16)

            batch = S.batch_specs(cfg, sh, mesh, rules)
            return _with_ctx(encode_fn, mesh, rules), (params, batch), ()

        def prefill_fn(params, batch, cache):
            return M.prefill(params, batch, cfg, cache)

        batch = S.batch_specs(cfg, sh, mesh, rules)
        cache = S.cache_specs(cfg, sh, mesh, rules)
        return _with_ctx(prefill_fn, mesh, rules), (params, batch, cache), (2,)

    def decode_fn(params, tokens_or_frames, cache, cache_len):
        if cfg.frontend == "audio_frames":
            raise NotImplementedError("encoder-only arch has no decode")
        return M.decode_step(params, tokens_or_frames, cache, cache_len, cfg)

    batch = S.batch_specs(cfg, sh, mesh, rules)
    cache = S.cache_specs(cfg, sh, mesh, rules)
    cache_len = jax.ShapeDtypeStruct((), jnp.int32)
    return (_with_ctx(decode_fn, mesh, rules),
            (params, batch["tokens"], cache, cache_len), (2,))
