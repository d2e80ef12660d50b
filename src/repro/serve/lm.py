"""Legacy LM serving engine: prefill + decode over KV caches.

The serving layer's first-class surface is the streaming frequent-itemset
``MiningService`` (``repro.serve.engine``); this LM engine is retained for
the model stack and its tests, and the ``launch/serve.py`` LM path is gated
behind ``REPRO_LM=1`` like ``examples/train_lm.py``.

jit-compiled prefill and decode steps (donated caches), batched requests,
per-sequence stop handling. On a mesh the cache is sharded by the same rules
as training activations.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import use_sharding
from repro.models import model as M
from repro.models.params import materialize


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params, max_len: int = 1024,
                 mesh=None, rules=None):
        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        self.mesh, self.rules = mesh, rules

        def _wrap(fn):
            if mesh is None:
                return fn

            def inner(*a, **kw):
                with use_sharding(mesh, rules):
                    return fn(*a, **kw)

            return inner

        self._prefill = jax.jit(_wrap(
            lambda p, b, c: M.prefill(p, b, cfg, c)), donate_argnums=(2,))
        self._decode = jax.jit(_wrap(
            lambda p, t, c, n: M.decode_step(p, t, c, n, cfg)),
            donate_argnums=(2,))

    def generate(
        self,
        prompts: np.ndarray,          # (B, S_prompt) int32
        max_new_tokens: int = 32,
        temperature: float = 0.0,
        stop_token: Optional[int] = None,
        rng: Optional[jax.Array] = None,
        vis_embeds=None,
    ) -> np.ndarray:
        b, s_prompt = prompts.shape
        assert s_prompt + max_new_tokens <= self.max_len
        cache = materialize(
            jax.random.PRNGKey(0), M.abstract_cache(self.cfg, b, self.max_len))
        batch = {"tokens": jnp.asarray(prompts)}
        if vis_embeds is not None:
            batch["vis_embeds"] = vis_embeds
        logits, cache = self._prefill(self.params, batch, cache)

        rng = rng if rng is not None else jax.random.PRNGKey(0)
        out = []
        done = np.zeros((b,), bool)
        tok = self._sample(logits, temperature, rng)
        for i in range(max_new_tokens):
            out.append(np.asarray(tok))
            if stop_token is not None:
                done |= np.asarray(tok)[:, 0] == stop_token
                if done.all():
                    break
            logits, cache = self._decode(
                self.params, tok, cache, jnp.int32(s_prompt + i + 1))
            rng, sub = jax.random.split(rng)
            tok = self._sample(logits, temperature, sub)
        return np.concatenate(out, axis=1)

    @staticmethod
    def _sample(logits, temperature, rng):
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        return jax.random.categorical(
            rng, logits / temperature, axis=-1).astype(jnp.int32)[:, None]
