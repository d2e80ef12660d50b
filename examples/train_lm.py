"""Train an LM with the fault-tolerant trainer + inline token-set mining.

PROVENANCE: this example (and ``repro.models``/``repro.train``/
``repro.configs``) is inherited scaffolding from the repo seed, not part of
the Apriori reproduction — the paper track is ``quickstart.py`` /
``mine_t10.py``.  It still runs, but is gated behind
``REPRO_LM=1`` so nobody mistakes it for the supported surface.

  REPRO_LM=1 PYTHONPATH=src python examples/train_lm.py --steps 30
  REPRO_LM=1 PYTHONPATH=src python examples/train_lm.py --width 768 \
      --layers 12 --steps 300                                    # ~100M params

Shows: training loop with atomic checkpoints and resume, the Apriori
analytics module mining frequent token-sets from the same data stream, and a
short greedy generation from the trained weights.
"""

import argparse
import dataclasses
import os
import sys

from repro.analytics import TokenSetMiner
from repro.configs import get_reduced
from repro.data.pipeline import SyntheticLM
from repro.train.optimizer import OptConfig
from repro.train.trainer import Trainer, TrainerConfig


def main() -> None:
    if os.environ.get("REPRO_LM") != "1":
        print("examples/train_lm.py is inherited LM scaffolding, not part of "
              "the Apriori reproduction (see README 'Inherited scaffolding').\n"
              "Set REPRO_LM=1 to run it anyway.")
        sys.exit(0)
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--width", type=int, default=128)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--vocab", type=int, default=4096)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    args = ap.parse_args()

    cfg = dataclasses.replace(
        get_reduced("qwen2-1.5b"),
        n_layers=args.layers, d_model=args.width,
        n_heads=max(4, args.width // 64), n_kv_heads=max(2, args.width // 128),
        d_ff=args.width * 4, vocab_size=args.vocab,
    )
    print(f"model: {cfg.param_count() / 1e6:.1f}M params "
          f"({cfg.n_layers}L x {cfg.d_model})")

    pipeline = SyntheticLM(cfg.vocab_size, args.batch, args.seq, seed=0)

    # Apriori analytics on the SAME training stream (the paper's technique as
    # a framework feature): which token sets co-occur suspiciously often?
    miner = TokenSetMiner(min_support=0.10, store="bitmap", window=16, max_k=3)
    mined = miner.mine_steps(pipeline, steps=range(2))
    print("\n" + TokenSetMiner.report(mined, top=5) + "\n")

    ocfg = OptConfig(lr=1e-3, total_steps=args.steps,
                     warmup_steps=max(1, args.steps // 10))
    tcfg = TrainerConfig(total_steps=args.steps, ckpt_every=max(5, args.steps // 4),
                         ckpt_dir=args.ckpt_dir, log_every=5)
    trainer = Trainer(cfg, ocfg, tcfg, pipeline.iterator)
    summary = trainer.run()
    first = summary["log"][0]["loss"] if summary["log"] else float("nan")
    print(f"trained {summary['final_step']} steps: "
          f"loss {first:.3f} -> {summary['final_loss']:.3f} "
          f"(straggler flags: {summary['straggler_flags']})")

    # quick greedy generation from the trained weights
    import numpy as np

    from repro.serve import ServeEngine

    engine = ServeEngine(cfg, trainer.params, max_len=args.seq + 16)
    prompt = np.asarray(pipeline.batch_at(0)["tokens"][:2, :16])
    out = engine.generate(prompt, max_new_tokens=8)
    print("sample continuation:", out[0].tolist())


if __name__ == "__main__":
    main()
