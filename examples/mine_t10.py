"""End-to-end driver (the paper's kind): mine a registry dataset with
checkpoint/restart fault tolerance.

  PYTHONPATH=src python examples/mine_t10.py [--scale 1.0] [--min-support 0.02]
  PYTHONPATH=src python examples/mine_t10.py --dataset T40I10D100K
  PYTHONPATH=src python examples/mine_t10.py --dataset long_tail

With --scale 1.0 this is the paper's full workload: 100k transactions, the
complete level-wise run. Any ``repro.data`` registry name (or ad-hoc Quest
``T<..>I<..>D<..>`` code) is accepted. The miner checkpoints after every
level job; kill it mid-run and re-run to watch it resume at the last
completed level.
"""

import argparse
import time

from repro.core import FrequentItemsetMiner
from repro.core.stores import ARRAY_STORES
from repro.data import get_dataset
from repro.launch.compile_cache import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="T10I4D100K",
                    help="registry dataset name or Quest code")
    ap.add_argument("--scale", type=float, default=0.25)
    ap.add_argument("--min-support", type=float, default=0.02)
    ap.add_argument("--store", default="bitmap", choices=list(ARRAY_STORES))
    ap.add_argument("--inflight", type=int, default=1,
                    help="async wave-dispatch depth (0 = fully synchronous)")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_mine_t10")
    args = ap.parse_args()
    enable_compile_cache()

    print(f"generating {args.dataset} @ scale {args.scale} ...")
    db = get_dataset(args.dataset, scale=args.scale, seed=42)
    print(f"{len(db)} transactions")

    miner = FrequentItemsetMiner(
        min_support=args.min_support, store=args.store,
        inflight=args.inflight, checkpoint_dir=args.ckpt_dir,
    )
    t0 = time.time()
    res = miner.mine(db)
    dt = time.time() - t0
    print(f"\nmined in {dt:.1f}s with store={args.store} "
          f"(min_count={res.min_count})")
    for lv in res.levels:
        print(f"  level k={lv.k}: {lv.n_candidates:6d} candidates -> "
              f"{lv.n_frequent:6d} frequent  ({lv.seconds:.2f}s)")
    print(f"total frequent itemsets: {len(res.itemsets)} (max k={res.max_k})")
    print(f"checkpoints in {args.ckpt_dir} — kill and re-run to test restart")


if __name__ == "__main__":
    main()
