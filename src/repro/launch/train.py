"""End-to-end UnifyFL training driver.

Two modes:
  - image: the paper's CIFAR-like workload (CNN, Dirichlet-NIID silos)
  - lm:    federated LM pretraining over per-silo Markov dialects, for any
           assigned architecture via --arch (the smoke preset trains a small
           same-family config; the full preset is the published config,
           which every silo holds whole on one device: qwen3-1.7b at its 28
           layers does not fit three silos on one 16 GB chip, while
           chip_smoke.py runs it at 4 layers).

The persistent compile cache is on: ``JAX_COMPILATION_CACHE_DIR`` when set,
else ``.jax_cache/`` in the checkout (repro.launch.cache).

Examples:
  PYTHONPATH=src python -m repro.launch.train --workload image --mode sync \
      --rounds 10 --silos 3
  PYTHONPATH=src python -m repro.launch.train --workload lm --arch qwen3-1.7b \
      --preset smoke --rounds 5 --mode async --policy top_k
"""
from __future__ import annotations

import argparse
import json
import time

from repro.config import FedConfig
from repro.configs import get_config, get_smoke_config
from repro.core.builder import (build_image_experiment, build_lm_experiment,
                                global_eval)
from repro.launch.cache import enable_compile_cache


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--workload", choices=["image", "lm"], default="image")
    p.add_argument("--arch", default="paper-cnn")
    p.add_argument("--preset", choices=["smoke", "full"], default="smoke")
    p.add_argument("--mode", choices=["sync", "async"], default="sync")
    p.add_argument("--rounds", type=int, default=5)
    p.add_argument("--silos", type=int, default=3)
    p.add_argument("--clients", type=int, default=2)
    p.add_argument("--local-epochs", type=int, default=1)
    p.add_argument("--policy", default="all")
    p.add_argument("--score-policy", default="median")
    p.add_argument("--scorer", default="accuracy")
    p.add_argument("--k", type=int, default=2)
    p.add_argument("--partition", choices=["iid", "niid"], default="niid")
    p.add_argument("--alpha", type=float, default=0.5)
    p.add_argument("--compression",
                   choices=["none", "int8", "int8-delta", "topk-delta"],
                   default="none")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--out", default=None)
    args = p.parse_args(argv)
    enable_compile_cache()

    fed = FedConfig(n_silos=args.silos, clients_per_silo=args.clients,
                    rounds=args.rounds, local_epochs=args.local_epochs,
                    mode=args.mode, scorer=args.scorer,
                    agg_policy=args.policy, score_policy=args.score_policy,
                    policy_k=args.k, compression=args.compression)
    t0 = time.time()
    if args.workload == "image":
        cfg = get_config("paper-cnn")
        orch = build_image_experiment(cfg, fed, partition=args.partition,
                                      alpha=args.alpha, seed=args.seed)
    else:
        cfg = (get_smoke_config(args.arch) if args.preset == "smoke"
               else get_config(args.arch))
        orch = build_lm_experiment(cfg, fed, seed=args.seed)
    print(f"workload={args.workload} arch={cfg.arch_id} mode={fed.mode} "
          f"silos={fed.n_silos}x{fed.clients_per_silo} rounds={fed.rounds} "
          f"policy={fed.agg_policy}/{fed.score_policy}")
    orch.run(args.rounds)
    ge = global_eval(orch)
    wall = time.time() - t0
    print(f"\nfinished in {wall:.1f}s wall / {orch.env.now:.1f}s simulated")
    print(f"ledger: {orch.ledger.height} blocks, "
          f"{orch.ledger.stats['txs']} txs, verify={orch.ledger.verify()}")
    for sid, m in ge.items():
        print(f"  {sid}: global acc={m['accuracy']:.4f} loss={m['loss']:.4f}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"global_eval": ge, "summary": orch.summary(),
                       "sim_time": orch.env.now, "wall": wall}, f, indent=1,
                      default=str)
    return ge


if __name__ == "__main__":
    main()
