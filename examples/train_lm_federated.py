"""End-to-end driver: federated LM pretraining across 3 silos with UnifyFL.

Each silo's clients train a decoder LM (the reduced qwen3-family smoke
config, small enough for the CPU) on the silo's own Markov-dialect token
stream — the LM analogue of cross-silo NIID. Async mode, top-k policy,
loss-based scoring. A few hundred client steps total. The published widths
run on a TPU through ``python chip_smoke.py`` (qwen3-1.7b cut to 4 layers:
three silos of the full 28 layers do not fit one 16 GB chip).

  PYTHONPATH=src python examples/train_lm_federated.py
"""
import sys

sys.path.insert(0, "src")

import numpy as np

from repro.config import FedConfig
from repro.configs import get_smoke_config
from repro.core.builder import build_lm_experiment

ARCH = sys.argv[1] if len(sys.argv) > 1 else "qwen3-1.7b"
fed = FedConfig(n_silos=3, clients_per_silo=2, rounds=5, local_epochs=1,
                mode="async", scorer="loss", agg_policy="top_k", policy_k=2)

cfg = get_smoke_config(ARCH)
print(f"arch={cfg.arch_id} (reduced: {cfg.n_layers}L d={cfg.d_model} "
      f"vocab={cfg.vocab_size}) — async UnifyFL, 3 dialect silos")
orch = build_lm_experiment(cfg, fed, seq_len=64, batch_size=8,
                           steps_per_epoch=6, lr=0.2, stream_len=30_000)
pre = {s.silo_id: s.cluster.evaluate()["loss"] for s in orch.silos}
orch.run(fed.rounds)
post = {s.silo_id: s.cluster.evaluate()["loss"] for s in orch.silos}
print(f"\nledger verified={orch.ledger.verify()}  "
      f"simulated_time={orch.env.now:.1f}s")
for sid in pre:
    print(f"  {sid}: eval loss {pre[sid]:.3f} -> {post[sid]:.3f} "
          f"(ppl {np.exp(pre[sid]):.1f} -> {np.exp(post[sid]):.1f})")
assert all(post[s] < pre[s] for s in pre), "training failed to reduce loss"
print("OK: every silo's loss improved under federated training")
