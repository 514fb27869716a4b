"""An FL cluster (= silo = organization): one aggregator + its clients.

This is the unit UnifyFL coordinates. The cluster runs single-level FL
internally (clients -> FedAvg), evaluates on its private test set (which also
serves as its scoring set when the silo acts as a scorer), and may be
byzantine (submitting poisoned models — paper Figure 7).
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.fed import scorebatch
from repro.fed.aggregator import SiloAggregator
from repro.fed.client import Client, validate_byzantine
from repro.models.api import Model
from repro.obs import wall


class Cluster:
    def __init__(self, silo_id: str, model: Model, clients: List[Client], *,
                 test_data: Dict[str, np.ndarray], server_opt: str = "fedavg",
                 local_epochs: int = 2, byzantine: Optional[str] = None,
                 seed: int = 0, edge_fleet=None):
        self.silo_id = silo_id
        self.model = model
        self.clients = clients
        self.test_data = test_data
        self.aggregator = SiloAggregator(silo_id, server_opt)
        self.local_epochs = local_epochs
        self.byzantine = validate_byzantine(byzantine, silo_id)
        self.params = model.init(jax.random.PRNGKey(seed))
        self.round = 0
        self.history: List[Dict] = []
        # hierarchical mode (repro.edge): when set, the silo's trainer
        # population is an EdgeFleet — train_round delegates to it
        self.edge_fleet = edge_fleet

    # ------------------------------------------------------------------ #
    def train_round(self) -> Dict:
        """One local FL round: fan out to clients, FedAvg their results.
        Returns metrics; updates self.params (the silo 'local model').

        With an ``edge_fleet`` attached this is the *edge tier* instead:
        sampled edge clients train on their device profiles and FedAvg up
        here — the multilevel pre-round the paper compares against, charged
        on the fabric when one is wired."""
        if self.edge_fleet is not None:
            self.params, m = self.edge_fleet.train_round(self.params)
            self._perturb()
            self.round += 1
            m["round"] = self.round
            return m
        results = [c.local_train(self.params, self.local_epochs)
                   for c in self.clients]
        with wall.span("train.fedavg", round=self.round + 1,
                       silo=self.silo_id):
            self.params = self.aggregator.aggregate_clients(results)
        self._perturb()
        self.round += 1
        mean_loss = float(np.mean([r[2] for r in results]))
        return {"round": self.round, "client_loss": mean_loss}

    def _perturb(self) -> None:
        """Silo-level byzantine poisoning of the aggregated model."""
        if self.byzantine == "signflip":
            self.params = jax.tree.map(lambda p: -p, self.params)
        elif self.byzantine == "noise":
            rng = np.random.default_rng((self.round, 13))
            self.params = jax.tree.map(
                lambda p: p + jnp.asarray(rng.normal(0, 0.5, p.shape),
                                          p.dtype),
                self.params)

    # ------------------------------------------------------------------ #
    def evaluate(self, params=None) -> Dict[str, float]:
        """Accuracy/loss of a model on this silo's private test set.

        Runs through the batched scoring engine with K=1: the whole
        accumulation (including the correctly-weighted partial batch)
        happens inside one jitted pass — no per-batch ``float()`` syncs."""
        params = self.params if params is None else params
        return scorebatch.evaluate_params(self, params)

    # ------------------------------------------------------------------ #
    def score_model(self, params, method: str = "accuracy") -> float:
        """Score a peer model on the silo's private test set (paper §2.6:
        accuracy scoring works in both sync and async modes)."""
        m = self.evaluate(params)
        if method == "accuracy":
            return m["accuracy"]
        if method == "loss":
            return -m["loss"]
        raise ValueError(method)
