"""Silo-level aggregation: FedAvg over client weights + the FedOpt family for
applying cross-silo deltas (paper Table 5 mixes FedAvg and FedYogi silos).

The cross-silo merge runs in flat-vector space end-to-end: peer models arrive
as ``DecodedModel``s (possibly still int8-packed), quantized peers flow
through the fused ``wsum_q8`` kernel without ever materializing as f32, and
the caller unflattens the merged vector back into its params exactly once."""
from __future__ import annotations

from typing import List, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.wire import DecodedModel
from repro.kernels import ops
from repro.obs import wall
from repro.optim.fedopt import ServerOptimizer, make_server_optimizer


def fedavg_params(params_list: Sequence, weights: Sequence[float]):
    """Sample-count-weighted average of parameter pytrees (kernel-backed).
    One client's average is its own params: weight 1.0 is exact, so the
    flatten / sum / unflatten round trip is skipped."""
    if len(params_list) == 1:
        return params_list[0]
    w = np.asarray(weights, np.float64)
    w = (w / w.sum()).astype(np.float32)
    vecs, spec = ops.flatten_batch(params_list)
    agg = ops.weighted_sum(vecs, jnp.asarray(w))
    return ops.unflatten_pytree(agg, spec)


class SiloAggregator:
    """Aggregates client updates within one silo and applies cross-silo
    models via a configurable server optimizer (fedavg / fedyogi / ...)."""

    def __init__(self, silo_id: str, server_opt: str = "fedavg"):
        self.silo_id = silo_id
        self.server_opt: ServerOptimizer = make_server_optimizer(server_opt)
        self._opt_state = None

    def aggregate_clients(self, results: List[Tuple]):
        """results: [(params, n_samples, loss)] -> silo local model."""
        params_list = [r[0] for r in results]
        weights = [r[1] for r in results]
        return fedavg_params(params_list, weights)

    def apply_cross_silo_vec(self, own_vec, peers: List[DecodedModel],
                             weights: List[float]):
        """Merge peer models into the silo's flat f32 vector [n].

        weights[0] is the self-weight; weights[1:] align with ``peers``.
        int8 peers are grouped by padded length and consumed by one fused
        kernel call per group; f32 peers add their (cached) vectors."""
        if not peers:
            return own_vec
        w = np.asarray(weights, np.float64)
        w = (w / w.sum()).astype(np.float32)
        n = int(own_vec.shape[0])
        mixed = w[0] * own_vec
        groups: dict = {}
        f32_peers = []
        for wi, p in zip(w[1:], peers):
            if p.is_q8:
                groups.setdefault(int(p.q.shape[0]), []).append((wi, p))
            else:
                f32_peers.append((wi, p))
        for grp in groups.values():
            # payloads arrive as host arrays: stack there, move once
            q = np.stack([np.asarray(p.q) for _, p in grp])
            s = np.stack([np.asarray(p.scales) for _, p in grp])
            wall.count("h2d_bytes", q.nbytes + s.nbytes)
            gw = jnp.asarray(np.asarray([wi for wi, _ in grp], np.float32))
            mixed = mixed + ops.weighted_sum_q8(q, s, gw, n)
        for wi, p in f32_peers:
            mixed = mixed + wi * p.vec()[:n]
        delta = mixed - own_vec
        if self._opt_state is None:
            self._opt_state = self.server_opt.init(own_vec)
        new, self._opt_state = self.server_opt.apply(own_vec, delta,
                                                     self._opt_state)
        return new

    def apply_cross_silo(self, own_params, peer_params: List,
                         weights: List[float]):
        """Pytree-facing wrapper over the flat-vector merge."""
        if not peer_params:
            return own_params
        spec = ops.make_flatten_spec(own_params)
        own_vec, _ = ops.flatten_pytree(own_params, spec)
        peers = [DecodedModel(int(v.shape[0]), vec=v)
                 for v, _ in (ops.flatten_pytree(p, spec) for p in peer_params)]
        new_vec = self.apply_cross_silo_vec(own_vec, peers, weights)
        return ops.unflatten_pytree(new_vec, spec)
