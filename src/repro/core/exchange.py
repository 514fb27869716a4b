"""Jittable cross-silo UnifyFL exchange over the ``pod`` mesh axis.

When silos are TPU pods on a shared fabric, the paper's IPFS-pull +
score + policy-select + re-aggregate round becomes collectives over the
``pod`` axis, fused into one compiled program with the local train step:

  round_step(state, batch):
    shard_map manual over every mesh axis (a silo's block is replicated
    over its own 'data' x 'model' devices):
      1. local train step (client SGD on the silo's batch)
      2. exchange:
         'all' policy  -> weighted psum over 'pod' (no gather, no scoring)
         scored policy -> all_gather models over 'pod' (optionally int8,
                          cutting gather bytes 4x), score each peer model on a
                          local scoring microbatch (paper's accuracy scorer)
                          or on JL sketches (MultiKRUM), all_gather the score
                          matrix, collapse via the score policy, mask via the
                          aggregation policy, weighted-sum the gathered models.

Used by launch/dryrun.py for the multi-pod mesh and by ``chip_smoke.py
--chips 4`` on four one-chip silos; the control-plane
(ledger+CAS) path in core/orchestrator.py is the faithful WAN variant.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from repro import pshard
from repro.models.api import Model


def _pod_manual_shard_map(f, mesh, in_specs, out_specs):
    """shard_map manual over every mesh axis, replication check off.

    Inputs split along 'pod' only, so each silo's block is replicated over
    its 'data' x 'model' devices and its step runs whole on each of them.
    Leaving 'data'/'model' to the partitioner (partial-auto shard_map) makes
    XLA's SPMD partitioner abort (``spmd_partitioner_util.cc`` replica-group
    CHECK) once a batch constraint on 'data' meets a 'data' axis larger than
    1; with every axis manual there is nothing left for it to partition."""
    return shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                     check_vma=False)


@dataclass(frozen=True)
class ExchangeConfig:
    policy: str = "top_k"          # 'all' | 'self' | 'top_k' | 'above_average'
    score_policy: str = "median"   # 'median' | 'mean' | 'min' | 'max'
    k: int = 1
    scorer: str = "loss"           # 'loss' (accuracy proxy) | 'multikrum'
    compression: str = "none"      # 'none' | 'int8'
    score_batch: int = 2           # rows of the local batch used for scoring
    sketch_dim: int = 2048         # multikrum JL sketch width
    mix_rate: float = 0.5          # self-weight when merging peers


# --------------------------------------------------------------------------- #
# In-jit compression (pure jnp; the Pallas kernel covers the control plane)
# --------------------------------------------------------------------------- #

def _q8(leaf):
    amax = jnp.max(jnp.abs(leaf.astype(jnp.float32)))
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(leaf.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale


def _dq8(q, scale, dtype):
    return (q.astype(jnp.float32) * scale).astype(dtype)


# --------------------------------------------------------------------------- #
# Score -> weights (policies, all jnp)
# --------------------------------------------------------------------------- #

def _collapse_scores(mat, how: str):
    """mat: [scorer, model] -> [model]."""
    if how == "median":
        return jnp.median(mat, axis=0)
    if how == "mean":
        return jnp.mean(mat, axis=0)
    if how == "min":
        return jnp.min(mat, axis=0)
    if how == "max":
        return jnp.max(mat, axis=0)
    raise ValueError(how)


def _policy_weights(scores, my_idx, cfg: ExchangeConfig, n: int):
    """scores: [n] higher=better -> normalized weights [n] incl. self."""
    if cfg.policy == "all":
        return jnp.full((n,), 1.0 / n, jnp.float32)
    if cfg.policy == "self":
        return jax.nn.one_hot(my_idx, n, dtype=jnp.float32)
    if cfg.policy == "top_k":
        k = min(cfg.k, n - 1)
        peer_scores = jnp.where(jnp.arange(n) == my_idx, -jnp.inf, scores)
        thresh = jnp.sort(peer_scores)[-k]
        mask = (peer_scores >= thresh).astype(jnp.float32)
    elif cfg.policy == "above_average":
        peer_mask = (jnp.arange(n) != my_idx)
        avg = jnp.sum(jnp.where(peer_mask, scores, 0.0)) / jnp.maximum(
            jnp.sum(peer_mask), 1)
        mask = ((scores >= avg) & peer_mask).astype(jnp.float32)
    else:
        raise ValueError(cfg.policy)
    n_pick = jnp.sum(mask)
    self_w = jnp.where(n_pick > 0, cfg.mix_rate, 1.0)
    peer_w = jnp.where(n_pick > 0, (1.0 - self_w) / jnp.maximum(n_pick, 1.0), 0.0)
    return mask * peer_w + jax.nn.one_hot(my_idx, n, dtype=jnp.float32) * self_w


# --------------------------------------------------------------------------- #
# The exchange body (runs inside the pod-manual shard_map region)
# --------------------------------------------------------------------------- #

def _sketch(params, dim: int):
    """Sharding-aware linear sketch of a parameter pytree -> [dim] f32.

    Per leaf: reduce all-but-the-first axis (reductions stay sharded — a
    reshape(-1) would force a full all-gather of every leaf), then fold the
    leading-axis profile into the accumulator. This is a block-sum linear
    projection: pairwise L2 distances in sketch space track full-space
    distances well enough to preserve the krum ranking.
    """
    leaves = jax.tree_util.tree_leaves(params)
    acc = jnp.zeros((dim,), jnp.float32)
    for i, leaf in enumerate(leaves):
        s = leaf.astype(jnp.float32)
        if s.ndim > 1:
            s = jnp.sum(s, axis=tuple(range(1, s.ndim)))
        take = min(s.shape[0], dim)
        acc = acc.at[:take].add(jax.lax.slice(s, (0,), (take,)))
    return acc / jnp.sqrt(jnp.float32(len(leaves)))


def exchange(params, score_fn: Callable, score_batch, cfg: ExchangeConfig):
    """Inside the pod shard_map region. params: silo-local pytree.
    score_fn(params, batch) -> scalar loss. Returns merged params."""
    n = lax.axis_size("pod")
    my_idx = lax.axis_index("pod")
    if cfg.policy == "self" or n == 1:
        return params
    if cfg.policy == "all" and cfg.scorer != "multikrum":
        # fast path: no scoring needed -> single psum (beyond-paper: avoids
        # the all-gather of full models entirely)
        return jax.tree.map(
            lambda p: (lax.pmean(p.astype(jnp.float32), "pod")).astype(p.dtype),
            params)

    # gather peer models over the pod axis (optionally int8-compressed)
    if cfg.compression == "int8":
        qs = jax.tree.map(_q8, params, is_leaf=lambda x: hasattr(x, "dtype"))
        gathered = jax.tree.map(
            lambda p, qsl: _dq8(lax.all_gather(qsl[0], "pod"),
                                lax.all_gather(qsl[1], "pod")
                                .reshape((n,) + (1,) * p.ndim), p.dtype),
            params, qs, is_leaf=lambda x: isinstance(x, tuple))
    else:
        gathered = jax.tree.map(lambda p: lax.all_gather(p, "pod"), params)

    if cfg.scorer == "multikrum":
        sk = _sketch(params, cfg.sketch_dim)
        sks = lax.all_gather(sk, "pod")  # [n, dim]
        d = jnp.sum((sks[:, None, :] - sks[None, :, :]) ** 2, axis=-1)
        d = d + jnp.where(jnp.eye(n, dtype=bool), jnp.inf, 0.0)
        m = max(1, min(n - 1, 2))
        scores = -jnp.sum(jnp.sort(d, axis=1)[:, :m], axis=1)  # [n]
    else:
        # paper's accuracy scoring: each silo scores every gathered model on
        # its local scoring microbatch; scan over the model dimension
        def score_one(_, i):
            pi = jax.tree.map(lambda g: g[i], gathered)
            return None, -score_fn(pi, score_batch)

        _, my_scores = lax.scan(score_one, None, jnp.arange(n))  # [n]
        mat = lax.all_gather(my_scores, "pod")  # [scorer, model]
        scores = _collapse_scores(mat, cfg.score_policy)

    w = _policy_weights(scores, my_idx, cfg, n)  # [n]
    # one f32 term per model, summed in one fusion: no f32 copy of the
    # gathered [n, ...] stack (that copy planned 12.3 GB of temporaries per
    # v5e chip for four 516M-parameter silos; this sum plans 9.2 GB)
    merged = jax.tree.map(
        lambda g, p: sum(w[i] * g[i].astype(jnp.float32)
                         for i in range(n)).astype(p.dtype),
        gathered, params)
    return merged


# --------------------------------------------------------------------------- #
# Round-step builder (multi-pod program for the dry-run / production launch)
# --------------------------------------------------------------------------- #

def make_train_step(model: Model, lr: float = 0.01, *,
                    reduce_in_param_dtype: bool = False):
    """Single-silo train step: SGD on model.loss (the paper's client opt).

    reduce_in_param_dtype=True keeps the SGD arithmetic in the parameter
    dtype (bf16), so XLA's cross-replica gradient reduction runs on bf16
    values instead of f32 — 2x fewer collective bytes (beyond-paper; real
    training keeps f32 master accumulators in optim/local.py).
    """

    def train_step(params, batch):
        (loss, metrics), grads = jax.value_and_grad(model.loss, has_aux=True)(
            params, batch)
        # pin gradients to the parameter sharding: turns XLA's cross-replica
        # grad all-reduce into a reduce-scatter under fsdp (ZeRO-2/3 proper)
        mesh = pshard.get_mesh()
        if mesh is not None:
            shardings = pshard.param_shardings(grads, model.param_rules())
            grads = jax.tree.map(
                lambda g, s: jax.lax.with_sharding_constraint(g, s)
                if s is not None else g, grads, shardings)
        if reduce_in_param_dtype:
            new_params = jax.tree.map(
                lambda p, g: p - jnp.asarray(lr, p.dtype) * g.astype(p.dtype),
                params, grads)
        else:
            new_params = jax.tree.map(
                lambda p, g: (p.astype(jnp.float32)
                              - lr * g.astype(jnp.float32)).astype(p.dtype),
                params, grads)
        return new_params, metrics

    return train_step


def make_unifyfl_round_step(model: Model, mesh, ex_cfg: ExchangeConfig,
                            lr: float = 0.01):
    """Multi-pod program: params/batch stacked on a leading pod dim.

    params leaves [P, ...] sharded on 'pod'; batch leaves [P, B, ...].
    Lowers to silo-local train (+grads) plus pod-axis exchange collectives.
    """
    train_step = make_train_step(model, lr)

    def per_pod(params_blk, batch_blk):
        with pshard.manual_axes(mesh.axis_names):
            params = jax.tree.map(lambda x: x[0], params_blk)
            batch = jax.tree.map(lambda x: x[0], batch_blk)
            new_params, metrics = train_step(params, batch)
            score_fn = lambda p, b: model.loss(p, b)[0]
            score_batch = jax.tree.map(lambda x: x[:ex_cfg.score_batch], batch)
            merged = exchange(new_params, score_fn, score_batch, ex_cfg)
            out = jax.tree.map(lambda x: x[None], merged)
            loss = metrics["loss"][None]
        return out, loss

    def round_step(params_stacked, batch_stacked):
        return _pod_manual_shard_map(
            per_pod, mesh,
            (P("pod"), P("pod")), (P("pod"), P("pod")),
        )(params_stacked, batch_stacked)

    return round_step


def make_pod_serve_step(model: Model, mesh, kind: str):
    """Multi-pod serving: each pod serves its own silo model (no cross-pod
    collectives; proves pod-axis sharding coherence for serve shapes)."""

    def per_pod_decode(params_blk, batch_blk, cache_blk):
        with pshard.manual_axes(mesh.axis_names):
            params = jax.tree.map(lambda x: x[0], params_blk)
            batch = jax.tree.map(lambda x: x[0] if x.ndim > 0 else x, batch_blk)
            cache = jax.tree.map(lambda x: x[0], cache_blk)
            logits, cache = model.decode_step(params, batch, cache)
            return (jax.tree.map(lambda x: x[None], logits),
                    jax.tree.map(lambda x: x[None], cache))

    def per_pod_prefill(params_blk, batch_blk):
        with pshard.manual_axes(mesh.axis_names):
            params = jax.tree.map(lambda x: x[0], params_blk)
            batch = jax.tree.map(lambda x: x[0], batch_blk)
            logits, cache = model.prefill(params, batch)
            return (jax.tree.map(lambda x: x[None], logits),
                    jax.tree.map(lambda x: x[None], cache))

    if kind == "decode":
        def serve_step(params_stacked, batch_stacked, cache_stacked):
            return _pod_manual_shard_map(
                per_pod_decode, mesh,
                (P("pod"), P("pod"), P("pod")), (P("pod"), P("pod")),
            )(params_stacked, batch_stacked, cache_stacked)
    else:
        def serve_step(params_stacked, batch_stacked):
            return _pod_manual_shard_map(
                per_pod_prefill, mesh,
                (P("pod"), P("pod")), (P("pod"), P("pod")),
            )(params_stacked, batch_stacked)

    return serve_step
