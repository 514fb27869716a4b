"""Scoring functions (paper §2.6).

  - accuracy / loss: the scorer evaluates the pulled model on its *own*
    private test set. Works in both Sync and Async modes; compute-heavy
    (one forward pass over the scorer's test set). The per-(scorer, round)
    hot path is the batched engine (``repro.fed.scorebatch``): all K models
    of a round score in one scan x vmap pass with a single device→host
    transfer.
  - MultiKRUM: similarity-based — needs *all* models of a round at once, so
    Sync only (paper Table 3). Backed by the Pallas pairwise-distance kernel.

Scores are normalized so that HIGHER IS BETTER for every method (MultiKRUM's
sum-of-distances is negated), so the policy layer is method-agnostic.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import List, Sequence

import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.obs import wall


def multikrum_scores_for_round(models: Sequence, m: int) -> List[float]:
    """Score every model of a Sync round at once (higher = better).

    models: list of parameter pytrees. m: neighbourhood size (paper's f-derived
    parameter; we expose it directly)."""
    x, _ = ops.flatten_batch(models)
    scores = ops.multikrum_scores(x, m)
    # negate: lower distance sum = better; ONE device->host transfer
    return (-wall.to_host(scores)).tolist()


def multikrum_scores_for_decoded(decoded: Sequence, m: int) -> List[float]:
    """MultiKRUM over a round's ``DecodedModel``s (higher = better).

    When every model arrived int8-packed with one padded length — the normal
    case under ``compression='int8'`` — the Gram matrix is accumulated
    straight off the packed payloads by the fused ``gram_q8`` kernel: no f32
    [M, N] materialization, ~1/9 the HBM traffic. Mixed or uncompressed
    rounds stack through the engine's batched-dequant ingest (one kernel
    pass per q8 length group, no per-model dequant loop). Either way the
    [M] score vector crosses to the host exactly once."""
    with wall.span("score.multikrum"):
        if (all(d.is_q8 for d in decoded)
                and len({int(d.q.shape[0]) for d in decoded}) == 1):
            q = np.stack([np.asarray(d.q) for d in decoded])
            s = np.stack([np.asarray(d.scales) for d in decoded])
            wall.count("h2d_bytes", q.nbytes + s.nbytes)
            scores = ops.multikrum_scores_q8(q, s, m)
        else:
            from repro.fed.scorebatch import stack_decoded_vecs
            x = stack_decoded_vecs(decoded, int(decoded[0].n))
            scores = ops.multikrum_scores(x, m)
        return (-wall.to_host(scores)).tolist()


# JL projections are a pure function of (n, sketch_dim, seed) — regenerating
# the gaussian matrix (the dominant cost for big models) every call wasted
# host time on the sketched-krum path. Bounded LRU: one [4k, k] f32
# projection can be hundreds of MiB for big models, so evict, don't pin.
_JL_CACHE: "OrderedDict" = OrderedDict()
MAX_JL_CACHE = 8


def _jl_projection(n: int, sketch_dim: int, seed: int):
    key = (n, sketch_dim, seed)
    hit = _JL_CACHE.get(key)
    if hit is None:
        rng = np.random.default_rng(seed)
        k = min(sketch_dim, n)
        # sparse JL: sample k coordinates * dense gaussian on those
        idx = rng.choice(n, size=min(n, 4 * k), replace=False)
        proj = rng.normal(0, 1.0 / np.sqrt(k), (len(idx), k)).astype(np.float32)
        _JL_CACHE[key] = hit = (idx, jnp.asarray(proj))
        while len(_JL_CACHE) > MAX_JL_CACHE:
            _JL_CACHE.popitem(last=False)
    else:
        _JL_CACHE.move_to_end(key)
    return hit


def multikrum_sketched(models: Sequence, m: int, *, sketch_dim: int = 4096,
                       seed: int = 0) -> List[float]:
    """Beyond-paper: MultiKRUM on Johnson-Lindenstrauss sketches.

    Pairwise L2 distances are preserved within (1 +- eps) by a random
    projection, so the krum ranking is stable while the all-gather/compute
    cost drops from O(N) to O(sketch_dim) per model — this is what the
    in-fabric jittable exchange uses (core/exchange.py). The projection is
    cached per (n, sketch_dim, seed)."""
    vecs = [np.asarray(ops.flatten_pytree(p)[0]) for p in models]
    n = vecs[0].shape[0]
    idx, proj = _jl_projection(n, sketch_dim, seed)
    x = jnp.stack([jnp.asarray(v[idx]) @ proj for v in vecs])
    scores = ops.multikrum_scores(x, m)
    return (-np.asarray(scores)).tolist()
