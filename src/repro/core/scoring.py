"""Scoring functions (paper §2.6).

  - accuracy / loss: the scorer evaluates the pulled model on its *own*
    private test set. Works in both Sync and Async modes; compute-heavy
    (one forward pass over the scorer's test set). The per-(scorer, round)
    hot path is the batched engine (``scorebatch.score_round_batch``): all
    K models of a round score in one scan x vmap pass with a single
    device→host transfer.
  - MultiKRUM: similarity-based — needs *all* models of a round at once, so
    Sync only (paper Table 3). Backed by the Pallas pairwise-distance kernel.

Scores are normalized so that HIGHER IS BETTER for every method (MultiKRUM's
sum-of-distances is negated), so the policy layer is method-agnostic.
"""
from __future__ import annotations

from typing import List, Sequence

import jax.numpy as jnp

from repro.core.wire import stack_q8
from repro.kernels import ops
from repro.obs import wall


def multikrum_scores_for_decoded(decoded: Sequence, m: int) -> List[float]:
    """MultiKRUM over a round's ``DecodedModel``s (higher = better).

    When every model arrived whole-int8 — the normal case under
    ``compression='int8'`` — the Gram matrix is accumulated straight off
    the packed payloads (``wire.stack_q8``) by the fused ``gram_q8`` kernel:
    no f32 [M, N] materialization, ~1/9 the HBM traffic. Any other round
    stacks each model's reconstructed vector. Either way the [M] score
    vector crosses to the host exactly once."""
    with wall.span("score.multikrum"):
        if all(d.is_q8 for d in decoded):
            scores = ops.multikrum_scores_q8(*stack_q8(decoded), m)
        else:
            n = int(decoded[0].n)
            scores = ops.multikrum_scores(
                jnp.stack([d.vec()[:n] for d in decoded]), m)
        # negate: lower distance sum = better
        return (-wall.to_host(scores)).tolist()
