"""Batched scoring engine: vmapped multi-model evaluation, q8-direct ingest.

Every round, each scorer silo evaluates every pulled peer model on its
private test set (paper §2.6) — the validation cost the hierarchical-FL
literature flags as the scalability bottleneck of trustless cross-silo
schemes. The seed pipeline paid it in the worst possible shape: one jitted
forward per (model, batch) pair inside a Python loop, with a ``float()``
device→host sync per batch, repeated K models × S scorers per round.

This engine restructures the whole score phase around two ideas:

  * **Stack, don't loop.** All K peer models of a round are stacked along a
    leading axis into ONE pytree (leaves ``[K, ...]``) and evaluated in one
    jitted ``lax.scan``-over-batches × ``vmap``-over-models pass. The full
    ``[K]`` score vector comes back with a **single** device→host transfer
    (``BatchedScorer.host_syncs`` counts them; it increments once per
    (scorer, round) score call).

  * **q8-direct ingest.** The stack is fed straight from the wire layer: a
    whole-int8 round's payloads are stacked once (``wire.stack_q8``) and
    expanded by the batched-dequant Pallas kernel (``ops.dequantize_batch``,
    oracle ``ref.dequantize_rows``) straight into the stacked pytree's
    leaves — K separate f32 pytrees are never materialized. Any other round
    stacks each model's (cached) reconstructed vector.

``Cluster.evaluate`` shares the same machinery with K=1, which also moves
its per-batch accumulation inside jit (no per-batch host syncs for
self-eval either).
"""
from __future__ import annotations

import functools
from collections import OrderedDict
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.wire import stack_q8
from repro.kernels import ops
from repro.obs import wall

BATCH_SIZE = 256          # eval batch width (matches the pre-engine loop)
MAX_PREPARED = 8          # device-resident test-set layouts kept process-wide
MAX_EVAL_FNS = 16         # jitted eval closures kept process-wide

# jitted eval fns shared across silos: keyed on the Model instance (one
# compile per (model, data-shape), not per cluster), bounded LRU so long
# sweeps over many build_model() calls don't pin every model forever
_EVAL_FNS: "OrderedDict" = OrderedDict()

# test-set layouts shared across scorers: builder.global_eval swaps the SAME
# global test dict into every silo — keying on (id(td), batch_size) means S
# silos evaluating one shared test set hold ONE device copy, not S
_PREPARED: "OrderedDict" = OrderedDict()


# --------------------------------------------------------------------------- #
# Wire -> stacked models (the q8-direct ingest path)
# --------------------------------------------------------------------------- #

def _leaf_dtype(spec):
    """The one dtype every leaf of ``spec`` has, else f32."""
    dtypes = {dt for _, dt in spec[1]}
    return dtypes.pop() if len(dtypes) == 1 else jnp.float32


@functools.partial(jax.jit, static_argnums=(2,))
def _dequantize_stacked(q, scales, spec):
    # one leaf dtype: dequantize straight into it (the kernel rounds its f32
    # product once, as the cast in unflatten_batch would) at half the bytes
    return ops.unflatten_batch(
        ops.dequantize_batch(q, scales, ops.spec_length(spec),
                             _leaf_dtype(spec)), spec)


@functools.partial(jax.jit, static_argnums=(1,))
def _stack_rows(rows, spec):
    """K flat [n] vectors -> stacked pytree, stacking leaf by leaf."""
    treedef, shapes = spec
    leaves, off = [], 0
    for shape, dtype in shapes:
        n = int(np.prod(shape)) if shape else 1
        leaves.append(jnp.stack([r[off:off + n].reshape(shape).astype(dtype)
                                 for r in rows]))
        off += n
    return jax.tree_util.tree_unflatten(treedef, leaves)


def stack_decoded(decoded: Sequence, spec):
    """Wire payloads -> stacked parameter pytree (leaves [K, *shape]).

    One jitted program runs from the payloads to the stacked leaves, so no
    f32 [K, N] matrix is left beside them (4 GB for two 516M-parameter
    models): a whole-int8 round dequantizes its ``wire.stack_q8`` inside
    it, and any other round reconstructs each model's vector, casts it to
    the leaf dtype before the next one is built (a cast per element, so the
    result is the same) and stacks them inside it."""
    if decoded and all(d.is_q8 for d in decoded):
        return _dequantize_stacked(*stack_q8(decoded), spec)
    n = ops.spec_length(spec)
    dtype = _leaf_dtype(spec)
    return _stack_rows(tuple(jnp.asarray(d.vec(), jnp.float32)[:n].astype(dtype)
                             for d in decoded), spec)


# --------------------------------------------------------------------------- #
# Jitted batched eval (scan over batches x vmap over models)
# --------------------------------------------------------------------------- #

def _image_eval_fn(model):
    """(stacked, xb [nb,bs,...], yb, xr [r,...], yr) -> [2, K] (loss, acc).

    Full batches stream through a ``lax.scan``; the partial remainder batch
    (if any — its size is static in the trace) is weighted by its true
    count, exactly the pre-engine per-batch math, accumulated on device."""
    def raw(stacked, xb, yb, xr, yr):
        nb = xb.shape[0]
        bs = xb.shape[1]
        r = xr.shape[0]
        n = nb * bs + r

        def per_model(params):
            def step(carry, inp):
                x, y = inp
                _, m = model.loss(params, {"image": x, "label": y})
                return (carry[0] + m["loss"] * bs,
                        carry[1] + m.get("accuracy", jnp.float32(0.0)) * bs), None

            carry = (jnp.float32(0.0), jnp.float32(0.0))
            if nb:
                carry, _ = jax.lax.scan(step, carry, (xb, yb))
            ls, ac = carry
            if r:
                _, m = model.loss(params, {"image": xr, "label": yr})
                ls = ls + m["loss"] * r
                ac = ac + m.get("accuracy", jnp.float32(0.0)) * r
            return ls / n, ac / n

        loss, acc = jax.vmap(per_model)(stacked)
        return jnp.stack([loss, acc])

    return jax.jit(raw)


def _lm_eval_fn(model):
    """(stacked, tok [W,S], tgt [W,S]) -> [2, K] (loss, exp(-loss))."""
    def raw(stacked, tok, tgt):
        W = tok.shape[0]

        def per_model(params):
            def step(carry, inp):
                t, g = inp
                _, m = model.loss(params, {"tokens": t[None], "targets": g[None]})
                return carry + m["loss"], None

            total, _ = jax.lax.scan(step, jnp.float32(0.0), (tok, tgt))
            loss = total / W
            return loss, jnp.exp(-loss)

        loss, acc = jax.vmap(per_model)(stacked)
        return jnp.stack([loss, acc])

    return jax.jit(raw)


def _eval_fn(model, kind: str):
    key = (id(model), kind)
    hit = _EVAL_FNS.get(key)
    if hit is None:
        fn = _image_eval_fn(model) if kind == "image" else _lm_eval_fn(model)
        # pin the model so the id key can't be recycled under us
        _EVAL_FNS[key] = hit = (model, fn)
        while len(_EVAL_FNS) > MAX_EVAL_FNS:
            _EVAL_FNS.popitem(last=False)
    else:
        _EVAL_FNS.move_to_end(key)
    return hit[1]


# --------------------------------------------------------------------------- #
# Per-cluster scorer
# --------------------------------------------------------------------------- #

class BatchedScorer:
    """One per scorer cluster: evaluates K stacked models on the cluster's
    private test set wholly on device, one host transfer per call."""

    def __init__(self, cluster, batch_size: int = BATCH_SIZE):
        self.cluster = cluster
        self.batch_size = batch_size
        self.host_syncs = 0          # device->host transfers issued
        self.calls = 0

    # -- test-set layout (device-resident, derived once per test_data) ------ #
    def _prepare(self, td) -> Dict:
        if "x" in td:
            x = np.asarray(td["x"])
            y = np.asarray(td["y"])
            n = len(x)
            bs = self.batch_size
            nb, r = divmod(n, bs)
            cut = nb * bs
            return {
                "td": td, "kind": "image",
                "args": (jnp.asarray(x[:cut].reshape(nb, bs, *x.shape[1:])),
                         jnp.asarray(y[:cut].reshape(nb, bs)),
                         jnp.asarray(x[cut:]), jnp.asarray(y[cut:])),
            }
        stream = np.asarray(td["tokens"])
        seq = int(td.get("seq_len", 128))
        starts = list(range(0, min(len(stream) - seq - 1, 4 * seq), seq))
        if not starts:
            return {"td": td, "kind": "empty", "args": None}
        tok = np.stack([stream[i:i + seq] for i in starts]).astype(np.int32)
        tgt = np.stack([stream[i + 1:i + seq + 1] for i in starts]
                       ).astype(np.int32)
        return {"td": td, "kind": "lm",
                "args": (jnp.asarray(tok), jnp.asarray(tgt))}

    def _prep(self) -> Dict:
        td = self.cluster.test_data
        key = (id(td), self.batch_size)
        p = _PREPARED.get(key)
        if p is None or p["td"] is not td:
            p = self._prepare(td)
            _PREPARED[key] = p       # p["td"] pins td, keeping id(td) valid
            while len(_PREPARED) > MAX_PREPARED:
                _PREPARED.popitem(last=False)
        else:
            _PREPARED.move_to_end(key)
        return p

    # -- the one batched pass ------------------------------------------------ #
    def evaluate_stacked(self, stacked) -> np.ndarray:
        """stacked: pytree with leaves [K, ...] -> host [2, K] (loss, acc)
        via exactly ONE device->host transfer."""
        with wall.span("score.eval"):
            p = self._prep()
            self.calls += 1
            K = int(jax.tree_util.tree_leaves(stacked)[0].shape[0])
            if p["kind"] == "empty":
                # degenerate LM stream: matches the pre-engine fallback
                return np.stack([np.zeros(K), np.ones(K)])
            out = _eval_fn(self.cluster.model, p["kind"])(stacked, *p["args"])
            host = wall.to_host(out)     # the single device->host transfer
            self.host_syncs += 1
            return host


def get_scorer(cluster) -> BatchedScorer:
    """The cluster's (cached) batched scorer."""
    sc = getattr(cluster, "_batched_scorer", None)
    if sc is None or sc.cluster is not cluster:
        sc = BatchedScorer(cluster)
        cluster._batched_scorer = sc
    return sc


# --------------------------------------------------------------------------- #
# Public entry points
# --------------------------------------------------------------------------- #

def evaluate_params(cluster, params) -> Dict[str, float]:
    """Self/peer evaluation of ONE model through the engine (K=1): the
    accumulation runs inside jit, no per-batch host syncs."""
    stacked = jax.tree.map(lambda a: jnp.asarray(a)[None], params)
    host = get_scorer(cluster).evaluate_stacked(stacked)
    return {"loss": float(host[0, 0]), "accuracy": float(host[1, 0])}


def score_round_batch(cluster, decoded: Sequence, spec, *,
                      method: str = "accuracy") -> List[float]:
    """Score a round's K pulled peer models on ``cluster``'s private test
    set in ONE batched pass (higher = better for every method), with a
    single device->host transfer for the whole [K] score vector."""
    if not decoded:
        return []
    stacked = stack_decoded(decoded, spec)
    host = get_scorer(cluster).evaluate_stacked(stacked)
    if method == "accuracy":
        return [float(a) for a in host[1]]
    if method == "loss":
        return [float(-l) for l in host[0]]
    raise ValueError(f"per-model scorer {method!r} unknown")
