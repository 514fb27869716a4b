"""Jit'd public wrappers over the Pallas kernels.

On TPU the pallas path compiles natively; elsewhere (the CPU, where the
tests run) the same kernel body runs under ``interpret=True`` so numerics
are identical and every kernel is exercised end-to-end. ``force='ref'``
selects the pure-jnp oracle (used by tests to cross-validate).

Each wrapper jits its padding, kernel call and trim as one program. The q8
kernels take the f32 side unpadded (``kernels/quant.py``), so for a vector
whose length is a multiple of 128 the pad and the trim are no-ops and no
padded copy of a model-sized vector is made.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import multikrum as _mk
from repro.kernels import q8agg as _q8
from repro.kernels import quant as _q
from repro.kernels import ref as _ref
from repro.kernels import rwkv6 as _rwkv
from repro.kernels import wsum as _ws


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pad_to(x, axis: int, multiple: int, value=0.0):
    n = x.shape[axis]
    pad = (-n) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


# --------------------------------------------------------------------------- #
# Flatten helpers (model pytree <-> single vector)
# --------------------------------------------------------------------------- #

_SPEC_CACHE: dict = {}


def make_flatten_spec(params):
    """Derive (and cache) the flatten spec for a pytree's config: one spec per
    (structure, shapes, dtypes) — the round-critical path flattens/unflattens
    against it every round without re-deriving leaf metadata."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    key = (treedef, tuple((tuple(l.shape), np.dtype(l.dtype)) for l in leaves))
    spec = _SPEC_CACHE.get(key)
    if spec is None:
        spec = (treedef, key[1])  # hashable: a static argument of jit
        _SPEC_CACHE[key] = spec
    return spec


def flatten_pytree(params, spec=None):
    """Pytree -> (vector f32 [N], treedef+shapes for unflatten)."""
    if spec is None:
        spec = make_flatten_spec(params)
    leaves = jax.tree_util.tree_leaves(params)
    vec = jnp.concatenate([jnp.ravel(l).astype(jnp.float32) for l in leaves]) \
        if leaves else jnp.zeros((0,), jnp.float32)
    return vec, spec


def flatten_batch(params_list, spec=None):
    """M pytrees of one config -> ([M, N] f32, spec) in a single batched
    flatten: per-leaf stack across models, one concatenate along N (replaces
    the per-model python re-flatten loop on the aggregation hot path)."""
    if spec is None:
        spec = make_flatten_spec(params_list[0])
    rows = [jax.tree_util.tree_leaves(p) for p in params_list]
    if not rows[0]:
        return jnp.zeros((len(rows), 0), jnp.float32), spec
    cols = [jnp.stack([jnp.ravel(r[i]).astype(jnp.float32) for r in rows])
            for i in range(len(rows[0]))]
    return jnp.concatenate(cols, axis=1), spec


def unflatten_pytree(vec, spec):
    treedef, shapes = spec
    leaves = []
    off = 0
    for shape, dtype in shapes:
        n = int(np.prod(shape)) if shape else 1
        leaves.append(jnp.reshape(vec[off:off + n], shape).astype(dtype))
        off += n
    return jax.tree_util.tree_unflatten(treedef, leaves)


def unflatten_batch(mat, spec):
    """[K, N] f32 -> stacked pytree with leaves [K, *shape] (the batched
    inverse of ``flatten_batch``): one slice per leaf instead of K separate
    unflattens, so a whole round of models lands as one vmappable pytree."""
    treedef, shapes = spec
    K = mat.shape[0]
    leaves = []
    off = 0
    for shape, dtype in shapes:
        n = int(np.prod(shape)) if shape else 1
        leaves.append(jnp.reshape(mat[:, off:off + n],
                                  (K,) + tuple(shape)).astype(dtype))
        off += n
    return jax.tree_util.tree_unflatten(treedef, leaves)


def spec_length(spec) -> int:
    """True (unpadded) flattened length of a flatten spec's pytree."""
    _, shapes = spec
    return sum(int(np.prod(shape)) if shape else 1 for shape, _ in shapes)


# --------------------------------------------------------------------------- #
# MultiKRUM
# --------------------------------------------------------------------------- #

@jax.jit
def _pairwise_dists(x):
    g, sq = _mk.gram_and_norms(_pad_to(x, 1, _mk.TILE_N),
                               interpret=_interpret())
    return jnp.maximum(sq + sq.T - 2.0 * g, 0.0)


def pairwise_dists(x, force: str = "auto"):
    """x: [M, N] -> pairwise squared L2 [M, M]."""
    if force == "ref":
        return _ref.multikrum_dists(x)
    return _pairwise_dists(x)


def multikrum_scores(x, m: int, force: str = "auto"):
    """Sum of squared distances to the m nearest peers (lower = better)."""
    if force == "ref":
        return _ref.multikrum_scores(x, m)
    d = pairwise_dists(x, force)
    M = d.shape[0]
    d = d + jnp.diag(jnp.full((M,), jnp.inf))
    m = min(m, M - 1)
    return jnp.sum(jnp.sort(d, axis=1)[:, :m], axis=1)


# --------------------------------------------------------------------------- #
# Weighted aggregation
# --------------------------------------------------------------------------- #

@jax.jit
def _weighted_sum(x, w):
    xp = _pad_to(x, 1, _ws.TILE_N)
    return _ws.weighted_sum(xp, w, interpret=_interpret())[:x.shape[1]]


def weighted_sum(x, w, force: str = "auto"):
    """x: [M, N], w: [M] -> [N]."""
    if force == "ref":
        return _ref.weighted_sum(x, w)
    return _weighted_sum(x, w)


# --------------------------------------------------------------------------- #
# Fused int8-native aggregation (quantized models never materialize as f32)
# --------------------------------------------------------------------------- #

QTILE = _q.TILE  # scale granularity of the quantized payload


def _pad_q8(q, scales):
    """Pad [M, Np] int8 + [M, Np/QTILE] scales to the kernel block width.
    Zero-padded q contributes nothing regardless of the padded scale."""
    return (_pad_to(q, q.ndim - 1, _q8.TILE_N),
            _pad_to(scales, scales.ndim - 1, _q8.QPB))


def _up128(n: int) -> int:
    return -(-n // 128) * 128


@functools.partial(jax.jit, static_argnames=("n",))
def _wsum_q8(q, scales, w, n: int):
    qp, sp = _pad_q8(q, scales)
    return _q8.wsum_q8(qp, sp, w, _up128(n), interpret=_interpret())[:n]


def weighted_sum_q8(q, scales, w, n: int = None, force: str = "auto"):
    """Fused dequantize + weighted sum. q: [M, Np] int8 (Np % QTILE == 0),
    scales: [M, Np/QTILE], w: [M] -> [n] f32 (n defaults to Np)."""
    M, Np = q.shape
    assert Np % QTILE == 0, f"quantized payload must be {QTILE}-aligned"
    n = Np if n is None else n
    if force == "ref":
        return _ref.wsum_q8(q, scales, w, QTILE)[:n]
    return _wsum_q8(q, scales, w, n)


@functools.partial(jax.jit, static_argnames=("n",))
def _add_q8_delta(base, q, scales, n: int):
    qp = _pad_to(q, 0, QUANT_BLOCK)
    sp = _pad_to(scales, 0, QUANT_BLOCK // QTILE)
    bp = _pad_to(base[:n].astype(jnp.float32), 0, 128)
    return _q8.add_q8_delta(bp, qp, sp, interpret=_interpret())[:n]


def add_q8_delta(base, q, scales, n: int = None, force: str = "auto"):
    """Fused delta-apply: base [n] f32 + dequantized int8 delta, one pass.
    q: [Np] int8 (Np % QTILE == 0), scales: [Np/QTILE] -> [n] f32 without
    materializing the f32 delta (n defaults to len(base))."""
    n = int(base.shape[0]) if n is None else n
    assert q.shape[0] % QTILE == 0, f"delta payload must be {QTILE}-aligned"
    if force == "ref":
        return _ref.add_q8_delta(base[:n], q, scales, QTILE)
    return _add_q8_delta(base, q, scales, n)


@jax.jit
def _pairwise_dists_q8(q, scales):
    g, sq = _q8.gram_q8(*_pad_q8(q, scales), interpret=_interpret())
    return jnp.maximum(sq + sq.T - 2.0 * g, 0.0)


def pairwise_dists_q8(q, scales, force: str = "auto"):
    """Fused dequantize + pairwise squared L2 of quantized models [M, M]."""
    if force == "ref":
        g, sq = _ref.gram_q8(q, scales, QTILE)
        return jnp.maximum(sq + sq.T - 2.0 * g, 0.0)
    return _pairwise_dists_q8(q, scales)


def multikrum_scores_q8(q, scales, m: int, force: str = "auto"):
    """MultiKRUM scores straight off the int8 payloads (lower = better)."""
    d = pairwise_dists_q8(q, scales, force)
    M = d.shape[0]
    d = d + jnp.diag(jnp.full((M,), jnp.inf))
    m = min(m, M - 1)
    return jnp.sum(jnp.sort(d, axis=1)[:, :m], axis=1)


# --------------------------------------------------------------------------- #
# int8 compression
# --------------------------------------------------------------------------- #

QUANT_BLOCK = _q.BLOCK


@jax.jit
def _quantize(x):
    return _q.quantize(_pad_to(x, 0, 128), interpret=_interpret())


def quantize(x, force: str = "auto"):
    """x: [N] -> (q int8 [Np], scales [Np/TILE], N) with Np padded."""
    N = x.shape[0]
    if force == "ref":
        xp = _pad_to(x, 0, _q.TILE)
        q, s = _ref.quantize_int8(xp, _q.TILE)
        return q, s, N
    q, s = _quantize(x)
    return q, s, N


@functools.partial(jax.jit, static_argnames=("n", "dtype"))
def _dequantize(q, scales, n: int, dtype):
    return _q.dequantize(q, scales, _up128(n), dtype=dtype,
                         interpret=_interpret())[:n]


def dequantize(q, scales, n, dtype=jnp.float32, force: str = "auto"):
    if force == "ref":
        return _ref.dequantize_int8(q, scales, _q.TILE)[:n].astype(dtype)
    return _dequantize(q, scales, int(n), dtype)


def dequantize_batch(q, scales, n, dtype=jnp.float32, force: str = "auto"):
    """Batched dequant: q [K, Np] int8 + scales [K, Np/QTILE] -> [K, n] f32
    in ONE kernel pass (oracle: ``ref.dequantize_rows``). The scoring
    engine's q8-direct ingest: a round's packed payloads become one stacked
    matrix without K per-model dequant dispatches."""
    if force == "ref":
        return _ref.dequantize_rows(q, scales, _q.TILE)[:, :n].astype(dtype)
    return _dequantize_batch(q, scales, int(n), dtype)


@functools.partial(jax.jit, static_argnames=("n", "dtype"))
def _dequantize_batch(q, scales, n: int, dtype):
    return _q.dequantize_batch(q, scales, dtype=dtype,
                               interpret=_interpret())[:, :n]


# --------------------------------------------------------------------------- #
# WKV6
# --------------------------------------------------------------------------- #

def wkv6(r, k, v, w, u, state, force: str = "auto"):
    """r,k,v,w: [B, T, H, hs]; u: [H, hs]; state: [B, H, hs, hs]."""
    if force == "ref":
        return _ref.wkv6_naive(r, k, v, w, u, state)
    B, T, H, hs = r.shape
    fold = lambda a: a.transpose(0, 2, 1, 3).reshape(B * H, T, hs)
    rt, kt, vt = fold(r), fold(k), fold(v)
    wt = fold(w)
    pad = (-T) % _rwkv.CHUNK
    if pad:
        z = lambda a, cv=0.0: jnp.pad(a, ((0, 0), (0, pad), (0, 0)),
                                      constant_values=cv)
        rt, kt, vt, wt = z(rt), z(kt), z(vt), z(wt, 1.0)
    ub = jnp.broadcast_to(u, (B, H, hs)).reshape(B * H, hs)
    y, s1 = _rwkv.wkv6(rt, kt, vt, wt, ub, state.reshape(B * H, hs, hs),
                       interpret=_interpret())
    y = y[:, :T].reshape(B, H, T, hs).transpose(0, 2, 1, 3)
    return y, s1.reshape(B, H, hs, hs)
