"""qwen3-1.7b cut to 4 layers: FLOP counts and the plain float32 reference.

The reference is the benchmark's own copy of the Qwen3 dense decoder as its
published ``config.json`` describes it (the program keeps another copy,
``repro.models.qwen3_ref``, for its CPU tests; this one shares no code with
it or with the program's model): RMSNorm (eps ``rms_norm_eps``), grouped-
query attention with a per-head RMSNorm of q and k before RoPE (theta
``rope_theta``), causal softmax over 1/sqrt(head_dim)-scaled scores, a
SwiGLU MLP, down(silu(gate(x)) * up(x)), and the tied head over the
published vocabulary. It runs on the default device (the chip, once the
program's state is released), every matrix product at ``HIGHEST``
precision, and follows the program's params layout. Departures: params
arrive in bfloat16 and are upcast to float32; no rematerialization; the
loss's logsumexp runs over blocks of ``VOCAB_BLOCK`` vocabulary rows, each
block's logits recomputed in the backward pass, so that no [tokens,
vocabulary] array exists whole and the gradient fits on one chip.

``cast`` (the control, one precision below the configuration's bfloat16)
rounds the inputs of every matrix product to float8_e4m3fn in the forward
pass; gradients flow through the rounding unchanged.
"""
from __future__ import annotations

import functools
import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
from jax import lax

CONFIG = json.loads(Path(__file__).with_suffix(".json").read_text())
HIGHEST = lax.Precision.HIGHEST
VOCAB_BLOCK = 8192


# --------------------------------------------------------------------------- #
# FLOPs from the configuration's shapes
# --------------------------------------------------------------------------- #

def fwd_flops_per_token(seq_len: int, c: dict = CONFIG) -> float:
    """Multiply-adds x 2 of one token's forward pass at ``seq_len``: the
    q, k, v, o and MLP projections of every layer, the causal attention
    (q.k and p.v against (seq_len + 1) / 2 keys on average) and the tied
    head over the published vocabulary. Norms, RoPE and softmax are left
    out."""
    d, hd = c["hidden_size"], c["head_dim"]
    h, kv = c["num_attention_heads"], c["num_key_value_heads"]
    proj = d * h * hd + 2 * d * kv * hd + h * hd * d
    mlp = 3 * d * c["intermediate_size"]
    attn = 2 * h * hd * (seq_len + 1) / 2
    return 2.0 * (c["num_hidden_layers"] * (proj + mlp + attn)
                  + d * c["vocab_size"])


def round_flops(work: dict) -> float:
    """A round's training (forward and backward: 3x forward, recompute not
    counted) and scoring forwards, from the tokens the driver counted."""
    f = fwd_flops_per_token(work["seq_len"])
    return 3.0 * f * work["train_tokens"] + f * work["eval_tokens"]


# --------------------------------------------------------------------------- #
# Plain reference
# --------------------------------------------------------------------------- #

def _through(cast):
    """Round to ``cast`` in the forward pass, identity in the backward."""
    if cast is None:
        return lambda a: a
    return lambda a: a + lax.stop_gradient(a.astype(cast).astype(a.dtype) - a)


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _hidden(p, tokens, dims, lo):
    mm = lambda spec, a, b: jnp.einsum(spec, lo(a), lo(b), precision=HIGHEST)
    eps, H, KV, hd = dims["eps"], dims["n_heads"], dims["n_kv_heads"], \
        dims["head_dim"]
    S = tokens.shape[1]
    causal = jnp.tril(jnp.ones((S, S), bool))

    def layer(x, lp):
        a = lp["attn"]
        xn = _rms(x, lp["attn_norm"], eps)
        q = _rms(mm("bsd,dhk->bshk", xn, a["wq"]), a["q_norm"], eps)
        k = _rms(mm("bsd,dhk->bshk", xn, a["wk"]), a["k_norm"], eps)
        v = mm("bsd,dhk->bshk", xn, a["wv"])
        q, k = _rope(q, dims["rope_theta"]), _rope(k, dims["rope_theta"])
        k, v = jnp.repeat(k, H // KV, axis=2), jnp.repeat(v, H // KV, axis=2)
        s = jnp.where(causal, mm("bshk,bthk->bhst", q, k) / math.sqrt(hd),
                      -jnp.inf)
        o = mm("bhst,bthk->bshk", jax.nn.softmax(s, axis=-1), v)
        x = x + mm("bshk,hkd->bsd", o, a["wo"])
        m = lp["mlp"]
        xn = _rms(x, lp["mlp_norm"], eps)
        g = jax.nn.silu(mm("bsd,df->bsf", xn, m["wg"]))
        return x + mm("bsf,fd->bsd", g * mm("bsd,df->bsf", xn, m["wi"]),
                      m["wo"])

    x = p["embed"]["embedding"][:dims["vocab_size"]][tokens]
    layers = p["layers"]
    for i in range(layers["attn_norm"].shape[0]):
        x = layer(x, jax.tree.map(lambda a: a[i], layers))
    return _rms(x, p["final_norm"], eps), mm


def _seq_losses(p, tokens, targets, dims, cast):
    """Mean next-token cross-entropy of each sequence, [B]."""
    lo = _through(cast)
    h, mm = _hidden(p, tokens, dims, lo)
    B, S, D = h.shape
    h = h.reshape(B * S, D)
    V = dims["vocab_size"]
    emb = p["embed"]["embedding"][:V]
    nb = -(-V // VOCAB_BLOCK)
    blocks = jnp.pad(emb, ((0, nb * VOCAB_BLOCK - V), (0, 0))
                     ).reshape(nb, VOCAB_BLOCK, D)

    @jax.checkpoint
    def block(carry, xs):
        m, s = carry
        i, eb = xs
        lg = mm("nd,vd->nv", h, eb)
        real = i * VOCAB_BLOCK + jnp.arange(VOCAB_BLOCK) < V
        lg = jnp.where(real[None, :], lg, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(lg, axis=-1))
        s = s * jnp.exp(m - m_new) + jnp.sum(jnp.exp(lg - m_new[:, None]), -1)
        return (m_new, s), None

    init = (jnp.full((B * S,), -jnp.inf, jnp.float32),
            jnp.zeros((B * S,), jnp.float32))
    (m, s), _ = lax.scan(block, init, (jnp.arange(nb), blocks))
    gold = jnp.sum(lo(h) * lo(emb[targets.reshape(-1)]), axis=-1)
    return jnp.mean((m + jnp.log(s) - gold).reshape(B, S), axis=1)


def _dims_key(dims: dict):
    return tuple(sorted(dims.items()))


@functools.partial(jax.jit, static_argnums=(3, 4))
def _value_and_grad(p, tokens, targets, dims_key, cast):
    dims = dict(dims_key)
    f = lambda q: jnp.mean(_seq_losses(q, tokens, targets, dims, cast))
    return jax.value_and_grad(f)(p)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _losses(p, tokens, targets, dims_key, cast):
    return _seq_losses(p, tokens, targets, dict(dims_key), cast)


@functools.partial(jax.jit, donate_argnums=(0,))
def _sgd(p, g, lr):
    """One SGD step in float32, the params then stored as the program
    stores them (bfloat16) and upcast again."""
    return jax.tree.map(lambda a, d: (a - lr * d).astype(jnp.bfloat16)
                        .astype(jnp.float32), p, g)


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)


def sgd_steps(start, batches, lr: float, dims: dict, cast=None):
    """The reference's SGD steps from the program's params ``start`` (host,
    bfloat16) on ``batches``: each step's loss, and, on the device, the
    params after the last step (bfloat16) and the first step's gradient
    (float32)."""
    with jax.default_matmul_precision("highest"):
        p = _f32(start)
        losses, first = [], None
        for b in batches:
            value, g = _value_and_grad(p, jnp.asarray(b["tokens"]),
                                       jnp.asarray(b["targets"]),
                                       _dims_key(dims), cast)
            losses.append(float(value))
            first = g if first is None else first
            p = _sgd(p, g, jnp.float32(lr))
            del g
        return losses, jax.tree.map(lambda a: a.astype(jnp.bfloat16), p), first


def loss_of(params, tokens, targets, dims: dict, cast=None) -> float:
    """The mean over windows of each window's mean cross-entropy: what the
    program's loss scorer computes, here from float32 params (host)."""
    with jax.default_matmul_precision("highest"):
        out = _losses(_f32(params), jnp.asarray(tokens), jnp.asarray(targets),
                      _dims_key(dims), cast)
        return float(jnp.mean(out))
