"""Plain float32 reference of the Qwen3 dense decoder: forward pass, mean
next-token cross-entropy, and its gradient through ``jax.grad``.

Written from the published description (Qwen3-1.7B's ``config.json``,
https://huggingface.co/Qwen/Qwen3-1.7B/blob/main/config.json) in
straightforward ``jax.numpy``, every matrix product at ``HIGHEST``
precision, sharing no code with ``models/layers.py`` or
``models/transformer.py``. A layer is

    h = x + Attn(RMSNorm(x)),   x' = h + MLP(RMSNorm(h))

with grouped-query attention (``n_kv_heads`` heads of keys and values, each
serving ``n_heads / n_kv_heads`` query heads), RMSNorm applied per head to
q and k before RoPE (theta ``rope_theta``), causal softmax scaled by
1/sqrt(head_dim), and a SwiGLU MLP, down(silu(gate(x)) * up(x)). The final
RMSNorm feeds the tied head: logits = h @ embedding^T. Every RMSNorm is
x / sqrt(mean(x^2) + eps) * weight with eps = ``rms_norm_eps`` (1e-6).

Departures, from the published model and from the system:

  * Parameters come in the system's layout (``params["embed"]["embedding"]``
    [V_padded, D]; ``params["layers"]`` stacked over layers: ``attn_norm``,
    ``attn`` {``wq`` [D, H, hd], ``wk``/``wv`` [D, KV, hd], ``wo``
    [H, hd, D], ``q_norm``/``k_norm`` [hd]}, ``mlp_norm``, ``mlp`` {``wg``
    gate, ``wi`` up, ``wo`` down}; ``final_norm``) and are upcast to
    float32 here. The system keeps them in bfloat16 and computes in
    bfloat16, with norms, softmax and the loss in float32.
  * The system pads the vocabulary's rows to a multiple of 2048. The
    reference reads only the first ``vocab_size`` rows, the published
    vocabulary: the padded rows take no part in the loss (the system masks
    their logits out), so their gradient is zero in both.
  * No rematerialization: the system recomputes each layer's forward pass
    in its backward pass.
  * Attention is the whole [B, H, S, S] softmax (the system's is an online
    softmax over key chunks).
  * The loss's logsumexp runs over blocks of ``vocab_block`` rows of the
    vocabulary, each block's logits recomputed in the backward pass, so
    that no [tokens, vocabulary] float32 array exists whole: at the
    published widths that is what lets the gradient fit on one chip.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST


@dataclass(frozen=True)
class Dims:
    n_heads: int
    n_kv_heads: int
    head_dim: int
    vocab_size: int
    rope_theta: float
    eps: float = 1e-6

    @classmethod
    def of(cls, cfg) -> "Dims":
        """From a ``repro.config.ModelConfig``."""
        return cls(cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim,
                   cfg.vocab_size, float(cfg.rope_theta), float(cfg.norm_eps))


def _mm(spec: str, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def rms_norm(x, w, eps: float):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, theta: float):
    """x [B, S, heads, hd] at positions 0..S-1: rotate the pairs (i,
    i + hd/2) by position x theta^(-2i / hd)."""
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv      # [S, half]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(p, x, d: Dims):
    q = rms_norm(_mm("bsd,dhk->bshk", x, p["wq"]), p["q_norm"], d.eps)
    k = rms_norm(_mm("bsd,dhk->bshk", x, p["wk"]), p["k_norm"], d.eps)
    v = _mm("bsd,dhk->bshk", x, p["wv"])
    q, k = rope(q, d.rope_theta), rope(k, d.rope_theta)
    group = d.n_heads // d.n_kv_heads       # query head i reads kv head
    k = jnp.repeat(k, group, axis=2)        # i // group
    v = jnp.repeat(v, group, axis=2)
    S = x.shape[1]
    s = _mm("bshk,bthk->bhst", q, k) / math.sqrt(d.head_dim)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    o = _mm("bhst,bthk->bshk", jax.nn.softmax(s, axis=-1), v)
    return _mm("bshk,hkd->bsd", o, p["wo"])


def mlp(p, x):
    gate = _mm("bsd,df->bsf", x, p["wg"])
    up = _mm("bsd,df->bsf", x, p["wi"])
    return _mm("bsf,fd->bsd", jax.nn.silu(gate) * up, p["wo"])


def hidden(params, tokens, d: Dims):
    """tokens [B, S] -> the final RMSNorm's output [B, S, D], float32."""
    f32 = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    emb = f32["embed"]["embedding"][:d.vocab_size]
    x = emb[tokens]
    layers = f32["layers"]
    for i in range(layers["attn_norm"].shape[0]):
        lp = jax.tree.map(lambda a: a[i], layers)
        x = x + attention(lp["attn"], rms_norm(x, lp["attn_norm"], d.eps), d)
        x = x + mlp(lp["mlp"], rms_norm(x, lp["mlp_norm"], d.eps))
    return rms_norm(x, f32["final_norm"], d.eps)


def logits(params, tokens, d: Dims):
    """[B, S, vocab_size] through the tied head (small models and tests)."""
    emb = jnp.asarray(params["embed"]["embedding"], jnp.float32)
    return _mm("bsd,vd->bsv", hidden(params, tokens, d), emb[:d.vocab_size])


def token_nll(h, emb, targets, vocab_block: int):
    """-log softmax(h @ emb^T)[target] per row of h [N, D], the logsumexp
    taken block by block over emb's [V, D] rows."""
    V, D = emb.shape
    nb = -(-V // vocab_block)
    blocks = jnp.pad(emb, ((0, nb * vocab_block - V), (0, 0))
                     ).reshape(nb, vocab_block, D)

    @jax.checkpoint
    def step(carry, xs):
        m, s = carry
        i, eb = xs
        lg = _mm("nd,vd->nv", h, eb)
        real = i * vocab_block + jnp.arange(vocab_block) < V
        lg = jnp.where(real[None, :], lg, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(lg, axis=-1))
        s = s * jnp.exp(m - m_new) + jnp.sum(jnp.exp(lg - m_new[:, None]), -1)
        return (m_new, s), None

    n = h.shape[0]
    init = (jnp.full((n,), -jnp.inf, jnp.float32),
            jnp.zeros((n,), jnp.float32))
    (m, s), _ = lax.scan(step, init, (jnp.arange(nb), blocks))
    gold = jnp.sum(h * emb[targets], axis=-1)
    return m + jnp.log(s) - gold


def seq_losses(params, tokens, targets, d: Dims, vocab_block: int = 8192):
    """Mean next-token cross-entropy of each sequence: [B]."""
    B, S = tokens.shape
    h = hidden(params, tokens, d).reshape(B * S, -1)
    emb = jnp.asarray(params["embed"]["embedding"], jnp.float32)
    nll = token_nll(h, emb[:d.vocab_size], targets.reshape(-1), vocab_block)
    return jnp.mean(nll.reshape(B, S), axis=1)


def loss(params, batch, d: Dims, vocab_block: int = 8192):
    """Mean next-token cross-entropy over the batch's tokens."""
    return jnp.mean(seq_losses(params, batch["tokens"], batch["targets"], d,
                               vocab_block))


def value_and_grad(params, batch, d: Dims, vocab_block: int = 8192):
    """(loss, d loss / d params), the gradient in float32."""
    f32 = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    return jax.value_and_grad(loss)(f32, batch, d, vocab_block)
