"""Mixture-of-Experts layer: top-k routing, capacity-bounded, sort-free dispatch.

Two production shardings over the ``model`` mesh axis:
  - 'ep': experts partitioned across chips (olmoe: 64 experts / 16 chips).
    Tokens are replicated across the model axis (as activations already are
    under TP), each chip routes the *local* token block to its *local*
    experts, and partial outputs are psum'd — dispatch needs no all-to-all
    and no distributed sort.
  - 'tp': every chip holds all experts with the ff dim sharded (mixtral:
    8 experts < 16 chips). Same code path; the psum reduces ff partials.

Capacity ranking is computed with a one-hot cumsum (static shapes, no sort),
tokens over capacity are dropped (GShard-style) and their residual passes
through unchanged.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from repro import pshard
from repro.config import ModelConfig
from repro.models import layers as L



def init_moe(key, cfg: ModelConfig):
    assert cfg.moe is not None
    e, d, f = cfg.moe.n_experts, cfg.d_model, cfg.d_ff
    pd = L.dtype_of(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    p = {
        "router": L.dense_init(ks[0], (d, e), d, jnp.float32),
        "wi": L.dense_init(ks[1], (e, d, f), d, pd),
        "wo": L.dense_init(ks[2], (e, f, d), f, pd),
    }
    if cfg.gated_mlp:
        p["wg"] = L.dense_init(ks[3], (e, d, f), d, pd)
    return p


def _capacity(n_tokens: int, cfg: ModelConfig) -> int:
    m = cfg.moe
    c = int(math.ceil(n_tokens * m.top_k / m.n_experts * m.capacity_factor))
    return max(1, min(n_tokens, c))


def _route(router_w, x2d, cfg: ModelConfig):
    """x2d [T, D] -> (probs [T,k], idx [T,k], aux scalar)."""
    m = cfg.moe
    logits = jnp.einsum("td,de->te", x2d.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = lax.top_k(probs, m.top_k)
    top_p = top_p / jnp.maximum(jnp.sum(top_p, axis=-1, keepdims=True), 1e-9)
    # Switch-style load-balance loss: E * sum_e f_e * p_e
    e = m.n_experts
    f_e = jnp.mean(jax.nn.one_hot(top_i[:, 0], e, dtype=jnp.float32), axis=0)
    p_e = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(f_e * p_e)
    return top_p.astype(x2d.dtype), top_i, aux


def _dispatch_indices(top_i, n_experts: int, capacity: int):
    """Sort-free capacity ranking.

    top_i: [T, k] expert ids. Returns (buf_idx [E, C] token indices with
    sentinel T for empty slots, slot_of [T, k] capacity slot or -1 if dropped).
    """
    T, k = top_i.shape
    flat = top_i.reshape(-1)  # [T*k] in token-major order (earlier tokens win)
    onehot = jax.nn.one_hot(flat, n_experts, dtype=jnp.int32)  # [Tk, E]
    ranks = jnp.cumsum(onehot, axis=0) - onehot  # exclusive rank within expert
    my_rank = jnp.take_along_axis(ranks, flat[:, None], axis=1)[:, 0]  # [Tk]
    keep = my_rank < capacity
    # scatter token index into [E, C] buffer
    buf = jnp.full((n_experts * capacity,), T, jnp.int32)
    dest = jnp.where(keep, flat * capacity + my_rank, n_experts * capacity)
    buf = buf.at[dest].set(jnp.repeat(jnp.arange(T, dtype=jnp.int32), k),
                           mode="drop")
    slot = jnp.where(keep, my_rank, -1).reshape(T, k)
    return buf.reshape(n_experts, capacity), slot


def _moe_local(p, x2d, cfg: ModelConfig, *, e_lo: int, e_hi: int):
    """Route local tokens [T, D] to experts in [e_lo, e_hi) held locally.

    p['wi'/'wg'/'wo'] carry only the local expert slices (or local ff slice
    in 'tp' mode). Returns (partial output [T, D], aux).
    """
    m = cfg.moe
    T = x2d.shape[0]
    C = _capacity(T, cfg)
    probs, idx, aux = _route(p["router"], x2d, cfg)
    buf_idx, slot = _dispatch_indices(idx, m.n_experts, C)  # global expert ids
    buf_local = buf_idx[e_lo:e_hi]  # [E_loc, C]
    # gather tokens (sentinel T -> zero row via padded x)
    xpad = jnp.concatenate([x2d, jnp.zeros((1, x2d.shape[1]), x2d.dtype)], 0)
    xe = xpad[buf_local]  # [E_loc, C, D]
    h = jnp.einsum("ecd,edf->ecf", xe, p["wi"].astype(xe.dtype))
    if cfg.gated_mlp:
        g = jnp.einsum("ecd,edf->ecf", xe, p["wg"].astype(xe.dtype))
        h = L._act(cfg.mlp_act)(g) * h
    else:
        h = L._act(cfg.mlp_act)(h)
    y = jnp.einsum("ecf,efd->ecd", h, p["wo"].astype(xe.dtype))
    # combine: weight by router prob and scatter back to token order
    gate = jnp.zeros((m.n_experts, C), probs.dtype)
    tk = jnp.arange(T, dtype=jnp.int32)[:, None]
    gate = gate.at[idx, slot].add(jnp.where(slot >= 0, probs, 0.0), mode="drop")
    y = y * gate[e_lo:e_hi, :, None].astype(y.dtype)
    out = jnp.zeros((T + 1, x2d.shape[1]), y.dtype)
    out = out.at[buf_local.reshape(-1)].add(y.reshape(-1, y.shape[-1]),
                                            mode="drop")
    return out[:T], aux


def moe_block(p, x, cfg: ModelConfig):
    """x [B, S, D] -> (out [B, S, D], aux scalar)."""
    B, S, D = x.shape
    mesh = pshard.get_mesh()
    m = cfg.moe
    if mesh is None or "model" not in mesh.axis_names or pshard._MANUAL:
        # no model axis, or inside a shard_map region (every axis manual):
        # this device holds and runs every expert
        out, aux = _moe_local(p, x.reshape(-1, D), cfg, e_lo=0, e_hi=m.n_experts)
        return out.reshape(B, S, D), aux

    n_model = mesh.shape["model"]
    ep = m.sharding == "ep" and m.n_experts % n_model == 0
    bd = pshard.resolve_spec(pshard.BATCH, None, None)[0]
    # batch-1 decode (long_500k) can't shard B over data: replicate tokens
    def _divisible(ax):
        if ax is None:
            return True
        axes = ax if isinstance(ax, tuple) else (ax,)
        n = 1
        for a in axes:
            n *= mesh.shape[a]
        return B % n == 0 and B >= n
    if not _divisible(bd):
        bd = None
    x_spec = P(bd, None, None)
    if ep:
        w_spec = {"router": P(None, None), "wi": P("model", None, None),
                  "wo": P("model", None, None)}
        if cfg.gated_mlp:
            w_spec["wg"] = P("model", None, None)
        e_per = m.n_experts // n_model
    else:  # tp: ff dim sharded
        w_spec = {"router": P(None, None), "wi": P(None, None, "model"),
                  "wo": P(None, "model", None)}
        if cfg.gated_mlp:
            w_spec["wg"] = P(None, None, "model")
        e_per = m.n_experts

    def fn(p_l, x_l, blk_idx):
        xl2 = x_l.reshape(-1, D)
        if ep:
            # blk_idx: [1] slice of arange(n_model) sharded on 'model' — the
            # shard's own index without lax.axis_index (which mis-lowers
            # inside a nested pod-manual region)
            out, aux = _moe_local_offset(p_l, xl2, cfg, e_per, blk_idx[0])
        else:
            out, aux = _moe_local(p_l, xl2, cfg, e_lo=0, e_hi=m.n_experts)
        out = lax.psum(out, "model")
        aux = lax.pmean(aux, tuple(a for a in ("data", "model")
                                   if a in mesh.axis_names))
        return out.reshape(x_l.shape), aux

    names = {a for a in ("data", "model") if a in mesh.axis_names}
    blk_idx = jnp.arange(n_model, dtype=jnp.int32)
    out, aux = shard_map(fn, mesh=mesh,
                         in_specs=(w_spec, x_spec, P("model")),
                         out_specs=(x_spec, P()), axis_names=names,
                         check_vma=False)(p, x, blk_idx)
    return out, aux


def _moe_local_offset(p_l, x2d, cfg: ModelConfig, e_per: int, mi):
    """EP shard body: local expert block is [mi*e_per, +e_per)."""
    m = cfg.moe
    T = x2d.shape[0]
    C = _capacity(T, cfg)
    probs, idx, aux = _route(p_l["router"], x2d, cfg)
    buf_idx, slot = _dispatch_indices(idx, m.n_experts, C)
    buf_local = lax.dynamic_slice_in_dim(buf_idx, mi * e_per, e_per, axis=0)
    xpad = jnp.concatenate([x2d, jnp.zeros((1, x2d.shape[1]), x2d.dtype)], 0)
    xe = xpad[buf_local]
    h = jnp.einsum("ecd,edf->ecf", xe, p_l["wi"].astype(xe.dtype))
    if cfg.gated_mlp:
        g = jnp.einsum("ecd,edf->ecf", xe, p_l["wg"].astype(xe.dtype))
        h = L._act(cfg.mlp_act)(g) * h
    else:
        h = L._act(cfg.mlp_act)(h)
    y = jnp.einsum("ecf,efd->ecd", h, p_l["wo"].astype(xe.dtype))
    gate = jnp.zeros((m.n_experts, C), probs.dtype)
    gate = gate.at[idx, slot].add(jnp.where(slot >= 0, probs, 0.0), mode="drop")
    gate_local = lax.dynamic_slice_in_dim(gate, mi * e_per, e_per, axis=0)
    y = y * gate_local[:, :, None].astype(y.dtype)
    out = jnp.zeros((T + 1, x2d.shape[1]), y.dtype)
    out = out.at[buf_local.reshape(-1)].add(y.reshape(-1, y.shape[-1]),
                                            mode="drop")
    return out[:T], aux
