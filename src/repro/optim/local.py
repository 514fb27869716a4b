"""Client-side (local) optimizers: SGD(+momentum), Adam/AdamW.

Functional interface: opt.init(params) -> state;
opt.update(grads, state, params, lr) -> (new_params, new_state).
The paper's clients use plain SGD lr=0.01.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class Optimizer:
    init: Callable
    update: Callable


def sgd(momentum: float = 0.0, weight_decay: float = 0.0) -> Optimizer:
    def init(params):
        if momentum == 0.0:
            return ()
        return jax.tree.map(jnp.zeros_like, params)

    # params keep their dtype: an f32 ``lr`` would otherwise promote bf16
    # params to f32 on the first step, doubling every silo's resident model
    def update(grads, state, params, lr):
        if weight_decay:
            grads = jax.tree.map(lambda g, p: g + weight_decay * p, grads, params)
        if momentum == 0.0:
            new_params = jax.tree.map(lambda p, g: (p - lr * g).astype(p.dtype),
                                      params, grads)
            return new_params, ()
        new_m = jax.tree.map(lambda m, g: momentum * m + g, state, grads)
        new_params = jax.tree.map(lambda p, m: (p - lr * m).astype(p.dtype),
                                  params, new_m)
        return new_params, new_m

    return Optimizer(init, update)


def adam(b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
         weight_decay: float = 0.0) -> Optimizer:
    def init(params):
        z = jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params)
        return {"m": z, "v": jax.tree.map(jnp.zeros_like, z),
                "t": jnp.zeros((), jnp.int32)}

    def update(grads, state, params, lr):
        t = state["t"] + 1
        gf = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state["m"], gf)
        v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, state["v"], gf)
        bc1 = 1 - b1 ** t.astype(jnp.float32)
        bc2 = 1 - b2 ** t.astype(jnp.float32)

        def step(p, mi, vi):
            upd = (mi / bc1) / (jnp.sqrt(vi / bc2) + eps)
            if weight_decay:
                upd = upd + weight_decay * p.astype(jnp.float32)
            return (p.astype(jnp.float32) - lr * upd).astype(p.dtype)

        new_params = jax.tree.map(step, params, m, v)
        return new_params, {"m": m, "v": v, "t": t}

    return Optimizer(init, update)


def make_optimizer(name: str, *, momentum: float = 0.0,
                   weight_decay: float = 0.0) -> Optimizer:
    if name == "sgd":
        return sgd(momentum, weight_decay)
    if name in ("adam", "adamw"):
        return adam(weight_decay=weight_decay if name == "adamw" else 0.0)
    raise ValueError(f"unknown optimizer {name!r}")
