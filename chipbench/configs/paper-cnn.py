"""The paper CNN: FLOP counts and the plain reference.

The reference is the network as the paper's Flower-tutorial CNN defines it
(conv 5x5 3->6, 2x2 max pool, conv 5x5 6->16, 2x2 max pool, dense
400->120->84->10 with ReLU, mean cross-entropy), written with ``jax.numpy``
and ``jax.lax`` alone and run on the host's CPU at ``highest`` matmul
precision. Its initial weights follow the repo's published init from the
seed (normal, scaled by 1/sqrt(fan-in); zero biases), rebuilt here.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

C1, C2, F1, F2, CLASSES, K = 6, 16, 120, 84, 10, 5
HW = 32


def fwd_flops_per_image() -> int:
    """Multiply-adds x 2 of one forward pass (pooling and ReLU left out)."""
    h1 = HW - K + 1                       # 28
    h2 = h1 // 2 - K + 1                  # 10
    conv1 = h1 * h1 * C1 * K * K * 3
    conv2 = h2 * h2 * C2 * K * K * C1
    flat = C2 * (h2 // 2) ** 2            # 400
    dense = flat * F1 + F1 * F2 + F2 * CLASSES
    return 2 * (conv1 + conv2 + dense)


def round_flops(work: dict) -> float:
    """A round's training (forward and backward: 3x forward, no recompute)
    and scoring forwards, from the samples the driver counted."""
    f = fwd_flops_per_image()
    return 3.0 * f * work["train_samples"] + f * work["eval_samples"]


# --------------------------------------------------------------------------- #
# Plain reference
# --------------------------------------------------------------------------- #

def _dense(key, shape, fan_in, dtype):
    return (jax.random.normal(key, shape) * (1.0 / math.sqrt(fan_in))
            ).astype(dtype)


def init(seed: int, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    flat = C2 * 5 * 5
    z = lambda n: jnp.zeros((n,), dtype)
    return {
        "conv1": {"w": _dense(ks[0], (K, K, 3, C1), 75, dtype), "b": z(C1)},
        "conv2": {"w": _dense(ks[1], (K, K, C1, C2), K * K * C1, dtype),
                  "b": z(C2)},
        "fc1": {"w": _dense(ks[2], (flat, F1), flat, dtype), "b": z(F1)},
        "fc2": {"w": _dense(ks[3], (F1, F2), F1, dtype), "b": z(F2)},
        "out": {"w": _dense(ks[4], (F2, CLASSES), F2, dtype), "b": z(CLASSES)},
    }


def _conv(x, p):
    y = jax.lax.conv_general_dilated(x, p["w"], (1, 1), "VALID",
                                     dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + p["b"]


def _pool(x):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                                 (1, 2, 2, 1), "VALID")


def loss(params, images, labels):
    """Mean cross-entropy, computed in the params' dtype up to the logits."""
    x = images.astype(params["conv1"]["w"].dtype)
    x = _pool(jax.nn.relu(_conv(x, params["conv1"])))
    x = _pool(jax.nn.relu(_conv(x, params["conv2"])))
    x = x.reshape(x.shape[0], -1)
    for name in ("fc1", "fc2"):
        x = jax.nn.relu(x @ params[name]["w"] + params[name]["b"])
    logits = (x @ params["out"]["w"] + params["out"]["b"]).astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - gold)


def sgd_steps(seed: int, batches, lr: float, dtype=jnp.float32):
    """The first len(batches) SGD steps from the seed's init, on the host's
    CPU: (params before, losses, params after each step), as NumPy."""
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu), jax.default_matmul_precision("highest"):
        p = init(seed, dtype)
        vg = jax.jit(jax.value_and_grad(loss))
        start = jax.tree.map(np.asarray, p)
        losses, after = [], []
        for b in batches:
            value, g = vg(p, jnp.asarray(b["image"]), jnp.asarray(b["label"]))
            p = jax.tree.map(lambda a, d: (a - dtype(lr) * d).astype(dtype),
                             p, g)
            losses.append(float(value))
            after.append(jax.tree.map(np.asarray, p))
    return start, losses, after
