"""FL client: local training over a private data shard (paper: standard
Flower clients — SGD, 2 local epochs). Clients are unaware of UnifyFL; they
receive a global model and return locally-trained weights + sample count.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.api import Model
from repro.obs import wall
from repro.optim import make_optimizer

_STEP_CACHE: Dict[Tuple[int, str, float], callable] = {}


def make_train_step(model: Model, opt_name: str = "sgd", momentum: float = 0.0):
    key = (id(model), opt_name, momentum)
    if key in _STEP_CACHE:
        return _STEP_CACHE[key]
    opt = make_optimizer(opt_name, momentum=momentum)

    @jax.jit
    def step(params, opt_state, batch, lr):
        (loss, metrics), grads = jax.value_and_grad(model.loss, has_aux=True)(
            params, batch)
        params, opt_state = opt.update(grads, opt_state, params, lr)
        return params, opt_state, metrics

    _STEP_CACHE[key] = (step, opt)
    return _STEP_CACHE[key]


# the only byzantine client behaviours that exist; anything else (e.g. a
# typo like 'sign_flip') would silently train honestly — fail fast instead,
# mirroring config.FAULT_ACTIONS
BYZANTINE_MODES = (None, "signflip", "noise")


def validate_byzantine(mode: Optional[str], who: str) -> Optional[str]:
    if mode not in BYZANTINE_MODES:
        raise ValueError(f"{who}: unknown byzantine mode {mode!r} "
                         f"(choose from {BYZANTINE_MODES})")
    return mode


class Client:
    """One FL client with a private shard of (x, y) or an LM stream."""

    def __init__(self, client_id: str, model: Model, data: Dict[str, np.ndarray],
                 *, batch_size: int = 32, lr: float = 0.01,
                 optimizer: str = "sgd", seed: int = 0,
                 byzantine: Optional[str] = None):
        self.client_id = client_id
        self.model = model
        self.data = data  # {'x': ..., 'y': ...} or {'tokens': stream}
        self.batch_size = batch_size
        self.lr = lr
        self.optimizer = optimizer
        self.rng = np.random.default_rng(seed)
        self.byzantine = validate_byzantine(byzantine, client_id)

    @property
    def n_samples(self) -> int:
        if "x" in self.data:
            return len(self.data["x"])
        return len(self.data["tokens"])

    def _batches(self, epochs: int):
        if "x" in self.data:
            n = len(self.data["x"])
            for _ in range(epochs):
                order = self.rng.permutation(n)
                for i in range(0, n - self.batch_size + 1, self.batch_size):
                    sel = order[i:i + self.batch_size]
                    yield {"image": jnp.asarray(self.data["x"][sel]),
                           "label": jnp.asarray(self.data["y"][sel])}
        else:
            stream = self.data["tokens"]
            seq = self.data.get("seq_len", 128)
            steps = self.data.get("steps_per_epoch", 8)
            for _ in range(epochs):
                for _ in range(steps):
                    starts = self.rng.integers(0, len(stream) - seq - 1,
                                               self.batch_size)
                    toks = np.stack([stream[s:s + seq] for s in starts])
                    tgts = np.stack([stream[s + 1:s + seq + 1] for s in starts])
                    yield {"tokens": jnp.asarray(toks, jnp.int32),
                           "targets": jnp.asarray(tgts, jnp.int32)}

    def local_train(self, params, epochs: int = 2):
        """Returns (trained params, n_samples, mean loss).

        Each step is two wall spans: ``train.feed`` (the next batch: the
        host gather and its copy to the device) and ``train.dispatch`` (the
        jitted step). The losses stay on the device until the last step;
        ``train.wait`` is their one read, which starts every copy before it
        blocks, so the host runs ahead of the device meanwhile."""
        step, opt = make_train_step(self.model, self.optimizer)
        opt_state = opt.init(params)
        w = wall.active()
        batches = self._batches(epochs)
        losses = []
        while True:
            with w.span("train.feed"):
                batch = next(batches, None)
            if batch is None:
                break
            with w.span("train.dispatch"):
                lr = jnp.float32(self.lr)
                params, opt_state, metrics = step(params, opt_state, batch, lr)
            losses.append(metrics["loss"])
            if w.enabled:
                w.count("h2d_bytes", lr.nbytes + sum(
                    a.nbytes for a in batch.values()))
        if w.enabled:
            w.count("d2h_bytes", sum(l.nbytes for l in losses))
        with w.span("train.wait"):
            # float64, as the Python floats of a per-step read were
            losses = np.asarray(jax.device_get(losses), np.float64)
        w.count("host_syncs", len(losses))
        if self.byzantine == "signflip":
            params = jax.tree.map(lambda p: -p, params)
        elif self.byzantine == "noise":
            params = jax.tree.map(
                lambda p: p + jnp.asarray(
                    self.rng.normal(0, 1.0, p.shape), p.dtype), params)
        return params, self.n_samples, float(np.mean(losses)) if losses.size else 0.0
