"""FL substrate: FedAvg math, FedOpt family, client local training."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import FedConfig
from repro.configs import get_config, get_smoke_config
from repro.fed import client as client_mod
from repro.fed.aggregator import SiloAggregator, fedavg_params
from repro.fed.client import Client, make_train_step
from repro.models import build_model
from repro.obs import wall
from repro.optim.fedopt import make_server_optimizer
from repro.optim.local import make_optimizer
from repro.optim.schedules import make_schedule


def test_fedavg_weighted_mean_exact():
    p1 = {"w": jnp.asarray([1.0, 2.0]), "b": jnp.asarray([0.0])}
    p2 = {"w": jnp.asarray([3.0, 4.0]), "b": jnp.asarray([1.0])}
    avg = fedavg_params([p1, p2], [1.0, 3.0])
    np.testing.assert_allclose(np.asarray(avg["w"]), [2.5, 3.5], rtol=1e-6)
    np.testing.assert_allclose(np.asarray(avg["b"]), [0.75], rtol=1e-6)


def test_fedavg_convexity():
    rng = np.random.default_rng(0)
    ps = [{"w": jnp.asarray(rng.normal(0, 1, (64,)), jnp.float32)}
          for _ in range(5)]
    avg = fedavg_params(ps, [1] * 5)
    stacked = np.stack([np.asarray(p["w"]) for p in ps])
    assert np.all(np.asarray(avg["w"]) <= stacked.max(0) + 1e-5)
    assert np.all(np.asarray(avg["w"]) >= stacked.min(0) - 1e-5)


@pytest.mark.parametrize("name", ["fedavg", "fedyogi", "fedadam", "fedadagrad"])
def test_server_optimizers_move_toward_delta(name):
    opt = make_server_optimizer(name)
    params = {"w": jnp.zeros((8,))}
    delta = {"w": jnp.ones((8,))}
    state = opt.init(params)
    new, state = opt.apply(params, delta, state)
    assert float(jnp.mean(new["w"])) > 0  # moved in delta direction
    new2, _ = opt.apply(new, delta, state)
    assert float(jnp.mean(new2["w"])) > float(jnp.mean(new["w"]))


def test_sgd_momentum_and_adam():
    for name, kw in (("sgd", {"momentum": 0.9}), ("adam", {})):
        opt = make_optimizer(name, **kw)
        params = {"w": jnp.ones((4,))}
        st = opt.init(params)
        grads = {"w": jnp.ones((4,))}
        new, st = opt.update(grads, st, params, 0.1)
        assert float(jnp.mean(new["w"])) < 1.0


def test_wsd_schedule_shape():
    sched = make_schedule("wsd", 1.0, 100, warmup_steps=10, decay_frac=0.2)
    assert float(sched(0)) < 0.2            # warmup
    assert float(sched(50)) == 1.0          # stable
    assert float(sched(99)) < 0.1           # decay
    const = make_schedule("constant", 0.01, 100)
    assert float(const(7)) == pytest.approx(0.01)


def test_client_local_train_changes_params_and_counts():
    cfg = get_config("paper-cnn")
    model = build_model(cfg)
    rng = np.random.default_rng(0)
    data = {"x": rng.normal(0, 1, (64, 32, 32, 3)).astype(np.float32),
            "y": rng.integers(0, 10, 64).astype(np.int32)}
    client = Client("c0", model, data, batch_size=16, lr=0.05)
    params = model.init(jax.random.PRNGKey(0))
    new_params, n, loss = client.local_train(params, epochs=1)
    assert n == 64 and loss > 0
    diff = sum(float(jnp.sum(jnp.abs(a - b)))
               for a, b in zip(jax.tree.leaves(params),
                               jax.tree.leaves(new_params)))
    assert diff > 0


def test_unknown_byzantine_mode_fails_fast():
    """A typo'd byzantine mode (e.g. 'sign_flip') must raise at
    construction, not silently train honestly."""
    import pytest

    from repro.fed.client import BYZANTINE_MODES
    from repro.fed.cluster import Cluster

    assert BYZANTINE_MODES == (None, "signflip", "noise")
    cfg = get_config("paper-cnn")
    model = build_model(cfg)
    rng = np.random.default_rng(0)
    data = {"x": rng.normal(0, 1, (8, 32, 32, 3)).astype(np.float32),
            "y": rng.integers(0, 10, 8).astype(np.int32)}
    with pytest.raises(ValueError, match="byzantine"):
        Client("evil", model, data, byzantine="sign_flip", batch_size=8)
    with pytest.raises(ValueError, match="byzantine"):
        Cluster("silo0", model, [], test_data=data, byzantine="nois")
    # the valid modes still construct
    for mode in BYZANTINE_MODES:
        Client("ok", model, data, byzantine=mode, batch_size=8)


def test_byzantine_client_flips_sign():
    cfg = get_config("paper-cnn")
    model = build_model(cfg)
    rng = np.random.default_rng(0)
    data = {"x": rng.normal(0, 1, (32, 32, 32, 3)).astype(np.float32),
            "y": rng.integers(0, 10, 32).astype(np.int32)}
    client = Client("evil", model, data, byzantine="signflip", batch_size=16)
    params = model.init(jax.random.PRNGKey(0))
    new_params, _, _ = client.local_train(params, epochs=1)
    # sign flip: large negative correlation with honest params
    v0 = np.concatenate([np.asarray(l).ravel() for l in jax.tree.leaves(params)])
    v1 = np.concatenate([np.asarray(l).ravel() for l in jax.tree.leaves(new_params)])
    assert np.dot(v0, v1) < 0


def _shard(kind):
    """A model, a client shard and initial params: an image shard of the
    paper CNN, or a token stream of a smoke-sized qwen3."""
    rng = np.random.default_rng(1)
    if kind == "image":
        model = build_model(get_config("paper-cnn"))
        data = {"x": rng.normal(0, 1, (48, 32, 32, 3)).astype(np.float32),
                "y": rng.integers(0, 10, 48).astype(np.int32)}
    else:
        cfg = get_smoke_config("qwen3-1.7b")
        model = build_model(cfg)
        data = {"tokens": rng.integers(0, cfg.vocab_size, 400).astype(np.int32),
                "seq_len": 16, "steps_per_epoch": 2}
    return model, data, model.init(jax.random.PRNGKey(0))


def _client(model, data):
    return Client("c0", model, data, batch_size=8, lr=0.05, seed=7)


@pytest.mark.parametrize("kind", ["image", "tokens"])
def test_local_train_matches_a_loop_that_reads_each_loss(kind):
    """Reading the losses once after the last step gives the params and the
    mean loss of a loop that reads every loss on its step, bit for bit."""
    model, data, params = _shard(kind)
    got, _, loss = _client(model, data).local_train(params, epochs=2)
    ref = _client(model, data)
    step, opt = make_train_step(model, ref.optimizer)
    want, opt_state, losses = params, opt.init(params), []
    for batch in ref._batches(2):
        want, opt_state, metrics = step(want, opt_state, batch,
                                        jnp.float32(ref.lr))
        losses.append(float(metrics["loss"]))
    assert len(losses) == (12 if kind == "image" else 4)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert loss == float(np.mean(losses))


def test_local_train_steps_through_the_modules_train_step(monkeypatch):
    """The seam a caller may wrap: ``make_train_step`` is looked up on the
    module at call time and its step called once per batch, in order, with
    the batch dict; with the wall on, one ``train.wait`` per call and one
    host sync per loss read back."""
    model, data, params = _shard("image")
    want = list(_client(model, data)._batches(2))
    make_step, fed = client_mod.make_train_step, []

    def counting(model, opt_name="sgd", momentum=0.0):
        step, opt = make_step(model, opt_name, momentum)

        def counted(params, opt_state, batch, lr):
            fed.append(batch)
            return step(params, opt_state, batch, lr)
        return counted, opt

    monkeypatch.setattr(client_mod, "make_train_step", counting)
    wall.switch(True)
    try:
        _client(model, data).local_train(params, epochs=2)
        t = wall.totals()
    finally:
        wall.switch(False)
    assert len(fed) == len(want) == 12
    for got, exp in zip(fed, want):
        assert isinstance(got, dict) and list(got) == list(exp)
        for k in exp:
            np.testing.assert_array_equal(np.asarray(got[k]),
                                          np.asarray(exp[k]))
    assert t["train.wait_n"] == 1 and t["train.dispatch_n"] == len(want)
    assert t["host_syncs"] == len(want)
