"""Batched scoring engine: q8-direct ingest, batched == sequential parity
(mixed wire rounds, K=1 included), single device->host transfer."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import wire
from repro.core.store import deserialize_pytree, serialize_pytree
from repro.fed import scorebatch
from repro.fed.cluster import Cluster
from repro.kernels import ops
from repro.models import build_model

CNN = get_config("paper-cnn")


@pytest.fixture(scope="module")
def model():
    return build_model(CNN)


@pytest.fixture(scope="module")
def cluster(model):
    rng = np.random.default_rng(0)
    td = {"x": rng.normal(0, 1, (300, 32, 32, 3)).astype(np.float32),
          "y": rng.integers(0, 10, 300).astype(np.int32)}
    return Cluster("scorer0", model, [], test_data=td)


def _peer_vecs(model, k, seed=0):
    base, spec = ops.flatten_pytree(model.init(jax.random.PRNGKey(seed)))
    rng = np.random.default_rng(seed)
    vecs = [jnp.asarray(np.asarray(base)
                        + rng.normal(0, 0.05 * (i + 1),
                                     base.shape).astype(np.float32))
            for i in range(k)]
    return vecs, spec


def _decode(env):
    """Envelope -> DecodedModel through the real store codec roundtrip."""
    return wire.decode_store(deserialize_pytree(serialize_pytree(
        env.to_store())))


def _sequential_oracle(model, td, params, bs=256):
    """The pre-engine loop: per-batch jitted forward + float() syncs."""
    ev = jax.jit(lambda p, b: model.loss(p, b)[1])
    n = len(td["x"])
    loss = acc = 0.0
    for i in range(0, n, bs):
        batch = {"image": jnp.asarray(td["x"][i:i + bs]),
                 "label": jnp.asarray(td["y"][i:i + bs])}
        m = ev(params, batch)
        c = len(td["x"][i:i + bs])
        loss += float(m["loss"]) * c
        acc += float(m.get("accuracy", 0.0)) * c
    return loss / n, acc / n


# --------------------------------------------------------------------------- #
# Ingest primitives
# --------------------------------------------------------------------------- #

def test_dequantize_batch_matches_per_model():
    rng = np.random.default_rng(3)
    n = ops.QUANT_BLOCK + 777
    qs = []
    for i in range(4):
        v = jnp.asarray(rng.normal(0, 0.1 * (i + 1), n).astype(np.float32))
        qs.append(ops.quantize(v))
    q = jnp.stack([p[0] for p in qs])
    s = jnp.stack([p[1] for p in qs])
    batched = ops.dequantize_batch(q, s, n)
    for i in range(4):
        one = ops.dequantize(q[i], s[i], n)
        np.testing.assert_allclose(np.asarray(batched[i]), np.asarray(one),
                                   rtol=0, atol=0)
    # and against the jnp oracle
    ref = ops.dequantize_batch(q, s, n, force="ref")
    np.testing.assert_allclose(np.asarray(batched), np.asarray(ref),
                               rtol=0, atol=1e-6)


def test_unflatten_batch_matches_per_row(model):
    vecs, spec = _peer_vecs(model, 3)
    stacked = ops.unflatten_batch(jnp.stack(vecs), spec)
    for i, v in enumerate(vecs):
        one = ops.unflatten_pytree(v, spec)
        for a, b in zip(jax.tree.leaves(stacked), jax.tree.leaves(one)):
            assert a.shape[1:] == b.shape and a.dtype == b.dtype
            np.testing.assert_allclose(np.asarray(a[i], np.float32),
                                       np.asarray(b, np.float32),
                                       rtol=0, atol=1e-6)


def test_stack_decoded_mixed_wire_matches_vecs(model):
    vecs, spec = _peer_vecs(model, 4)
    n = ops.spec_length(spec)
    decoded = [_decode(wire.encode_vec(v, m))
               for v, m in zip(vecs, ("raw", "int8", "int8", "raw"))]
    stacked = scorebatch.stack_decoded(decoded, spec)
    for i, d in enumerate(decoded):
        row, _ = ops.flatten_pytree(
            jax.tree.map(lambda leaf: leaf[i], stacked), spec)
        assert row.shape == (n,)
        np.testing.assert_allclose(np.asarray(row),
                                   np.asarray(d.vec())[:n], rtol=0, atol=1e-6)


# --------------------------------------------------------------------------- #
# Batched == sequential parity (the acceptance invariant)
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("method", ["accuracy", "loss"])
def test_batched_scores_match_sequential_on_mixed_round(cluster, model,
                                                        method):
    """Mixed q8 + raw round: engine scores == per-model sequential loop."""
    vecs, spec = _peer_vecs(model, 5)
    methods = ("int8", "raw", "int8", "int8", "raw")
    decoded = [_decode(wire.encode_vec(v, m)) for v, m in zip(vecs, methods)]
    got = scorebatch.score_round_batch(cluster, decoded, spec, method=method)
    assert len(got) == 5
    for d, g in zip(decoded, got):
        params = ops.unflatten_pytree(d.vec(), spec)
        loss, acc = _sequential_oracle(model, cluster.test_data, params)
        want = acc if method == "accuracy" else -loss
        assert abs(g - want) <= 1e-5


def test_batched_scores_match_sequential_k1(cluster, model):
    """K=1 round (the Async engine's per-assignment shape)."""
    vecs, spec = _peer_vecs(model, 1, seed=7)
    decoded = [_decode(wire.encode_vec(vecs[0], "int8"))]
    got = scorebatch.score_round_batch(cluster, decoded, spec,
                                       method="accuracy")
    params = ops.unflatten_pytree(decoded[0].vec(), spec)
    _, acc = _sequential_oracle(model, cluster.test_data, params)
    assert len(got) == 1 and abs(got[0] - acc) <= 1e-5


def test_delta_envelope_rides_the_batch(cluster, model):
    """An int8-delta peer resolves its base, then stacks like any other."""
    vecs, spec = _peer_vecs(model, 2, seed=11)
    base_env = wire.encode_vec(vecs[0], "int8")
    base_dm = _decode(base_env)
    delta_env = wire.encode_vec(vecs[1], "int8-delta",
                                base_vec=base_dm.vec(), base_cid="b0")
    flat = deserialize_pytree(serialize_pytree(delta_env.to_store()))
    delta_dm = wire.decode_store(flat, resolver=lambda cid: base_dm)
    got = scorebatch.score_round_batch(cluster, [base_dm, delta_dm], spec,
                                       method="accuracy")
    for d, g in zip((base_dm, delta_dm), got):
        params = ops.unflatten_pytree(d.vec(), spec)
        _, acc = _sequential_oracle(model, cluster.test_data, params)
        assert abs(g - acc) <= 1e-5


def test_single_host_transfer_per_score_call(cluster, model):
    vecs, spec = _peer_vecs(model, 3, seed=5)
    decoded = [_decode(wire.encode_vec(v, "int8")) for v in vecs]
    engine = scorebatch.get_scorer(cluster)
    before = engine.host_syncs
    scorebatch.score_round_batch(cluster, decoded, spec)
    assert engine.host_syncs == before + 1


def test_cluster_evaluate_parity_and_swapped_test_data(cluster, model):
    """Cluster.evaluate (K=1 engine path) == the pre-engine loop, including
    after a test_data swap (builder.global_eval does this)."""
    params = model.init(jax.random.PRNGKey(2))
    e = cluster.evaluate(params)
    loss, acc = _sequential_oracle(model, cluster.test_data, params)
    assert abs(e["loss"] - loss) <= 1e-5 and abs(e["accuracy"] - acc) <= 1e-5

    rng = np.random.default_rng(9)
    other = {"x": rng.normal(0, 1, (130, 32, 32, 3)).astype(np.float32),
             "y": rng.integers(0, 10, 130).astype(np.int32)}
    saved = cluster.test_data
    cluster.test_data = other
    try:
        e2 = cluster.evaluate(params)
        loss2, acc2 = _sequential_oracle(model, other, params)
        assert abs(e2["loss"] - loss2) <= 1e-5
        assert abs(e2["accuracy"] - acc2) <= 1e-5
    finally:
        cluster.test_data = saved
