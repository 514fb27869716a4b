"""Fused int8-native aggregation: wsum_q8/gram_q8 kernel parity against the
f32 oracles (within quantization error), and the zero-copy exchange layer
(CID-keyed decoded cache, exact-key envelope decoding)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import wire
from repro.core.wire import DecodedModel
from repro.core.scoring import multikrum_scores_for_decoded
from repro.core.store import StoreNode
from repro.kernels import ops, ref
from repro.kernels import q8agg
from repro.kernels import quant as qk


def _quantized_rows(m, n, seed=0, scale=2.0):
    """m models of true length n -> (x f32 [m, n], q int8 [m, Np], s [m, Np/QT])."""
    x = jax.random.normal(jax.random.PRNGKey(seed), (m, n)) * scale
    qs, ss = [], []
    for i in range(m):
        q, s, _ = ops.quantize(x[i])
        qs.append(q)
        ss.append(s)
    return x, jnp.stack(qs), jnp.stack(ss)


# --------------------------------------------------------------------------- #
# Kernel parity
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("m,n", [(1, 4096), (2, 3 * 1024), (5, 5000),
                                 (8, 12288)])
def test_wsum_q8_matches_oracle(m, n):
    """Fused kernel vs dequantize-then-sum oracle: near-exact (both consume
    the same int8 payload). Covers M=1 and odd N (kernel padding path)."""
    _, q, s = _quantized_rows(m, n, seed=m + n)
    w = jax.random.uniform(jax.random.fold_in(jax.random.PRNGKey(n), 1), (m,))
    out = ops.weighted_sum_q8(q, s, w, n)
    oracle = ops.weighted_sum_q8(q, s, w, n, force="ref")
    assert out.shape == (n,)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("m,n", [(2, 4096), (8, 12288)])
def test_wsum_q8_within_quant_error_of_f32(m, n):
    """Fused q8 path vs the full-precision pipeline: bounded by the symmetric
    per-tile quantization error scaled by the weight mass."""
    x, q, s = _quantized_rows(m, n, seed=7 * m + n)
    w = jax.random.uniform(jax.random.PRNGKey(m), (m,))
    out = ops.weighted_sum_q8(q, s, w, n)
    f32 = ref.weighted_sum(x, w)
    amax = float(jnp.max(jnp.abs(x)))
    tol = amax / 127.0 * 0.51 * float(jnp.sum(jnp.abs(w))) + 1e-5
    assert float(jnp.max(jnp.abs(out - f32))) <= tol


@pytest.mark.parametrize("m,n", [(1, 4096), (3, 5000), (4, 3 * 1024),
                                 (8, 12288)])
def test_gram_q8_dists_match_oracle(m, n):
    """Pairwise distances off the packed payloads vs the dequantize-first
    oracle. Diagonals excluded: the fused int32 path cancels them exactly
    while the f32 oracle leaves rounding residue (krum masks them anyway)."""
    _, q, s = _quantized_rows(m, n, seed=m * n)
    d1 = np.array(ops.pairwise_dists_q8(q, s))
    d2 = np.array(ops.pairwise_dists_q8(q, s, force="ref"))
    np.fill_diagonal(d1, 0.0)
    np.fill_diagonal(d2, 0.0)
    np.testing.assert_allclose(d1, d2, rtol=1e-4,
                               atol=1e-4 * max(d2.max(), 1.0))


def test_multikrum_q8_matches_dequantized_scores():
    m, n = 6, 8192
    _, q, s = _quantized_rows(m, n, seed=3)
    s_fused = ops.multikrum_scores_q8(q, s, 2)
    x = jnp.stack([ops.dequantize(q[i], s[i], int(q.shape[1]))
                   for i in range(m)])
    s_f32 = ops.multikrum_scores(x, 2)
    np.testing.assert_allclose(np.asarray(s_fused), np.asarray(s_f32),
                               rtol=1e-3, atol=1e-2)


def test_multikrum_q8_flags_outlier():
    key = jax.random.PRNGKey(0)
    honest = jax.random.normal(key, (4, 5000)) * 0.1
    outlier = jax.random.normal(jax.random.fold_in(key, 1), (1, 5000)) * 5.0
    x = jnp.concatenate([honest, outlier])
    qs, ss = zip(*[ops.quantize(x[i])[:2] for i in range(5)])
    scores = ops.multikrum_scores_q8(jnp.stack(qs), jnp.stack(ss), 2)
    assert int(jnp.argmax(scores)) == 4


def test_q8_mixed_dtype_leaves():
    """Models with mixed f32/bf16 leaves flatten to one f32 vector; the fused
    aggregate of their quantized forms round-trips back into the pytree."""
    def tree(seed):
        k = jax.random.PRNGKey(seed)
        return {"w": jax.random.normal(k, (64, 33)).astype(jnp.bfloat16),
                "b": jax.random.normal(jax.random.fold_in(k, 1), (1000,))}

    trees = [tree(i) for i in range(3)]
    vecs, spec = ops.flatten_batch(trees)
    qs, ss = zip(*[ops.quantize(vecs[i])[:2] for i in range(3)])
    w = jnp.asarray([0.2, 0.3, 0.5])
    agg = ops.weighted_sum_q8(jnp.stack(qs), jnp.stack(ss), w,
                              int(vecs.shape[1]))
    back = ops.unflatten_pytree(agg, spec)
    want = ref.weighted_sum(vecs, w)
    got, _ = ops.flatten_pytree(back, spec)
    # bf16 leaves re-round on unflatten; bound is quant error + bf16 ulp
    assert float(jnp.max(jnp.abs(got - want))) <= 0.1
    assert back["w"].dtype == jnp.bfloat16 and back["b"].dtype == jnp.float32


def test_flatten_batch_matches_per_model_flatten():
    trees = [{"a": jnp.full((3, 2), float(i)), "b": jnp.arange(5.0) * i}
             for i in range(4)]
    batched, spec = ops.flatten_batch(trees)
    for i, t in enumerate(trees):
        v, _ = ops.flatten_pytree(t, spec)
        np.testing.assert_array_equal(np.asarray(batched[i]), np.asarray(v))


def test_flatten_spec_is_cached_per_config():
    t1 = {"a": jnp.ones((4, 4))}
    t2 = {"a": jnp.zeros((4, 4))}
    assert ops.make_flatten_spec(t1) is ops.make_flatten_spec(t2)
    t3 = {"a": jnp.ones((2, 2))}
    assert ops.make_flatten_spec(t1) is not ops.make_flatten_spec(t3)


# --------------------------------------------------------------------------- #
# Zero-copy exchange layer
# --------------------------------------------------------------------------- #

def _int8_envelope(vec):
    return wire.encode_vec(vec, "int8").to_store()


def test_store_decodes_once_for_k_scorers():
    """Acceptance: a model fetched by k scorers in one round is deserialized/
    dequantized exactly once per silo."""
    node = StoreNode("agg0")
    vec = jnp.arange(5000, dtype=jnp.float32) / 5000.0
    cid = node.put(_int8_envelope(vec))
    k = 5
    decoded = [node.get_decoded(cid, wire.decode_store) for _ in range(k)]
    assert node.stats["decodes"] == 1
    assert node.stats["decode_hits"] == k - 1
    assert all(d is decoded[0] for d in decoded)  # one object, zero copies
    # dequantization is also one-shot: k vec() calls share the cached array
    assert all(decoded[0].vec() is decoded[0].vec() for _ in range(3))
    np.testing.assert_allclose(np.asarray(decoded[0].vec()), np.asarray(vec),
                               atol=1.0 / 127.0)


def test_decoded_cache_is_bounded():
    from repro.core import store as store_mod
    node = StoreNode("n")
    cids = [node.put({"x": np.full((8,), float(i), np.float32)})
            for i in range(store_mod.DECODED_CACHE_MAX + 5)]
    for c in cids:
        node.get_decoded(c, wire.decode_store)
    assert len(node._decoded) == store_mod.DECODED_CACHE_MAX


def test_decode_store_exact_keys_param_named_q():
    """Regression: a raw model with params literally named 'q'/'scales'/'n'
    must not be mistaken for an int8 envelope (the old substring matching
    against keystr paths did exactly that)."""
    params = {"q": np.arange(6, dtype=np.float32),
              "scales": np.ones((3,), np.float32),
              "n": np.asarray([9.0], np.float32)}
    node = StoreNode("n")
    cid = node.put(params)
    dm = node.get_decoded(cid, wire.decode_store)
    assert not dm.is_q8
    # leaf order is jax tree flatten order (sorted keys: n, q, scales)
    want = np.concatenate([params["n"], params["q"], params["scales"]])
    np.testing.assert_array_equal(np.asarray(dm.vec()), want)


def test_decode_store_int8_envelope_roundtrip():
    vec = jax.random.normal(jax.random.PRNGKey(0), (7000,)) * 3.0
    node = StoreNode("n")
    cid = node.put(_int8_envelope(vec))
    dm = node.get_decoded(cid, wire.decode_store)
    assert dm.is_q8 and dm.n == 7000
    amax = float(jnp.max(jnp.abs(vec)))
    assert float(jnp.max(jnp.abs(dm.vec() - vec))) <= amax / 127.0 * 0.51 + 1e-6


def test_multikrum_for_decoded_uses_fused_path():
    m, n = 4, 6000
    x, q, s = _quantized_rows(m, n, seed=11)
    decoded = [DecodedModel(n, q=q[i], scales=s[i]) for i in range(m)]
    fused = multikrum_scores_for_decoded(decoded, 2)
    # none of the packed models were dequantized by the fused path
    assert all(d._vec is None for d in decoded)
    f32 = multikrum_scores_for_decoded(
        [DecodedModel(n, vec=x[i]) for i in range(m)], 2)
    np.testing.assert_allclose(fused, f32, rtol=0.05, atol=0.05)


def test_multikrum_for_decoded_mixed_round_matches_batched_dequant():
    """A round of int8 and raw models stacks each model's vector; its int8
    rows (``ops.dequantize``) equal the batched dequant's bit for bit, so
    the scores are those of the batched ingest."""
    m, n = 4, 6000
    x, q, s = _quantized_rows(m, n, seed=5)
    decoded = [DecodedModel(n, q=q[i], scales=s[i]) for i in (0, 1, 3)]
    decoded.insert(2, DecodedModel(n, vec=x[2]))
    got = multikrum_scores_for_decoded(decoded, 2)
    rows = ops.dequantize_batch(q, s, n)
    want = ops.multikrum_scores(rows.at[2].set(x[2]), 2)
    np.testing.assert_array_equal(np.asarray(got),
                                  -np.asarray(want, np.float32))


def test_merge_of_an_int8_and_a_raw_peer(monkeypatch):
    """The cross-silo merge adds a whole-int8 peer through one wsum_q8 call
    and a raw peer through its vector."""
    from repro.fed.aggregator import SiloAggregator
    n = 5000
    x, q, s = _quantized_rows(3, n, seed=9)
    own, raw = x[0], x[2]
    peers = [DecodedModel(n, q=q[1], scales=s[1]),
             DecodedModel(n, vec=raw)]
    calls = []
    wsum = ops.weighted_sum_q8

    def counted(q, scales, w, n=None, force="auto"):
        calls.append(q.shape)
        return wsum(q, scales, w, n, force)
    monkeypatch.setattr(ops, "weighted_sum_q8", counted)
    got = SiloAggregator("s0").apply_cross_silo_vec(own, peers,
                                                    [1.0, 2.0, 1.0])
    assert calls == [(1, q.shape[1])]
    want = (0.25 * own + 0.5 * ops.dequantize(q[1], s[1], n, force="ref")
            + 0.25 * raw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=1e-5)


def test_e2e_int8_multikrum_round_decodes_once(tmp_path):
    """One sync round with int8 compression + multikrum: every CID the
    scoring silo touches is decoded exactly once even though scoring and
    pull_and_merge both consume the same models."""
    from repro.configs import get_config
    from repro.config import FedConfig
    from repro.core.builder import build_image_experiment

    fed = FedConfig(n_silos=3, clients_per_silo=2, rounds=2, local_epochs=1,
                    mode="sync", scorer="multikrum", agg_policy="all",
                    compression="int8")
    orch = build_image_experiment(get_config("paper-cnn"), fed, n_train=300,
                                  n_test=120, seed=0)
    orch.run(2)
    silo0 = orch.silos[0]
    st = silo0.store.stats
    assert st["decodes"] > 0
    # scoring + merging reuse the decoded models instead of re-decoding
    assert st["decode_hits"] > 0
    # decodes never exceed the number of distinct models submitted to silo0
    distinct = len(silo0.store._decoded)
    assert st["decodes"] == distinct
