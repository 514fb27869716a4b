"""The system's qwen3 against the plain float32 reference
(``repro.models.qwen3_ref``) on seeded random weights, at smoke size on the
CPU.

Both sides run in float32 here (params and compute), so every tolerance is
a float32 one: the two sides sum in different orders (the system's online
softmax over key chunks, its fused norms) and agree to within about 1e-5
relative (an SGD step's change, the largest, reads 8e-6); each limit, 1e-4,
leaves ten times that. The variant without qk-norm (the system's attention
skipping the per-head RMSNorm of q and k) must fall outside every one of
them (it reads 1e-3 to 1).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import FedConfig, replace
from repro.configs import get_config
from repro.configs.qwen3_1_7b import smoke_config
from repro.models import build_model, qwen3_ref

CFG = replace(smoke_config(), param_dtype="float32", compute_dtype="float32")
DIMS = qwen3_ref.Dims.of(CFG)
SEED = 2 ** 31 + 5
LR = 0.05


def _batch(key, b=2, s=24):
    toks = jax.random.randint(key, (b, s + 1), 0, CFG.vocab_size)
    return {"tokens": toks[:, :-1], "targets": toks[:, 1:]}


def _rel(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def _worst_leaf(got, want) -> float:
    """Worst leaf's |got - want| / |want| (L2), over leaves the reference
    moves (the padded vocabulary rows and nothing else sit at zero)."""
    out = 0.0
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        g, w = np.asarray(g, np.float64), np.asarray(w, np.float64)
        if np.linalg.norm(w) > 0:
            out = max(out, np.linalg.norm(g - w) / np.linalg.norm(w))
    return out


def _logits(model, params):
    from repro.models import transformer
    batch = _batch(jax.random.PRNGKey(1))
    got, _ = transformer.logits_fn(params, batch["tokens"], model.cfg)
    want = qwen3_ref.logits(params, batch["tokens"], DIMS)
    # the system's padded vocabulary rows are masked out of its loss
    return _rel(got[..., :CFG.vocab_size], want)


def _loss_and_grad(model, params):
    batch = _batch(jax.random.PRNGKey(2))
    (loss, _), grads = jax.value_and_grad(model.loss, has_aux=True)(params,
                                                                   batch)
    # a vocabulary block smaller than the vocabulary: the blocked logsumexp
    ref_loss, ref_grads = qwen3_ref.value_and_grad(params, batch, DIMS,
                                                   vocab_block=96)
    return max(_rel(loss, ref_loss), _worst_leaf(grads, ref_grads))


def _sgd_step(model, params):
    from repro.fed.client import make_train_step
    batch = _batch(jax.random.PRNGKey(3))
    step, opt = make_train_step(model)
    new, _, _ = step(params, opt.init(params), batch, jnp.float32(LR))
    _, g = qwen3_ref.value_and_grad(params, batch, DIMS)
    want = jax.tree.map(lambda p, d: p - LR * d, params, g)
    # the change each side made, so that the params' own size hides nothing
    moved = jax.tree.map(lambda a, p: a - p, new, params)
    return _worst_leaf(moved, jax.tree.map(lambda a, p: a - p, want, params))


def _round_loss_scores(model, params):
    """One sync round, int8 wire, loss scoring: each score against minus the
    reference's loss of the dequantized payload on the scorer's windows."""
    from repro.core.builder import build_lm_experiment
    from repro.fed import scorebatch

    fed = FedConfig(n_silos=3, clients_per_silo=1, rounds=1, local_epochs=1,
                    compression="int8", scorer="loss", scorer_deadline_s=0.0)
    orch = build_lm_experiment(CFG, fed, seq_len=24, batch_size=2,
                               steps_per_epoch=2, stream_len=1500, seed=SEED)
    for s in orch.silos:
        s.cluster.model.cfg = model.cfg
    seen = []
    score = scorebatch.score_round_batch

    def recorded(cluster, decoded, spec, *, method="accuracy"):
        out = score(cluster, decoded, spec, method=method)
        seen.append((cluster, list(decoded), out))
        return out

    scorebatch.score_round_batch = recorded
    try:
        orch.run(1)
    finally:
        scorebatch.score_round_batch = score
    like = orch.silos[0].cluster.params
    leaves, treedef = jax.tree_util.tree_flatten(like)
    worst = 0.0
    assert len(seen) == fed.n_silos
    for cluster, decoded, scores in seen:
        stream = np.asarray(cluster.test_data["tokens"])
        seq = cluster.test_data["seq_len"]
        starts = range(0, min(len(stream) - seq - 1, 4 * seq), seq)
        toks = np.stack([stream[i:i + seq] for i in starts])
        tgts = np.stack([stream[i + 1:i + seq + 1] for i in starts])
        for d, got in zip(decoded, scores):
            vec = (np.asarray(d.q, np.float32).reshape(-1, 1024)
                   * np.asarray(d.scales, np.float32)[:, None]).reshape(-1)
            parts, off = [], 0
            for leaf in leaves:
                parts.append(vec[off:off + leaf.size].reshape(leaf.shape))
                off += leaf.size
            peer = jax.tree_util.tree_unflatten(treedef, parts)
            want = -float(jnp.mean(qwen3_ref.seq_losses(
                peer, jnp.asarray(toks), jnp.asarray(tgts), DIMS)))
            worst = max(worst, abs(got - want) / abs(want))
    return worst


# (what is compared, its limit): ten times float32's worst agreement
CASES = {
    "logits": (_logits, 1e-4),
    "loss_and_grad": (_loss_and_grad, 1e-4),
    "sgd_step": (_sgd_step, 1e-4),
    "round_loss_scores": (_round_loss_scores, 1e-4),
}


@pytest.mark.parametrize("qk_norm", [True, False],
                         ids=["as-published", "without-qk-norm"])
@pytest.mark.parametrize("case", list(CASES))
def test_the_system_agrees_with_the_float32_reference(case, qk_norm):
    fn, limit = CASES[case]
    model = build_model(CFG)
    params = model.init(jax.random.PRNGKey(SEED % 2 ** 31))
    # the system's attention without qk-norm reads the same params, whose
    # q_norm and k_norm it leaves untouched
    model.cfg = replace(CFG, qk_norm=qk_norm)
    gap = fn(model, params)
    if qk_norm:
        assert gap <= limit, gap
    else:
        assert gap > limit, gap


def test_the_published_config_is_the_reference_dims():
    cfg = get_config("qwen3-1.7b")
    assert cfg.source == ("https://huggingface.co/Qwen/Qwen3-1.7B/blob/main/"
                          "config.json")
    assert qwen3_ref.Dims.of(cfg) == qwen3_ref.Dims(16, 8, 128, 151936, 1e6,
                                                    1e-6)
