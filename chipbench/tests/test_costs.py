"""The yardstick's counts: FLOPs of the configurations, bytes and operations
of the kernels, and the plain int8 reference against the program's format."""
import jax
import jax.numpy as jnp
import numpy as np

from chipbench import harness, q8ref

CNN = harness.load_module("configs", "paper-cnn")


def test_cnn_forward_flops_match_xla_count():
    """XLA's own count of the reference forward (multiply-adds of the convs
    and dense layers, plus the elementwise work we leave out)."""
    params = CNN.init(0)
    x = jnp.zeros((8, 32, 32, 3), jnp.float32)
    y = jnp.zeros((8,), jnp.int32)
    cost = jax.jit(CNN.loss).lower(params, x, y).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    ours = 8 * CNN.fwd_flops_per_image()
    assert ours == 8 * 2 * (28 * 28 * 6 * 75 + 10 * 10 * 16 * 150
                            + 400 * 120 + 120 * 84 + 84 * 10)
    assert ours <= cost["flops"] <= 1.1 * ours


def test_cnn_round_flops_are_three_forwards_per_trained_sample():
    f = CNN.fwd_flops_per_image()
    assert CNN.round_flops({"train_samples": 10, "eval_samples": 4}) \
        == 30 * f + 4 * f


def test_cnn_reference_init_has_the_published_shapes():
    p = CNN.init(1)
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(p))
    assert n == 62_006


def test_wsum_q8_bytes_and_ops_count_the_algorithms_data():
    # two 1M-element payloads summed into an f32 vector
    n = 8 * q8ref.BLOCK
    b, ops = q8ref.wsum_q8_cost(2, n)
    assert b == 2 * n + 2 * (n // 1024) * 4 + 4 * n
    assert ops == 2 * 2 * n
    # the CNN's payloads: the codes and scales that hold n elements, not the
    # padded block (62,006 elements fill 61 tiles)
    b, ops = q8ref.wsum_q8_cost(2, 62_006)
    assert b == 2 * 62_006 + 2 * 61 * 4 + 4 * 62_006 == 372_524
    assert ops == 2 * 2 * 62_006


def test_wsum_q8_bytes_agree_with_the_programs_byte_model():
    """The fused row of ``benchmarks/kernelbench.py:_q8_bytes`` at a length
    of whole tiles, where its floor and our ceiling of n / TILE agree."""
    from benchmarks.kernelbench import _q8_bytes
    n = 61 * 1024
    assert q8ref.wsum_q8_cost(3, n)[0] == _q8_bytes(3, n, 4 * n, fused=True)


def test_reference_quantizer_agrees_with_the_programs_format():
    from repro.kernels import ops
    x = np.random.default_rng(0).normal(size=62_006).astype(np.float32)
    x[2048:3072] = 0.0                               # an all-zero tile
    q, s, n = ops.quantize(jnp.asarray(x))
    qr, sr = q8ref.quantize(x)
    assert n == 62_006 and q.shape == qr.shape == (q8ref.BLOCK,)
    assert np.array_equal(np.asarray(q), qr)
    np.testing.assert_allclose(np.asarray(s), sr, rtol=2 ** -23)
    assert sr[2] == 1.0
    back = q8ref.dequantize(qr, sr, n)
    assert np.max(np.abs(back - x)) <= np.max(np.abs(x)) / 127 / 2 * 1.0001


def test_the_controls_lower_precision_rounds_every_operation():
    bf16 = jnp.bfloat16
    x = np.asarray([1.0 + 2 ** -10], np.float64)
    assert q8ref.rounded(x, bf16)[0] == 1.0
    assert q8ref.rounded(x, np.float32)[0] == x[0]


def test_the_qwen3_configuration_file_is_what_the_program_builds():
    """The configuration kept for the qwen3 cells (PERF.md, Open
    questions): its sizes are the program's, and its count is the
    program's parameter count at 4 layers."""
    from repro.config import replace
    from repro.configs import get_config
    from repro.models import build_model

    cfg = harness.load_json("configs", "qwen3-1.7b-4l")
    model_cfg = replace(get_config(cfg["registry"]), **cfg["overrides"])
    for key, want in cfg["model"].items():
        assert getattr(model_cfg, key) == want, key
    assert cfg["num_hidden_layers"] == model_cfg.n_layers == 4
    assert cfg["hidden_size"] == model_cfg.d_model
    assert cfg["intermediate_size"] == model_cfg.d_ff
    shapes = jax.eval_shape(build_model(model_cfg).init, jax.random.PRNGKey(0))
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    assert n == cfg["parameters"]
