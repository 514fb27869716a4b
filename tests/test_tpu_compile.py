"""Compile-only checks against a described TPU v5e chip (no chip attached).

The round path's Pallas kernels are compiled at the LM phase's real width —
qwen3-1.7b at 4 layers flattens to 515,918,848 parameters (the f32 side, kept
unpadded), whose int8 payloads pad to 516,030,464 — for one chip of a
described v5e:2x2 host. Interpret mode on the
CPU skips Mosaic's block-shape rules and its VMEM limit; this compile does
not, so a kernel that would be refused on the chip fails here. Each compiled
program must hold a ``tpu_custom_call``: the kernel lowered natively.

The topology is described inside a module fixture, never while a module is
imported: only the worker that runs these tests loads the TPU compiler.
"""
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import q8agg, quant

N = 515_918_848
N_PADDED = quant.padded(N)  # 3937 * 131072
QT = quant.TILE


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


CASES = {
    "quantize": (quant.quantize, [((N,), jnp.float32)]),
    "dequantize_batch": (quant.dequantize_batch,
                         [((2, N_PADDED), jnp.int8),
                          ((2, N_PADDED // QT), jnp.float32)]),
    "add_q8_delta": (q8agg.add_q8_delta,
                     [((N,), jnp.float32), ((N_PADDED,), jnp.int8),
                      ((N_PADDED // QT,), jnp.float32)]),
    "wsum_q8": (lambda q, s, w: q8agg.wsum_q8(q, s, w, N),
                [((2, N_PADDED), jnp.int8), ((2, N_PADDED // QT), jnp.float32),
                 ((2,), jnp.float32)]),
    "dequantize": (lambda q, s: quant.dequantize(q, s, N),
                   [((N_PADDED,), jnp.int8), ((N_PADDED // QT,), jnp.float32)]),
    "gram_q8": (q8agg.gram_q8,
                [((3, N_PADDED), jnp.int8),
                 ((3, N_PADDED // QT), jnp.float32)]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_lowers_for_v5e_at_lm_width(one_chip, name):
    fn, shapes = CASES[name]
    args = [_shape(one_chip, s, d) for s, d in shapes]
    compiled = _compile(fn, *args)
    assert "tpu_custom_call" in compiled.as_text()
    if args[0].ndim == 1:
        # a single flat vector is viewed as [N/128, 128], which is free on
        # a TPU, and the f32 side is read or written unpadded: no copy of
        # it may appear beside the kernel (the [N/1024, 1024] view cost
        # one of N f32, the padding another)
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < N_PADDED, (name, temp)


# the paper CNN's 62,006 parameters, to a multiple of 128
SMALL = 62_080
SMALL_PADDED = quant.padded(SMALL)

# each kernel called without its own jit, from a lambda: the device op took
# the enclosing jitted function's name (``_lambda_``) until the pallas_call
# named it
NAMED = {
    "wsum_q8": (lambda q, s, w: q8agg.wsum_q8.__wrapped__(q, s, w, SMALL),
                [((2, SMALL_PADDED), jnp.int8),
                 ((2, SMALL_PADDED // QT), jnp.float32), ((2,), jnp.float32)]),
    "add_q8_delta": (lambda b, q, s: q8agg.add_q8_delta.__wrapped__(b, q, s),
                     [((SMALL,), jnp.float32), ((SMALL_PADDED,), jnp.int8),
                      ((SMALL_PADDED // QT,), jnp.float32)]),
    "gram_q8": (lambda q, s: q8agg.gram_q8.__wrapped__(q, s),
                [((3, SMALL_PADDED), jnp.int8),
                 ((3, SMALL_PADDED // QT), jnp.float32)]),
    "quantize": (lambda x: quant.quantize.__wrapped__(x),
                 [((SMALL,), jnp.float32)]),
    "dequantize": (lambda q, s: quant.dequantize.__wrapped__(q, s, SMALL),
                   [((SMALL_PADDED,), jnp.int8),
                    ((SMALL_PADDED // QT,), jnp.float32)]),
    "dequantize_batch": (lambda q, s: quant.dequantize_batch.__wrapped__(q, s),
                         [((2, SMALL_PADDED), jnp.int8),
                          ((2, SMALL_PADDED // QT), jnp.float32)]),
}


@pytest.mark.parametrize("name", sorted(NAMED))
def test_a_kernels_device_op_takes_the_kernels_name(one_chip, name):
    """A profiler trace names a kernel's op after the kernel, whatever jitted
    function calls it (``wsum_q8_roofline`` finds ``wsum_q8.N`` so)."""
    fn, shapes = NAMED[name]
    text = _compile(fn, *[_shape(one_chip, s, d) for s, d in shapes]
                    ).as_text()
    ops = re.findall(r'%([\w.]+) = [^\n]*custom_call_target="tpu_custom_call"',
                     text)
    assert len(ops) == 1 and re.fullmatch(rf"{name}(\.\d+)?", ops[0]), ops


def test_lm_train_step_compiles_and_fits_one_v5e(one_chip):
    """The client step of qwen3-1.7b at published widths and 4 layers."""
    from repro.config import replace
    from repro.configs import get_config
    from repro.fed.client import make_train_step
    from repro.models import build_model

    model = build_model(replace(get_config("qwen3-1.7b"), n_layers=4))
    on = lambda t: jax.tree.map(
        lambda a: _shape(one_chip, a.shape, a.dtype), t)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    step, opt = make_train_step(model)
    batch = {k: _shape(one_chip, (8, 128), jnp.int32)
             for k in ("tokens", "targets")}
    compiled = step.lower(on(params), on(jax.eval_shape(opt.init, params)),
                          batch, _shape(one_chip, (), jnp.float32)).compile()
    mem = compiled.memory_analysis()
    n_params = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    assert n_params == 515_918_848
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < 8 << 30, total     # well inside one chip's 16 GB
